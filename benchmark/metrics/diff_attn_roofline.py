"""Least time the differential attention's scores and weighted values of
one call need (the reference's `operations`, part `diff_attn`: per (query,
key) pair of a layer's mask, the causal triangle of a full or a cross layer
and the band of a sliding one, every query head's scores and every
softmax's sum over a value twice a head wide; the queries and the output
once a layer, the keys and values once a layer that MAKES them; at the
chip's bfloat16 peak or its memory bandwidth, whichever bounds) over the
measured time of the Pallas calls `diff_attn_*` and `diff_swa_*` in a
traced call, %: the plain causal forwards of the full and cross layers,
the banded forward `diff_swa_w<window>` and, where a row fits its window,
the sliding layers' plain calls `diff_swa_<i>`. The need is the mask
itself, so the share cannot pass 100%; the counts are the reference's,
from shapes alone. A program without those kernels gives nothing to
read."""
from harness import counts
from harness.cells import load_module
from harness.trace import is_pallas, short_name


def is_differential(name: str) -> bool:
    return is_pallas(name) and short_name(name).startswith(
        ("diff_attn_", "diff_swa_"))


def attend_seconds(run):
    """Device seconds of the differential forwards in the traced window;
    `None` without a trace or without such a call."""
    trace = run["trace"]
    if trace is None:
        return None
    return sum(s for _c, s in trace.op_seconds(
        select=is_differential).values()) or None


def read(run):
    taken = attend_seconds(run)
    if not taken:
        return None
    cell = run["cell"]
    need = load_module("metrics", "moe_expert_roofline").reference_part(
        cell, "diff_attn")
    least, _bound = counts.least_seconds(need, run["peaks"])
    return 100.0 * least * int(cell.traffic["trace_calls"]) / taken
