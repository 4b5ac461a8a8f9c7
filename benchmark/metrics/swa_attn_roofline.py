"""Least time the sliding-window attention's scores and weighted values of
one call need (the reference's `operations`, part `window_attention`: per
query the keys of its BAND, the `window` that end with its own, the causal
triangle itself for a row no longer than the window, over every query head
of every sliding layer; the queries, the key and the value heads read and
the output written once a layer; at the chip's bfloat16 peak or its memory
bandwidth, whichever bounds) over the measured time of the Pallas calls
`swa_attn_*` in a traced call, %: the banded forward `swa_attn_w<window>`
and, where a row fits its window, the sliding layers' plain causal calls
`swa_attn_<i>`. The need is the band itself, so the share cannot pass
100%; the counts are the reference's, from shapes alone, whatever
implements the kernel."""
from harness import counts
from harness.cells import load_module
from harness.data import length_groups
from harness.trace import is_pallas, short_name


def is_window_attention(name: str) -> bool:
    return is_pallas(name) and short_name(name).startswith("swa_attn_")


def read(run):
    trace = run["trace"]
    if trace is None:
        return None
    taken = sum(s for _c, s in
                trace.op_seconds(select=is_window_attention).values())
    if not taken:
        return None
    cell = run["cell"]
    need = load_module("reference", cell.config["reference"]).operations(
        cell.config, length_groups(int(cell.traffic["rows"]),
                                   cell.traffic["lengths"]))["parts"].get(
                                       "window_attention")
    if need is None:
        return None
    least, _bound = counts.least_seconds(need, run["peaks"])
    return 100.0 * least * int(cell.traffic["trace_calls"]) / taken
