"""Least time the grouped products of the experts held need in one call
(the reference's `operations`, part `routed_experts`: `per_pick` operations
and bytes times the picks the program COUNTED for its held experts, the
root spans' `moe_picks_held`, plus the held experts' weights read once; at
the chip's bfloat16 peak or its memory bandwidth, whichever bounds) over
the measured time of those products in a traced call, %. The products are
`jax.lax.ragged_dot`, which the TPU compiler lowers to its own grouped
kernel: the custom calls named `ragged-dot*`. Only work the held experts
need is counted, so the share cannot pass 100%."""
import statistics

from harness import counts
from harness.cells import load_module
from harness.data import length_groups
from harness.program_spans import window_args
from harness.trace import is_pallas, short_name

ROOT = "runner.transform"


def is_grouped_product(name: str) -> bool:
    return is_pallas(name) and short_name(name).startswith("ragged-dot")


def root_args(run):
    """-> [[arguments of each `runner.transform` root span], ...], one list
    per call of the timed window (a call enters the runner once a length);
    `None` where the ring does not hold them."""
    traffic = run["cell"].traffic
    tables = len(length_groups(int(traffic["rows"]), traffic["lengths"]))
    return window_args(run, ROOT, ROOT, tables)


def reference_part(cell, part: str) -> dict:
    """The operations and bytes of one part of the cell's table, as the
    configuration's reference counts them from shapes alone."""
    return load_module("reference", cell.config["reference"]).operations(
        cell.config, length_groups(int(cell.traffic["rows"]),
                                   cell.traffic["lengths"]))["parts"][part]


def picks_held_per_call(run):
    """Median over the window's calls of the picks the program routed to
    the experts it holds (padding rows' included: the device computed
    them); `None` where the program counts none."""
    held = [sum(args["moe_picks_held"] for args in call)
            for call in root_args(run) or ()
            if call and all("moe_picks_held" in args for args in call)]
    return statistics.median(held) if held else None


def read(run):
    trace = run["trace"]
    if trace is None:
        return None
    taken = sum(s for _c, s in
                trace.op_seconds(select=is_grouped_product).values())
    picks = picks_held_per_call(run)
    if not taken or not picks:
        return None
    part = reference_part(run["cell"], "routed_experts")
    need = {"ops": picks * part["per_pick"]["ops"],
            "bytes": part["bytes"] + picks * part["per_pick"]["bytes"]}
    least, _bound = counts.least_seconds(need, run["peaks"])
    return 100.0 * least * int(run["cell"].traffic["trace_calls"]) / taken
