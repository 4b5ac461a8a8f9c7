"""Least time the windowed-and-summarised attention's scores and weighted
values of one call need (the reference's `operations`, part `attention`:
per query the causal TRIANGLE of its own window and the summaries of the
windows before it, not the square, over every head; the queries, keys,
values and summaries read and the output written once a layer; at the
chip's bfloat16 peak or its memory bandwidth, whichever bounds) over the
measured time of the Pallas calls `eva_attn_*` in a traced call, %. The
counts are the reference's, from shapes alone, whatever implements the
kernel."""
from harness import counts
from harness.cells import load_module
from harness.data import length_groups
from harness.trace import is_pallas, short_name


def is_eva_attention(name: str) -> bool:
    return is_pallas(name) and short_name(name).startswith("eva_attn_")


def read(run):
    trace = run["trace"]
    if trace is None:
        return None
    taken = sum(s for _c, s in
                trace.op_seconds(select=is_eva_attention).values())
    if not taken:
        return None
    cell = run["cell"]
    need = load_module("reference", cell.config["reference"]).operations(
        cell.config, length_groups(int(cell.traffic["rows"]),
                                   cell.traffic["lengths"]))["parts"][
                                       "attention"]
    least, _bound = counts.least_seconds(need, run["peaks"])
    return 100.0 * least * int(cell.traffic["trace_calls"]) / taken
