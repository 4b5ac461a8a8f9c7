"""Least time the grouped-query attention's scores and weighted values of
one call need (the reference's `operations`, part `attention`: the causal
TRIANGLE over every query head, a query and the keys at or before it, not
the square; the queries, the key and the value heads read and the output
written once a layer; at the chip's bfloat16 peak or its memory bandwidth,
whichever bounds) over the measured time of the Pallas calls
`gqa_attn_<i>` in a traced call, %."""
from harness import counts
from harness.cells import load_module
from harness.trace import is_pallas, short_name


def is_grouped_query_attention(name: str) -> bool:
    return is_pallas(name) and short_name(name).startswith("gqa_attn_")


def read(run):
    trace = run["trace"]
    if trace is None:
        return None
    taken = sum(s for _c, s in trace.op_seconds(
        select=is_grouped_query_attention).values())
    if not taken:
        return None
    cell = run["cell"]
    need = load_module("metrics", "moe_expert_roofline").reference_part(
        cell, "attention")
    least, _bound = counts.least_seconds(need, run["peaks"])
    return 100.0 * least * int(cell.traffic["trace_calls"]) / taken
