"""Least time the scoring products of one call need (harness/counts.py:
operations bound them, at the chip's bfloat16 peak) over the measured time
of the product fusions (matmul with the seen mask fused in) in a traced
call, %."""
from harness import counts
from harness.trace import is_matmul_fusion


def read(run):
    trace = run["trace"]
    if trace is None:
        return None
    taken = sum(s for _c, s in
                trace.op_seconds(select=is_matmul_fusion).values())
    if not taken:
        return None
    config = run["cell"].config
    need = counts.sar_scores(int(config["num_users"]),
                             int(config["num_items"]))
    least, _bound = counts.least_seconds(need, run["peaks"])
    return 100.0 * least * int(run["cell"].traffic["trace_calls"]) / taken
