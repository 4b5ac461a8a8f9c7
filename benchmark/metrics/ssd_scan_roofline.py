"""Least time the state-space scans of one call need (the reference's
`operations`, part `ssd`: the chunked form's four products, three a chunk
a head and C B^T once a chunk a GROUP, whatever implements them; xs, B, C
and dt read and y written once a layer; at the chip's bfloat16 peak or its
memory bandwidth, whichever bounds) over the measured time of the Pallas
calls `ssd_scan_<i>` in a traced call, %. A program without that kernel
gives nothing to read."""
from harness import counts
from harness.cells import load_module
from harness.trace import is_pallas, short_name


def is_state_space_scan(name: str) -> bool:
    return is_pallas(name) and short_name(name).startswith("ssd_scan_")


def scan_seconds(run):
    """Device seconds of the `ssd_scan_<i>` calls in the traced window;
    `None` without a trace or without such a call."""
    trace = run["trace"]
    if trace is None:
        return None
    return sum(s for _c, s in trace.op_seconds(
        select=is_state_space_scan).values()) or None


def read(run):
    taken = scan_seconds(run)
    if not taken:
        return None
    cell = run["cell"]
    need = load_module("metrics", "moe_expert_roofline").reference_part(
        cell, "ssd")
    least, _bound = counts.least_seconds(need, run["peaks"])
    return 100.0 * least * int(cell.traffic["trace_calls"]) / taken
