"""Least time the channel-decay selective scans of one call need (the
reference's `operations`, part `selscan`: the recurrence's elementwise
operations, an exp and five more a (token, channel, state) cell, whatever
implements them; x and y in the served type, dt in float32 and B and C read
or written once a layer; at the chip's peak or its memory bandwidth,
whichever bounds) over the measured time of the Pallas calls `sel_scan_<i>`
in a traced call, %. The harness's table of peaks has the matrix unit's
rate and no vector-unit figure, and this scan has no product in it: by
that table its bound is its BYTES, which the vector unit cannot reach, so
a sound kernel reads well under 100 and the number is read against
itself. A program without that kernel gives nothing to read."""
from harness import counts
from harness.cells import load_module
from harness.trace import is_pallas, short_name


def is_selective_scan(name: str) -> bool:
    return is_pallas(name) and short_name(name).startswith("sel_scan_")


def scan_seconds(run):
    """Device seconds of the `sel_scan_<i>` calls in the traced window;
    `None` without a trace or without such a call."""
    trace = run["trace"]
    if trace is None:
        return None
    return sum(s for _c, s in trace.op_seconds(
        select=is_selective_scan).values()) or None


def read(run):
    taken = scan_seconds(run)
    if not taken:
        return None
    cell = run["cell"]
    need = load_module("metrics", "moe_expert_roofline").reference_part(
        cell, "selscan")
    least, _bound = counts.least_seconds(need, run["peaks"])
    return 100.0 * least * int(cell.traffic["trace_calls"]) / taken
