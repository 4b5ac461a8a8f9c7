"""Of the device's busy time in the traced window, the differential
attention's forwards: the Pallas calls `diff_attn_*` and `diff_swa_*`, by
NAME, %. The projections, the layout of the queries, the subtraction and
the norm a pair around them are not in it."""
from harness.cells import load_module


def read(run):
    taken = load_module("metrics", "diff_attn_roofline").attend_seconds(run)
    if not taken:
        return None
    trace = run["trace"]
    busy = trace.busy_seconds() * max(len(trace.device_ops), 1)
    return 100.0 * taken / busy if busy else None
