"""Of the device's busy time in the traced window, the channel-decay
selective scans: the Pallas calls `sel_scan_<i>`, by NAME, %. The
projections, the convolution and the gate around the scan are not in it."""
from harness.cells import load_module


def read(run):
    taken = load_module("metrics", "sel_scan_roofline").scan_seconds(run)
    if not taken:
        return None
    trace = run["trace"]
    busy = trace.busy_seconds() * max(len(trace.device_ops), 1)
    return 100.0 * taken / busy if busy else None
