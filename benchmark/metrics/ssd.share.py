"""Of the device's busy time in the traced window, the state-space scans:
the Pallas calls `ssd_scan_<i>`, by NAME, %. The projections, the
convolution and the gated norm around the scan are not in it: `ssm.share`
reads the mixer whole."""
from harness.cells import load_module


def read(run):
    taken = load_module("metrics", "ssd_scan_roofline").scan_seconds(run)
    if not taken:
        return None
    trace = run["trace"]
    busy = trace.busy_seconds() * max(len(trace.device_ops), 1)
    return 100.0 * taken / busy if busy else None
