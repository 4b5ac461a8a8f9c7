"""Device time of the `TopK` custom calls (`lax.top_k` over a block's
masked scores) over the device's busy time in the traced window, %."""
from harness.trace import is_top_k


def read(run):
    trace = run["trace"]
    if trace is None:
        return None
    kernel = sum(s for _c, s in trace.op_seconds(select=is_top_k).values())
    busy = trace.busy_seconds()
    return 100.0 * kernel / busy if kernel and busy else None
