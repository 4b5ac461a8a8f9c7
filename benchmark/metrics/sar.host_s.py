"""A call (untraced, host clock) minus the time the device is busy inside
a call (device trace under the benchmark's `recommend.call` span): slicing
a block's rows, dispatching it, waiting for and reading back its top k,
building the table."""
from harness.readers import median_host_seconds as read  # noqa: F401
