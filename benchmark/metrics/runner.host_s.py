"""A call (untraced, host clock) minus the time the device is busy inside
a call (device trace under the benchmark's `transform.call` span): stacking
and padding the batches, uploading them, dispatching, reading the fetched
columns back and joining them."""
from harness.readers import median_host_seconds as read  # noqa: F401
