"""Seconds of a call spent copying finished batches to the host and
slicing their padding off (`np.asarray` of every fetched output and of a
module's counters, after the wait): the call's `runner.readback` spans
summed, median over the window's untraced calls (tracer's ring)."""
from harness.runner_spans import median_seconds


def read(run):
    return median_seconds(run, "runner.readback")
