"""Seconds of a call spent enqueueing batches: the executable cache's
lookup and the jitted forward's call until it returns its futures, the
call's `runner.dispatch` spans summed, median over the window's untraced
calls (tracer's ring)."""
from harness.runner_spans import median_seconds


def read(run):
    return median_seconds(run, "runner.dispatch")
