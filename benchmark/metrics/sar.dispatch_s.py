"""Seconds of a call spent calling the jitted top-k program until it
returns its futures: the call's `sar.dispatch` spans summed, median over
the window's untraced calls (tracer's ring)."""
from harness.program_spans import median_seconds


def read(run):
    return median_seconds(run, "sar.dispatch")
