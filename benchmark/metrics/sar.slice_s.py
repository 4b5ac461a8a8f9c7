"""Seconds of a call spent cutting the blocks' affinity and seen rows out
of the resident arrays (`dev["affinity"][lo:hi]`): the call's `sar.slice`
spans summed, median over the window's untraced calls (tracer's ring)."""
from harness.program_spans import median_seconds


def read(run):
    return median_seconds(run, "sar.slice")
