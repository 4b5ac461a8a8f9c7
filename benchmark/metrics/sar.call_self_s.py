"""Seconds of a call's `sar.recommend_all` span that none of its child
spans covers (the loop, concatenation, the invalid mask, the table),
median over the window's untraced calls (tracer's ring)."""
from harness.program_spans import SELF, median_seconds


def read(run):
    return median_seconds(run, SELF)
