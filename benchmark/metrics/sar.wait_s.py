"""Seconds of a call the host waited for the blocks' top k
(`block_until_ready`), which is when the device scores: the call's
`sar.wait` spans summed, median over the window's untraced calls
(tracer's ring)."""
from harness.program_spans import median_seconds


def read(run):
    return median_seconds(run, "sar.wait")
