"""Seconds of a call the host waited for the blocks' top k
(`block_until_ready`) with the next block already queued on the device:
most of it the device works on that next block too, so it shrinks with
the device's work and grows when the look-ahead is lost. The call's
`sar.wait` spans summed, median over the window's untraced calls
(tracer's ring)."""
from harness.program_spans import median_seconds


def read(run):
    return median_seconds(run, "sar.wait")
