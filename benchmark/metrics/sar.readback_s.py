"""Seconds of a call spent copying the blocks' top k to the host and
casting them (`np.asarray(v, float64)`, `np.asarray(i, int64)`): the
call's `sar.readback` spans summed, median over the window's untraced
calls (tracer's ring)."""
from harness.program_spans import median_seconds


def read(run):
    return median_seconds(run, "sar.readback")
