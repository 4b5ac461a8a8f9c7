"""Seconds of a call the host waited for the device (`block_until_ready`
on batch N-1's outputs with batch N enqueued, and on the last batch's in
the drain): where the host SHOULD be in a loop the device bounds, so it
shrinks with the device's work. The call's `runner.wait` spans summed,
median over the window's untraced calls (tracer's ring)."""
from harness.runner_spans import WAIT, median_seconds


def read(run):
    return median_seconds(run, WAIT)
