"""Seconds of a call spent slicing, padding and uploading batches: the
call's `runner.prepare` spans summed (`runner.upload`, the host-to-device
copy as the host sees it, lies inside each), median over the window's
untraced calls (tracer's ring). On the prefetcher's thread, under the
device's work on the batch before: hidden while `runner.feed_wait_s` is
small, so it is not one of the parts that add up to the call."""
from harness.runner_spans import PREPARE, median_seconds


def read(run):
    return median_seconds(run, PREPARE)
