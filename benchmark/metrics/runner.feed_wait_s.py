"""Seconds of a call the loop waited for the prefetcher to hand it a batch
(sliced, padded and uploaded on the prefetcher's thread): the call's
`runner.feed_wait` spans summed, median over the window's untraced calls
(tracer's ring). The first wait of a length is the start gap, nothing being
prepared ahead yet; the others are microseconds while the device, and not
the host, sets the pace."""
from harness.runner_spans import median_seconds


def read(run):
    return median_seconds(run, "runner.feed_wait")
