"""Seconds of a call's `runner.transform` spans that none of the five
phases on the calling thread covers (`runner.stack`, `runner.feed_wait`,
`runner.dispatch`, `runner.wait`, `runner.readback`): a step's own
bookkeeping, the prefetcher's start, the join of the chunks, the family's
report, the table. With those five it adds up to the root. Median over the
window's untraced calls (tracer's ring)."""
from harness.runner_spans import SELF, median_seconds


def read(run):
    return median_seconds(run, SELF)
