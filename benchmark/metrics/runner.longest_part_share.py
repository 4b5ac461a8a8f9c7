"""Of a call's `runner.transform` seconds, the share spent in the root
span whose `row_shape` is longest: what the long rows cost of a call, %,
median over the window's untraced calls (tracer's ring). 100 where the
traffic file has one length."""
from harness.runner_spans import longest_part_share as read  # noqa: F401
