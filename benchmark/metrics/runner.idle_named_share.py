"""Of the device's idle time inside the traced calls' `runner.transform`
spans, the share that falls inside `runner.stack`, `runner.feed_wait`,
`runner.dispatch`, `runner.wait` or `runner.readback` on the calling
thread (device trace), %. Better HIGHER: idle time a phase names can be
laid at that phase's door; the rest is the root's self time."""
from harness.runner_spans import idle_named_share as read  # noqa: F401
