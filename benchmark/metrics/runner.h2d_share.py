"""Of the traced calls' length, the share in which an input transfer was
in flight: each host-to-device transfer from the runtime's call that
issues it to the event that reports it done (device trace, the host
plane's `tpu::System::TransferToDevice` events), their union inside the
benchmark's `transform.call` spans, %. Nothing is read from a trace that
holds no such event."""
from harness.trace import union_seconds


def read(run):
    trace = run["trace"]
    if trace is None or not trace.transfers_in:
        return None
    calls = trace.spans(run["annotation"])
    total = sum(c.seconds for c in calls)
    if not total:
        return None
    inside = sum(union_seconds(
        (max(t.start, c.start), min(t.end, c.end))
        for t in trace.transfers_in if t.end > c.start and t.start < c.end)
        for c in calls)
    return 100.0 * inside / total
