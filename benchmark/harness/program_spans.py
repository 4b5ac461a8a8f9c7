"""The program's own spans, read two ways.

From the tracer's ring (`mmlspark_tpu.observability.tracing.get_tracer()`,
host clock, `time.monotonic`): every call the run made left one root span
and its children there, the untraced calls of the timed window included,
so a call's length splits into phases without the profiler.

From the device trace: while the profiler records, the same spans sit on
the calling thread's line of the `.xplane.pb`, in the trace's nanoseconds,
so the device's idle time can be laid against them.

A program without these spans (a parent commit) gives nothing to read:
both return `None` and raise nothing."""

from __future__ import annotations

import statistics
import sys

SAR_ROOT = "sar.recommend_all"
SAR_PHASES = ("sar.slice", "sar.dispatch", "sar.wait", "sar.readback")
SELF = "self"


def window_calls(run, root: str = SAR_ROOT):
    """-> [{span name: seconds}], one per call of the timed window: the
    root span's own length under `root`, each child name's summed length
    under that name, and what no child covers under `SELF`. The ring holds
    the warm-up call, the window's and the traced ones, in that order;
    `None` (and a line on stderr) where it holds another number of roots
    or has dropped spans."""
    key = ("program_span_calls", root)
    if key in run:                           # several metrics ask
        return run[key]
    from mmlspark_tpu.observability.tracing import get_tracer

    tracer = get_tracer()
    spans = tracer.spans()
    roots = [s for s in spans if s.name == root]
    expected = (1 + len(run["calls"])
                + int(run["cell"].traffic["trace_calls"]))
    calls = None
    if tracer.drop_count or len(roots) != expected:
        print(f"program_spans: {len(roots)} {root} span(s) in the ring, "
              f"{expected} expected, {tracer.drop_count} dropped: nothing "
              f"read", file=sys.stderr, flush=True)
    else:
        kept = roots[1:1 + len(run["calls"])]
        by_id = {s.span_id: {root: s.dur_us * 1e-6} for s in kept}
        for s in spans:
            sums = by_id.get(s.parent_id)
            if sums is not None:
                sums[s.name] = sums.get(s.name, 0.0) + s.dur_us * 1e-6
        calls = list(by_id.values())
        for sums in calls:
            sums[SELF] = sums[root] - sum(
                v for name, v in sums.items() if name != root)
    run[key] = calls
    return calls


def median_seconds(run, name: str, root: str = SAR_ROOT):
    """Median over the window's calls of the call's `name` seconds."""
    calls = window_calls(run, root)
    if not calls:
        return None
    return statistics.median(sums.get(name, 0.0) for sums in calls)


def idle_seconds_inside(trace, names) -> "float | None":
    """Seconds of the traced window in which no operation ran on the
    device while the host was inside a span of one of `names`; `None`
    without device operations or without such spans."""
    if trace is None or not trace.device_ops:
        return None
    spans = [s for name in names for s in trace.spans(name)]
    if not spans:
        return None
    return sum(s.seconds - trace.busy_seconds(s.start, s.end) for s in spans)
