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


def window_spans(run, root: str = SAR_ROOT, each: int = 1):
    """-> [[(root span, [its child spans]), ...]], one list per call of
    the timed window. The ring holds the warm-up call, the window's and the
    traced ones, in that order, each with `each` root spans (the reader
    knows how often a call of its adapter enters the program). `None` (and
    a line on stderr) where the ring holds another number of roots or has
    dropped spans."""
    key = ("program_spans", root, each)
    if key in run:                           # several metrics ask
        return run[key]
    from mmlspark_tpu.observability.tracing import get_tracer

    tracer = get_tracer()
    spans = tracer.spans()
    roots = [s for s in spans if s.name == root]
    made = 1 + len(run["calls"]) + int(run["cell"].traffic["trace_calls"])
    calls = None
    if tracer.drop_count or len(roots) != each * made:
        print(f"program_spans: {len(roots)} {root} span(s) in the ring, "
              f"{each} x {made} expected, {tracer.drop_count} dropped: "
              f"nothing read", file=sys.stderr, flush=True)
    else:
        kept = roots[each:each * (1 + len(run["calls"]))]
        children = {s.span_id: [] for s in kept}
        for s in spans:
            if s.parent_id in children:
                children[s.parent_id].append(s)
        calls = [[(r, children[r.span_id]) for r in kept[i:i + each]]
                 for i in range(0, len(kept), each)]
    run[key] = calls
    return calls


def window_calls(run, root: str = SAR_ROOT, each: int = 1):
    """-> [{span name: seconds}], one per call of the timed window: the
    root spans' own length under `root`, each child name's summed length
    under that name, and what no child covers under `SELF`."""
    calls = window_spans(run, root, each)
    if calls is None:
        return None
    out = []
    for call in calls:
        sums = {root: sum(r.dur_us for r, _c in call) * 1e-6}
        for _r, kids in call:
            for s in kids:
                sums[s.name] = sums.get(s.name, 0.0) + s.dur_us * 1e-6
        sums[SELF] = sums[root] - sum(
            v for name, v in sums.items() if name != root)
        out.append(sums)
    return out


def window_args(run, name: str, root: str = SAR_ROOT, each: int = 1):
    """-> [[arguments of each span `name`], ...], one list per call of the
    timed window; `name` is the root or one of its children."""
    calls = window_spans(run, root, each)
    if calls is None:
        return None
    return [[s.args for r, kids in call
             for s in ([r] if name == root else kids) if s.name == name]
            for call in calls]


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
