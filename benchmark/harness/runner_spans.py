"""The runner's own phases, read from the tracer's ring after the run.

Since PR 51 a streamed `DeepModelTransformer.transform` leaves, under its
root span `runner.transform` (argument `row_shape`), `runner.stack`, and a
batch `runner.feed_wait`, `runner.prepare` with `runner.upload` inside it
(on the prefetcher's thread, the root handed over as parent), and under
`runner.step`, `runner.dispatch`, `runner.wait` and `runner.readback`; the
last batch's wait and readback hang under the root. A call of the adapter
is one root span a length of the traffic file.

`program_spans.window_spans` finds the window's root spans (and refuses a
ring whose roots do not add up to the calls made); this file walks the ring
once more for ALL their descendants, the worker thread's too. The five
PHASES lie on the calling thread and never overlap there, so with what no
phase covers (`self`) they add up to the root.

A ring without these spans (a parent commit has the root and `runner.step`
only) gives nothing to read: `None`, and nothing raised."""

from __future__ import annotations

import math
import statistics

from harness import program_spans
from harness.data import length_groups
from harness.setup_spans import _root_of, union_seconds

ROOT = "runner.transform"
PHASES = ("runner.stack", "runner.feed_wait", "runner.dispatch",
          "runner.wait", "runner.readback")
PREPARE = "runner.prepare"
WAIT = "runner.wait"
SELF = "self"
LONGEST = "longest"     # the root span whose rows are longest


def window_calls(run) -> "list[dict] | None":
    """-> [{name: seconds}], one per call of the timed window: the root
    spans' length under ROOT, each descendant name's summed length under
    that name, under SELF the roots' length less the union of the PHASES on
    the calling thread, and under LONGEST the length of the root whose
    rows are longest."""
    if "runner_spans" in run:                # eleven metrics ask
        return run["runner_spans"]
    traffic = run["cell"].traffic
    out = None
    if "rows" in traffic and "lengths" in traffic:
        each = len(length_groups(int(traffic["rows"]), traffic["lengths"]))
        calls = program_spans.window_spans(run, ROOT, each)
        if calls:
            out = _sums(calls)
    run["runner_spans"] = out
    return out


def _sums(calls) -> "list[dict] | None":
    from mmlspark_tpu.observability.tracing import get_tracer

    under = {r.span_id: [] for call in calls for r, _kids in call}
    for s in get_tracer().spans():
        if s.parent is not None:
            top = _root_of(s)
            if top.span_id in under:
                under[top.span_id].append(s)
    if not any(s.name in PHASES for spans in under.values() for s in spans):
        return None
    out = []
    for call in calls:
        sums = {ROOT: sum(r.dur_us for r, _kids in call) * 1e-6}
        covered = 0.0
        for r, _kids in call:
            spans = under[r.span_id]
            for s in spans:
                sums[s.name] = sums.get(s.name, 0.0) + s.dur_us * 1e-6
            covered += union_seconds(
                s for s in spans if s.name in PHASES and s.tid == r.tid)
        sums[SELF] = sums[ROOT] - covered
        longest = max(call, key=lambda pair: math.prod(
            pair[0].args.get("row_shape", ())))[0]
        sums[LONGEST] = longest.dur_us * 1e-6
        out.append(sums)
    return out


def median_seconds(run, name: str):
    """Median over the window's calls of the call's `name` seconds."""
    calls = window_calls(run)
    if not calls:
        return None
    return statistics.median(sums.get(name, 0.0) for sums in calls)


def longest_part_share(run):
    """Median over the window's calls of the share of the call's root
    seconds spent in the root whose rows are longest, %."""
    calls = window_calls(run)
    if not calls:
        return None
    return statistics.median(
        100.0 * sums[LONGEST] / sums[ROOT] for sums in calls if sums[ROOT])


def slowest_call_excess(run) -> "tuple[float, float] | None":
    """-> (excess, host excess) of the window's longest call: its root
    seconds less the median call's, and its seconds OUTSIDE `runner.wait`
    less the median call's. The second is the raw difference: where the
    device bounds the loop a slow host phase is absorbed by a shorter wait,
    so it can read above the excess, and below nothing where the longest
    call's host phases ran faster than the median call's."""
    calls = window_calls(run)
    if not calls:
        return None
    slowest = max(calls, key=lambda sums: sums[ROOT])
    excess = slowest[ROOT] - statistics.median(s[ROOT] for s in calls)

    def outside(sums):
        return sums[ROOT] - sums.get(WAIT, 0.0)

    host = outside(slowest) - statistics.median(outside(s) for s in calls)
    return excess, host


def idle_named_share(run):
    """Of the device's idle time inside the traced calls' root spans, the
    share inside one of the PHASES (device trace; the trace keeps the
    calling thread's line), %."""
    named = program_spans.idle_seconds_inside(run["trace"], PHASES)
    idle = program_spans.idle_seconds_inside(run["trace"], (ROOT,))
    return 100.0 * named / idle if named is not None and idle else None
