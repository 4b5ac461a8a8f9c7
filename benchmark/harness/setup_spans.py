"""Where a start goes, read from the tracer's ring after the run.

Since PR 37 the program records, beside the spans of its calls, its own
imports (`package.import`, argument `module`) and every trace, lowering and
backend compile JAX reports (`jax.trace`, `jax.lower`, `jax.compile`,
argument `fun_name`; a `jax.compile` also `cache_hit` and `retrieval_s`),
each a child of the span that was active on the thread that paid for it.

The cut is the start of the first root span of the timed window, as
`program_spans.window_spans` finds it. Of what was recorded before it, the
imports count whole, and a `jax.*` span counts where it hangs under a root
span of the warm-up call: the adapter's own jitted weight-making has no
program span above it and is not the program's. Nested spans (a jit traced
inside another, a package imported by another) are summed as the union of
their intervals.

Nothing here names a cell or a family: the program's root span is whatever
name the ring's parentless spans carry, and a call leaves as many of them
as the ring holds over the calls made. A ring without the new spans (a
parent commit) gives nothing to read: `None`, and nothing raised."""

from __future__ import annotations

import sys

from harness import program_spans

IMPORT = "package.import"
TRACE, LOWER, COMPILE = "jax.trace", "jax.lower", "jax.compile"


def union_seconds(spans) -> float:
    """Seconds covered by at least one of `spans`."""
    total, covered = 0.0, float("-inf")
    for s in sorted(spans, key=lambda s: s.start_us):
        end = s.start_us + s.dur_us
        total += max(0.0, end - max(covered, s.start_us))
        covered = max(covered, end)
    return total * 1e-6


def _root_of(span):
    while span.parent is not None:
        span = span.parent
    return span


def split(run) -> "dict | None":
    """-> {part: value}, a part a `setup.*` metric, or `None` where the
    ring holds none of the new spans, has dropped spans, or its root spans
    do not add up to the calls made."""
    if "setup_spans" in run:                 # seven metrics ask
        return run["setup_spans"]
    from mmlspark_tpu.observability.tracing import get_tracer

    tracer = get_tracer()
    spans = tracer.spans()
    new = (IMPORT, TRACE, LOWER, COMPILE)
    out = None
    if any(s.name in new for s in spans) and not tracer.drop_count:
        roots = [s for s in spans if s.parent_id == 0 and s.name not in new]
        names = {s.name for s in roots}
        made = 1 + len(run["calls"]) + int(run["cell"].traffic["trace_calls"])
        if len(names) != 1 or len(roots) % made:
            print(f"setup_spans: {len(roots)} root span(s) named "
                  f"{sorted(names)} over {made} call(s): nothing read",
                  file=sys.stderr, flush=True)
        else:
            each = len(roots) // made
            window = program_spans.window_spans(run, roots[0].name, each)
            if window:
                out = _split(spans, roots[:each],
                             window[0][0][0].start_us, run["setup_s"])
    run["setup_spans"] = out
    return out


def _split(spans, warm, cut_us: float, setup_s: float) -> dict:
    """`warm`: the warm-up call's root spans; `cut_us`: where the timed
    window's first root span starts."""
    early = [s for s in spans if s.start_us < cut_us]
    ours = {r.span_id for r in warm}
    under = {name: [s for s in early
                    if s.name == name and _root_of(s).span_id in ours]
             for name in (TRACE, LOWER, COMPILE)}
    import_s = union_seconds(s for s in early if s.name == IMPORT)
    warm_s = sum(r.dur_us for r in warm) * 1e-6
    return {
        "import_s": import_s,
        "trace_s": union_seconds(under[TRACE]),
        "lower_s": union_seconds(under[LOWER]),
        "compile_s": union_seconds(under[COMPILE]),
        "first_run_s": warm_s - union_seconds(
            s for group in under.values() for s in group),
        "traces": float(len(under[TRACE])),
        "unspanned_s": setup_s - import_s - warm_s,
    }


def part(run, name: str):
    parts = split(run)
    return None if parts is None else parts[name]
