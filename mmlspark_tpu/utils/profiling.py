"""Tracing/profiling utilities (SURVEY.md §5.1).

The reference's tracing story is the `Timer` pipeline stage (wall-clock per
fit/transform, Timer.scala:55-124) plus per-test timing; the TPU-native
equivalent adds `jax.profiler` device traces — the tool that actually shows
where HBM bandwidth and MXU time go. Usage:

    with device_trace("/tmp/trace"):          # XPlane trace for xprof/tensorboard
        booster = Booster.train(...)

    with annotate("histogram"):               # named region inside a trace
        ...

    stats = profile_fn(fn, *args)             # quick wall+device timing dict

`device_trace` is also switchable by env var: MMLSPARK_TPU_TRACE_DIR set ->
every `device_trace(None)` call traces into it; unset -> no-op context.
"""

from __future__ import annotations

import contextlib
import os
import time
from typing import Any, Callable

__all__ = ["device_trace", "annotate", "profile_fn", "block_until_ready"]


@contextlib.contextmanager
def device_trace(trace_dir: str | None):
    """jax.profiler.trace wrapper; no-op when no directory is configured."""
    target = trace_dir or os.environ.get("MMLSPARK_TPU_TRACE_DIR")
    if not target:
        yield None
        return
    import jax

    os.makedirs(target, exist_ok=True)
    with jax.profiler.trace(target):
        yield target


def annotate(name: str):
    """Named region (TraceAnnotation) visible in the device trace."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def block_until_ready(tree: Any) -> Any:
    import jax

    return jax.block_until_ready(tree)


def profile_fn(fn: Callable, *args, warmup: int = 1, iters: int = 3,
               registry: Any = None, name: "str | None" = None,
               clock: "Callable[[], float] | None" = None,
               **kwargs) -> "tuple[Any, dict]":
    """Quick timing: compile (first-call) time, then per-iteration steady
    wall times with device completion awaited. Returns `(out, stats)` —
    the model output separated from the stats dict (the old API buried the
    output under an `"out"` key inside the numbers). All times in seconds:
    first_call_s, steady_s (mean), compile_overhead_s, iter_min_s,
    iter_median_s, iter_max_s, iters.

    `clock` is any zero-arg monotonic float source (default
    `time.perf_counter`); tests inject a fake to assert on the stats
    arithmetic without depending on real elapsed time.

    The measurements also land in `registry` (the process default when
    None) as `mmlspark_tpu_profile_*` series labeled `fn=` the callable's
    name (override with `name=`)."""
    now = clock if clock is not None else time.perf_counter
    t0 = now()
    out = block_until_ready(fn(*args, **kwargs))
    first = now() - t0
    for _ in range(max(warmup - 1, 0)):
        block_until_ready(fn(*args, **kwargs))
    samples = []
    for _ in range(iters):
        t0 = now()
        out = block_until_ready(fn(*args, **kwargs))
        samples.append(now() - t0)
    steady = sum(samples) / len(samples) if samples else 0.0
    ordered = sorted(samples)
    stats = {
        "first_call_s": first, "steady_s": steady,
        "compile_overhead_s": max(first - steady, 0.0),
        "iter_min_s": ordered[0] if ordered else 0.0,
        "iter_median_s": ordered[len(ordered) // 2] if ordered else 0.0,
        "iter_max_s": ordered[-1] if ordered else 0.0,
        "iters": len(samples),
    }
    try:
        from ..observability.metrics import get_registry

        reg = registry if registry is not None else get_registry()
        label = name or getattr(fn, "__name__", None) or "fn"
        reg.gauge("mmlspark_tpu_profile_steady_seconds",
                  "profile_fn steady-state wall time (mean over iters)",
                  labels=("fn",)).labels(fn=label).set(steady)
        reg.gauge("mmlspark_tpu_profile_first_call_seconds",
                  "profile_fn first-call (compile-inclusive) wall time",
                  labels=("fn",)).labels(fn=label).set(first)
        reg.counter("mmlspark_tpu_profile_runs_total",
                    "profile_fn invocations",
                    labels=("fn",)).labels(fn=label).inc()
    except Exception:
        pass
    return out, stats
