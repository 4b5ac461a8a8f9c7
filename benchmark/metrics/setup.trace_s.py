"""Seconds of a start spent tracing: the union of the `jax.trace` spans
(argument `fun_name`) under the warm-up call's root spans; a jit traced
inside another lies inside the outer one's interval and is counted once
(tracer's ring)."""
from harness.setup_spans import part


def read(run):
    return part(run, "trace_s")
