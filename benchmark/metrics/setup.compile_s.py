"""Seconds of a start inside the backend's compile step: the union of the
`jax.compile` spans (arguments `fun_name`, `cache_hit`, `retrieval_s`)
under the warm-up call's root spans: XLA's compile on a compiling start,
the persistent cache's retrieval on a warm one (tracer's ring)."""
from harness.setup_spans import part


def read(run):
    return part(run, "compile_s")
