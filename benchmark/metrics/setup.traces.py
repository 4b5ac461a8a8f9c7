"""How many `jax.trace` spans the warm-up call left: the functions traced
anew, one span each (a call that finds its jaxpr in JAX's cache leaves
none). It does not move with the host's load (tracer's ring)."""
from harness.setup_spans import part


def read(run):
    return part(run, "traces")
