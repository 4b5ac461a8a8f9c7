"""Seconds of a start spent lowering jaxprs to MLIR modules: the union of
the `jax.lower` spans (argument `fun_name`) under the warm-up call's root
spans (tracer's ring)."""
from harness.setup_spans import part


def read(run):
    return part(run, "lower_s")
