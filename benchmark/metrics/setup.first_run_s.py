"""Seconds of the warm-up call that were neither trace, lowering nor
compile: its root spans' length less the union of the `jax.trace`,
`jax.lower` and `jax.compile` spans under them: the first execution and
its host work (tracer's ring)."""
from harness.setup_spans import part


def read(run):
    return part(run, "first_run_s")
