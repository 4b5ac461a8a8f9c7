"""Seconds of a start spent importing the program: the union of the
`package.import` spans (argument `module`: `mmlspark_tpu`, which holds
`import jax`, and the sub-packages an entry point loads lazily) recorded
before the timed window (tracer's ring)."""
from harness.setup_spans import part


def read(run):
    return part(run, "import_s")
