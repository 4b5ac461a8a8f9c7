"""Seconds of `setup_s` no span of the program covers: `setup_s` less the
imports' union less the warm-up call's root spans: the interpreter's
start and the benchmark's own set-up (weights and inputs from the seed),
which no change to the program can move (tracer's ring, host clock)."""
from harness.setup_spans import part


def read(run):
    return part(run, "unspanned_s")
