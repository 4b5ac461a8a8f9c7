"""Pipeline parallelism: GPipe-style microbatched stage execution over a
mesh axis.

The reference has NO model sharding of any kind (SURVEY.md §2.2: CNTK models
are fully replicated per executor, CNTKModel.scala:83) — pipeline parallelism
is one of the "reserved axes" capabilities the TPU build adds so large models
can be split across chips without API change. Design is TPU-first: every
stage runs the SAME jitted program under `shard_map`; activations move
between adjacent stages with `lax.ppermute` (a neighbor hop that rides ICI),
and microbatches stream through the pipeline so all stages are busy after
the fill phase (the classic GPipe schedule: fill, steady state, drain).

No torch-style per-stage processes, no send/recv threads — ONE SPMD program
in which device i applies stage i.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .collectives import pcast

PIPE_AXIS = "pipe"

__all__ = ["PIPE_AXIS", "make_pipe_mesh", "pipeline_apply", "pipeline_forward"]


def make_pipe_mesh(n_stages: int, devices=None) -> Mesh:
    """A 1-axis mesh whose only axis is the pipeline-stage axis."""
    import numpy as np

    devs = list(devices) if devices is not None else list(jax.devices())
    if len(devs) < n_stages:
        raise ValueError(f"need {n_stages} devices, have {len(devs)}")
    return Mesh(np.asarray(devs[:n_stages]), (PIPE_AXIS,))


def pipeline_apply(stage_fn, n_stages: int, axis_name: str = PIPE_AXIS):
    """Build the SPMD pipeline body (call inside shard_map over `axis_name`).

    stage_fn(stage_params, x) -> y applies ONE stage; all stages must share
    the activation shape (stacked-transformer-block case). Returns
    body(stage_params, microbatches) -> outputs where `microbatches` is
    (n_micro, mb, ...) REPLICATED input and `outputs` is (n_micro, mb, ...)
    replicated output (every device ends with the full result via a psum of
    the last stage's accumulator).

    Schedule: n_micro + n_stages - 1 ticks. At tick t, stage 0 ingests
    microbatch t (if any), every stage applies itself to its current
    activation, and activations hop one stage to the right (ppermute).
    """

    def body(stage_params, microbatches):
        n_micro = microbatches.shape[0]
        idx = lax.axis_index(axis_name)
        is_first = idx == 0
        is_last = idx == n_stages - 1
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(t, carry):
            state, outputs = carry
            # stage 0 ingests microbatch t while it exists; other stages use
            # the activation handed to them at the end of the previous tick
            mb = microbatches[jnp.clip(t, 0, n_micro - 1)]
            x = jnp.where(is_first, mb, state)
            y = stage_fn(stage_params, x)
            # the microbatch leaving the LAST stage at tick t entered at
            # t - (n_stages - 1); record it once it is a real microbatch
            done = t - (n_stages - 1)
            take = is_last & (done >= 0)
            slot = jnp.clip(done, 0, n_micro - 1)
            outputs = outputs.at[slot].set(
                jnp.where(take, y, outputs[slot])
            )
            state = lax.ppermute(y, axis_name, perm)
            return state, outputs

        # the loop body makes both carries device-varying (ppermute / writes
        # gated on axis_index); the initial values must carry that type too
        # (collectives.pcast is an identity on older jax, which has no
        # varying-manual-axes typing)
        state0 = pcast(
            jnp.zeros_like(microbatches[0]), (axis_name,), to="varying"
        )
        out0 = pcast(
            jnp.zeros_like(microbatches), (axis_name,), to="varying"
        )
        _, outputs = lax.fori_loop(
            0, n_micro + n_stages - 1, tick, (state0, out0)
        )
        # only the last stage holds real outputs; replicate to all stages so
        # callers (loss, metrics) see the full batch everywhere
        outputs = jnp.where(is_last, outputs, jnp.zeros_like(outputs))
        return lax.psum(outputs, axis_name)

    return body


def pipeline_forward(stage_fn, params_stacked, x, n_micro: int,
                     mesh: Mesh | None = None):
    """Convenience wrapper: jitted end-to-end pipelined forward.

    params_stacked: pytree whose leaves have leading dim n_stages (stage i's
    slice lives on device i); x: (batch, ...) host/global array, split into
    n_micro microbatches. Returns (batch, ...) outputs.
    """
    mesh = mesh or make_pipe_mesh(len(jax.devices()))
    n_stages = mesh.shape[PIPE_AXIS]
    for leaf in jax.tree.leaves(params_stacked):
        if leaf.shape[0] != n_stages:
            # a multiple of n_stages would shard silently and drop stages
            raise ValueError(
                f"params leading dim {leaf.shape[0]} != pipeline stages "
                f"{n_stages}"
            )
    b = x.shape[0]
    if b % n_micro:
        raise ValueError(f"batch {b} not divisible into {n_micro} microbatches")
    xm = x.reshape(n_micro, b // n_micro, *x.shape[1:])
    fn = _compiled_pipeline(stage_fn, mesh, n_stages)
    out = fn(params_stacked, xm)
    return out.reshape(b, *out.shape[2:])


@functools.lru_cache(maxsize=8)
def _compiled_pipeline(stage_fn, mesh: Mesh, n_stages: int):
    """Cache the jitted shard_map per (stage_fn identity, mesh) so repeated
    pipeline_forward calls hit jax.jit's own shape cache instead of
    retracing a fresh closure every time. Identity keying means stage_fn
    should be a STABLE function (module-level, not a per-call lambda) for
    the cache to help — per-call closures retrace, they are never wrong."""
    body = pipeline_apply(stage_fn, n_stages)

    def run(params, xm):
        # shard_map hands each device its stage's params slice (leading dim
        # indexed by pipe position); squeeze that dim inside
        local = jax.tree.map(lambda a: a[0], params)
        return body(local, xm)

    # a bare PartitionSpec acts as a pytree prefix covering every params leaf
    return jax.jit(shard_map(
        run, mesh=mesh,
        in_specs=(P(PIPE_AXIS), P()),
        out_specs=P(),
    ))
