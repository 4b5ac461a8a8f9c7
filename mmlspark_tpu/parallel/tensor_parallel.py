"""Tensor-parallel building blocks (Megatron-style column/row sharding).

The reference replicates every model whole (CNTKModel.scala:83 clones per
partition; SURVEY.md §2.2 marks TP/PP as absent). Here tensor parallelism is
a first-class mesh axis: a column-parallel matmul (no comm on entry, output
sharded on features) followed by a row-parallel matmul (features-sharded in,
ONE psum out) gives the classic MLP block with a single all-reduce — laid
out so the collective rides ICI over the "model" axis.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .mesh import MODEL_AXIS

__all__ = [
    "column_parallel",
    "row_parallel",
    "ring_all_gather",
    "gathered_column_parallel",
    "dense_column_specs",
    "make_tp_mlp",
]


def column_parallel(x, w_local, b_local=None):
    """x replicated (on the model axis), w sharded on OUTPUT features.
    Returns output sharded on features; no collective."""
    y = jnp.einsum("...i,io->...o", x, w_local,
                   preferred_element_type=jnp.float32)
    if b_local is not None:
        y = y + b_local
    return y


def row_parallel(x_local, w_local, axis_name: str, b=None):
    """x sharded on INPUT features, w sharded on input features.
    ONE psum over the model axis reassembles the output."""
    y = jnp.einsum("...i,io->...o", x_local, w_local,
                   preferred_element_type=jnp.float32)
    y = lax.psum(y, axis_name)
    if b is not None:
        y = y + b
    return y


def ring_all_gather(y, axis_name: str, axis: int = -1):
    """Hand-scheduled tiled all_gather: N-1 neighbor `ppermute` steps
    (collective-permute tiling) instead of one monolithic all_gather op.

    The point is SCHEDULING, not values: XLA can only overlap a collective
    with compute at the granularity of the ops it sees, and when its async
    pass leaves `all-gather` synchronous the whole gather serializes
    behind the matmul.  Decomposed into a ring of permutes, each step is
    independently schedulable, so compute slides between steps — the
    classic fallback when the phase ledger shows the gather NOT
    overlapping (SNIPPETS.md [3] pattern).

    Bit-exact by construction: blocks are moved, never added — chip i's
    slice lands in slot i on every chip, the same disjoint concatenation
    `all_gather(..., tiled=True)` produces."""
    n = lax.psum(1, axis_name)  # static axis size (constant-folded)
    if n == 1:
        return y
    axis = axis % y.ndim
    # receive from the next chip each step: after step k this chip holds
    # the slice owned by (idx + k) mod n, so the received order is the
    # full ring rotated left by idx — one roll restores slot order
    perm = [(i, (i - 1) % n) for i in range(n)]
    blocks = [y]
    blk = y
    for _ in range(n - 1):
        blk = lax.ppermute(blk, axis_name, perm)
        blocks.append(blk)
    out = jnp.concatenate(blocks, axis=axis)
    idx = lax.axis_index(axis_name)
    return jnp.roll(out, idx * y.shape[axis], axis=axis)


def gathered_column_parallel(x, w_local, b_local, axis_name: str,
                             ring: bool = False):
    """Column-parallel dense followed by a tiled all_gather, so every chip
    leaves with the FULL output features.

    This is the bit-exact tensor-parallel layout: unlike the Megatron
    column->row pair (whose psum adds PARTIAL contraction sums in a
    device-count-dependent order), every output element here is one full
    -contraction dot — identical arithmetic to the unsharded matmul — and
    the gather merely concatenates disjoint feature slices.  That is what
    lets the fused pipeline engine keep its byte-identity contract while
    splitting matmul FLOPs/weights over the model axis.

    `ring=True` swaps the monolithic gather for `ring_all_gather`'s
    collective-permute tiling — same bytes, finer-grained schedule — for
    meshes where XLA fails to overlap the all_gather with compute."""
    y = column_parallel(x, w_local, b_local)
    if ring:
        return ring_all_gather(y, axis_name, axis=y.ndim - 1)
    return lax.all_gather(y, axis_name, axis=y.ndim - 1, tiled=True)


def _column_spec(leaf, model_axis: str) -> P:
    nd = getattr(leaf, "ndim", None)
    if nd == 2:
        return P(None, model_axis)   # kernel: shard OUTPUT features
    if nd == 1:
        return P(model_axis)         # bias: same feature slices
    return P()


def dense_column_specs(params, model_axis: str = MODEL_AXIS):
    """PartitionSpec pytree for a tree of dense layers under column
    parallelism: 2-D kernels shard on OUTPUT features, 1-D biases on the
    same axis, anything else replicated.  Matches flax's
    {layer: {"kernel", "bias"}} layout but only looks at ranks, so any
    dict-of-dense params works."""
    return jax.tree.map(lambda leaf: _column_spec(leaf, model_axis), params)


def dense_column_shardings(mesh: Mesh, params, model_axis: str = MODEL_AXIS):
    """`dense_column_specs` bound to a mesh as NamedSharding leaves — the
    placement pytree `jax.device_put` takes.  (Built directly from the
    params tree: PartitionSpec leaves can't be tree-mapped over, they ARE
    containers to some jax versions.)"""
    from jax.sharding import NamedSharding

    return jax.tree.map(
        lambda leaf: NamedSharding(mesh, _column_spec(leaf, model_axis)),
        params)


def make_tp_mlp(mesh: Mesh, model_axis: str,
                activation: Callable = jax.nn.gelu):
    """Jitted 2-layer tensor-parallel MLP:
    fn(x (B, F), w1 (F, H), b1 (H,), w2 (H, F), b2 (F,)) -> (B, F), with H
    sharded over the model axis (ONE psum total, Megatron layout)."""

    def body(x, w1, b1, w2, b2):
        h = activation(column_parallel(x, w1, b1))
        return row_parallel(h, w2, model_axis, b2)

    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(),                       # x replicated on the model axis
            P(None, model_axis),       # w1: output-feature sharded
            P(model_axis),             # b1
            P(model_axis, None),       # w2: input-feature sharded
            P(),                       # b2 replicated
        ),
        out_specs=P(),
    )
    return jax.jit(fn)
