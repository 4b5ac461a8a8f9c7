"""Unified collectives + the distributed-determinism strategy.

One communication backend. The reference runs THREE (SURVEY.md §5.8):
LightGBM's C++ TCP ring with a hand-rolled driver-socket rendezvous
(LightGBMUtils.scala:97-136), `mpirun` over ssh for CNTK
(CommandBuilders.scala:102-147), and Spark broadcast/shuffle. Here every
cross-device byte moves through XLA collectives over ICI (intra-slice) /
DCN (inter-slice), issued inside `shard_map`/`jit` — no sockets, no port
probing, no hostfiles.

The substantive content of this module is DETERMINISTIC REDUCTION.
LightGBM's data-parallel learner gets a replicated model *by construction*
because every worker applies splits computed from one synchronized histogram
merge; its `deterministic` flag additionally pins summation order so reruns
are bit-identical. A float `psum` gives no such pin: float addition is not
associative, the reduction order XLA picks can depend on topology / device
order, and a near-tied split-gain argmax can flip on rounding jitter —
different shards would then grow DIFFERENT trees and the replicated-model
invariant (LightGBMClassifier.scala:82-85 `.reduce((b1,_)=>b1)`) silently
breaks. Three strategies, increasing strength (SURVEY.md §7 "distributed
determinism" hard part):

  * `psum_ordered`   — all-gather the shard partials, reduce them in a FIXED
    left-to-right axis-index order via `lax.scan`. Every device computes the
    same bits from the same gathered operands, independent of the physical
    reduction topology XLA would pick for a plain psum. Costs an all-gather
    (S× the payload) instead of a psum — fine for (F, B, 3) histograms.
  * `psum_kahan`     — same fixed order, Neumaier-compensated accumulation:
    rounding error stays O(eps) in the shard count on top of determinism.
  * `psum_exact_fixedpoint` — quantize to integer multiples of a shared
    scale such that the worst-case |partial sum| < 2^23, then plain `psum`:
    every intermediate is an integer exactly representable in float32, so
    integer-associativity makes the result BIT-EXACT under ANY reduction
    order and any device permutation. This is the strongest guarantee and
    uses the fast native psum path; precision is bounded by ~2^23 relative
    steps of the dynamic range (documented at the call site).

`GrowConfig.deterministic` routes the GBDT histogram merge through the
fixed-point reduction (gbdt/engine.py), mirroring LightGBM's own
`deterministic` parameter.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = [
    "psum",
    "pmean",
    "pmax",
    "all_gather",
    "reduce_scatter",
    "ppermute_ring",
    "all_to_all",
    "axis_index",
    "axis_size",
    "pcast",
    "psum_ordered",
    "psum_kahan",
    "psum_exact_fixedpoint",
]


def psum(x, axis_name: str):
    """Histogram/gradient all-reduce (replaces LightGBM's socket
    reduce-scatter + allgather and MPI allreduce)."""
    return lax.psum(x, axis_name)


def pmean(x, axis_name: str):
    return lax.pmean(x, axis_name)


def pmax(x, axis_name: str):
    return lax.pmax(x, axis_name)


def all_gather(x, axis_name: str, axis: int = 0, tiled: bool = True):
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name: str, scatter_dimension: int = 0):
    return lax.psum_scatter(x, axis_name, scatter_dimension=scatter_dimension,
                            tiled=True)


def ppermute_ring(x, axis_name: str, reverse: bool = False):
    """Rotate shards one step around the ring — the building block of ring
    attention. Lowered by XLA to a neighbor exchange on the ICI torus."""
    n = axis_size(axis_name)
    if reverse:
        perm = [(i, (i - 1) % n) for i in range(n)]
    else:
        perm = [(i, (i + 1) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm)


def all_to_all(x, axis_name: str, split_axis: int, concat_axis: int):
    """Shard-axis exchange (Ulysses-style sequence<->head reshard)."""
    return lax.all_to_all(x, axis_name, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=True)


def axis_index(axis_name: str):
    return lax.axis_index(axis_name)


def axis_size(axis_name: str):
    return lax.axis_size(axis_name)


def pcast(x, axis_names, to: str = "varying"):
    return lax.pcast(x, axis_names, to=to)


# --------------------------------------------------------------------- #
# deterministic reductions                                              #
# --------------------------------------------------------------------- #


def psum_ordered(x, axis_name: str):
    """All-reduce with a FIXED summation order (shard 0, then 1, ...).

    All shards are gathered (stacked on a new leading axis in axis-index
    order) and folded left-to-right with `lax.scan`, so the float rounding
    sequence is pinned by the mesh's logical axis order — not by whatever
    ring/tree schedule the plain psum lowers to on this topology. Every
    device runs the same fold over the same operands and gets identical
    bits.
    """
    g = lax.all_gather(x, axis_name)          # (S, ...) in axis-index order

    def fold(acc, shard):
        return acc + shard, None

    total, _ = lax.scan(fold, jnp.zeros_like(x), g)
    return total


def psum_kahan(x, axis_name: str):
    """Fixed-order all-reduce with Neumaier-compensated accumulation.

    On top of `psum_ordered`'s pinned order, carries a compensation term so
    the rounding error is O(eps), independent of the shard count — useful
    when many shards' near-cancelling gradient partials would otherwise
    lose low-order bits (the near-tied-split hazard).
    """
    g = lax.all_gather(x, axis_name)          # (S, ...)

    def fold(carry, shard):
        acc, comp = carry
        t = acc + shard
        # Neumaier: pick the larger-magnitude operand to recover the
        # low-order bits lost in t
        comp = comp + jnp.where(
            jnp.abs(acc) >= jnp.abs(shard),
            (acc - t) + shard,
            (shard - t) + acc,
        )
        return (t, comp), None

    (total, comp), _ = lax.scan(
        fold, (jnp.zeros_like(x), jnp.zeros_like(x)), g
    )
    return total + comp


def psum_exact_fixedpoint(x, axis_name: str, *, n_shards: int | None = None):
    """Bit-exact all-reduce under ANY reduction order / device permutation.

    Quantizes each shard's values to integer multiples of a shared scale
    chosen so the worst-case |partial sum| stays below 2^23, then runs the
    plain (fast) `psum`. Every intermediate sum is an integer exactly
    representable in float32, and integer addition is associative and
    commutative — so the result is identical bits no matter how XLA
    schedules the reduction or how the mesh permutes devices.

    Precision: values are rounded to `max_abs * n_shards / 2^23` — about
    2^23 relative steps of the dynamic range. For GBDT histograms (sums of
    per-row gradients) this is far below the split-gain noise floor of the
    histogram binning itself; it is NOT appropriate for quantities needing
    full float32 precision.

    `n_shards` defaults to the (static) mapped axis size.

    The scale is computed PER trailing-axis channel (not one global max):
    the GBDT histogram stacks [grad, hess, count] on its last axis, and a
    single shared scale would let the large count channel (~rows) destroy
    the much smaller hessian channel's precision. Each channel quantizes
    against its own dynamic range; for scalars/1-D inputs this degenerates
    to the global max.
    """
    if n_shards is None:
        n_shards = axis_size(axis_name)
    # per-channel scale over all but the last axis; every shard must agree,
    # so reduce the max with pmax (max is order-independent — no
    # determinism leak here)
    if x.ndim >= 2:
        reduce_axes = tuple(range(x.ndim - 1))
        max_abs = lax.pmax(jnp.max(jnp.abs(x), axis=reduce_axes), axis_name)
        max_abs = max_abs[(None,) * (x.ndim - 1) + (slice(None),)]
    else:
        max_abs = lax.pmax(jnp.max(jnp.abs(x)), axis_name)
    # worst case |sum of partials| <= n_shards * max_abs -> keep below 2^23.
    # Two-step division (never forming max_abs * n_shards, which overflows
    # float32 for max_abs > ~4e37) plus a floor on max_abs (keeps scale
    # finite for denormal-tiny inputs): |x * scale| <= 2^23 / n_shards by
    # construction, so the quantized partials can never overflow either.
    per_shard_budget = (2.0 ** 23) / n_shards
    scale = per_shard_budget / jnp.maximum(max_abs, 2.0 ** -100)
    scale = jnp.where(max_abs > 0, scale, 1.0)
    q = jnp.round(x * scale)                  # integer-valued float32
    total = lax.psum(q, axis_name)            # exact: all partials < 2^24
    return total / scale
