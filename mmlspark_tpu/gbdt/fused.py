"""Fused boosting: the ENTIRE multi-round training loop as one XLA program.

Reference contrast: the reference dispatches one JNI call per boosting round
(`LGBM_BoosterUpdateOneIter` in the hot loop, TrainUtils.scala:74-121), which
is cheap on a local JVM but on TPU every per-round dispatch is a host↔device
round trip — the dominant cost when driving a remote chip. Here the whole
loop (objective grad/hess → bagging/GOSS masks → leaf-wise tree growth →
prediction update → early-stopping validation) is a single `lax.scan` over
rounds inside one `jit` (optionally one `shard_map` over the data mesh axis
with a `psum` histogram all-reduce per split — the ICI stand-in for
LightGBM's socket reduce-scatter). One dispatch per fit; trees come back in
one transfer at the end.

Covers gbdt / goss / rf, WITH early stopping for gbdt/goss: validation raw
scores are maintained incrementally on device, the per-objective loss is
tracked in the scan carry, and once `since_best >= early_stopping_round`
every remaining round takes the `lax.cond` no-op branch (near-zero work) —
the host truncates the returned tree stack to the best round.

Single-class dart fuses too (`make_fused_dart_fn`): the cross-round drop
bookkeeping that kept it on the host loop — per-tree weights mutated by
every drop, and dropped trees' row contributions subtracted from the round's
predictions — is carried IN the scan as a (rounds, n) contribution matrix
and a (rounds,) weight vector. Each round's base prediction is one matvec
`contribs^T @ (weights * keep)`, an MXU-friendly O(R*n) read instead of a
host round trip; O(1) dispatches per dart fit. Multiclass dart (plain gbdt
updates — the drop algebra is single-model) rides the fused gbdt scan in
booster.py, so EVERY boosting mode is O(1) dispatches per fit.

Randomness is `jax.random` threaded through the scan (fold_in per round and
per mesh shard), so the fused path is deterministic for a fixed seed.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..parallel.collectives import pcast, psum_exact_fixedpoint
from ..parallel.mesh import DATA_AXIS
from .engine import GrowConfig, TreeArrays, make_grow_fn, tree_apply

__all__ = ["FusedTrainSpec", "make_fused_train_fn", "make_fused_dart_fn"]


class FusedTrainSpec(NamedTuple):
    """Static configuration of the fused loop (everything that shapes the
    compiled program)."""

    num_rounds: int
    num_class: int = 1                 # trees per round (multiclass K)
    boosting_type: str = "gbdt"        # gbdt | goss | rf
    bagging_fraction: float = 1.0
    bagging_freq: int = 0
    feature_fraction: float = 1.0
    top_rate: float = 0.2              # goss
    other_rate: float = 0.1            # goss
    early_stopping_round: int = 0      # 0: off (gbdt/goss only)
    drop_rate: float = 0.1             # dart
    # leaf-output renewal (LightGBM RenewTreeOutput, objectives.py
    # get_leaf_renewal): percentile of in-leaf residuals replacing the
    # grad/hess leaf value for the L1-family objectives. None = off.
    renew_alpha: "float | None" = None
    renew_weighted: bool = False       # mape: weight residuals by 1/max(|y|,1)


_FUSED_CACHE: dict = {}
_FUSED_CACHE_MAX = 8

_RENEW_BINS = 256      # residual-histogram resolution for leaf renewal
_RENEW_CHUNK = 4096


# refinement rounds: each round multiplies percentile resolution by
# _RENEW_BINS within the node's own residual bracket, so 2 rounds resolve
# to node-span/65536 — robust when a leaf holds a far outlier (a single
# global-range pass collapses all normal residuals into one 'span/256'
# bin and renews every leaf to that bin's center)
_RENEW_ROUNDS = 2


def _renew_tree_values(tree, node_of_row, resid, w, alpha, learning_rate,
                       axis_name, deterministic=False):
    """LightGBM RenewTreeOutput, TPU-native: replace each leaf's value with
    learning_rate x the alpha-percentile of the residuals of its (weighted)
    rows. Exact per-leaf sorting needs data-dependent gathers; instead each
    node keeps its own [lo, hi] residual bracket and the percentile is
    found by _RENEW_ROUNDS rounds of 256-bin histogram refinement (chunked
    one-hot matmuls, psum-able under the data mesh, so every shard renews
    to the IDENTICAL value — replicated-model guarantee, mesh == single
    device). Resolution: node-span / 256^rounds."""
    m = tree.value.shape[0]
    f32 = jnp.float32
    n = resid.shape[0]
    chunk = min(_RENEW_CHUNK, n)
    pad = (-n) % chunk
    if pad:
        node_of_row = jnp.concatenate(
            [node_of_row, jnp.zeros((pad,), node_of_row.dtype)])
        resid = jnp.concatenate([resid, jnp.zeros((pad,), resid.dtype)])
        w = jnp.concatenate([w, jnp.zeros((pad,), w.dtype)])
    nc = (n + pad) // chunk
    nd_c = node_of_row.reshape(nc, chunk)
    r_c = resid.reshape(nc, chunk).astype(f32)
    w_c = w.reshape(nc, chunk).astype(f32)

    # per-NODE residual bracket: an outlier only widens its own node's span
    def minmax_body(carry, xs):
        lo_a, hi_a = carry
        nd, rb, wc = xs
        # non-finite residuals (inf labels, diverged predictions) carry no
        # weight: one bad row must degrade only itself, not poison its
        # node's span (span=inf -> 0*inf=NaN cascades through every later
        # iteration's predictions)
        sel = ((jax.nn.one_hot(nd, m, dtype=f32) > 0) & (wc[:, None] > 0)
               & jnp.isfinite(rb)[:, None])
        lo_a = jnp.minimum(lo_a, jnp.where(sel, rb[:, None], jnp.inf).min(0))
        hi_a = jnp.maximum(hi_a, jnp.where(sel, rb[:, None], -jnp.inf).max(0))
        return (lo_a, hi_a), None

    # + 0*tag: carry adopts the shard-varying type under shard_map. The tag
    # must be finite: 0*inf = NaN would poison every node's bracket and the
    # histogram accumulator if the shard's first residual diverged.
    tag = 0.0 * jnp.where(jnp.isfinite(r_c[0, 0]), r_c[0, 0], 0.0)
    init = (jnp.full((m,), jnp.inf, f32) + tag,
            jnp.full((m,), -jnp.inf, f32) + tag)
    (lo, hi), _ = jax.lax.scan(minmax_body, init, (nd_c, r_c, w_c))
    if axis_name is not None:
        lo = jax.lax.pmin(lo, axis_name)
        hi = jax.lax.pmax(hi, axis_name)
    # empty nodes keep inf brackets; neutralize so arithmetic stays finite
    empty = lo > hi
    lo = jnp.where(empty, 0.0, lo)
    hi = jnp.where(empty, 0.0, hi)

    def hist_pass(lo, hi, target, first):
        span = jnp.maximum(hi - lo, 1e-12)                         # (M,)

        def body(acc, xs):
            nd, rb, wc = xs
            lo_r, hi_r = lo[nd], hi[nd]                            # (ch,)
            bin_f = (rb - lo_r) / span[nd] * _RENEW_BINS
            bidx = jnp.clip(bin_f.astype(jnp.int32), 0, _RENEW_BINS - 1)
            # rows outside their node's current bracket carry no weight;
            # non-finite residuals were excluded from the brackets and must
            # stay excluded here (NaN compares false, but +-inf would not)
            inw = jnp.where(
                (rb >= lo_r) & (rb <= hi_r) & jnp.isfinite(rb), wc, 0.0)
            oh_n = jax.nn.one_hot(nd, m, dtype=f32)                # (ch, M)
            oh_b = jax.nn.one_hot(bidx, _RENEW_BINS, dtype=f32)
            oh_b = oh_b * inw[:, None]                             # (ch, B)
            h = jax.lax.dot_general(
                oh_n, oh_b, (((0,), (0,)), ((), ())),
                preferred_element_type=f32,
                precision=jax.lax.Precision.HIGHEST,
            )                                                      # (M, B)
            return acc + h, None

        acc0 = jnp.zeros((m, _RENEW_BINS), f32) + tag
        hist, _ = jax.lax.scan(body, acc0, (nd_c, r_c, w_c))
        if axis_name is not None:
            if deterministic:
                hist = psum_exact_fixedpoint(hist, axis_name)
            else:
                hist = jax.lax.psum(hist, axis_name)
        cum = jnp.cumsum(hist, axis=1)                             # (M, B)
        tot = cum[:, -1]
        if first:
            target = alpha * tot
        idx = jnp.argmax(cum >= target[:, None], axis=1)
        below = jnp.take_along_axis(
            cum, jnp.maximum(idx - 1, 0)[:, None], 1)[:, 0]
        below = jnp.where(idx > 0, below, 0.0)
        width = span / _RENEW_BINS
        new_lo = lo + idx.astype(f32) * width
        return new_lo, new_lo + width, target - below, tot

    target = jnp.zeros((m,), f32)
    tot0 = None
    for rnd in range(_RENEW_ROUNDS):
        lo, hi, target, tot = hist_pass(lo, hi, target, first=(rnd == 0))
        if tot0 is None:
            tot0 = tot

    centers = (lo + hi) * 0.5
    new_val = jnp.where(
        tree.is_leaf & (tot0 > 0),
        (centers * learning_rate).astype(jnp.float32),
        tree.value,
    )
    return tree._replace(value=new_val)


def _apply_renewal(tree, node_row, resid, mask, base_w, y, spec, cfg,
                   axis_name):
    """Renew a freshly grown tree's leaves and recompute its row values.

    Renewal weights are BAG MEMBERSHIP x data weight — NOT the grow mask:
    the goss mask amplifies sampled small-gradient rows by
    (1-top_rate)/other_rate for the gradient sums, but LightGBM's
    RenewTreeOutput percentile runs over the partition rows with their
    original data weights only."""
    member_w = jnp.where(mask > 0, base_w, 0.0)
    if spec.renew_weighted:
        member_w = member_w / jnp.maximum(jnp.abs(y), 1.0)
    tree = _renew_tree_values(
        tree, node_row, resid, member_w, spec.renew_alpha,
        cfg.learning_rate, axis_name, deterministic=cfg.deterministic,
    )
    return tree, tree.value[node_row]


def _zero_tree(num_leaves: int, num_bins: int) -> TreeArrays:
    m = 2 * num_leaves - 1
    return TreeArrays(
        feature=jnp.full((m,), -1, jnp.int32),
        threshold_bin=jnp.zeros((m,), jnp.int32),
        is_categorical=jnp.zeros((m,), bool),
        left=jnp.full((m,), -1, jnp.int32),
        right=jnp.full((m,), -1, jnp.int32),
        value=jnp.zeros((m,), jnp.float32),
        is_leaf=jnp.zeros((m,), bool).at[0].set(True),
        gain=jnp.zeros((m,), jnp.float32),
        cat_bitset=jnp.zeros((m, num_bins), bool),
    )


def make_fused_train_fn(
    num_features: int,
    num_bins: int,
    cfg: GrowConfig,
    feature_num_bins: np.ndarray,
    categorical_mask: np.ndarray,
    obj_fn: Callable,
    spec: FusedTrainSpec,
    mesh: Mesh | None = None,
    cache_key: tuple | None = None,
    val_loss_fn: Callable | None = None,
):
    """Build the fused training function.

    Without early stopping:
      fn(bins, y, base_w, pred0, seed)
        -> (TreeArrays stacked over rounds [x K], final_pred, es_info)
    With spec.early_stopping_round > 0 (requires val_loss_fn):
      fn(bins, y, base_w, pred0, seed, val_bins, y_val, val_raw0)
        -> same, where es_info = (best_iter i32, stopped bool); best_iter is
           the 0-based round index within THIS fused run (host adds any
           warm-start offset), -1 only if the loss never improved on round 0
           (impossible: best_loss starts at +inf).

    bins: (n, F) int32; y: (n,) or (n, K) float32; base_w: (n,) float32
    (0 on padded rows); pred0: same shape as y; seed: int32 scalar;
    val_bins: (nv, F) int32 replicated; y_val: (nv,) f32 or (nv,) i32
    class indexes for multiclass; val_raw0: (nv,) / (nv, K) f32.

    `cache_key` (hashable summary of obj_fn/val_loss_fn construction)
    memoizes the returned jitted function so repeated fits with the same
    config reuse the SAME jit object — otherwise every fit would build a
    fresh closure with an empty compile cache and pay full XLA compilation
    again.
    """
    es = spec.early_stopping_round > 0
    if es and val_loss_fn is None:
        raise ValueError("early stopping requires val_loss_fn")
    if cache_key is not None:
        from ..core.kernels import kernel_mode

        full_key = (
            num_features, num_bins, cfg,
            bytes(np.asarray(feature_num_bins)),
            bytes(np.asarray(categorical_mask, np.uint8)),
            spec, mesh, cache_key, kernel_mode(),
        )
        hit = _FUSED_CACHE.get(full_key)
        if hit is not None:
            return hit
    k = spec.num_class
    f = num_features
    grow = make_grow_fn(
        num_features, num_bins, cfg, feature_num_bins, categorical_mask, raw=True
    )
    rf_mode = spec.boosting_type == "rf"
    use_goss = spec.boosting_type == "goss"
    use_bagging = rf_mode or (
        spec.boosting_type == "gbdt"
        and spec.bagging_fraction < 1.0
        and spec.bagging_freq > 0
    )
    if spec.bagging_fraction < 1.0:
        bag_frac = spec.bagging_fraction
    else:
        bag_frac = 0.632 if rf_mode else 1.0  # rf defaults to bootstrap-ish
    bag_freq = max(spec.bagging_freq, 1)

    def loop(bins, y, base_w, pred0, seed, round_offset,
             val_bins=None, y_val=None, val_raw0=None, axis_name=None):
        n = bins.shape[0]  # local rows (per shard under shard_map)
        # key_repl stays replicated: the FEATURE mask must be identical on
        # every shard (it feeds the replicated tree state — a shard-varying
        # mask breaks the lax.cond branch types and the algorithm itself).
        # key is per-shard for ROW masks (bagging/GOSS), which are psummed.
        key_repl = jax.random.PRNGKey(seed)
        key = key_repl
        if axis_name is not None:
            # independent draws per shard: same key would correlate bags
            key = jax.random.fold_in(key, jax.lax.axis_index(axis_name))
        present = (base_w > 0).astype(jnp.float32)

        def feature_mask_of(kf):
            u = jax.random.uniform(kf, (f,))
            sel = u < spec.feature_fraction
            fallback = jnp.arange(f) == jnp.argmin(u)
            return jnp.where(sel.any(), sel, fallback).astype(jnp.float32)

        def goss_mask_of(g, kg):
            ga = jnp.abs(g) * present   # padded rows must not set the bar
            # the top-rate bar comes from the UNPADDED row count, so padding
            # cannot inflate n_top; under shard_map this is the local shard's
            # real-row count — a documented per-shard approximation of the
            # host path's global top-k (each shard keeps its own top fraction)
            n_eff = present.sum()
            # truncate like the host path's int(): a RELATIVE epsilon absorbs
            # float32 rounding of the product (true 7.0 stored as 6.9999995
            # must floor to 7) without crossing genuine fractional boundaries
            # (2.8 + eps still floors to 2) the way an additive fudge would
            n_top = jnp.maximum(
                jnp.floor(spec.top_rate * n_eff * (1.0 + 1e-6) + 1e-6), 1.0
            ).astype(jnp.int32)
            ga_desc = -jnp.sort(-ga)
            thresh = ga_desc[jnp.minimum(n_top - 1, n - 1)]
            is_top = (ga >= thresh) & (present > 0)
            keep_small = jax.random.uniform(kg, ga.shape) < spec.other_rate / max(
                1.0 - spec.top_rate, 1e-6
            )
            amp = (1.0 - spec.top_rate) / max(spec.other_rate, 1e-6)
            return jnp.where(is_top, 1.0, jnp.where(keep_small, amp, 0.0))

        def grow_round(pred, bag, val_raw, it):
            """One full boosting round (K trees); returns updated state."""
            kr = jax.random.fold_in(key, it)
            if use_bagging:
                kb = jax.random.fold_in(kr, 1)
                fresh = jnp.where(
                    jax.random.uniform(kb, (n,)) < bag_frac, base_w, 0.0
                )
                if rf_mode:
                    bag = fresh  # rf resamples every round
                else:
                    bag = jnp.where(it % bag_freq == 0, fresh, bag)
            g, h = obj_fn(y, pred)

            trees_k, rowvals = [], []
            for cls in range(k):
                gc = g[:, cls] if k > 1 else g
                hc = h[:, cls] if k > 1 else h
                if use_goss:
                    mask = base_w * goss_mask_of(gc, jax.random.fold_in(kr, 2 + cls))
                else:
                    mask = bag
                fmask = (
                    feature_mask_of(
                        jax.random.fold_in(jax.random.fold_in(key_repl, it), 100 + cls)
                    )
                    if spec.feature_fraction < 1.0
                    else jnp.ones((f,), jnp.float32)
                )
                tree, rv, node_row = grow(
                    bins, gc, hc, mask, fmask, axis_name=axis_name)
                if spec.renew_alpha is not None and k == 1:
                    # L1-family leaf renewal (the objectives are
                    # single-model regressions, so k is always 1 here)
                    tree, rv = _apply_renewal(
                        tree, node_row, y - pred, mask, base_w, y, spec,
                        cfg, axis_name)
                trees_k.append(tree)
                rowvals.append(rv)

            if rf_mode:
                new_pred = pred  # rf trees are independent of pred
            elif k > 1:
                new_pred = pred + jnp.stack(rowvals, axis=-1)
            else:
                new_pred = pred + rowvals[0]

            if es:
                # validation scores update incrementally (replicated inputs)
                for cls in range(k):
                    contrib = tree_apply(trees_k[cls], val_bins, cfg.num_leaves)
                    if k > 1:
                        val_raw = val_raw.at[:, cls].add(contrib)
                    else:
                        val_raw = val_raw + contrib

            if k > 1:
                out = jax.tree.map(lambda *a: jnp.stack(a), *trees_k)
            else:
                out = trees_k[0]
            return new_pred, bag, val_raw, out

        def body(carry, it):
            pred, bag, val_raw, best_loss, best_iter, since, stopped = carry

            def active(op):
                pred, bag, val_raw, out = grow_round(*op, it)
                # loss evaluated INSIDE the branch: stopped rounds must not
                # keep paying a full validation reduction for a masked result
                vloss = val_loss_fn(val_raw, y_val)
                return pred, bag, val_raw, out, vloss

            def inactive(op):
                pred, bag, val_raw = op
                z = _zero_tree(cfg.num_leaves, num_bins)
                if k > 1:
                    z = jax.tree.map(
                        lambda a: jnp.broadcast_to(a, (k,) + a.shape), z
                    )
                # +inf can never register as an improvement
                return pred, bag, val_raw, z, jnp.asarray(jnp.inf, jnp.float32)

            if es:
                # post-stop rounds take the near-zero-work no-op branch
                pred, bag, val_raw, out, vloss = jax.lax.cond(
                    stopped, inactive, active, (pred, bag, val_raw)
                )
            else:
                # hot benchmark path: no conditional around the round body
                pred, bag, val_raw, out = grow_round(pred, bag, val_raw, it)

            if es:
                improved = (~stopped) & (vloss < best_loss - 1e-9)
                best_loss = jnp.where(improved, vloss, best_loss)
                best_iter = jnp.where(improved, it, best_iter)
                since = jnp.where(
                    stopped, since, jnp.where(improved, 0, since + 1)
                )
                stopped = stopped | (since >= spec.early_stopping_round)

            return (pred, bag, val_raw, best_loss, best_iter, since,
                    stopped), out

        if val_raw0 is None:
            # dummy scalar keeps the carry structure static when ES is off
            val_raw0 = jnp.zeros((), jnp.float32)
        carry0 = (
            pred0, base_w, val_raw0,
            jnp.asarray(jnp.inf, jnp.float32), jnp.asarray(-1, jnp.int32),
            jnp.asarray(0, jnp.int32), jnp.asarray(False),
        )
        # global round indices: a checkpointed fit re-enters the scan with
        # round_offset = rounds-already-done, so every RNG fold_in sees the
        # same `it` it would in an uninterrupted run (byte-identity of the
        # resumed model depends on this). A traced offset shares one
        # executable across chunks.
        its = jnp.arange(spec.num_rounds) + jnp.asarray(
            round_offset, jnp.int32)
        (pred, _, _, _, best_iter, _, stopped), trees = jax.lax.scan(
            body, carry0, its
        )
        return trees, pred, (best_iter, stopped)

    y_extra = (None,) if k > 1 else ()
    if mesh is not None and mesh.shape.get(DATA_AXIS, 1) > 1:
        row = P(DATA_AXIS)
        rowk = P(DATA_AXIS, *y_extra)
        es_in = (P(None, None), P(None), P(None, *y_extra)) if es else ()
        # check_vma=False: on the TPU the body calls the Pallas histogram
        # kernel, and neither a pallas_call's kernel body nor its
        # interpreter is typed for varying manual axes (an iota built
        # inside the kernel meets per-shard blocks), so the check cannot
        # pass there; one setting on every backend keeps CPU tests honest
        fn = jax.jit(shard_map(
            functools.partial(loop, axis_name=DATA_AXIS),
            mesh=mesh, check_vma=False,
            in_specs=(P(DATA_AXIS, None), rowk, row, rowk, P(), P()) + es_in,
            out_specs=(
                TreeArrays(*([P()] * len(TreeArrays._fields))),
                rowk,
                (P(), P()),
            ),
        ))
    else:
        fn = jax.jit(functools.partial(loop, axis_name=None))
    if cache_key is not None:
        if len(_FUSED_CACHE) >= _FUSED_CACHE_MAX:
            _FUSED_CACHE.pop(next(iter(_FUSED_CACHE)))
        _FUSED_CACHE[full_key] = fn
    return fn


def make_fused_dart_fn(
    num_features: int,
    num_bins: int,
    cfg: GrowConfig,
    feature_num_bins: np.ndarray,
    categorical_mask: np.ndarray,
    obj_fn: Callable,
    spec: FusedTrainSpec,
    mesh: Mesh | None = None,
    cache_key: tuple | None = None,
):
    """Fused single-class DART: the whole drop/renormalize boosting loop as
    one XLA program (the standard DART algorithm the host loop implements,
    with identical weight algebra; jax.random drops instead of numpy).

      fn(bins, y, base_w, pred0, drop_seed, bag_seed, feat_seed)
        -> (TreeArrays stacked over rounds, tree_weights (R,), final_pred)

    Seeds are per purpose — drop selection, bagging, feature sampling —
    preserving the host path's contract that e.g. varying bagging_seed
    alone changes the bags without reshuffling the drops.

    Per round r: a replicated Bernoulli(drop_rate) mask over trees < r is
    drawn; the round's base prediction is pred0 + contribs^T @ (weights *
    keep) (one matvec over the carried (R, n) contribution matrix); the new
    tree trains on gradients at that prediction; dropped weights scale by
    k/(k+1) and the new tree enters at 1/(k+1). Tree VALUES come back
    unscaled — the host folds the returned weights in, exactly like the
    host loop's end-of-fit rescale.

    Memory: the carry holds R*n float32 contributions (e.g. 1M rows x 100
    rounds = 400 MB HBM — fine on-chip; row-sharded under the mesh).
    """
    if spec.num_class != 1:
        raise ValueError("fused dart covers the single-class path only")
    if cache_key is not None:
        from ..core.kernels import kernel_mode

        full_key = (
            "dart", num_features, num_bins, cfg,
            bytes(np.asarray(feature_num_bins)),
            bytes(np.asarray(categorical_mask, np.uint8)),
            spec, mesh, cache_key, kernel_mode(),
        )
        hit = _FUSED_CACHE.get(full_key)
        if hit is not None:
            return hit
    f = num_features
    rounds = spec.num_rounds
    grow = make_grow_fn(
        num_features, num_bins, cfg, feature_num_bins, categorical_mask, raw=True
    )
    use_bagging = spec.bagging_fraction < 1.0 and spec.bagging_freq > 0
    bag_freq = max(spec.bagging_freq, 1)

    def loop(bins, y, base_w, pred0, drop_seed, bag_seed, feat_seed,
             axis_name=None):
        n = bins.shape[0]
        key_drop = jax.random.PRNGKey(drop_seed)     # replicated
        key_feat = jax.random.PRNGKey(feat_seed)     # replicated
        key_bag = jax.random.PRNGKey(bag_seed)       # per-shard rows
        if axis_name is not None:
            key_bag = jax.random.fold_in(
                key_bag, jax.lax.axis_index(axis_name)
            )

        def feature_mask_of(kf):
            u = jax.random.uniform(kf, (f,))
            sel = u < spec.feature_fraction
            fallback = jnp.arange(f) == jnp.argmin(u)
            return jnp.where(sel.any(), sel, fallback).astype(jnp.float32)

        trees0 = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (rounds,) + a.shape),
            _zero_tree(cfg.num_leaves, num_bins),
        )
        contribs0 = jnp.zeros((rounds, n), jnp.float32)
        weights0 = jnp.zeros((rounds,), jnp.float32)
        if axis_name is not None:
            # the contribution matrix holds row-sharded values; the zeros
            # init must carry the varying manual-axis type so the scan
            # carry types line up (engine.py's node_of_row pattern)
            contribs0 = pcast(contribs0, (axis_name,), to="varying")

        def body(carry, it):
            trees, contribs, weights, bag = carry
            # drop selection is REPLICATED (same key on every shard): the
            # weight vector feeds the replicated tree bookkeeping
            kd = jax.random.fold_in(key_drop, it)
            drop = (
                jax.random.uniform(kd, (rounds,)) < spec.drop_rate
            ) & (jnp.arange(rounds) < it)
            k_drop = drop.sum().astype(jnp.float32)
            keep_w = jnp.where(drop, 0.0, weights)
            # HIGHEST precision: on TPU the default einsum would be a bf16
            # MXU dot, degrading every round's base prediction (and
            # breaking dart(drop_rate=0) == gbdt bit-identity) — same rule
            # as the histogram kernels (hist_kernel.py)
            pred_round = pred0 + jnp.einsum(
                "rn,r->n", contribs, keep_w,
                precision=jax.lax.Precision.HIGHEST,
            ).astype(pred0.dtype)

            if use_bagging:
                kb = jax.random.fold_in(key_bag, it)
                fresh = jnp.where(
                    jax.random.uniform(kb, (n,)) < spec.bagging_fraction,
                    base_w, 0.0,
                )
                bag = jnp.where(it % bag_freq == 0, fresh, bag)
            g, h = obj_fn(y, pred_round)
            fmask = (
                feature_mask_of(jax.random.fold_in(key_feat, it))
                if spec.feature_fraction < 1.0
                else jnp.ones((f,), jnp.float32)
            )
            tree, rv, node_row = grow(bins, g, h, bag, fmask,
                                      axis_name=axis_name)
            if spec.renew_alpha is not None:
                tree, rv = _apply_renewal(
                    tree, node_row, y - pred_round, bag, base_w, y, spec,
                    cfg, axis_name)

            # standard DART renormalization (the host loop's algebra):
            # dropped weights shrink by k/(k+1), the new tree enters at
            # 1/(k+1); k_drop == 0 degrades to a plain gbdt round
            norm_new = 1.0 / (k_drop + 1.0)
            weights = jnp.where(drop, weights * k_drop / (k_drop + 1.0),
                                weights)
            weights = weights.at[it].set(norm_new)
            contribs = contribs.at[it].set(rv)
            trees = jax.tree.map(lambda s, t: s.at[it].set(t), trees, tree)
            return (trees, contribs, weights, bag), None

        carry0 = (trees0, contribs0, weights0, base_w)
        (trees, contribs, weights, _), _ = jax.lax.scan(
            body, carry0, jnp.arange(rounds)
        )
        final_pred = pred0 + jnp.einsum(
            "rn,r->n", contribs, weights,
            precision=jax.lax.Precision.HIGHEST,
        ).astype(pred0.dtype)
        return trees, weights, final_pred

    if mesh is not None and mesh.shape.get(DATA_AXIS, 1) > 1:
        row = P(DATA_AXIS)
        # check_vma=False: see make_fused_train_fn
        fn = jax.jit(shard_map(
            functools.partial(loop, axis_name=DATA_AXIS),
            mesh=mesh, check_vma=False,
            in_specs=(P(DATA_AXIS, None), row, row, row, P(), P(), P()),
            out_specs=(
                TreeArrays(*([P()] * len(TreeArrays._fields))),
                P(),
                row,
            ),
        ))
    else:
        fn = jax.jit(functools.partial(loop, axis_name=None))
    if cache_key is not None:
        if len(_FUSED_CACHE) >= _FUSED_CACHE_MAX:
            _FUSED_CACHE.pop(next(iter(_FUSED_CACHE)))
        _FUSED_CACHE[full_key] = fn
    return fn
