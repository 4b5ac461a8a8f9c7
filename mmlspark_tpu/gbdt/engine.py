"""Histogram-GBDT training engine: jit-compiled leaf-wise tree growth.

Reference semantics: lib_lightgbm's serial/data-parallel tree learner as
driven by src/lightgbm/src/main/scala/TrainUtils.scala:74-121 (boosting loop
calling LGBM_BoosterUpdateOneIter) — per-feature histogram build over local
rows, distributed reduce-scatter of histograms, best-gain split, leaf-wise
growth bounded by num_leaves/max_depth.

TPU-first redesign:
  - The whole single-tree growth loop is ONE jitted function
    (`lax.fori_loop` over num_leaves-1 split steps) on fixed-shape arrays —
    no per-node Python dispatch, no dynamic shapes.
  - Histograms are built with segment-sums over (bin + feature*B) ids — a
    shape XLA lowers well — per split step only for the NEW left child; the
    right child comes from the classic parent-minus-sibling subtraction.
  - Data parallelism: rows are sharded over the mesh "data" axis with
    `shard_map`; the single collective is a `psum` of the (F, B, 3)
    histogram — the ICI equivalent of LightGBM's socket reduce-scatter
    (TrainUtils.scala:217 LGBM_NetworkInit ring). All devices then grow
    identical trees from the identical summed histogram, mirroring the
    reference's replicated-model-by-construction design
    (LightGBMClassifier.scala:82-85 `.reduce((b1,_)=>b1)`).
  - Categorical splits are LightGBM's many-vs-many sorted-subset search
    (LightGBMUtils.scala:63-88 metadata feeding lib_lightgbm's categorical
    path): at each node the categories are ordered by grad/(hess+cat_smooth)
    and scanned as prefixes of that ordering, exactly like a numeric
    feature — the winning prefix becomes a per-node category BITSET
    (TreeArrays.cat_bitset) that routes rows. cat_l2 adds extra L2 to
    categorical split gains; max_cat_threshold caps the smaller side of the
    subset; the other/unseen bin (0) always routes right, matching
    LightGBM's unseen-category semantics and keeping every trained model
    expressible in its finite on-file bitsets.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..parallel.mesh import DATA_AXIS
from ..parallel.collectives import pcast, psum_exact_fixedpoint

__all__ = ["TreeArrays", "GrowConfig", "make_grow_fn", "pad_rows"]


class TreeArrays(NamedTuple):
    """SoA tree layout (M = 2*num_leaves - 1 nodes, fixed)."""

    feature: jnp.ndarray        # (M,) int32, -1 on leaves
    threshold_bin: jnp.ndarray  # (M,) int32 (numeric: <= goes left;
                                #  categorical: sorted-prefix length - 1)
    is_categorical: jnp.ndarray # (M,) bool
    left: jnp.ndarray           # (M,) int32, -1 on leaves
    right: jnp.ndarray          # (M,) int32
    value: jnp.ndarray          # (M,) float32 (already shrunk by learning_rate)
    is_leaf: jnp.ndarray        # (M,) bool
    gain: jnp.ndarray           # (M,) float32 split gain (importance bookkeeping)
    cat_bitset: jnp.ndarray     # (M, B) bool — bins routed LEFT at a
                                # categorical node (many-vs-many subset);
                                # all-False on numeric/leaf nodes


class GrowConfig(NamedTuple):
    num_leaves: int = 31
    max_depth: int = -1           # <=0: unlimited (bounded by num_leaves)
    max_bin: int = 255
    min_data_in_leaf: float = 20.0
    min_sum_hessian_in_leaf: float = 1e-3
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_gain_to_split: float = 0.0
    learning_rate: float = 0.1
    # voting-parallel (tree_learner=voting_parallel, LightGBMParams.scala:12-13):
    # each shard proposes its top-k features by local root gain, shards vote,
    # and only the globally top-2k voted features' histograms are merged —
    # the top_k/all_gather mapping from SURVEY.md §2.2. 0 = full data-parallel.
    voting_top_k: int = 0
    # LightGBM's `deterministic` param, TPU-style: route the histogram
    # all-reduce through the bit-exact fixed-point psum
    # (parallel/collectives.py) so the merged histogram — and therefore the
    # grown tree — is identical bits under any reduction order or device
    # permutation. Off by default: plain psum is faster and the replicated
    # model is still self-consistent within one compiled program.
    deterministic: bool = False
    # categorical split controls (LightGBM's cat_smooth / cat_l2 /
    # max_cat_threshold, with LightGBM's defaults)
    cat_smooth: float = 10.0
    cat_l2: float = 10.0
    max_cat_threshold: int = 32


def pad_rows(n: int, shards: int) -> int:
    """Rows padded up so the data axis divides evenly (mask kills the pad)."""
    return ((n + shards - 1) // shards) * shards


def tree_apply(tree: "TreeArrays", bins, max_steps: int):
    """Vectorized gather-walk of one tree over binned rows -> (n,) values.

    Traceable (no jit of its own) so callers compose it inside their own
    scan/jit — the fused loop uses it for early-stopping validation scores,
    the booster host loop for incremental validation updates.
    """
    n = bins.shape[0]
    node = jnp.zeros((n,), jnp.int32)

    def body(_, node):
        f = jnp.maximum(tree.feature[node], 0)
        col = bins[jnp.arange(n), f]
        bcol = jnp.minimum(col, tree.cat_bitset.shape[-1] - 1)
        go_left = jnp.where(
            tree.is_categorical[node],
            tree.cat_bitset[node, bcol],
            col <= tree.threshold_bin[node],
        )
        leaf = tree.feature[node] < 0
        return jnp.where(
            leaf, node, jnp.where(go_left, tree.left[node], tree.right[node])
        )

    node = jax.lax.fori_loop(0, max_steps, body, node)
    return tree.value[node]


def _l1_threshold(g, l1):
    return jnp.sign(g) * jnp.maximum(jnp.abs(g) - l1, 0.0)


def _leaf_objective(g, h, l1, l2):
    """-Thr(G)^2 / (H + l2): the (negated) optimal leaf loss."""
    t = _l1_threshold(g, l1)
    return (t * t) / (h + l2 + 1e-12)


# Histogram build lives in hist_kernel.py behind the kernel registry
# (core/kernels.py, the NativeLoader analogue): Pallas TPU kernel on tpu,
# one-hot-matmul XLA composition elsewhere.
from .hist_kernel import histogram as _histogram  # noqa: E402


def make_grow_fn(
    num_features: int,
    num_bins: int,
    cfg: GrowConfig,
    feature_num_bins: np.ndarray,
    categorical_mask: np.ndarray,
    mesh: Mesh | None = None,
    raw: bool = False,
):
    """Build the jitted single-tree growth function.

    Returns fn(bins(n,F) i32, grad(n,) f32, hess(n,) f32, sample_mask(n,) f32,
               feature_mask(F,) f32)
            -> (TreeArrays, per_row_value(n,) f32, node_of_row(n,) i32)

    When `mesh` has a data axis > 1 the function is shard_mapped: row inputs
    sharded on DATA_AXIS, histogram psummed, tree state replicated.

    With raw=True, returns the unjitted core closure (taking an explicit
    axis_name kwarg) so callers — the fused boosting loop — can compose it
    inside their own scan/shard_map.
    """
    nl = cfg.num_leaves
    m = 2 * nl - 1
    fbins = jnp.asarray(feature_num_bins, jnp.int32)          # (F,)
    is_cat_f = jnp.asarray(categorical_mask, bool)            # (F,)
    max_depth = cfg.max_depth if cfg.max_depth and cfg.max_depth > 0 else nl + 1

    def grow(bins, grad, hess, sample_mask, feature_mask, axis_name=None):
        n = bins.shape[0]

        def hist_psum(h, axis):
            """The one histogram-merge collective. deterministic=True pins
            the result to identical bits under any reduction order/device
            permutation (LightGBM's `deterministic`; SURVEY.md §7)."""
            if cfg.deterministic:
                return psum_exact_fixedpoint(h, axis)
            return jax.lax.psum(h, axis)

        def local_hist(mask):
            # channels: [grad, hess, row count] — count is unweighted so
            # min_data_in_leaf means ROWS (LightGBM semantics), not weight
            # mass, even under sample weights / GOSS amplification.
            stats = jnp.stack(
                [grad * mask, hess * mask, (mask > 0).astype(jnp.float32)],
                axis=-1,
            )
            return _histogram(bins, stats, num_bins)           # (F, B, 3)

        # -- static bin-validity masks ---------------------------------
        cat_any = bool(np.asarray(categorical_mask).any())
        bin_idx = jnp.arange(num_bins)                         # (B,)
        # numeric: can split at any bin except the last real one
        valid_num = bin_idx[None, :] < (fbins[:, None] - 1)    # (F, B)
        # categorical: positions index PREFIXES of the per-node sorted
        # category ordering (many-vs-many); a prefix of size k+1 must leave
        # at least one real category on the right, and the smaller side of
        # the subset is capped by max_cat_threshold (LightGBM semantics)
        n_cats = fbins[:, None] - 1                            # excl. other-bin 0
        kp1 = bin_idx[None, :] + 1
        valid_cat = (kp1 <= n_cats - 1) & (
            jnp.minimum(kp1, n_cats - kp1) <= cfg.max_cat_threshold
        )
        valid_base = jnp.where(is_cat_f[:, None], valid_cat, valid_num)

        def cat_order(hist, fb):
            """Per-node category ordering by grad/(hess + cat_smooth) —
            the sort underlying LightGBM's many-vs-many subset search.
            The other/missing bin (0), empty bins, and out-of-range bins
            key to +inf so they sort last and never join a (valid) left
            prefix: unseen categories route RIGHT, which also keeps every
            trained model expressible in LightGBM's finite on-file
            bitsets. argsort is stable, so recomputing at split time
            reproduces the gain scan's ordering bit-for-bit."""
            g, h, c = hist[..., 0], hist[..., 1], hist[..., 2]
            ratio = g / (h + cfg.cat_smooth)
            pos = jnp.arange(num_bins)
            pushed = (pos == 0) | (c <= 0) | (pos >= fb[..., None])
            return jnp.argsort(jnp.where(pushed, jnp.inf, ratio), axis=-1)

        # -- voting-parallel feature pre-selection (per tree) -----------
        # Each shard proposes top-k features by LOCAL root-split gain
        # (lax.top_k); a psum of one-hot proposals is the vote tally (the
        # all_gather+count collapse); only the winning 2k features'
        # histograms are merged for this tree. Reference semantics:
        # tree_learner=voting_parallel inside lib_lightgbm
        # (LightGBMParams.scala:12-13).
        def split_gain_tensor(hist, ng, nh, nc, vb):
            """(F,B) split gains for one node's histogram — the single source
            of the gain/constraint rule (shared by the splitter and the
            voting ranking so they can never drift apart).

            Numeric columns: position b = split at bin b (cumulative left).
            Categorical columns: position k = left set is the first k+1
            categories of this node's grad/hess-sorted order (cumulative
            over the SORTED histogram), with cat_l2 extra regularization."""
            cum = jnp.cumsum(hist, axis=1)
            if cat_any:
                order = cat_order(hist, fbins)                 # (F, B)
                sorted_hist = jnp.take_along_axis(
                    hist, order[..., None], axis=1
                )
                left = jnp.where(
                    is_cat_f[:, None, None],
                    jnp.cumsum(sorted_hist, axis=1), cum,
                )
            else:
                left = cum
            gl, hl, cl = left[..., 0], left[..., 1], left[..., 2]
            gr, hr, cr = ng - gl, nh - hl, nc - cl
            ok = (
                vb
                & (cl >= cfg.min_data_in_leaf)
                & (cr >= cfg.min_data_in_leaf)
                & (hl >= cfg.min_sum_hessian_in_leaf)
                & (hr >= cfg.min_sum_hessian_in_leaf)
            )
            parent = _leaf_objective(ng, nh, cfg.lambda_l1, cfg.lambda_l2)
            gain = (
                _leaf_objective(gl, hl, cfg.lambda_l1, cfg.lambda_l2)
                + _leaf_objective(gr, hr, cfg.lambda_l1, cfg.lambda_l2)
                - parent
            )
            if cat_any:
                l2c = cfg.lambda_l2 + cfg.cat_l2
                gain_cat = (
                    _leaf_objective(gl, hl, cfg.lambda_l1, l2c)
                    + _leaf_objective(gr, hr, cfg.lambda_l1, l2c)
                    - _leaf_objective(ng, nh, cfg.lambda_l1, l2c)
                )
                gain = jnp.where(is_cat_f[:, None], gain_cat, gain)
            return jnp.where(ok, gain, -jnp.inf)

        sel_vec = None      # (F,) 0/1 — None = all features (data-parallel)
        sel_ids = None      # (k2,) voted feature ids (psum only these)
        tot_feat = 0        # any kept feature's bins sum to the node totals
        root_h0 = None
        if axis_name is not None and cfg.voting_top_k > 0:
            h_local = local_hist(sample_mask)
            tot_local = h_local[0].sum(axis=0)                 # (3,)
            vb = valid_base & (feature_mask[:, None] > 0)
            gains_f = split_gain_tensor(
                h_local, tot_local[0], tot_local[1], tot_local[2], vb
            ).max(axis=1)                                      # (F,)
            k2 = min(2 * cfg.voting_top_k, num_features)
            top_gains, top_ids = jax.lax.top_k(gains_f, k2)
            # a -inf "candidate" is a filler slot, not a proposal — it must
            # not vote, or junk low-index features outpoll informative ones
            ballots = (top_gains > -jnp.inf).astype(jnp.float32)
            votes = jnp.zeros((num_features,), jnp.float32).at[top_ids].add(ballots)
            votes = jax.lax.psum(votes, axis_name)
            # deterministic tie-break: more votes first, then lower feature id
            sel_score = votes * (num_features + 1) - jnp.arange(num_features)
            _, sel_ids = jax.lax.top_k(sel_score, k2)
            sel_vec = jnp.zeros((num_features,), jnp.float32).at[sel_ids].set(1.0)
            feature_mask = feature_mask * sel_vec
            tot_feat = jnp.argmin(-sel_vec).astype(jnp.int32)  # first kept feature

        def hist_for(mask):
            h = local_hist(mask)
            if sel_ids is not None:
                # the communication saving that motivates voting mode: only
                # the k2 voted features' histograms cross the ICI (k2*B*3
                # floats instead of F*B*3), scattered back to full shape.
                # fresh zeros (not zeros_like) keep the result axis-invariant
                # under shard_map — h itself is device-varying.
                h_sel = hist_psum(h[sel_ids], axis_name)       # (k2, B, 3)
                h = jnp.zeros(h.shape, h.dtype).at[sel_ids].set(h_sel)
            elif axis_name is not None:
                h = hist_psum(h, axis_name)
            return h  # (F, B, 3)

        if sel_ids is not None:
            root_h0 = jnp.zeros(h_local.shape, h_local.dtype).at[sel_ids].set(
                hist_psum(h_local[sel_ids], axis_name)
            )

        valid_bin = valid_base & (feature_mask[:, None] > 0)

        def best_split_of(hist, node_g, node_h, node_c):
            """hist: (F,B,3) for one node -> (gain, feature, bin)."""
            gain = split_gain_tensor(hist, node_g, node_h, node_c, valid_bin)
            flat = jnp.argmax(gain)
            f, b = flat // num_bins, flat % num_bins
            return gain.reshape(-1)[flat], f.astype(jnp.int32), b.astype(jnp.int32)

        # -- state ------------------------------------------------------
        tree = TreeArrays(
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
        node_of_row = jnp.zeros((n,), jnp.int32)
        if axis_name is not None:
            # constants are replicated under shard_map; row state must carry
            # the varying-manual-axis type so lax.cond branches agree
            node_of_row = pcast(node_of_row, (axis_name,), to="varying")
        hists = jnp.zeros((m, num_features, num_bins, 3), jnp.float32)
        hists = hists.at[0].set(
            root_h0 if root_h0 is not None else hist_for(sample_mask)
        )
        depth = jnp.zeros((m,), jnp.int32)
        # cached per-leaf best splits (recomputed only for new children)
        best_gain = jnp.full((m,), -jnp.inf, jnp.float32)
        best_f = jnp.zeros((m,), jnp.int32)
        best_b = jnp.zeros((m,), jnp.int32)

        def node_totals(h):
            # summing any single KEPT feature's bins over a node = node
            # totals (every row lands in exactly one bin per feature);
            # tot_feat is 0 normally, the first voted feature under voting
            t = h[:, tot_feat, :, :].sum(axis=1)               # (M, 3)
            return t[:, 0], t[:, 1], t[:, 2]                   # grad, hess, count

        g0, f0, b0 = best_split_of(hists[0], *(x[0] for x in node_totals(hists)))
        best_gain = best_gain.at[0].set(g0)
        best_f = best_f.at[0].set(f0)
        best_b = best_b.at[0].set(b0)

        State = tuple  # (tree, node_of_row, hists, depth, best_*, num_nodes, done)

        def step(k, state):
            # No lax.cond: the step computes the split unconditionally and
            # gates every state update on `act` (selects are cheap; a cond
            # carrying the multi-MB hists state costs more than the masked
            # ops it skips, and trees that exhaust their gain before
            # num_leaves are the rare case). Active-step results are
            # bit-identical to the old cond branch.
            (tree, node_of_row, hists, depth, best_gain, best_f, best_b,
             num_nodes, done) = state
            splittable = tree.is_leaf & (depth < max_depth) & (best_gain > cfg.min_gain_to_split)
            cand_gain = jnp.where(splittable, best_gain, -jnp.inf)
            p = jnp.argmax(cand_gain).astype(jnp.int32)
            no_split = (cand_gain[p] <= cfg.min_gain_to_split) | (cand_gain[p] == -jnp.inf)
            done = done | no_split
            act = ~done

            def gated(old, new):
                return jnp.where(act, new, old)

            f, b = best_f[p], best_b[p]
            cat = is_cat_f[f]
            # clamp so an inactive step still indexes in bounds; node nl_id
            # has no rows yet when active, and all writes are gated when not
            nl_id = jnp.minimum(num_nodes, m - 2)
            nr_id = nl_id + 1
            col = bins[jnp.arange(n), jnp.broadcast_to(f, (n,))]
            if cat_any:
                # materialize the winning prefix of this node's sorted
                # category order as a bitset over bins (the many-vs-many
                # left set); cat_order on the stored node histogram
                # reproduces the gain scan's ordering exactly
                order_f = cat_order(hists[p, f], fbins[f])     # (B,)
                in_prefix = jnp.arange(num_bins) <= b
                bitset = (
                    jnp.zeros((num_bins,), bool).at[order_f].set(in_prefix)
                    & cat
                )
                go_left = jnp.where(cat, bitset[col], col <= b)
            else:
                bitset = jnp.zeros((num_bins,), bool)
                go_left = col <= b
            in_p = (node_of_row == p) & act
            node_of_row = jnp.where(
                in_p, jnp.where(go_left, nl_id, nr_id), node_of_row
            )
            lh = hist_for(sample_mask * (node_of_row == nl_id) * act)
            rh = hists[p] - lh
            hists = hists.at[nl_id].set(gated(hists[nl_id], lh))
            hists = hists.at[nr_id].set(gated(hists[nr_id], rh))
            tree = tree._replace(
                feature=tree.feature.at[p].set(gated(tree.feature[p], f)),
                threshold_bin=tree.threshold_bin.at[p].set(gated(tree.threshold_bin[p], b)),
                is_categorical=tree.is_categorical.at[p].set(gated(tree.is_categorical[p], cat)),
                cat_bitset=tree.cat_bitset.at[p].set(
                    gated(tree.cat_bitset[p], bitset)
                ),
                left=tree.left.at[p].set(gated(tree.left[p], nl_id)),
                right=tree.right.at[p].set(gated(tree.right[p], nr_id)),
                is_leaf=(tree.is_leaf
                         .at[p].set(gated(tree.is_leaf[p], False))
                         .at[nl_id].set(gated(tree.is_leaf[nl_id], True))
                         .at[nr_id].set(gated(tree.is_leaf[nr_id], True))),
                gain=tree.gain.at[p].set(gated(tree.gain[p], best_gain[p])),
            )
            depth = (depth
                     .at[nl_id].set(gated(depth[nl_id], depth[p] + 1))
                     .at[nr_id].set(gated(depth[nr_id], depth[p] + 1)))
            # refresh cached best splits for the two new leaves
            ng2, nh2, nc2 = node_totals(hists)
            gl_, fl_, bl_ = best_split_of(hists[nl_id], ng2[nl_id], nh2[nl_id], nc2[nl_id])
            gr_, fr_, br_ = best_split_of(hists[nr_id], ng2[nr_id], nh2[nr_id], nc2[nr_id])
            best_gain = (best_gain
                         .at[nl_id].set(gated(best_gain[nl_id], gl_))
                         .at[nr_id].set(gated(best_gain[nr_id], gr_))
                         .at[p].set(gated(best_gain[p], -jnp.inf)))
            best_f = (best_f.at[nl_id].set(gated(best_f[nl_id], fl_))
                      .at[nr_id].set(gated(best_f[nr_id], fr_)))
            best_b = (best_b.at[nl_id].set(gated(best_b[nl_id], bl_))
                      .at[nr_id].set(gated(best_b[nr_id], br_)))
            num_nodes = num_nodes + jnp.where(act, 2, 0).astype(num_nodes.dtype)
            return (tree, node_of_row, hists, depth, best_gain, best_f, best_b,
                    num_nodes, done)

        state = (tree, node_of_row, hists, depth, best_gain, best_f, best_b,
                 jnp.int32(1), jnp.asarray(False))
        state = jax.lax.fori_loop(0, nl - 1, step, state)
        (tree, node_of_row, hists, depth, best_gain, best_f, best_b,
         num_nodes, done) = state

        # leaf values (shrunk), from final per-node totals
        ng, nh, nc = node_totals(hists)
        leaf_val = -_l1_threshold(ng, cfg.lambda_l1) / (nh + cfg.lambda_l2 + 1e-12)
        leaf_val = jnp.where(tree.is_leaf, leaf_val * cfg.learning_rate, 0.0)
        tree = tree._replace(value=leaf_val.astype(jnp.float32))
        per_row_value = tree.value[node_of_row]
        # node_of_row is returned so callers can renew leaf outputs
        # post-hoc (LightGBM RenewTreeOutput for the L1-family objectives)
        return tree, per_row_value, node_of_row

    if raw:
        return grow
    if mesh is not None and mesh.shape.get(DATA_AXIS, 1) > 1:
        row = P(DATA_AXIS)
        grow_sharded = functools.partial(grow, axis_name=DATA_AXIS)
        # check_vma=False: on the TPU the body calls the Pallas histogram
        # kernel, and neither a pallas_call's kernel body nor its
        # interpreter is typed for varying manual axes (an iota built
        # inside the kernel meets per-shard blocks), so the check cannot
        # pass there; one setting on every backend keeps CPU tests honest
        fn = shard_map(
            grow_sharded,
            mesh=mesh, check_vma=False,
            in_specs=(P(DATA_AXIS, None), row, row, row, P()),
            out_specs=(
                TreeArrays(*([P()] * len(TreeArrays._fields))),
                row,
                row,
            ),
        )
        return jax.jit(fn)
    return jax.jit(functools.partial(grow, axis_name=None))
