"""Booster: the trained GBDT model — array-of-trees SoA + jit predict.

Reference: src/lightgbm/src/main/scala/LightGBMBooster.scala:15-181 (model
string, per-row JNI predict via LGBM_BoosterPredictForMat) and TrainUtils.scala
:74-121 (boosting loop). The reference predicts ONE ROW PER JNI CALL
(LightGBMBooster.scala:38-113, a known perf sink noted in SURVEY.md §3.1);
here prediction is a single jitted batched traversal: `lax.scan` over trees,
vectorized gather-walk over nodes, all rows at once on the MXU-fed VPU.

Training (`Booster.train`) drives the jitted grow function from engine.py:
  host loop over boosting rounds (compiled once, dispatched ~num_iterations
  times), objective grad/hess fused on device, bagging / GOSS masks on
  device, optional early stopping against a validation split.
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from .binning import BinMapper
from .engine import GrowConfig, TreeArrays, pad_rows
from .objectives import (get_leaf_renewal, get_objective,
                         get_validation_loss, init_raw_score)
from ..parallel.mesh import DATA_AXIS

__all__ = ["Booster", "TrainOptions"]

_FORMAT_VERSION = 2   # v2: many-vs-many categorical subset splits (cat_sets)


@dataclass
class TrainOptions:
    """Training hyperparameters (reference: the 19 params of
    src/lightgbm/src/main/scala/LightGBMParams.scala:11-149 plus regressor
    objective extras, LightGBMRegressor.scala:17-36)."""

    objective: str = "regression"
    boosting_type: str = "gbdt"       # gbdt | rf | dart | goss
    # data_parallel (default) | voting_parallel (reference tree_learner,
    # LightGBMParams.scala:12-14); voting uses `top_k` local candidates
    tree_learner: str = "data_parallel"
    top_k: int = 20
    num_iterations: int = 100
    learning_rate: float = 0.1
    num_leaves: int = 31
    max_bin: int = 255
    # LightGBM bin_construct_sample_cnt: bin boundaries sketched from a
    # deterministic sample of this many values per column (0 = all rows)
    bin_construct_sample_cnt: int = 200_000
    max_depth: int = -1
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_gain_to_split: float = 0.0
    bagging_fraction: float = 1.0
    bagging_freq: int = 0
    bagging_seed: int = 3
    feature_fraction: float = 1.0
    feature_fraction_seed: int = 2
    # goss
    top_rate: float = 0.2
    other_rate: float = 0.1
    # dart
    drop_rate: float = 0.1
    drop_seed: int = 4
    # objective extras
    alpha: float = 0.9                 # huber/quantile
    tweedie_variance_power: float = 1.5
    fair_c: float = 1.0
    num_class: int = 1
    boost_from_average: bool = True
    is_unbalance: bool = False
    early_stopping_round: int = 0
    # LightGBM's `deterministic` flag: bit-exact histogram merge under any
    # reduction order / device permutation (parallel/collectives.py)
    deterministic: bool = False
    categorical_indexes: tuple[int, ...] = ()
    # categorical split controls (LightGBM defaults): sorted-subset
    # smoothing, extra L2 on categorical gains, smaller-side size cap
    cat_smooth: float = 10.0
    cat_l2: float = 10.0
    max_cat_threshold: int = 32
    # bin the training matrix ON DEVICE (BinMapper.transform_device): a
    # jitted compare-count instead of the serial host binary search —
    # worth ~2 s at Higgs scale on a 1-core host. float32 comparisons, so
    # boundary-straddling values may bin one off vs the host path;
    # opt-in, numeric-only (rejected with categorical features).
    device_binning: bool = False
    # device storage dtype of the binned matrix: "int32" (default) or
    # "uint8". Bins never exceed max_bin (<=255) + the missing bin, so
    # uint8 is lossless and reads 4x less HBM in every histogram pass —
    # the dominant stream of a large fit. Kernels cast to int32 inside
    # VMEM. Opt-in until measured on-chip (tools/sweep_hist.py sweeps it).
    bin_dtype: str = "int32"
    init_model: "Booster | None" = None   # warm start (reference modelString)
    # preemption-tolerant training (resilience/elastic.py): with a
    # checkpoint_dir and checkpoint_every_n > 0 the fused boosting loop
    # runs in round-aligned chunks, snapshotting the booster-so-far after
    # each chunk and resuming from the newest verified snapshot. The
    # resumed model is byte-identical to an uninterrupted fit (global
    # round indices feed every RNG fold). Disabled under early stopping
    # (the ES carry spans rounds) and single-class dart (cross-round
    # drop algebra).
    checkpoint_dir: "str | None" = None
    checkpoint_every_n: int = 0
    seed: int = 0


@dataclass
class Booster:
    """Immutable trained model. Trees are stacked SoA arrays (T, M)."""

    feature: np.ndarray          # (T, M) int32
    threshold_bin: np.ndarray    # (T, M) int32
    threshold_value: np.ndarray  # (T, M) float64 — raw-space numeric threshold
    is_categorical: np.ndarray   # (T, M) bool
    left: np.ndarray             # (T, M) int32
    right: np.ndarray            # (T, M) int32
    value: np.ndarray            # (T, M) float32 (shrunk leaf values)
    gain: np.ndarray             # (T, M) float32
    tree_class: np.ndarray       # (T,) int32 — class id per tree (multiclass)
    # (T, M, Bc) bool — bins routed LEFT at categorical nodes (many-vs-many
    # subset splits); Bc=1 placeholder for models with no categorical splits
    cat_bitset: np.ndarray
    bin_mapper: BinMapper
    objective: str = "regression"
    num_class: int = 1
    init_score: float = 0.0
    best_iteration: int = -1
    feature_names: list[str] = field(default_factory=list)
    class_labels: list[float] | None = None   # original classifier label values
    _predict_cache: dict = field(default_factory=dict, repr=False, compare=False)

    # ------------------------------------------------------------------ #
    # training                                                           #
    # ------------------------------------------------------------------ #

    @staticmethod
    def train(
        x: np.ndarray,
        y: np.ndarray,
        opts: TrainOptions,
        weights: np.ndarray | None = None,
        valid: tuple[np.ndarray, np.ndarray] | None = None,
        mesh=None,
        feature_names: list[str] | None = None,
        log: Callable[[str], None] | None = None,
    ) -> "Booster":
        from .sparse import as_features, is_sparse

        tl = str(opts.tree_learner)
        if tl not in ("serial", "data", "data_parallel", "voting", "voting_parallel"):
            raise ValueError(
                f"tree_learner={tl!r} is not supported; use data_parallel or "
                "voting_parallel (LightGBMParams.scala:12-14)"
            )
        if opts.boosting_type not in ("gbdt", "rf", "dart", "goss"):
            raise ValueError(
                f"boosting_type={opts.boosting_type!r} is not supported; "
                "use gbdt, rf, dart, or goss (LightGBMParams.scala:56-60)"
            )
        if tl.startswith("voting") and mesh is None and log is not None:
            log("tree_learner=voting_parallel has no effect without a mesh "
                "(use_mesh=True); training data_parallel")

        x = as_features(x)  # CSR stays sparse until binning (binned-dense path)
        y = np.asarray(y, dtype=np.float64)
        n, f = x.shape
        k = opts.num_class if opts.objective == "multiclass" else 1

        warm = opts.init_model
        shared_hit = None
        if warm is not None:
            mapper = warm.bin_mapper
        else:
            # AutoML sweeps seed a SharedBinContext: when this fit's rows
            # are a slice of the seeded full table under the same binning
            # config, reuse its mapper + device-resident binned matrix
            # (a device gather) instead of re-sketching and re-binning
            from .shared_bins import lookup_shared_bins, note_bin_build

            shared_hit = lookup_shared_bins(x, opts)
            if shared_hit is not None:
                mapper = shared_hit.mapper
            else:
                mapper = BinMapper(
                    max_bin=opts.max_bin,
                    categorical_indexes=tuple(opts.categorical_indexes),
                    bin_construct_sample_cnt=opts.bin_construct_sample_cnt,
                ).fit(x)
                note_bin_build()
        use_device_bin = (
            opts.device_binning and not mapper.category_maps
            and not is_sparse(x)
        )
        if use_device_bin:
            # train/serve consistency: the device transform compares in f32,
            # so snap the mapper's boundaries through f32 up front — predict
            # (host f64 searchsorted) then routes against the SAME thresholds
            # the training matrix was binned with, instead of f64 boundaries
            # that can disagree for values straddling an f32-invisible gap.
            # Snap a COPY: a warm-start caller's model keeps the boundaries
            # it was trained/serialized with.
            import copy as _copy

            mapper = _copy.copy(mapper)
            mapper.upper_bounds = np.float64(
                np.float32(mapper.upper_bounds))
        bins_np = (None if use_device_bin or shared_hit is not None
                   else mapper.transform(x))
        num_bins = max(int(mapper.num_bins.max(initial=2)), 2)

        # pad rows so the data mesh axis divides evenly
        shards = mesh.shape.get(DATA_AXIS, 1) if mesh is not None else 1
        n_pad = pad_rows(n, shards)
        pad = n_pad - n
        if pad and bins_np is not None:
            bins_np = np.concatenate([bins_np, np.zeros((pad, f), np.int32)])
        if opts.bin_dtype not in ("int32", "uint8"):
            raise ValueError(
                f"bin_dtype must be 'int32' or 'uint8', got {opts.bin_dtype!r}"
            )
        use_u8 = opts.bin_dtype == "uint8"
        if use_u8 and num_bins > 256:
            # loudly, not silently: the caller asked for the 4x-narrower
            # storage but this mapper's bin count (max_bin > 255, possibly
            # via a warm-start mapper) cannot fit it
            import warnings

            warnings.warn(
                f"bin_dtype='uint8' requested but the bin mapper produces "
                f"{num_bins} bins (> 256); storing bins as int32",
                stacklevel=2,
            )
            if log:
                log(f"bin_dtype='uint8' unavailable at {num_bins} bins; "
                    "using int32")
            use_u8 = False
        if use_device_bin:
            bd = mapper.transform_device(x)
            if pad:
                bd = jnp.concatenate(
                    [bd, jnp.zeros((pad, f), bd.dtype)])
            bins_dev = bd.astype(jnp.uint8 if use_u8 else jnp.int32)
        elif shared_hit is not None:
            # binning is row-wise, so the gathered rows of the shared
            # full-table matrix ARE this fit's binned matrix
            bd = shared_hit.device_bins()
            if pad:
                bd = jnp.concatenate(
                    [bd, jnp.zeros((pad, f), bd.dtype)])
            bins_dev = bd.astype(jnp.uint8 if use_u8 else jnp.int32)
        else:
            bins_dev = jnp.asarray(
                bins_np, jnp.uint8 if use_u8 else jnp.int32)

        w = np.ones(n, np.float64) if weights is None else np.asarray(weights, np.float64)
        if opts.is_unbalance and opts.objective == "binary":
            # reference is_unbalance: scale positive class by neg/pos ratio
            npos = max(float((y == 1).sum()), 1.0)
            nneg = max(float((y == 0).sum()), 1.0)
            w = np.where(y == 1, w * nneg / npos, w)
        base_mask_np = np.concatenate([w, np.zeros(pad)]).astype(np.float32)
        base_mask = jnp.asarray(base_mask_np)

        obj_fn = get_objective(
            opts.objective,
            alpha=opts.alpha,
            tweedie_variance_power=opts.tweedie_variance_power,
            fair_c=opts.fair_c,
        )

        cfg = GrowConfig(
            num_leaves=opts.num_leaves,
            max_depth=opts.max_depth,
            max_bin=opts.max_bin,
            min_data_in_leaf=float(opts.min_data_in_leaf),
            min_sum_hessian_in_leaf=opts.min_sum_hessian_in_leaf,
            lambda_l1=opts.lambda_l1,
            lambda_l2=opts.lambda_l2,
            min_gain_to_split=opts.min_gain_to_split,
            learning_rate=1.0 if opts.boosting_type == "rf" else opts.learning_rate,
            voting_top_k=(
                opts.top_k if str(opts.tree_learner).startswith("voting") else 0
            ),
            deterministic=opts.deterministic,
            cat_smooth=opts.cat_smooth,
            cat_l2=opts.cat_l2,
            max_cat_threshold=opts.max_cat_threshold,
        )
        cat_mask = np.zeros(f, bool)
        for ci in opts.categorical_indexes:
            cat_mask[int(ci)] = True
        # L1-family leaf renewal (LightGBM RenewTreeOutput) — see
        # objectives.get_leaf_renewal; applied inside the fused scans
        renewal = get_leaf_renewal(opts.objective, alpha=opts.alpha)
        renew_alpha, renew_weighted = renewal if renewal else (None, False)

        if opts.objective == "multiclass":
            init = 0.0
            y_enc = np.eye(k)[y.astype(int)]                  # (n, K)
            y_pad = np.concatenate([y_enc, np.zeros((pad, k))])
            pred = jnp.zeros((n_pad, k), jnp.float32)
        else:
            init = (
                warm.init_score
                if warm is not None
                else init_raw_score(opts.objective, y, w, opts.boost_from_average, opts.alpha)
            )
            y_pad = np.concatenate([y, np.zeros(pad)])
            pred = jnp.full((n_pad,), init, jnp.float32)
        # warm start: begin from the previous model's raw predictions
        prev_trees: list[dict[str, np.ndarray]] = []
        start_iter = 0
        if warm is not None:
            if opts.boosting_type == "rf":
                # rf trees are independent of pred (bagged averages): keep
                # pred at init, and UNDO the 1/T_prev scale baked into the
                # saved trees so the final uniform 1/T_total rescale is right.
                n_prev = max(warm.feature.shape[0] // k, 1)
                for t in range(warm.feature.shape[0]):
                    prev_trees.append(_scale_tree(warm._tree_dict(t), float(n_prev)))
            else:
                raw = warm.predict_raw(x)
                raw_p = np.concatenate([raw, np.zeros((pad,) + raw.shape[1:])])
                pred = jnp.asarray(raw_p, jnp.float32).reshape(pred.shape)
                for t in range(warm.feature.shape[0]):
                    prev_trees.append(warm._tree_dict(t))
            start_iter = len(prev_trees) // k

        # reference semantics: a nonzero top-level `seed` deterministically
        # derives the per-purpose seeds (LightGBM Config: seed generates
        # bagging/feature_fraction/drop seeds unless set individually)
        bag_seed, feat_seed, drop_seed = (
            opts.bagging_seed, opts.feature_fraction_seed, opts.drop_seed
        )
        if opts.seed:
            dr = np.random.default_rng(opts.seed)
            bag_seed, feat_seed, drop_seed = (
                int(dr.integers(2**31)) for _ in range(3)
            )
        trees: list[dict[str, np.ndarray]] = list(prev_trees)
        tree_classes: list[int] = [int(c) for c in (warm.tree_class if warm is not None else [])]

        # early stopping: tracked inside the fused scan (post-stop rounds
        # take a no-op branch). Undefined for rf (independent trees) and
        # single-class dart (trees are rescaled after the fact).
        best_iter = -1
        es_unsupported = opts.boosting_type == "rf" or (
            opts.boosting_type == "dart" and k == 1
        )
        es_active = (
            valid is not None and opts.early_stopping_round > 0 and not es_unsupported
        )
        if valid is not None and opts.early_stopping_round > 0 and es_unsupported and log:
            log(f"early stopping is not supported for boosting_type={opts.boosting_type}; ignored")
        if es_active:
            xv, yv = valid
            xv = as_features(xv)
            yv = np.asarray(yv, np.float64)
            xv_bins = jnp.asarray(mapper.transform(xv), jnp.int32)
            nv = len(yv)
            if warm is not None:
                # validation scores must include the warm model's trees
                val_raw = jnp.asarray(warm.predict_raw(xv), jnp.float32)
            elif k > 1:
                val_raw = jnp.zeros((nv, k), jnp.float32)
            else:
                val_raw = jnp.full((nv,), init, jnp.float32)
            y_val_dev = (
                jnp.asarray(yv.astype(int)) if k > 1 else jnp.asarray(yv, jnp.float32)
            )
            val_loss_fn = get_validation_loss(
                opts.objective, alpha=opts.alpha,
                tweedie_variance_power=opts.tweedie_variance_power,
            )

        # ---- fused path: one XLA program for the whole boosting loop ----
        # gbdt/goss/rf, INCLUDING early stopping (tracked in the scan carry,
        # post-stop rounds take a lax.cond no-op branch). Multiclass dart
        # also lands here: its updates are plain additive gbdt (the
        # drop/renormalize algebra is single-model only — the fused dart
        # branch below), so it rides the gbdt scan and gains the same O(1)
        # dispatch count. It thereby adopts the fused path's single-seed
        # convention (bag + feature draws fold from one key, like multiclass
        # gbdt) in place of the old host loop's separate numpy streams —
        # models differ from pre-reroute fits only by RNG stream; the
        # committed benchmark gates stay within tolerance.
        if opts.boosting_type in ("gbdt", "goss", "rf") or (
            opts.boosting_type == "dart" and k > 1
        ):
            from .fused import FusedTrainSpec, make_fused_train_fn

            num_rounds = opts.num_iterations - start_iter
            ckpt = None
            ck_every = int(opts.checkpoint_every_n or 0)
            if opts.checkpoint_dir and ck_every > 0 and num_rounds > 0:
                if es_active:
                    if log:
                        log("checkpointing disabled: early stopping carries "
                            "cross-round state inside the fused scan")
                else:
                    from ..resilience.elastic import TrainingCheckpointer

                    ckpt = TrainingCheckpointer(opts.checkpoint_dir)
            fit_done = 0
            if ckpt is not None:
                restored = _restore_snapshot(ckpt, opts, k, start_iter, log)
                if restored is not None:
                    snap, fit_done = restored
                    fit_done = min(fit_done, num_rounds)
                    trees = [snap._tree_dict(t)
                             for t in range(snap.feature.shape[0])]
                    tree_classes = [int(c) for c in snap.tree_class]
                    if opts.boosting_type != "rf" and fit_done > 0:
                        # re-derive the carry: predict_raw accumulates
                        # init + per-tree f32 adds in strict tree order,
                        # bit-identical to the in-scan pred updates
                        raw = snap.predict_raw(x)
                        raw_p = np.concatenate(
                            [raw, np.zeros((pad,) + raw.shape[1:])])
                        pred = jnp.asarray(
                            raw_p, jnp.float32).reshape(pred.shape)
            if num_rounds > 0 and fit_done < num_rounds:
                spec_boosting = (
                    "gbdt" if opts.boosting_type == "dart"
                    else opts.boosting_type
                )

                def build_fused(nr):
                    spec = FusedTrainSpec(
                        num_rounds=nr,
                        num_class=k,
                        boosting_type=spec_boosting,
                        bagging_fraction=opts.bagging_fraction,
                        bagging_freq=opts.bagging_freq,
                        feature_fraction=opts.feature_fraction,
                        top_rate=opts.top_rate,
                        other_rate=opts.other_rate,
                        early_stopping_round=(
                            opts.early_stopping_round if es_active else 0
                        ),
                        renew_alpha=renew_alpha,
                        renew_weighted=renew_weighted,
                    )
                    return make_fused_train_fn(
                        f, num_bins, cfg, mapper.num_bins, cat_mask, obj_fn,
                        spec, mesh=mesh,
                        cache_key=(opts.objective, opts.alpha,
                                   opts.tweedie_variance_power, opts.fair_c),
                        val_loss_fn=val_loss_fn if es_active else None,
                    )

                y_f = jnp.asarray(y_pad, jnp.float32)
                seed = opts.seed if opts.seed else opts.bagging_seed
                names = ("feature", "threshold_bin", "is_categorical",
                         "left", "right", "value", "gain", "cat_bitset")

                def append_round_trees(t_stack, nr):
                    t_host = {kf: np.asarray(v)
                              for kf, v in t_stack._asdict().items()}
                    for r in range(nr):
                        for cls in range(k):
                            idx = (r, cls) if k > 1 else (r,)
                            trees.append(
                                {name: t_host[name][idx] for name in names})
                            tree_classes.append(cls)

                if ckpt is None:
                    fused = build_fused(num_rounds)
                    if log:
                        log(f"fused boosting: {num_rounds} rounds x {k} "
                            "class(es) in one XLA program (first run "
                            "compiles)")
                    args = (bins_dev, y_f, base_mask, pred, seed,
                            jnp.asarray(0, jnp.int32))
                    if es_active:
                        args = args + (xv_bins, y_val_dev, val_raw)
                    t_stack, _pred, (r_best_dev, stopped_dev) = fused(*args)
                    kept_rounds = num_rounds
                    if es_active:
                        r_best = int(r_best_dev)
                        if bool(stopped_dev) and r_best >= 0:
                            kept_rounds = r_best + 1
                            if log:
                                log(f"early stop after round "
                                    f"{r_best + start_iter} (kept "
                                    f"{kept_rounds}/{num_rounds} rounds)")
                        best_iter = start_iter + r_best if r_best >= 0 else -1
                    if log:
                        log(f"fused boosting: done ({kept_rounds * k} trees)")
                    append_round_trees(t_stack, kept_rounds)
                else:
                    from ..resilience.elastic import preempt_now

                    # chunk boundaries must land on bagging-period edges:
                    # the gbdt bag refreshes when it % bagging_freq == 0
                    # and carries otherwise, and the carried bag lives only
                    # on device. (rf resamples and goss redraws per round,
                    # so any boundary works there.)
                    gbdt_bagging = (spec_boosting == "gbdt"
                                    and opts.bagging_fraction < 1.0
                                    and opts.bagging_freq > 0)
                    align = opts.bagging_freq if gbdt_bagging else 1
                    chunk = max((ck_every // align) * align, align)
                    if log:
                        log(f"fused boosting: {num_rounds} rounds x {k} "
                            f"class(es), checkpoint every {chunk} rounds"
                            + (f" (resumed at round {start_iter + fit_done})"
                               if fit_done else ""))
                    fused_chunk, chunk_nr = None, -1
                    while fit_done < num_rounds:
                        nr = min(chunk, num_rounds - fit_done)
                        if nr != chunk_nr:
                            fused_chunk, chunk_nr = build_fused(nr), nr
                        t_stack, pred, _ = fused_chunk(
                            bins_dev, y_f, base_mask, pred, seed,
                            jnp.asarray(fit_done, jnp.int32))
                        append_round_trees(t_stack, nr)
                        fit_done += nr
                        path = _write_snapshot(
                            ckpt, trees, tree_classes, mapper, opts, init,
                            feature_names, fit_done, start_iter, k)
                        preempt_now(None, lambda: path, "gbdt-train")
                    if log:
                        log(f"fused boosting: done ({num_rounds * k} trees)")
            if opts.boosting_type == "rf" and trees:
                scale = 1.0 / max(len(trees) // k, 1)
                trees = [_scale_tree(t, scale) for t in trees]
            out = Booster._from_tree_dicts(
                trees, tree_classes, mapper, opts, init, feature_names or []
            )
            out.best_iteration = best_iter
            return out

        # ---- fused dart (single-class): drop bookkeeping IN the scan ----
        if opts.boosting_type == "dart" and k == 1:
            from .fused import FusedTrainSpec, make_fused_dart_fn

            num_rounds = opts.num_iterations - start_iter
            if num_rounds > 0:
                spec = FusedTrainSpec(
                    num_rounds=num_rounds,
                    num_class=1,
                    boosting_type="dart",
                    bagging_fraction=opts.bagging_fraction,
                    bagging_freq=opts.bagging_freq,
                    feature_fraction=opts.feature_fraction,
                    drop_rate=opts.drop_rate,
                    renew_alpha=renew_alpha,
                    renew_weighted=renew_weighted,
                )
                fused = make_fused_dart_fn(
                    f, num_bins, cfg, mapper.num_bins, cat_mask, obj_fn, spec,
                    mesh=mesh,
                    cache_key=(opts.objective, opts.alpha,
                               opts.tweedie_variance_power, opts.fair_c),
                )
                if log:
                    log(f"fused dart: {num_rounds} rounds in one XLA "
                        "program (first run compiles)")
                # per-purpose seeds (already master-seed-derived above):
                # varying bagging_seed alone must change only the bags
                t_stack, w_dev, _pred = fused(
                    bins_dev, jnp.asarray(y_pad, jnp.float32), base_mask,
                    pred, drop_seed, bag_seed, feat_seed,
                )
                t_host = {kf: np.asarray(v) for kf, v in t_stack._asdict().items()}
                w_host = np.asarray(w_dev, np.float64)
                names = ("feature", "threshold_bin", "is_categorical",
                         "left", "right", "value", "gain", "cat_bitset")
                for r in range(num_rounds):
                    trees.append(_scale_tree(
                        {name: t_host[name][r] for name in names},
                        float(w_host[r]),
                    ))
                    tree_classes.append(0)
            out = Booster._from_tree_dicts(
                trees, tree_classes, mapper, opts, init, feature_names or []
            )
            out.best_iteration = best_iter
            return out

        raise RuntimeError(   # unreachable: boosting_type validated above
            f"unhandled boosting_type {opts.boosting_type!r}"
        )

    # ------------------------------------------------------------------ #
    # construction helpers                                               #
    # ------------------------------------------------------------------ #

    def _tree_dict(self, t: int) -> dict[str, np.ndarray]:
        return {
            "feature": self.feature[t],
            "threshold_bin": self.threshold_bin[t],
            "is_categorical": self.is_categorical[t],
            "left": self.left[t],
            "right": self.right[t],
            "value": self.value[t],
            "gain": self.gain[t],
            "cat_bitset": self.cat_bitset[t],
        }

    @staticmethod
    def from_tree_dicts(
        trees: "list[dict[str, np.ndarray]]",
        tree_classes: "list[int]",
        mapper: BinMapper,
        opts: TrainOptions,
        init: float,
        feature_names: "list[str]",
    ) -> "Booster":
        """Assemble a Booster from externally-grown per-tree dicts (the
        `TreeBuilder.to_dict` layout) — the entry point for distributed
        growers (resilience.elastic_fleet) whose trees are built outside
        `Booster.train` but must score/serialize exactly like its own."""
        return Booster._from_tree_dicts(
            trees, tree_classes, mapper, opts, init, feature_names)

    @staticmethod
    def _from_tree_dicts(
        trees: list[dict[str, np.ndarray]],
        tree_classes: list[int],
        mapper: BinMapper,
        opts: TrainOptions,
        init: float,
        feature_names: list[str],
    ) -> "Booster":
        if not trees:
            m = 2 * opts.num_leaves - 1
            z = lambda dt, fill=0: np.full((0, m), fill, dt)  # noqa: E731
            return Booster(
                feature=z(np.int32, -1), threshold_bin=z(np.int32),
                threshold_value=z(np.float64), is_categorical=z(bool),
                left=z(np.int32, -1), right=z(np.int32, -1),
                value=z(np.float32), gain=z(np.float32),
                cat_bitset=np.zeros((0, m, 1), bool),
                tree_class=np.zeros(0, np.int32), bin_mapper=mapper,
                objective=opts.objective,
                num_class=opts.num_class if opts.objective == "multiclass" else 1,
                init_score=init, feature_names=feature_names,
            )
        stack = lambda key: np.stack([np.asarray(t[key]) for t in trees])  # noqa: E731
        feature = stack("feature").astype(np.int32)
        thr_bin = stack("threshold_bin").astype(np.int32)
        is_cat = stack("is_categorical").astype(bool)
        # per-node category bitsets; widths can differ between warm-start
        # trees and this fit's trees — pad to the widest, and collapse to a
        # width-1 placeholder when the model has no categorical splits
        bitsets = [np.asarray(t["cat_bitset"], bool) for t in trees]
        bc = max(b.shape[-1] for b in bitsets)
        cat_bitset = np.stack([
            np.pad(b, ((0, 0), (0, bc - b.shape[-1]))) for b in bitsets
        ])
        if not is_cat.any():
            cat_bitset = cat_bitset[:, :, :1]
        # raw-space thresholds for numeric splits — one vectorized
        # (feature, bin) table lookup over all (tree, node) pairs; a Python
        # loop here is O(T*M) per fit and dominated training. Categorical
        # nodes have no single raw threshold (many-vs-many subset): NaN.
        ub = np.asarray(mapper.upper_bounds, np.float64)        # (F, B)
        n_b = ub.shape[1]
        split = feature >= 0
        fidx = np.where(split, feature, 0)
        bidx = np.minimum(thr_bin, n_b - 1)
        thr_val = np.where(
            split,
            np.where(is_cat, np.nan, ub[fidx, bidx]),
            0.0,
        )
        return Booster(
            feature=feature,
            threshold_bin=thr_bin,
            threshold_value=thr_val,
            is_categorical=is_cat,
            cat_bitset=cat_bitset,
            left=stack("left").astype(np.int32),
            right=stack("right").astype(np.int32),
            value=stack("value").astype(np.float32),
            gain=stack("gain").astype(np.float32),
            tree_class=np.asarray(tree_classes, np.int32),
            bin_mapper=mapper,
            objective=opts.objective,
            num_class=opts.num_class if opts.objective == "multiclass" else 1,
            init_score=init,
            feature_names=feature_names,
        )

    # ------------------------------------------------------------------ #
    # prediction                                                         #
    # ------------------------------------------------------------------ #

    @property
    def num_trees(self) -> int:
        return int(self.feature.shape[0])

    @property
    def num_features(self) -> int:
        return self.bin_mapper.num_features

    def _traverse_fn(self):
        """Jitted batched traversal over binned inputs: scan over trees,
        gather-walk num_leaves steps deep (fixed bound)."""
        key = "traverse"
        if key in self._predict_cache:
            return self._predict_cache[key]
        max_steps = int(self.feature.shape[1] // 2 + 1)  # deepest leaf-wise chain
        k = self.num_class
        # trees process in BLOCKS: within a block the gather-walks run
        # vmapped (T-way batched work for the TPU), blocks run as a scan so
        # live memory stays O(block * n) rather than O(T * n). Padding
        # trees are all-leaf/zero-value: they walk to node 0 and add 0.
        t_total = self.feature.shape[0]
        block = min(64, max(t_total, 1))
        pad = (-t_total) % block

        def padded(a, fill=0):
            a = np.asarray(a)
            if not pad:
                return a
            shape = (pad,) + a.shape[1:]
            return np.concatenate([a, np.full(shape, fill, a.dtype)])

        def blocked(a):
            return jnp.asarray(a).reshape((-1, block) + a.shape[1:])

        stacked = dict(
            feature=blocked(padded(self.feature, -1)),
            thr=blocked(padded(self.threshold_bin)),
            cat=blocked(padded(self.is_categorical)),
            bitset=blocked(padded(self.cat_bitset)),
            left=blocked(padded(self.left, -1)),
            right=blocked(padded(self.right, -1)),
            value=blocked(padded(self.value)),
            cls=blocked(padded(self.tree_class)),
        )
        bc = int(self.cat_bitset.shape[-1])

        @jax.jit
        def run(bins):
            n = bins.shape[0]
            out0 = jnp.zeros((n, k), jnp.float32) if k > 1 else jnp.full(
                (n,), self.init_score, jnp.float32
            )

            def walk_one(tr):
                """Leaf values of ONE tree for every row — vmapped over
                trees below, so XLA sees all T gather-walks as one batched
                program instead of T sequential ones."""
                node = jnp.zeros((n,), jnp.int32)

                def body(_, node):
                    f = jnp.maximum(tr["feature"][node], 0)
                    col = bins[jnp.arange(n), f]
                    go_left = jnp.where(
                        tr["cat"][node],
                        tr["bitset"][node, jnp.minimum(col, bc - 1)],
                        col <= tr["thr"][node],
                    )
                    leaf = tr["feature"][node] < 0
                    return jnp.where(
                        leaf, node,
                        jnp.where(go_left, tr["left"][node], tr["right"][node]),
                    )

                node = jax.lax.fori_loop(0, max_steps, body, node)
                return tr["value"][node]

            # accumulate IN TREE ORDER (a cheap scan of adds) so the
            # float32 sum is bit-identical to the host/C++ walk — the
            # expensive gather-walks stay batched within each block
            def add_one(acc, tv):
                val, cls = tv
                if k > 1:
                    return acc.at[:, cls].add(val), None
                return acc + val, None

            def do_block(acc, blk):
                vals = jax.vmap(walk_one)(blk)       # (block, n)
                acc, _ = jax.lax.scan(add_one, acc, (vals, blk["cls"]))
                return acc, None

            acc, _ = jax.lax.scan(do_block, out0, stacked)
            return acc

        self._predict_cache[key] = run
        return run

    # Below this row count a single device dispatch (upload, launch,
    # readback) costs far more than walking the trees on host —
    # the latency-path analogue of LightGBM's per-row CPU predict
    # (LightGBMBooster.scala:21-113). The host walk replays the jitted
    # traversal with identical float32 accumulation order, so both paths are
    # bit-identical.
    HOST_PREDICT_MAX_ROWS = 512

    def _predict_raw_host(self, bins: np.ndarray) -> np.ndarray:
        n = bins.shape[0]
        k = self.num_class
        max_steps = int(self.feature.shape[1] // 2 + 1)
        # native per-row scoring (the LGBM_BoosterPredictForMat analogue,
        # mmlspark_tpu/native); bit-identical to the numpy walk below.
        # The prepared closure caches the immutable tree arrays' ctypes
        # marshalling — rebuilt only if this instance never made one
        # (trees never change after construction; truncated views are new
        # instances with their own cache slot).
        fn = self._predict_cache.get("host_fn")
        if fn is None:
            from ..native import make_tree_predictor

            fn = make_tree_predictor(
                self.feature, self.threshold_bin, self.is_categorical,
                self.left, self.right, self.value, self.tree_class,
                k, max_steps, self.init_score, self.cat_bitset,
            )
            # truncated views get fresh instances with empty caches, and
            # the LRU eviction above only touches ("truncated", n) keys
            self._predict_cache["host_fn"] = fn or False
        if fn:
            return fn(np.asarray(bins, np.int32))
        out = (np.zeros((n, k), np.float32) if k > 1
               else np.full((n,), self.init_score, np.float32))
        for t in range(self.num_trees):
            node = self._walk_tree(t, bins, max_steps)
            val = self.value[t][node].astype(np.float32)
            if k > 1:
                out[:, int(self.tree_class[t])] += val
            else:
                out = out + val
        return out

    def truncated(self, num_iteration: int) -> "Booster":
        """A view of the model using only the first `num_iteration` boosting
        rounds (reference: LightGBM predict's num_iteration / the
        bestIteration early-stopping slice). One round = one tree, or K
        trees under multiclass."""
        import dataclasses

        # LightGBM semantics: num_iteration <= 0 means "all iterations" —
        # the predict(num_iteration=best_iteration) idiom must not produce
        # an empty model when no early stopping occurred (best_iteration=-1)
        if num_iteration is None or int(num_iteration) <= 0:
            return self
        key = ("truncated", int(num_iteration))
        if key in self._predict_cache:
            # move-to-end: the bound below evicts least-RECENTLY-used views,
            # so a repeated 1..N sweep (N>8) doesn't evict next sweep's keys
            view = self._predict_cache.pop(key)
            self._predict_cache[key] = view
            return view
        per_round = self.num_class if self.objective == "multiclass" else 1
        t = min(int(num_iteration) * per_round, self.num_trees)
        view = dataclasses.replace(
            self,
            feature=self.feature[:t], threshold_bin=self.threshold_bin[:t],
            threshold_value=self.threshold_value[:t],
            is_categorical=self.is_categorical[:t],
            cat_bitset=self.cat_bitset[:t],
            left=self.left[:t], right=self.right[:t],
            value=self.value[:t], gain=self.gain[:t],
            tree_class=self.tree_class[:t],
            best_iteration=-1,
            _predict_cache={},
        )
        self._predict_cache[key] = view
        # bound the view cache: a per-iteration eval sweep over a large model
        # would otherwise cache one view (each with its own jitted traversal)
        # per distinct num_iteration for the booster's lifetime
        trunc_keys = [k for k in self._predict_cache
                      if isinstance(k, tuple) and k and k[0] == "truncated"]
        for stale in trunc_keys[:-8]:
            del self._predict_cache[stale]
        return view

    def _walk_tree(self, t: int, bins: np.ndarray, max_steps: int) -> np.ndarray:
        """Leaf node index of every row in tree t — the single numpy
        traversal shared by host scoring and pred_leaf (semantics changes
        happen in ONE place)."""
        n = bins.shape[0]
        rows = np.arange(n)
        feature, thr = self.feature[t], self.threshold_bin[t]
        cat, left, right = self.is_categorical[t], self.left[t], self.right[t]
        bitset = self.cat_bitset[t]
        bc = bitset.shape[-1]
        node = np.zeros(n, np.int64)
        for _ in range(max_steps):
            f = np.maximum(feature[node], 0)
            col = bins[rows, f]
            go_left = np.where(cat[node],
                               bitset[node, np.minimum(col, bc - 1)],
                               col <= thr[node])
            leaf = feature[node] < 0
            node = np.where(leaf, node,
                            np.where(go_left, left[node], right[node]))
        return node

    def predict_leaf(self, x: np.ndarray) -> np.ndarray:
        """Per-row leaf NODE index for every tree -> (n, T) int32
        (reference: LightGBM predict(pred_leaf=True); useful for
        tree-embedding features)."""
        from .sparse import as_features

        x = as_features(x)
        bins = self.bin_mapper.transform(x).astype(np.int32)
        n = bins.shape[0]
        max_steps = int(self.feature.shape[1] // 2 + 1)
        out = np.zeros((n, self.num_trees), np.int32)
        for t in range(self.num_trees):
            out[:, t] = self._walk_tree(t, bins, max_steps)
        return out

    def predict_raw(self, x: np.ndarray, device: str | None = None,
                    num_iteration: int | None = None) -> np.ndarray:
        """Raw margin scores: (n,) or (n, K) for multiclass.

        device: None = auto (host walk for small batches, jitted device
        traversal otherwise), or explicitly "host" / "device".
        num_iteration: score with only the first N boosting rounds."""
        from .sparse import as_features

        if num_iteration is not None:
            return self.truncated(num_iteration).predict_raw(x, device=device)
        x = as_features(x)
        if self.num_trees == 0:
            shape = (len(x), self.num_class) if self.num_class > 1 else (len(x),)
            return np.full(shape, self.init_score, np.float32)
        if device is None:
            device = "host" if len(x) <= self.HOST_PREDICT_MAX_ROWS else "device"
        binned = self.bin_mapper.transform(x).astype(np.int32)
        if device == "host":
            return self._predict_raw_host(binned)
        return np.asarray(self._traverse_fn()(jnp.asarray(binned)))

    def transform_score(self, raw: np.ndarray) -> np.ndarray:
        """Raw margins -> transformed prediction (sigmoid / softmax / exp
        per objective — reference LightGBMBooster.score semantics).
        Factored out so callers that already hold the margins (e.g. the
        classification model's transform, which outputs BOTH columns)
        never pay the bin+traverse pass twice."""
        raw = np.asarray(raw, np.float64)
        if self.objective == "binary":
            return 1.0 / (1.0 + np.exp(-raw))
        if self.objective == "multiclass":
            e = np.exp(raw - raw.max(axis=-1, keepdims=True))
            return e / e.sum(axis=-1, keepdims=True)
        if self.objective in ("poisson", "gamma", "tweedie"):
            return np.exp(raw)
        return raw

    def predict(self, x: np.ndarray, device: str | None = None,
                num_iteration: int | None = None) -> np.ndarray:
        """Probability / transformed prediction (reference
        LightGBMBooster.score semantics)."""
        return self.transform_score(
            self.predict_raw(x, device=device, num_iteration=num_iteration))

    # objectives whose transform_score is the identity — the fused device
    # path can return raw margins directly for these
    IDENTITY_OBJECTIVES = (
        "regression", "l1", "l2", "huber", "fair", "quantile", "mape")

    def device_predict_fn(self):
        """(params, fn) for the pipeline fusion engine (core/fusion.py):
        `fn(params, x_f32) -> raw margins`, with the tree table and bin
        boundaries passed as DEVICE-RESIDENT params rather than baked into
        the executable as constants (so they upload once per segment, not
        once per compiled shape).

        This is the fused decode->bin->traverse inference kernel: ONE
        jitted program from the raw f32 feature matrix to margins, with
        binning as a vectorized `searchsorted` over ADJUSTED float32
        boundary keys (O(n*F*log B) instead of the O(n*F*B) broadcast
        compare it replaces).

        Bit-identity with the staged path: the traversal mirrors
        `_traverse_fn` exactly (same blocking, same tree-order float32
        accumulation), and binning replays the host's float64
        `searchsorted(ub, x, 'left')` == count(ub < x) via per-boundary
        keys `key = pred(f32(ub)) if f32(ub) rounded up else f32(ub)`:
        for float32-representable x, `key < x  <=>  ub < x` in both
        rounding cases (not-rounded-up: no f32 lies in (ub, f32(ub)], so
        f32(ub) < x iff ub < x; rounded-up: x > pred(f32(ub)) iff
        x >= f32(ub) iff ub < x, since no f32 lies strictly between ub
        and f32(ub)), and the keys stay nondecreasing (a decrease would
        need ub_i <= f32-midpoint < ub_{i+1} < the same midpoint). So
        `searchsorted(keys, x, 'left')` == count(ub < x) bit-for-bit.
        Callers must guarantee x is f32-representable (the estimator's
        `ready_values` check)."""
        from .binning import MISSING_BIN

        mapper = self.bin_mapper
        if mapper.category_maps:
            raise ValueError(
                "device predict does not support categorical features")
        nb_max = mapper.total_bins
        ub64 = np.asarray(mapper.upper_bounds[:, 1:max(nb_max, 2)], np.float64)
        ub32 = ub64.astype(np.float32)
        rounded_up = ub32.astype(np.float64) > ub64
        # +inf padding boundaries have rounded_up False, so they keep the
        # key +inf and never count; finite ub beyond f32 range maps to
        # nextafter(inf) == f32max, matching the old compare for every
        # f32-representable x
        keys = np.where(rounded_up,
                        np.nextafter(ub32, np.float32(-np.inf)), ub32)

        max_steps = int(self.feature.shape[1] // 2 + 1)
        k = self.num_class
        t_total = self.feature.shape[0]
        block = min(64, max(t_total, 1))
        pad = (-t_total) % block

        def padded(a, fill=0):
            a = np.asarray(a)
            if not pad:
                return a
            shape = (pad,) + a.shape[1:]
            return np.concatenate([a, np.full(shape, fill, a.dtype)])

        def blocked(a):
            return np.ascontiguousarray(a).reshape((-1, block) + a.shape[1:])

        params = dict(
            keys=keys,
            nb=np.asarray(mapper.num_bins, np.int32),
            trees=dict(
                feature=blocked(padded(self.feature, -1)),
                thr=blocked(padded(self.threshold_bin)),
                cat=blocked(padded(self.is_categorical)),
                bitset=blocked(padded(self.cat_bitset)),
                left=blocked(padded(self.left, -1)),
                right=blocked(padded(self.right, -1)),
                value=blocked(padded(self.value)),
                cls=blocked(padded(self.tree_class)),
            ),
        )
        bc = int(self.cat_bitset.shape[-1])
        init = float(self.init_score)

        def fn(params, x):
            x = x.astype(jnp.float32)
            keys, nb = params["keys"], params["nb"]
            # one binary search per (row, feature) against the adjusted
            # keys — the NaN result is overwritten by the isnan select
            cnt = jax.vmap(
                lambda kys, col: jnp.searchsorted(kys, col, side="left"),
                in_axes=(0, 1), out_axes=1,
            )(keys, x).astype(jnp.int32)
            b = jnp.clip(cnt + 1, 1, jnp.maximum(nb[None] - 1, 1))
            b = jnp.where(jnp.isnan(x), MISSING_BIN, b)
            # host transform skips nb<=1 columns entirely (even NaN stays 0)
            bins = jnp.where(nb[None] <= 1, 0, b).astype(jnp.int32)

            n = bins.shape[0]
            out0 = (jnp.zeros((n, k), jnp.float32) if k > 1
                    else jnp.full((n,), init, jnp.float32))

            def walk_one(tr):
                node = jnp.zeros((n,), jnp.int32)

                def body(_, node):
                    f = jnp.maximum(tr["feature"][node], 0)
                    col = bins[jnp.arange(n), f]
                    go_left = jnp.where(
                        tr["cat"][node],
                        tr["bitset"][node, jnp.minimum(col, bc - 1)],
                        col <= tr["thr"][node],
                    )
                    leaf = tr["feature"][node] < 0
                    return jnp.where(
                        leaf, node,
                        jnp.where(go_left, tr["left"][node], tr["right"][node]),
                    )

                node = jax.lax.fori_loop(0, max_steps, body, node)
                return tr["value"][node]

            def add_one(acc, tv):
                val, cls = tv
                if k > 1:
                    return acc.at[:, cls].add(val), None
                return acc + val, None

            def do_block(acc, blk):
                vals = jax.vmap(walk_one)(blk)
                acc, _ = jax.lax.scan(add_one, acc, (vals, blk["cls"]))
                return acc, None

            acc, _ = jax.lax.scan(do_block, out0, params["trees"])
            return acc

        return params, fn

    def device_predict_shardings(self, mesh, params=None):
        """Placement of `device_predict_fn` params under a mesh: everything
        REPLICATED — every row's traversal reads the whole binning table
        (keys/nb) and every tree SoA, while rows themselves shard
        over the data axis (the fusion engine's default input sharding).
        Stating the contract explicitly keeps the scoring path's placement
        pinned even if the engine's default ever changes."""
        import jax

        from ..parallel.mesh import replicated_sharding

        if params is None:
            params, _ = self.device_predict_fn()
        repl = replicated_sharding(mesh)
        return jax.tree.map(lambda _: repl, params)

    # ------------------------------------------------------------------ #
    # importances / persistence                                          #
    # ------------------------------------------------------------------ #

    def feature_importances(self, importance_type: str = "split") -> np.ndarray:
        """Reference: LightGBMBooster getFeatureImportances(split|gain)."""
        imp = np.zeros(self.num_features, np.float64)
        mask = self.feature >= 0
        if importance_type == "split":
            np.add.at(imp, self.feature[mask], 1.0)
        elif importance_type == "gain":
            np.add.at(imp, self.feature[mask], self.gain[mask])
        else:
            raise ValueError("importance_type must be 'split' or 'gain'")
        return imp

    def to_text(self) -> str:
        """Portable text model (reference saveNativeModel,
        LightGBMBooster.scala:115-124).

        Categorical subset splits serialize sparsely: `cat_sets` lists
        `[tree, node, [left bins...]]` for categorical nodes only, plus
        the bitset width — a (T, M, B) dense bool dump would dwarf the
        rest of the payload."""
        cat_sets = []
        for t, m in zip(*np.nonzero(self.is_categorical & (self.feature >= 0))):
            bins_left = np.nonzero(self.cat_bitset[t, m])[0]
            cat_sets.append([int(t), int(m), [int(b) for b in bins_left]])
        payload = {
            "format": "mmlspark_tpu.gbdt",
            "version": _FORMAT_VERSION,
            "objective": self.objective,
            "num_class": self.num_class,
            "init_score": self.init_score,
            "best_iteration": self.best_iteration,
            "feature_names": self.feature_names,
            "class_labels": self.class_labels,
            "tree_class": self.tree_class.tolist(),
            "trees": {
                "feature": self.feature.tolist(),
                "threshold_bin": self.threshold_bin.tolist(),
                "threshold_value": self.threshold_value.tolist(),
                "is_categorical": self.is_categorical.tolist(),
                "left": self.left.tolist(),
                "right": self.right.tolist(),
                "value": self.value.tolist(),
                "gain": self.gain.tolist(),
                "cat_bitset_width": int(self.cat_bitset.shape[-1]),
                "cat_sets": cat_sets,
            },
            "bin_mapper": self.bin_mapper.to_dict(),
        }
        return json.dumps(payload)

    @staticmethod
    def from_text(text: str) -> "Booster":
        d = json.loads(text)
        if d.get("format") != "mmlspark_tpu.gbdt":
            raise ValueError("not a mmlspark_tpu gbdt model")
        t = d["trees"]
        arr = lambda key, dt: np.asarray(t[key], dtype=dt)  # noqa: E731
        feature = arr("feature", np.int32)
        thr_bin = arr("threshold_bin", np.int32)
        is_cat = arr("is_categorical", bool)
        n_t, m = feature.shape
        mapper = BinMapper.from_dict(d["bin_mapper"])
        # bitset width must cover EVERY bin any categorical column can
        # produce (the traversal clamps col to bc-1; an under-sized bitset
        # would alias high bins onto the clamp index and flip their
        # routing), so take it from the mapper, not from the split bins
        full_bc = int(max(np.asarray(mapper.num_bins).max(initial=1), 1))
        if "cat_sets" in t:
            bc = max(int(t.get("cat_bitset_width", 1)), full_bc if is_cat.any() else 1)
            cat_bitset = np.zeros((n_t, m, bc), bool)
            for tt, mm, bins_left in t["cat_sets"]:
                cat_bitset[int(tt), int(mm), np.asarray(bins_left, int)] = True
        else:
            # version-1 files: categorical splits were one-vs-rest on a
            # single bin (col == threshold_bin); the equivalent subset is
            # the singleton bitset, so old saved models keep their exact
            # predictions under the bitset traversal
            bc = full_bc if is_cat.any() else 1
            cat_bitset = np.zeros((n_t, m, bc), bool)
            for tt, mm in zip(*np.nonzero(is_cat & (feature >= 0))):
                cat_bitset[tt, mm, thr_bin[tt, mm]] = True
        return Booster(
            feature=feature,
            threshold_bin=thr_bin,
            threshold_value=arr("threshold_value", np.float64),
            is_categorical=is_cat,
            cat_bitset=cat_bitset,
            left=arr("left", np.int32),
            right=arr("right", np.int32),
            value=arr("value", np.float32),
            gain=arr("gain", np.float32),
            tree_class=np.asarray(d["tree_class"], np.int32),
            bin_mapper=mapper,
            objective=d["objective"],
            num_class=int(d["num_class"]),
            init_score=float(d["init_score"]),
            best_iteration=int(d.get("best_iteration", -1)),
            feature_names=list(d.get("feature_names", [])),
            class_labels=d.get("class_labels"),
        )

    def save_native_model(self, path: str, format: str = "json") -> None:
        """Write the model to disk: this framework's JSON (default) or
        LightGBM's own model.txt (`format="lightgbm"`) — the reference's
        saveNativeModel surface (LightGBMClassifier.py shim)."""
        if format not in ("json", "lightgbm"):
            raise ValueError(f"format must be 'json' or 'lightgbm', got {format!r}")
        text = self.to_text() if format == "json" else self.to_lightgbm_text()
        with open(path, "w") as fh:
            fh.write(text)

    @staticmethod
    def load_native_model(path: str) -> "Booster":
        """Load a saved model: this framework's JSON format, or an actual
        LightGBM `model.txt` (auto-detected) — the reference's
        loadNativeModelFromFile (LightGBMBooster.scala:115-124)."""
        with open(path) as fh:
            text = fh.read()
        if text.lstrip().startswith("{"):
            return Booster.from_text(text)
        return Booster.from_lightgbm_text(text)

    # our objective name -> the name LightGBM writes/reads in model files
    # (these all share the identity-or-documented output transform on both
    # sides, so a roundtrip applies the same exp/sigmoid/softmax)
    _TO_LGBM = {
        "regression": "regression", "l2": "regression",
        "l1": "regression_l1", "huber": "huber", "fair": "fair",
        "poisson": "poisson", "quantile": "quantile", "mape": "mape",
        "gamma": "gamma", "tweedie": "tweedie",
    }

    def to_lightgbm_text(self) -> str:
        """Serialize in LightGBM's OWN model.txt format (the reference's
        saveNativeModel artifact, LightGBMBooster.scala:115-124) — the
        emitted file is loadable by actual LightGBM and by
        `from_lightgbm_text`, with identical predictions.

        The traversal semantics map exactly for numeric splits: node
        thresholds come from `threshold_value` (raw space), missing
        handling is encoded as missing_type=NaN + default_left
        (decision_type=10), matching this booster's NaN->missing-bin->left
        rule. ±inf inputs bin by comparison on both sides (-inf left of
        every threshold, +inf right), so they predict identically under
        real LightGBM and this booster; only NaN takes the missing path.
        `init_score` is folded into tree 0's leaf values (LightGBM
        files carry no separate init; every row hits exactly one leaf per
        tree, so the sum is unchanged).

        Categorical subset splits use LightGBM's own on-file encoding:
        decision_type bit 0 set, threshold = index into this tree's
        cat_boundaries, and cat_threshold packing the LEFT category VALUES
        as uint32 bitset words (bit v set -> raw category v goes left).
        Values outside any bitset route right on both sides (this
        booster's other-bin, LightGBM's unseen-category rule). Requires
        integer-valued non-negative categories — anything else has no
        LightGBM file representation and is refused."""
        # bin -> raw category value per categorical feature (for export)
        cat_inv: dict[int, dict[int, int]] = {}
        if bool(np.any(self.is_categorical[self.feature >= 0])):
            for j, cmap in self.bin_mapper.category_maps.items():
                inv = {}
                for v, b in cmap.items():
                    if not (float(v).is_integer() and v >= 0 and v < 2**31):
                        raise ValueError(
                            f"feature {j} has non-integer/negative category "
                            f"value {v!r}; LightGBM's categorical bitset "
                            "encoding cannot represent it"
                        )
                    inv[int(b)] = int(v)
                cat_inv[int(j)] = inv
        if self.objective not in ("binary", "multiclass") and \
                self.objective not in self._TO_LGBM:
            raise ValueError(
                f"objective {self.objective!r} has no LightGBM file-format "
                "name; export would lose the output transform"
            )
        k = self.num_class
        names = self.feature_names or [
            f"Column_{j}" for j in range(self.num_features)
        ]
        out = [
            "tree",
            "version=v3",
            f"num_class={k}",
            f"num_tree_per_iteration={k if self.objective == 'multiclass' else 1}",
            "label_index=0",
            f"max_feature_idx={self.num_features - 1}",
            ("objective=binary sigmoid:1" if self.objective == "binary"
             else f"objective=multiclass num_class:{k}"
             if self.objective == "multiclass"
             else f"objective={self._TO_LGBM[self.objective]}"),
            "feature_names=" + " ".join(names),
            "feature_infos=" + " ".join(["none"] * self.num_features),
            "",
        ]
        for t in range(self.num_trees):
            feature, left, right = self.feature[t], self.left[t], self.right[t]
            # renumber reachable nodes into LightGBM convention: internal
            # nodes 0..L-2 in preorder, leaf l -> child id -(l+1)
            internal: list[int] = []
            leaves: list[int] = []
            stack = [0]
            while stack:
                n = stack.pop()
                if feature[n] < 0:
                    leaves.append(n)
                else:
                    internal.append(n)
                    stack.append(int(right[n]))
                    stack.append(int(left[n]))
            imap = {n: i for i, n in enumerate(internal)}
            lmap = {n: i for i, n in enumerate(leaves)}

            def child(n: int) -> int:
                return imap[n] if feature[n] >= 0 else -(lmap[n] + 1)

            leaf_vals = [float(self.value[t][n]) for n in leaves]
            if t == 0 and self.objective != "multiclass" and self.init_score:
                leaf_vals = [v + float(self.init_score) for v in leaf_vals]
            # categorical nodes: threshold = per-tree cat split index;
            # bitset words pack the LEFT category values
            thresholds: list[str] = []
            decisions: list[str] = []
            cat_bounds = [0]
            cat_words: list[int] = []
            for n in internal:
                if bool(self.is_categorical[t][n]):
                    j = int(feature[n])
                    vals = [cat_inv[j][int(b)]
                            for b in np.nonzero(self.cat_bitset[t][n])[0]
                            if int(b) in cat_inv.get(j, {})]
                    if not vals or bool(self.cat_bitset[t][n][0]):
                        raise ValueError(
                            f"tree {t} node {n}: categorical left set routes "
                            "the other/unseen bin left — LightGBM's finite "
                            "bitset cannot express 'unseen goes left'"
                        )
                    n_words = max(v for v in vals) // 32 + 1
                    words = [0] * n_words
                    for v in vals:
                        words[v // 32] |= 1 << (v % 32)
                    thresholds.append(str(len(cat_bounds) - 1))
                    decisions.append("1")
                    cat_bounds.append(cat_bounds[-1] + n_words)
                    cat_words.extend(words)
                else:
                    thresholds.append(repr(float(self.threshold_value[t][n])))
                    decisions.append("10")
            num_cat = len(cat_bounds) - 1
            out += [f"Tree={t}", f"num_leaves={len(leaves)}",
                    f"num_cat={num_cat}"]
            if internal:
                out += [
                    "split_feature=" + " ".join(
                        str(int(feature[n])) for n in internal),
                    "split_gain=" + " ".join(
                        repr(float(self.gain[t][n])) for n in internal),
                    "threshold=" + " ".join(thresholds),
                    "decision_type=" + " ".join(decisions),
                    "left_child=" + " ".join(
                        str(child(int(left[n]))) for n in internal),
                    "right_child=" + " ".join(
                        str(child(int(right[n]))) for n in internal),
                ]
                if num_cat:
                    out += [
                        "cat_boundaries=" + " ".join(str(b) for b in cat_bounds),
                        "cat_threshold=" + " ".join(str(w) for w in cat_words),
                    ]
            out += [
                "leaf_value=" + " ".join(repr(v) for v in leaf_vals),
                "shrinkage=1",
                "",
            ]
        out += ["end of trees", ""]
        return "\n".join(out)

    @staticmethod
    def from_lightgbm_text(text: str) -> "Booster":
        """Parse LightGBM's OWN native model.txt format.

        This grounds tree semantics in the reference implementation's
        artifact: a model trained by actual LightGBM (what the reference's
        saveNativeModel emits, LightGBMBooster.scala:115-124) loads here
        and must reproduce its predictions (tests/test_lightgbm_format.py
        pins this with a hand-computed fixture).

        Numeric splits are `value <= threshold -> left`. The raw-space
        thresholds become this booster's bin boundaries (one bin per
        distinct threshold per feature), making the binned traversal
        EXACTLY equivalent to LightGBM's raw comparisons — no precision
        loss on finite values; ±inf also bins by comparison (-inf left,
        +inf right of every threshold), matching LightGBM's
        `value <= threshold` routing. Missing handling: NaN maps to this
        framework's missing bin, which always sorts LEFT. Nodes whose
        missing routing this booster cannot reproduce are REJECTED rather
        than silently mispredicting: missing_type=NaN with
        default_left=false (NaN would go right) and missing_type=Zero
        (zero-band values route by default_left, not by comparison). With
        missing_type=None (bits 2-3 == 0) LightGBM coerces NaN to 0.0
        before comparing, which can also differ from missing-bin-left —
        only relevant for NaN inputs.

        Categorical splits (decision_type bit 0) load natively: each
        node's cat_threshold bitset words decode to the raw category
        values routed LEFT; the union per feature synthesizes the
        category map (one bin per value), so the per-node bin bitsets
        reproduce LightGBM's value-level routing exactly. Values absent
        from every bitset — including unseen-at-predict categories — land
        in the other-bin and route RIGHT, LightGBM's rule. NaN
        categorical inputs route right here (LightGBM's missing handling
        for categories treats them as no-match).

        Still rejected: `average_output` (rf) models and linear trees —
        both would change predictions silently if ignored. The pinned
        hand-computed fixture lives in tests/test_external_truth.py."""
        header, tree_blocks = _parse_lightgbm_sections(text)
        if "average_output" in header:
            raise ValueError(
                "average_output (rf) LightGBM models are not supported — "
                "this booster sums leaf values; loading one would "
                "mispredict by the tree count"
            )
        if header.get("linear_tree", "0") not in ("0", "") or any(
            "leaf_const" in blk or "leaf_coeff" in blk for blk in tree_blocks
        ):
            raise ValueError("linear-tree LightGBM models are not supported")
        obj_tokens = header.get("objective", "regression").split()
        objective = obj_tokens[0]
        # LightGBM's binary output transform is 1/(1+exp(-sigmoid*raw));
        # this booster applies plain sigmoid (sigmoid=1). A non-unit
        # sigmoid parameter would silently scale every probability, so
        # reject it (reject-rather-than-mispredict policy).
        for tok in obj_tokens[1:]:
            if tok.startswith("sigmoid:") and float(tok.split(":", 1)[1]) != 1.0:
                raise ValueError(
                    f"objective parameter {tok!r} != sigmoid:1 would change "
                    "the probability transform; refusing to load"
                )
        obj_map = {
            "binary": "binary", "regression": "regression",
            "regression_l2": "regression", "regression_l1": "l1",
            "multiclass": "multiclass", "huber": "huber", "fair": "fair",
            "poisson": "poisson", "quantile": "quantile",
            "gamma": "gamma", "tweedie": "tweedie", "mape": "mape",
        }
        if objective not in obj_map:
            raise ValueError(f"unsupported LightGBM objective {objective!r}")
        objective = obj_map[objective]
        num_class = int(header.get("num_class", 1))
        max_feature = int(header.get("max_feature_idx", 0))
        f = max_feature + 1
        feature_names = header.get("feature_names", "").split()

        # collect per-feature thresholds (numeric) and left-routed category
        # values (categorical) -> synthesized bin boundaries / category maps
        def _cat_left_values(blk, i):
            """Decode node i's cat_threshold bitset words -> left values."""
            bounds = blk.get("cat_boundaries", [])
            words = blk.get("cat_threshold", [])
            ci = int(blk["threshold"][i])
            if not (0 <= ci < len(bounds) - 1) or bounds[ci + 1] > len(words):
                raise ValueError(
                    "malformed categorical split: cat_boundaries/"
                    "cat_threshold do not cover the node's split index"
                )
            vals = []
            for wi in range(bounds[ci], bounds[ci + 1]):
                w = int(words[wi])
                base = 32 * (wi - bounds[ci])
                for b in range(32):
                    if (w >> b) & 1:
                        vals.append(base + b)
            return vals

        thresholds: dict[int, set] = {}
        cat_vals: dict[int, set] = {}
        for blk in tree_blocks:
            # single-leaf (constant) trees carry no split arrays at all
            for i, (feat, thr, dt) in enumerate(
                zip(blk.get("split_feature", []),
                    blk.get("threshold", []),
                    blk.get("decision_type", []))
            ):
                dt = int(dt)
                feat = int(feat)
                if dt & 1:
                    # categorical: union the left values; routing of any
                    # value not in some node's set is right, our other-bin
                    cat_vals.setdefault(feat, set()).update(
                        _cat_left_values(blk, i)
                    )
                    continue
                # decision_type bits: 0 categorical, 1 default_left,
                # 2-3 missing_type (0 none, 1 zero, 2 nan)
                missing_type = (dt >> 2) & 3
                if missing_type == 2 and not (dt & 2):
                    raise ValueError(
                        "node routes missing (NaN) RIGHT "
                        "(missing_type=NaN, default_left=false); this "
                        "booster's missing bin always sorts left — refusing "
                        "to load a model it would mispredict"
                    )
                if missing_type == 1:
                    raise ValueError(
                        "missing_type=Zero (zero_as_missing) nodes route "
                        "the zero band by default_left, not by threshold "
                        "comparison — refusing to load a model this "
                        "booster would mispredict on zero values"
                    )
                thresholds.setdefault(feat, set()).add(float(thr))
        mixed = set(thresholds) & set(cat_vals)
        if mixed:
            raise ValueError(
                f"features {sorted(mixed)} have both numeric and categorical "
                "splits in the same model file"
            )
        per_feat = {j: sorted(s) for j, s in thresholds.items()}
        max_t = max((len(v) for v in per_feat.values()), default=0)
        mapper = BinMapper(
            max_bin=max(max_t + 1, 2,
                        *(len(v) for v in cat_vals.values())) if cat_vals
            else max(max_t + 1, 2),
            categorical_indexes=tuple(sorted(cat_vals)),
        )
        mapper.num_features = f
        bounds = np.full((f, max_t + 2), np.inf, np.float64)
        nbins = np.full(f, 1, np.int32)
        for j, ts in per_feat.items():
            bounds[j, 1 : 1 + len(ts)] = ts
            nbins[j] = len(ts) + 2       # missing bin + one per threshold + top
        cat_maps = {
            j: {float(v): i + 1 for i, v in enumerate(sorted(s))}
            for j, s in cat_vals.items()
        }
        for j, cmap in cat_maps.items():
            nbins[j] = len(cmap) + 1     # other-bin + one per left value
        mapper.category_maps = cat_maps
        mapper.upper_bounds = bounds
        mapper.num_bins = nbins

        # node-layout conversion: LightGBM internal i -> node i, leaf l ->
        # node (L-1+l); child c >= 0 is internal, c < 0 is leaf -(c+1)
        m = max(2 * blk["num_leaves"] - 1 for blk in tree_blocks)
        t_count = len(tree_blocks)
        bc = max((len(cm) + 1 for cm in cat_maps.values()), default=1)
        feature = np.full((t_count, m), -1, np.int32)
        thr_bin = np.zeros((t_count, m), np.int32)
        thr_val = np.zeros((t_count, m), np.float64)
        is_cat_arr = np.zeros((t_count, m), bool)
        cat_bitset = np.zeros((t_count, m, bc), bool)
        left = np.full((t_count, m), -1, np.int32)
        right = np.full((t_count, m), -1, np.int32)
        value = np.zeros((t_count, m), np.float32)
        gain = np.zeros((t_count, m), np.float32)
        for t, blk in enumerate(tree_blocks):
            nl = blk["num_leaves"]

            def node_of(c: int, nl=nl) -> int:
                return c if c >= 0 else nl - 1 + (-c - 1)

            if nl == 1:                  # single-leaf tree (constant)
                value[t, 0] = blk["leaf_value"][0]
                continue
            for i in range(nl - 1):
                j = int(blk["split_feature"][i])
                feature[t, i] = j
                dt = int(blk["decision_type"][i])
                if dt & 1:
                    is_cat_arr[t, i] = True
                    thr_val[t, i] = np.nan
                    cmap = cat_maps[j]
                    for v in _cat_left_values(blk, i):
                        cat_bitset[t, i, cmap[float(v)]] = True
                else:
                    thr = float(blk["threshold"][i])
                    # bin index of threshold: 1 + position in the sorted list
                    thr_bin[t, i] = 1 + per_feat[j].index(thr)
                    thr_val[t, i] = thr
                left[t, i] = node_of(int(blk["left_child"][i]))
                right[t, i] = node_of(int(blk["right_child"][i]))
                if blk.get("split_gain"):
                    gain[t, i] = blk["split_gain"][i]
            for leaf, lv in enumerate(blk["leaf_value"]):
                value[t, nl - 1 + leaf] = lv

        return Booster(
            feature=feature, threshold_bin=thr_bin, threshold_value=thr_val,
            is_categorical=is_cat_arr,
            cat_bitset=cat_bitset,
            left=left, right=right, value=value, gain=gain,
            tree_class=np.asarray(
                [t % num_class for t in range(t_count)], np.int32
            ),
            bin_mapper=mapper,
            objective=objective,
            num_class=num_class if objective == "multiclass" else 1,
            init_score=0.0,              # LightGBM bakes init into leaf values
            feature_names=feature_names,
            class_labels=[0.0, 1.0] if objective == "binary" else None,
        )


def _parse_lightgbm_sections(text: str):
    """Split a LightGBM model.txt into (header dict, [tree dict, ...])."""
    header: dict[str, str] = {}
    tree_blocks: list[dict] = []
    cur: dict | None = None
    _vec_int = ("split_feature", "left_child", "right_child", "decision_type",
                "cat_boundaries", "cat_threshold")
    _vec_float = ("threshold", "leaf_value", "split_gain",
                  "leaf_const", "leaf_coeff")
    for raw in text.splitlines():
        line = raw.strip()
        if line.startswith("Tree="):
            cur = {}
            tree_blocks.append(cur)
            continue
        if line in ("end of trees", "") or line.startswith(("tree", "pandas_")):
            continue
        if "=" not in line:
            # bare flag lines ("average_output") matter: they change
            # prediction semantics, so record their presence
            if cur is None and line and " " not in line:
                header[line] = "1"
            continue
        key, val = line.split("=", 1)
        if cur is None:
            header[key] = val
        elif key == "num_leaves":
            cur[key] = int(val)
        elif key in _vec_int:
            cur[key] = [int(v) for v in val.split()] if val else []
        elif key in _vec_float:
            cur[key] = [float(v) for v in val.split()] if val else []
        # other per-tree keys (leaf_weight, internal_value, shrinkage, ...)
        # are bookkeeping the traversal doesn't need
    if not tree_blocks:
        raise ValueError("no Tree= sections found; not a LightGBM model file")
    for blk in tree_blocks:
        if "num_leaves" not in blk or "leaf_value" not in blk:
            raise ValueError("malformed LightGBM tree block")
    return header, tree_blocks


def _tree_to_host(tree: TreeArrays) -> dict[str, np.ndarray]:
    return {
        "feature": np.asarray(tree.feature),
        "threshold_bin": np.asarray(tree.threshold_bin),
        "is_categorical": np.asarray(tree.is_categorical),
        "left": np.asarray(tree.left),
        "right": np.asarray(tree.right),
        "value": np.asarray(tree.value),
        "gain": np.asarray(tree.gain),
        "cat_bitset": np.asarray(tree.cat_bitset),
    }


def _scale_tree(t: dict[str, np.ndarray], scale: float) -> dict[str, np.ndarray]:
    t = dict(t)
    t["value"] = np.asarray(t["value"]) * scale
    return t


# ---- preemption-tolerant chunked training (resilience/elastic.py) ---- #

def _ckpt_config(opts: TrainOptions, k: int, start_iter: int) -> dict:
    """The fit identity a snapshot must match to be resumable: a snapshot
    from a different config would silently change the model."""
    return {
        "objective": opts.objective, "boosting_type": opts.boosting_type,
        "num_class": int(k), "seed": int(opts.seed),
        "bagging_seed": int(opts.bagging_seed),
        "num_iterations": int(opts.num_iterations),
        "num_leaves": int(opts.num_leaves),
        "learning_rate": float(opts.learning_rate),
        "start_iter": int(start_iter),
    }


def _write_snapshot(ckpt, trees, tree_classes, mapper, opts, init,
                    feature_names, fit_done: int, start_iter: int,
                    k: int) -> str:
    """Snapshot the booster-so-far (model text roundtrips f32-exactly).
    rf trees are stored UNSCALED — the 1/T averaging happens once at the
    end of the fit, and an unscale-rescale roundtrip is not f32-exact."""
    snap = Booster._from_tree_dicts(
        trees, tree_classes, mapper, opts, init, feature_names or [])
    doc = {"kind": "gbdt", "fit_rounds_done": int(fit_done),
           "config": _ckpt_config(opts, k, start_iter),
           "model": snap.to_text()}
    return ckpt.save(json.dumps(doc).encode("utf-8"),
                     tag=f"round-{start_iter + fit_done:06d}",
                     meta={"rounds_done": int(fit_done),
                           **_ckpt_config(opts, k, start_iter)})


def _restore_snapshot(ckpt, opts, k: int, start_iter: int, log):
    """Newest verified snapshot matching this fit's config, parsed back
    into (booster, rounds_done) — or None to start from round 0."""
    loaded = ckpt.load_latest()
    if loaded is None:
        return None
    payload, entry = loaded
    try:
        doc = json.loads(payload.decode("utf-8"))
        if doc.get("kind") != "gbdt":
            raise ValueError(f"kind {doc.get('kind')!r}")
        if doc.get("config") != _ckpt_config(opts, k, start_iter):
            raise ValueError("config mismatch")
        snap = Booster.from_text(doc["model"])
        fit_done = int(doc["fit_rounds_done"])
    except (ValueError, KeyError, TypeError) as e:
        if log:
            log(f"ignoring checkpoint {entry['file']}: {e}")
        return None
    if log:
        log(f"resumed from {entry['file']}: "
            f"{fit_done} rounds already trained")
    return snap, fit_done
