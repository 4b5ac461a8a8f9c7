"""GBDT engine tests.

Mirrors the reference test strategy (SURVEY.md §4): functional suites like
src/lightgbm/src/test/scala/VerifyLightGBMClassifier.scala — quality gates on
small datasets across boosting types — plus save/load roundtrips (the
SerializationFuzzing role) and a partitions-as-workers distributed check
(mesh8 = the reference's repartition(2) trick, done with 8 CPU devices).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.core.schema import Table
from mmlspark_tpu.gbdt import (
    Booster,
    GBDTClassifier,
    GBDTClassificationModel,
    GBDTRegressor,
    GBDTRegressionModel,
)
from mmlspark_tpu.gbdt.binning import BinMapper
from mmlspark_tpu.gbdt.booster import TrainOptions


def make_classification(n=2000, f=10, seed=0, classes=2):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, f))
    logits = x[:, 0] * 2.0 + x[:, 1] - 0.5 * x[:, 2] + 0.3 * rng.normal(size=n)
    if classes == 2:
        y = (logits > 0).astype(np.float64)
    else:
        y = np.digitize(logits, np.quantile(logits, np.linspace(0, 1, classes + 1)[1:-1]))
    return x, y.astype(np.float64)


def make_regression(n=2000, f=8, seed=1):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, f))
    y = 3.0 * x[:, 0] - 2.0 * x[:, 1] + np.sin(x[:, 2]) + 0.1 * rng.normal(size=n)
    return x, y


def table_of(x, y, weight=None):
    cols = {"features": x, "label": y}
    if weight is not None:
        cols["weight"] = weight
    return Table(cols)


# --------------------------------------------------------------------- #
# binning                                                               #
# --------------------------------------------------------------------- #

class TestBinMapper:
    def test_roundtrip_order(self):
        rng = np.random.default_rng(0)
        x = rng.normal(size=(500, 3))
        bm = BinMapper(max_bin=16).fit(x)
        b = bm.transform(x)
        assert b.shape == x.shape and b.dtype == np.int32
        # binning preserves order within a feature
        for j in range(3):
            order = np.argsort(x[:, j])
            assert (np.diff(b[order, j]) >= 0).all()
        assert b.min() >= 1  # no NaNs -> nothing in the missing bin

    def test_sampled_fit_deterministic_and_close(self):
        """bin_construct_sample_cnt (LightGBM default 200k): boundaries
        come from a deterministic per-column sample, so two fits agree
        bit-wise and stay close to the full-data sketch."""
        rng = np.random.default_rng(5)
        x = rng.normal(size=(50_000, 3))
        a = BinMapper(max_bin=64, bin_construct_sample_cnt=10_000).fit(x)
        b = BinMapper(max_bin=64, bin_construct_sample_cnt=10_000).fit(x)
        np.testing.assert_array_equal(a.upper_bounds, b.upper_bounds)
        full = BinMapper(max_bin=64, bin_construct_sample_cnt=0).fit(x)
        fin = np.isfinite(full.upper_bounds[:, 1:64])
        shift = np.abs(a.upper_bounds[:, 1:64] - full.upper_bounds[:, 1:64])
        assert float(shift[fin].max()) < 0.2  # sketch, not drift

    def test_device_binning_matches_host(self):
        rng = np.random.default_rng(6)
        x = rng.normal(size=(5000, 4))
        x[10, 0], x[11, 1], x[12, 2] = np.nan, np.inf, -np.inf
        bm = BinMapper(max_bin=32).fit(x)
        host = bm.transform(x)
        dev = np.asarray(bm.transform_device(x, chunk=512))
        # f32 compare may move boundary-straddlers by one bin; semantics
        # (NaN->0, +/-inf by comparison) must match exactly
        assert (host == dev).mean() > 0.999
        assert dev[10, 0] == 0
        assert dev[11, 1] == host[11, 1] and dev[12, 2] == host[12, 2]
        with pytest.raises(ValueError, match="categorical"):
            BinMapper(max_bin=8, categorical_indexes=(0,)).fit(
                np.abs(x)).transform_device(np.abs(x))

    def test_missing_goes_to_bin0(self):
        x = np.array([[1.0], [np.nan], [2.0]])
        bm = BinMapper(max_bin=4).fit(x)
        b = bm.transform(x)
        assert b[1, 0] == 0 and b[0, 0] >= 1

    def test_categorical_frequency_bins(self):
        x = np.array([[5.0]] * 10 + [[7.0]] * 5 + [[9.0]] * 1)
        bm = BinMapper(max_bin=8, categorical_indexes=(0,)).fit(x)
        b = bm.transform(x)
        assert b[0, 0] == 1  # most frequent category -> bin 1
        assert b[10, 0] == 2
        unseen = bm.transform(np.array([[123.0]]))
        assert unseen[0, 0] == 0  # unseen -> "other" bin

    def test_serialization(self):
        x = np.random.default_rng(0).normal(size=(200, 4))
        bm = BinMapper(max_bin=32).fit(x)
        bm2 = BinMapper.from_dict(bm.to_dict())
        assert np.array_equal(bm.transform(x), bm2.transform(x))


# --------------------------------------------------------------------- #
# booster core                                                          #
# --------------------------------------------------------------------- #

class TestBooster:
    def test_host_and_device_predict_identical(self):
        """The host tree walk (latency path, no device dispatch) must be
        bit-identical to the jitted device traversal — both binary and
        multiclass, including rows that exercise categorical-style bins."""
        x, y = make_classification()
        b = Booster.train(
            x, y, TrainOptions(objective="binary", num_iterations=12, num_leaves=15)
        )
        host = b.predict_raw(x, device="host")
        dev = b.predict_raw(x, device="device")
        np.testing.assert_array_equal(np.asarray(host), np.asarray(dev))

        xm, ym = make_classification(classes=3)
        bm = Booster.train(
            xm, ym,
            TrainOptions(objective="multiclass", num_class=3,
                         num_iterations=8, num_leaves=7),
        )
        np.testing.assert_array_equal(
            np.asarray(bm.predict_raw(xm, device="host")),
            np.asarray(bm.predict_raw(xm, device="device")),
        )

    def test_binary_quality(self):
        x, y = make_classification()
        opts = TrainOptions(objective="binary", num_iterations=30, num_leaves=15)
        b = Booster.train(x, y, opts)
        acc = ((b.predict(x) >= 0.5) == y).mean()
        assert acc > 0.95

    def test_regression_quality(self):
        x, y = make_regression()
        opts = TrainOptions(objective="regression", num_iterations=50, num_leaves=31)
        b = Booster.train(x, y, opts)
        rmse = np.sqrt(np.mean((b.predict(x) - y) ** 2))
        assert rmse < 0.8, rmse

    def test_multiclass(self):
        x, y = make_classification(classes=4)
        opts = TrainOptions(
            objective="multiclass", num_class=4, num_iterations=20, num_leaves=15
        )
        b = Booster.train(x, y, opts)
        p = b.predict(x)
        assert p.shape == (len(x), 4)
        np.testing.assert_allclose(p.sum(axis=1), 1.0, atol=1e-5)
        acc = (np.argmax(p, 1) == y).mean()
        assert acc > 0.85, acc

    @pytest.mark.parametrize("boosting", ["goss", "dart", "rf"])
    def test_boosting_modes(self, boosting):
        x, y = make_classification(n=1500)
        opts = TrainOptions(
            objective="binary",
            boosting_type=boosting,
            num_iterations=25,
            num_leaves=15,
            bagging_fraction=0.8,
            bagging_freq=1,
        )
        b = Booster.train(x, y, opts)
        acc = ((b.predict(x) >= 0.5) == y).mean()
        assert acc > 0.85, (boosting, acc)

    @pytest.mark.parametrize(
        "objective", ["l1", "huber", "fair", "poisson", "quantile", "mape", "gamma", "tweedie"]
    )
    def test_regression_objectives_run(self, objective):
        x, y = make_regression(n=800)
        if objective in ("poisson", "gamma", "tweedie", "mape"):
            y = np.abs(y) + 1.0
        opts = TrainOptions(objective=objective, num_iterations=10, num_leaves=7)
        b = Booster.train(x, y, opts)
        pred = b.predict(x)
        assert np.isfinite(pred).all()

    def test_quantile_coverage(self):
        x, y = make_regression(n=2000)
        for alpha in (0.1, 0.9):
            opts = TrainOptions(
                objective="quantile", alpha=alpha, num_iterations=40, num_leaves=15
            )
            b = Booster.train(x, y, opts)
            cover = (y <= b.predict(x)).mean()
            assert abs(cover - alpha) < 0.12, (alpha, cover)

    def test_weights_shift_model(self):
        x, y = make_classification(n=1000)
        w_hi = np.where(y == 1, 10.0, 1.0)
        opts = TrainOptions(objective="binary", num_iterations=10, num_leaves=7)
        b0 = Booster.train(x, y, opts)
        b1 = Booster.train(x, y, opts, weights=w_hi)
        # upweighting positives must raise mean predicted probability
        assert b1.predict(x).mean() > b0.predict(x).mean()

    def test_early_stopping(self):
        x, y = make_classification(n=1500)
        opts = TrainOptions(
            objective="binary",
            num_iterations=200,
            num_leaves=31,
            early_stopping_round=5,
        )
        b = Booster.train(x[:1200], y[:1200], opts, valid=(x[1200:], y[1200:]))
        assert b.num_trees < 200
        assert b.best_iteration >= 0
        # trees after the best iteration must be dropped from the model
        assert b.num_trees == b.best_iteration + 1

    def test_warm_start(self):
        x, y = make_classification()
        opts1 = TrainOptions(objective="binary", num_iterations=5, num_leaves=15)
        b1 = Booster.train(x, y, opts1)
        opts2 = TrainOptions(
            objective="binary", num_iterations=15, num_leaves=15, init_model=b1
        )
        b2 = Booster.train(x, y, opts2)
        assert b2.num_trees == 15
        acc1 = ((b1.predict(x) >= 0.5) == y).mean()
        acc2 = ((b2.predict(x) >= 0.5) == y).mean()
        assert acc2 >= acc1

    def test_text_roundtrip(self):
        x, y = make_classification(n=500)
        opts = TrainOptions(objective="binary", num_iterations=5, num_leaves=7)
        b = Booster.train(x, y, opts)
        b2 = Booster.from_text(b.to_text())
        np.testing.assert_allclose(b.predict_raw(x), b2.predict_raw(x), rtol=1e-6)

    def test_feature_importances(self):
        x, y = make_regression()
        opts = TrainOptions(objective="regression", num_iterations=10, num_leaves=15)
        b = Booster.train(x, y, opts)
        imp = b.feature_importances("split")
        gain = b.feature_importances("gain")
        # features 0 and 1 carry the signal
        assert imp[0] + imp[1] > imp[3:].sum()
        assert gain[0] > 0

    def test_categorical_feature(self):
        rng = np.random.default_rng(3)
        cat = rng.integers(0, 5, size=2000).astype(np.float64)
        noise = rng.normal(size=2000)
        y = np.isin(cat, [1.0, 3.0]).astype(np.float64)
        x = np.stack([cat, noise], axis=1)
        opts = TrainOptions(
            objective="binary",
            num_iterations=20,
            num_leaves=7,
            categorical_indexes=(0,),
            min_data_in_leaf=5,
        )
        b = Booster.train(x, y, opts)
        acc = ((b.predict(x) >= 0.5) == y).mean()
        assert acc > 0.98, acc

    def test_categorical_many_vs_many_single_split(self):
        """A planted 4-of-10 category subset must separate in ONE split —
        the LightGBM sorted-subset search (many-vs-many); one-vs-rest on a
        single bin structurally cannot. Reference: lib_lightgbm's
        categorical path driven by LightGBMUtils.scala:63-88 metadata."""
        rng = np.random.default_rng(0)
        n = 4000
        cats = rng.integers(0, 10, n).astype(np.float64)
        y = np.isin(cats, [0, 3, 5, 8]).astype(np.float64)
        x = np.column_stack([cats, rng.normal(size=n)])
        b = Booster.train(x, y, TrainOptions(
            objective="binary", num_iterations=3, num_leaves=4,
            categorical_indexes=(0,), min_data_in_leaf=5, learning_rate=0.5,
        ))
        acc = ((b.predict(x) >= 0.5) == y).mean()
        assert acc > 0.999, acc
        # the very first split must be a categorical subset of size 4
        assert bool(b.is_categorical[0, 0])
        assert int(b.cat_bitset[0, 0].sum()) == 4
        # unseen categories and NaN route right (the other-bin)
        p_unseen = b.predict(np.array([[42.0, 0.0]]))
        p_nan = b.predict(np.array([[np.nan, 0.0]]))
        np.testing.assert_allclose(p_unseen, p_nan)

    def test_categorical_max_cat_threshold_caps_subset(self):
        """max_cat_threshold=1 caps the SMALLER side of every categorical
        subset at one category (LightGBM semantics: the cap applies to one
        side of the split; the complement of a singleton is equally a
        one-vs-rest split)."""
        rng = np.random.default_rng(1)
        n = 3000
        n_categories = 8
        cats = rng.integers(0, n_categories, n).astype(np.float64)
        y = np.isin(cats, [1, 4, 6]).astype(np.float64)
        x = np.column_stack([cats, rng.normal(size=n)])
        b = Booster.train(x, y, TrainOptions(
            objective="binary", num_iterations=4, num_leaves=8,
            categorical_indexes=(0,), min_data_in_leaf=5,
            max_cat_threshold=1,
        ))
        cat_nodes = b.is_categorical & (b.feature >= 0)
        sizes = b.cat_bitset[cat_nodes].sum(axis=-1)
        smaller_side = np.minimum(sizes, n_categories - sizes)
        assert cat_nodes.any() and (smaller_side <= 1).all(), sizes

    def test_uint8_bin_storage_bit_identical(self):
        """bin_dtype="uint8" (4x narrower histogram HBM reads) must be a
        pure storage change: bins never exceed 255, kernels cast to int32
        in VMEM, and the trained model is BIT-IDENTICAL to int32 storage —
        across numeric+categorical features and both boosting loops."""
        rng = np.random.default_rng(4)
        n = 2000
        cats = rng.integers(0, 7, n).astype(np.float64)
        x = np.column_stack([rng.normal(size=(n, 5)), cats])
        y = ((x[:, 0] > 0) ^ np.isin(cats, [1, 4])).astype(np.float64)
        for boosting in ("gbdt", "dart"):
            kw = dict(objective="binary", boosting_type=boosting,
                      num_iterations=8, num_leaves=15,
                      categorical_indexes=(5,), min_data_in_leaf=5)
            b32 = Booster.train(x, y, TrainOptions(**kw))
            b8 = Booster.train(x, y, TrainOptions(bin_dtype="uint8", **kw))
            assert b8.to_text() == b32.to_text(), (
                f"{boosting}: uint8 bin storage changed the model"
            )

    def test_bad_bin_dtype_rejected(self):
        x, y = make_classification(n=200)
        with pytest.raises(ValueError, match="bin_dtype"):
            Booster.train(x, y, TrainOptions(
                objective="binary", num_iterations=2, bin_dtype="int8"))

    def test_fused_dart_zero_drop_equals_gbdt(self):
        """The fused dart loop with drop_rate=0 must be BIT-IDENTICAL to
        gbdt: every round's drop set is empty, weights stay 1, and the
        weight algebra degenerates to plain additive boosting — pins the
        fused drop/renormalize bookkeeping to the known-good path."""
        x, y = make_classification(n=1200)
        bg = Booster.train(x, y, TrainOptions(
            objective="binary", num_iterations=8, num_leaves=15))
        bd = Booster.train(x, y, TrainOptions(
            objective="binary", boosting_type="dart", num_iterations=8,
            num_leaves=15, drop_rate=0.0))
        np.testing.assert_array_equal(
            np.asarray(bd.predict_raw(x)), np.asarray(bg.predict_raw(x)))

    def test_quantile_leaf_renewal_calibrates(self):
        """Leaf renewal (LightGBM RenewTreeOutput): on label noise that is
        independent of x, a quantile fit must converge to the global
        alpha-quantile — without renewal, leaf steps live on the
        learning-rate scale and the fit stays pinned near its init."""
        rng = np.random.default_rng(11)
        x = rng.normal(size=(4000, 4))
        y = rng.normal(size=4000)                  # independent of x
        b = Booster.train(x, y, TrainOptions(
            objective="quantile", alpha=0.9, num_iterations=60,
            num_leaves=7, learning_rate=0.1,
        ))
        pred = np.asarray(b.predict(x))
        q = float(np.quantile(y, 0.9))
        assert abs(float(pred.mean()) - q) < 0.2, (pred.mean(), q)
        cover = float((y <= pred).mean())
        assert 0.84 <= cover <= 0.96, cover

    def test_renewal_robust_to_residual_outliers(self):
        """A single huge-label outlier must not corrupt other leaves'
        renewed values: per-node brackets + iterative histogram refinement
        keep each leaf's percentile on its own residual scale (a global
        256-bin range would put every normal residual into one bin)."""
        rng = np.random.default_rng(13)
        n = 2000
        x = rng.normal(size=(n, 4))
        y = 3.0 * x[:, 0] + rng.normal(scale=0.5, size=n)
        y[0] = 1e6                                 # one absurd outlier
        b = Booster.train(x, y, TrainOptions(
            objective="l1", num_iterations=40, num_leaves=15,
            min_data_in_leaf=5, learning_rate=0.1))
        pred = np.asarray(b.predict(x))
        mae = float(np.median(np.abs(pred - y)))   # median: ignore y[0]
        assert mae < 1.0, mae                      # normal rows still fit
        assert np.isfinite(pred).all()

    def test_renewal_survives_nonfinite_first_residual(self):
        """Regression: the shard-varying carry tag is built from the FIRST
        residual of the shard (fused.py); an inf there must not 0*inf=NaN
        its way into every node's bracket — only the outlier's own node may
        degrade, all other leaves must renew to finite values."""
        rng = np.random.default_rng(7)
        n = 1024
        x = rng.normal(size=(n, 4))
        y = 3.0 * x[:, 0] + rng.normal(scale=0.5, size=n)
        y[0] = np.inf                              # first residual = inf
        b = Booster.train(x, y, TrainOptions(
            objective="l1", num_iterations=20, num_leaves=15,
            min_data_in_leaf=5, learning_rate=0.1))
        pred = np.asarray(b.predict(x))
        assert np.isfinite(pred).all()
        mae = float(np.median(np.abs(pred - y)))   # median: ignore y[0]
        assert mae < 1.5, mae

    def test_l1_renewal_mesh_matches_single_device(self, mesh8):
        """The renewal histogram is psummed like the split histograms, so
        the renewed model must be identical on mesh vs single device."""
        rng = np.random.default_rng(12)
        x = rng.normal(size=(1024, 5))
        y = 10.0 * x[:, 0] + rng.normal(scale=2.0, size=1024)
        opts = TrainOptions(objective="l1", num_iterations=15, num_leaves=15)
        b1 = Booster.train(x, y, opts)
        b2 = Booster.train(x, y, opts, mesh=mesh8)
        np.testing.assert_allclose(
            np.asarray(b2.predict_raw(x)), np.asarray(b1.predict_raw(x)),
            rtol=2e-4, atol=2e-4)

    def test_bad_boosting_type_rejected(self):
        x, y = make_classification(n=200)
        with pytest.raises(ValueError, match="boosting_type"):
            Booster.train(x, y, TrainOptions(
                objective="binary", boosting_type="Dart", num_iterations=2))

    def test_multiclass_dart_rides_fused_path(self):
        """Multiclass dart performs plain additive updates (the
        drop/renormalize algebra is single-model only), so it must go
        through the fused gbdt scan — O(1) dispatches — not a host loop."""
        rng = np.random.default_rng(9)
        n = 1200
        x = rng.normal(size=(n, 6))
        y = (x[:, 0] + 0.7 * x[:, 1] > np.quantile(
            x[:, 0] + 0.7 * x[:, 1], [0.33, 0.66])[:, None]).sum(0).astype(float)
        msgs: list[str] = []
        b = Booster.train(x, y, TrainOptions(
            objective="multiclass", num_class=3, boosting_type="dart",
            num_iterations=6, num_leaves=7), log=msgs.append)
        assert any("fused boosting" in m for m in msgs), msgs
        acc = (np.argmax(b.predict(x), 1) == y).mean()
        assert acc > 0.8, acc

    def test_fused_dart_mesh_matches_single_device(self, mesh8):
        """dart under the data mesh: replicated drop decisions + psum
        histograms give the single-device model (same contract as gbdt)."""
        x, y = make_classification(n=1024)
        opts = TrainOptions(
            objective="binary", boosting_type="dart", num_iterations=10,
            num_leaves=15, drop_rate=0.15)
        b1 = Booster.train(x, y, opts)
        b2 = Booster.train(x, y, opts, mesh=mesh8)
        np.testing.assert_allclose(
            b1.predict_raw(x), b2.predict_raw(x), rtol=1e-3, atol=1e-3)

    def test_v1_text_format_one_vs_rest_compat(self):
        """Version-1 saved models encoded categorical splits as
        one-vs-rest (col == threshold_bin); the loader must reproduce
        that routing exactly — including categories in bins ABOVE the
        split bin, which must route RIGHT (regression: an under-sized
        bitset clamped high bins onto the split bin and sent them left)."""
        import json as _json

        payload = {
            "format": "mmlspark_tpu.gbdt", "version": 1,
            "objective": "regression", "num_class": 1, "init_score": 0.0,
            "best_iteration": -1, "feature_names": [], "class_labels": None,
            "tree_class": [0],
            "trees": {
                # one tree: cat split on bin 5 -> left leaf +1, right -1
                "feature": [[0, -1, -1]],
                "threshold_bin": [[5, 0, 0]],
                "threshold_value": [[5.0, 0.0, 0.0]],
                "is_categorical": [[True, False, False]],
                "left": [[1, -1, -1]], "right": [[2, -1, -1]],
                "value": [[0.0, 1.0, -1.0]], "gain": [[1.0, 0.0, 0.0]],
            },
            "bin_mapper": {
                "max_bin": 16, "categorical_indexes": [0],
                "num_features": 1,
                "num_bins": [10],
                "upper_bounds": [[np.inf] * 11],
                # category value v -> bin v+1 for v in 0..8
                "category_maps": {"0": {str(float(v)): v + 1
                                        for v in range(9)}},
            },
        }
        b = Booster.from_text(_json.dumps(payload))
        # value 4.0 -> bin 5 -> left (+1); value 7.0 -> bin 8 -> right (-1)
        got = np.asarray(b.predict(np.array([[4.0], [7.0], [0.0]])))
        np.testing.assert_allclose(got, [1.0, -1.0, -1.0])

    def test_categorical_mesh_matches_single_device(self, mesh8):
        """Sorted-subset categorical splits under the data mesh: the
        psum-merged histogram drives the same subset choice on every
        shard (replicated model)."""
        rng = np.random.default_rng(5)
        n = 2048
        cats = rng.integers(0, 6, n).astype(np.float64)
        y = np.isin(cats, [0, 2, 5]).astype(np.float64)
        x = np.column_stack([cats, rng.normal(size=n)])
        opts = TrainOptions(
            objective="binary", num_iterations=6, num_leaves=6,
            categorical_indexes=(0,), min_data_in_leaf=5,
        )
        b1 = Booster.train(x, y, opts)
        b2 = Booster.train(x, y, opts, mesh=mesh8)
        np.testing.assert_allclose(
            b1.predict_raw(x), b2.predict_raw(x), rtol=1e-3, atol=1e-3
        )

    def test_mesh_training_matches_single_device(self, mesh8):
        x, y = make_classification(n=1024)
        opts = TrainOptions(objective="binary", num_iterations=8, num_leaves=15)
        b_single = Booster.train(x, y, opts)
        b_mesh = Booster.train(x, y, opts, mesh=mesh8)
        a1 = ((b_single.predict(x) >= 0.5) == y).mean()
        a2 = ((b_mesh.predict(x) >= 0.5) == y).mean()
        assert a2 > 0.9
        # same histogram sums -> near-identical models (float reduction order
        # may differ); predictions must agree closely
        np.testing.assert_allclose(
            b_single.predict_raw(x), b_mesh.predict_raw(x), rtol=1e-3, atol=1e-3
        )


# --------------------------------------------------------------------- #
# estimator stages                                                      #
# --------------------------------------------------------------------- #

class TestEstimators:
    def test_classifier_pipeline(self):
        x, y = make_classification(n=1200)
        t = table_of(x, y)
        est = GBDTClassifier(num_iterations=15, num_leaves=15)
        model = est.fit(t)
        out = model.transform(t)
        assert "prediction" in out and "probability" in out and "raw_prediction" in out
        acc = (out["prediction"] == y).mean()
        assert acc > 0.93
        assert out["probability"].shape == (1200, 2)

    def test_classifier_string_labelish_classes(self):
        # non-contiguous numeric labels must map back to original values
        x, y = make_classification(n=800)
        y = np.where(y == 1, 7.0, 3.0)
        t = table_of(x, y)
        model = GBDTClassifier(num_iterations=10, num_leaves=7).fit(t)
        out = model.transform(t)
        assert set(np.unique(out["prediction"])) <= {3.0, 7.0}
        assert (out["prediction"] == y).mean() > 0.9

    def test_regressor_pipeline(self):
        x, y = make_regression(n=1200)
        t = table_of(x, y)
        model = GBDTRegressor(num_iterations=30, num_leaves=15).fit(t)
        out = model.transform(t)
        rmse = np.sqrt(np.mean((out["prediction"] - y) ** 2))
        assert rmse < 1.0

    def test_save_load_stage(self, tmp_path):
        x, y = make_classification(n=600)
        t = table_of(x, y)
        model = GBDTClassifier(num_iterations=5, num_leaves=7).fit(t)
        p = str(tmp_path / "gbdt_model")
        model.save(p)
        loaded = GBDTClassificationModel.load(p)
        assert model.transform(t).equals(loaded.transform(t))

    def test_native_model_roundtrip(self, tmp_path):
        x, y = make_regression(n=600)
        t = table_of(x, y)
        model = GBDTRegressor(num_iterations=5, num_leaves=7).fit(t)
        p = str(tmp_path / "model.txt")
        model.save_native_model(p)
        loaded = GBDTRegressionModel.load_native_model(p)
        np.testing.assert_allclose(
            model.transform(t)["prediction"], loaded.transform(t)["prediction"], rtol=1e-6
        )

    def test_weight_col(self):
        x, y = make_classification(n=800)
        w = np.ones(len(y))
        t = table_of(x, y, weight=w)
        model = GBDTClassifier(num_iterations=5, num_leaves=7, weight_col="weight").fit(t)
        out = model.transform(t)
        assert (out["prediction"] == y).mean() > 0.85

    def test_native_model_preserves_classes(self, tmp_path):
        x, y = make_classification(n=600)
        y = np.where(y == 1, 7.0, 3.0)
        t = table_of(x, y)
        model = GBDTClassifier(num_iterations=5, num_leaves=7).fit(t)
        p = str(tmp_path / "clf.txt")
        model.save_native_model(p)
        loaded = GBDTClassificationModel.load_native_model(p)
        assert set(np.unique(loaded.transform(t)["prediction"])) <= {3.0, 7.0}
        np.testing.assert_array_equal(
            model.transform(t)["prediction"], loaded.transform(t)["prediction"]
        )

    def test_model_string_warm_start(self):
        x, y = make_classification(n=800)
        t = table_of(x, y)
        m1 = GBDTClassifier(num_iterations=5, num_leaves=7).fit(t)
        est2 = GBDTClassifier(
            num_iterations=10, num_leaves=7, model_string=m1.booster.to_text()
        )
        m2 = est2.fit(t)
        assert m2.booster.num_trees == 10


class TestReviewRegressions:
    """Regressions for review findings: weighted min_data_in_leaf, rf
    warm-start rescale, seed steering, small-weight splits."""

    def test_small_weights_still_split(self):
        # min_data_in_leaf counts ROWS, not weight mass: tiny uniform
        # weights must not suppress every split.
        x, y = make_classification(n=1000)
        w = np.full(len(y), 0.01)
        t = table_of(x, y, weight=w)
        model = GBDTClassifier(
            num_iterations=5, num_leaves=7, min_data_in_leaf=20, weight_col="weight"
        ).fit(t)
        assert model.booster.feature_importances("split").sum() > 0
        out = model.transform(t)
        assert (out["prediction"] == y).mean() > 0.8

    def test_rf_warm_start_keeps_scale(self):
        x, y = make_regression(n=800)
        opts = dict(objective="regression", boosting_type="rf",
                    bagging_fraction=0.8, bagging_freq=1, num_leaves=15)
        full = Booster.train(x, y, TrainOptions(num_iterations=10, **opts))
        half = Booster.train(x, y, TrainOptions(num_iterations=5, **opts))
        cont = Booster.train(
            x, y, TrainOptions(num_iterations=10, init_model=half, **opts)
        )
        assert cont.num_trees == 10
        # continued rf must average like a 10-tree forest, not collapse
        # toward init_score (double-scaled trees would shrink predictions)
        var_full = np.var(full.predict(x))
        var_cont = np.var(cont.predict(x))
        assert var_cont > 0.5 * var_full

    def test_seed_steers_bagging(self):
        x, y = make_regression(n=800)
        base = dict(objective="regression", num_iterations=5, num_leaves=15,
                    bagging_fraction=0.5, bagging_freq=1)
        a = Booster.train(x, y, TrainOptions(seed=1, **base))
        b = Booster.train(x, y, TrainOptions(seed=2, **base))
        a2 = Booster.train(x, y, TrainOptions(seed=1, **base))
        assert not np.array_equal(a.value, b.value)
        np.testing.assert_array_equal(a.value, a2.value)

    def test_classifier_stats_without_probability_col(self):
        from mmlspark_tpu.automl.metrics import ComputeModelStatistics

        x, y = make_classification(n=600)
        t = table_of(x, y)
        model = GBDTClassifier(num_iterations=5, num_leaves=7).fit(t)
        out = model.transform(t)
        slim = Table(
            {"label": out["label"], "prediction": out["prediction"]},
            meta={"prediction": out.meta("prediction")},
        )
        stats = ComputeModelStatistics(scored_labels_col="prediction").transform(slim)
        assert "accuracy" in stats.columns

    def test_poisson_early_stopping_uses_own_loss(self):
        rng = np.random.default_rng(5)
        x = rng.normal(size=(900, 6))
        lam = np.exp(0.6 * x[:, 0] - 0.4 * x[:, 1])
        y = rng.poisson(lam).astype(np.float64)
        opts = TrainOptions(
            objective="poisson", num_iterations=60, num_leaves=15,
            early_stopping_round=5,
        )
        b = Booster.train(x[:700], y[:700], opts, valid=(x[700:], y[700:]))
        # with labels in count space vs log-space margins, raw-MSE tracking
        # stopped almost immediately; the poisson NLL must train further
        assert b.best_iteration >= 3

    def test_feature_fraction_on_mesh(self, mesh8):
        # regression: per-shard feature masks broke the replicated tree state
        x, y = make_classification(n=640)
        b = Booster.train(
            x, y,
            TrainOptions(objective="binary", num_iterations=3, num_leaves=7,
                         feature_fraction=0.5, seed=3),
            mesh=mesh8,
        )
        assert b.num_trees == 3

    def test_tweedie_boundary_early_stop(self):
        rng = np.random.default_rng(6)
        x = rng.normal(size=(600, 5))
        y = np.exp(0.5 * x[:, 0]) + rng.random(600)
        b = Booster.train(
            x[:500], y[:500],
            TrainOptions(objective="tweedie", tweedie_variance_power=1.0,
                         num_iterations=30, num_leaves=7, early_stopping_round=5),
            valid=(x[500:], y[500:]),
        )
        assert b.num_trees > 0


class TestPredictExtensions:
    """num_iteration-limited predict + pred_leaf (LightGBM predict-API
    parity: predict(num_iteration=...), predict(pred_leaf=True))."""

    def _data(self, n=600, f=6, seed=4):
        rng = np.random.default_rng(seed)
        x = rng.normal(size=(n, f))
        y = (x[:, 0] - 0.6 * x[:, 1] + 0.2 * rng.normal(size=n) > 0).astype(float)
        return x, y

    def test_truncated_equals_shorter_training(self):
        from mmlspark_tpu.gbdt.booster import Booster, TrainOptions

        x, y = self._data()
        full = Booster.train(x, y, TrainOptions(
            objective="binary", num_iterations=20, num_leaves=15))
        short = Booster.train(x, y, TrainOptions(
            objective="binary", num_iterations=8, num_leaves=15))
        # boosting is sequential: the first 8 trees of the 20-round model
        # ARE the 8-round model
        np.testing.assert_allclose(
            full.predict(x, num_iteration=8), short.predict(x),
            rtol=1e-5, atol=1e-6,
        )
        assert full.truncated(8).num_trees == 8
        # out-of-range request clamps to the full model
        np.testing.assert_allclose(
            full.predict(x, num_iteration=999), full.predict(x), rtol=1e-6)

    def test_predict_leaf(self):
        from mmlspark_tpu.gbdt.booster import Booster, TrainOptions

        x, y = self._data(n=300)
        b = Booster.train(x, y, TrainOptions(
            objective="binary", num_iterations=5, num_leaves=7))
        leaves = b.predict_leaf(x)
        assert leaves.shape == (300, b.num_trees)
        # every reported node is a leaf of its tree
        for t in range(b.num_trees):
            assert (b.feature[t][leaves[:, t]] < 0).all()
        # summing the leaf values reproduces the raw margin exactly
        vals = np.stack([b.value[t][leaves[:, t]] for t in range(b.num_trees)])
        recon = b.init_score + vals.astype(np.float32).sum(axis=0)
        np.testing.assert_allclose(
            recon, b.predict_raw(x, device="host"), rtol=1e-5, atol=1e-6)

    def test_truncated_multiclass_rounds(self):
        from mmlspark_tpu.gbdt.booster import Booster, TrainOptions

        rng = np.random.default_rng(5)
        x = rng.normal(size=(400, 5))
        y = rng.integers(0, 3, 400).astype(float)
        opts = dict(objective="multiclass", num_class=3, num_leaves=7)
        b = Booster.train(x, y, TrainOptions(num_iterations=6, **opts))
        tr = b.truncated(2)
        assert tr.num_trees == 6       # 2 rounds x 3 classes
        assert b.num_trees == 18
        # the real slicing contract: first 2 rounds of the 6-round model
        # ARE the 2-round model (catches wrong round-vs-class ordering)
        short = Booster.train(x, y, TrainOptions(num_iterations=2, **opts))
        np.testing.assert_allclose(tr.predict(x), short.predict(x),
                                   rtol=1e-5, atol=1e-6)
        # <=0 means all iterations (LightGBM semantics; the
        # num_iteration=best_iteration idiom with no early stopping)
        np.testing.assert_allclose(b.predict(x, num_iteration=-1),
                                   b.predict(x), rtol=1e-6)


class TestHistKernel:
    """Kernel registry (core/kernels.py, NativeLoader analogue) + the Pallas
    histogram kernel vs the XLA one-hot-matmul fallback."""

    def test_variants_agree(self):
        from mmlspark_tpu.gbdt.hist_kernel import (
            histogram_pallas_interpret,
            histogram_xla,
        )

        rng = np.random.default_rng(0)
        n, f, b, c = 700, 5, 16, 3
        bins = jnp.asarray(rng.integers(0, b, size=(n, f)), jnp.int32)
        stats = jnp.asarray(rng.normal(size=(n, c)), jnp.float32)
        hx = np.asarray(histogram_xla(bins, stats, b))
        hp = np.asarray(histogram_pallas_interpret(bins, stats, b))
        np.testing.assert_allclose(hx, hp, rtol=1e-5, atol=1e-5)
        from mmlspark_tpu.gbdt.hist_kernel import histogram_xla_scatter
        hs = np.asarray(histogram_xla_scatter(bins, stats, b))
        np.testing.assert_allclose(hx, hs, rtol=1e-5, atol=1e-5)
        # sanity against a plain numpy scatter
        ref = np.zeros((f, b, c))
        bn = np.asarray(bins)
        st = np.asarray(stats)
        for j in range(f):
            np.add.at(ref[j], bn[:, j], st)
        np.testing.assert_allclose(hx, ref, rtol=1e-4, atol=1e-4)
        # uint8 bin storage must be bit-identical through EVERY variant
        # (the kernels cast in VMEM; bench's bin_dtype="uint8" fast path)
        b8 = bins.astype(jnp.uint8)
        np.testing.assert_array_equal(hx, np.asarray(histogram_xla(b8, stats, b)))
        np.testing.assert_array_equal(
            hp, np.asarray(histogram_pallas_interpret(b8, stats, b)))
        np.testing.assert_array_equal(
            hs, np.asarray(histogram_xla_scatter(b8, stats, b)))

    def test_fused_variant_agrees(self, monkeypatch):
        # F*B 128-aligned AND the opt-in env set -> the FUSED single-dot
        # pallas kernel must be the one under test, not the per-feature
        # fallback (fused is opt-in until a chip sweep proves it faster)
        from mmlspark_tpu.gbdt import hist_kernel as hk

        monkeypatch.setenv("MMLSPARK_TPU_FUSED_HIST", "1")
        rng = np.random.default_rng(1)
        n, f, b, c = 700, 4, 32, 3            # F*B = 128
        assert (f * b) % 128 == 0 and hk._fused_chunk(f, b) >= 32
        bins = jnp.asarray(rng.integers(0, b, size=(n, f)), jnp.int32)
        stats = jnp.asarray(rng.normal(size=(n, c)), jnp.float32)
        hx = np.asarray(hk.histogram_xla(bins, stats, b))
        hp = np.asarray(hk.histogram_pallas_interpret(bins, stats, b))
        np.testing.assert_allclose(hx, hp, rtol=1e-5, atol=1e-5)
        # and at the bench shape's bin count (B=256, chunk budget kicks in)
        f2, b2 = 14, 256
        bins2 = jnp.asarray(rng.integers(0, b2, size=(n, f2)), jnp.int32)
        hx2 = np.asarray(hk.histogram_xla(bins2, stats, b2))
        hp2 = np.asarray(hk.histogram_pallas_interpret(bins2, stats, b2))
        np.testing.assert_allclose(hx2, hp2, rtol=1e-5, atol=1e-5)
        # the FUSED kernel's in-VMEM uint8 cast at the bench shape
        hp2_u8 = np.asarray(hk.histogram_pallas_interpret(
            bins2.astype(jnp.uint8), stats, b2))
        np.testing.assert_array_equal(hp2, hp2_u8)

    def test_grouped_variant_agrees(self, monkeypatch):
        # G features per dot (lane axis G·B): must match the XLA reference
        # for both a divisible and a ragged final group, and for uint8 bins
        from mmlspark_tpu.gbdt import hist_kernel as hk

        rng = np.random.default_rng(3)
        n, c, b = 700, 3, 32
        stats = jnp.asarray(rng.normal(size=(n, c)), jnp.float32)
        for f, g in ((8, 4), (14, 4), (5, 8)):   # exact, ragged, g > F
            monkeypatch.setenv("MMLSPARK_TPU_HIST_GROUP", str(g))
            bins = jnp.asarray(rng.integers(0, b, size=(n, f)), jnp.int32)
            hx = np.asarray(hk.histogram_xla(bins, stats, b))
            hp = np.asarray(hk.histogram_pallas_interpret(bins, stats, b))
            np.testing.assert_allclose(hx, hp, rtol=1e-5, atol=1e-5)
            hp_u8 = np.asarray(hk.histogram_pallas_interpret(
                bins.astype(jnp.uint8), stats, b))
            np.testing.assert_array_equal(hp, hp_u8)

    def test_registry_resolution(self):
        from mmlspark_tpu.core import kernels

        assert "gbdt_histogram" in kernels.registered_kernels()
        try:
            kernels.set_kernel_mode("pallas_interpret")
            from mmlspark_tpu.gbdt.hist_kernel import (
                histogram_pallas_interpret,
            )

            assert kernels.resolve("gbdt_histogram") is histogram_pallas_interpret
            kernels.set_kernel_mode("xla")
            from mmlspark_tpu.gbdt.hist_kernel import histogram_xla

            assert kernels.resolve("gbdt_histogram") is histogram_xla
        finally:
            kernels.set_kernel_mode(None)
        # auto on CPU resolves to the scatter variant (fast on CPU/GPU)
        assert kernels.resolve("gbdt_histogram").__name__ == "histogram_xla_scatter"

    def test_registry_raises_instead_of_substituting(self, monkeypatch):
        import jax

        from mmlspark_tpu.core import kernels

        kernels.register_kernel("only_xla", "xla", lambda: None)
        try:
            kernels.set_kernel_mode("pallas")
            with pytest.raises(KeyError, match="no 'pallas' variant"):
                kernels.resolve("only_xla")
        finally:
            kernels.set_kernel_mode(None)
            kernels._REGISTRY.pop("only_xla", None)

        # a backend that cannot initialise is an error, not "not a TPU"
        def dead_backend():
            raise RuntimeError("Unable to initialize backend 'tpu'")

        monkeypatch.setattr(jax, "default_backend", dead_backend)
        with pytest.raises(RuntimeError, match="Unable to initialize"):
            kernels.resolve("gbdt_histogram")

    def test_fit_under_interpret_kernel_matches_xla(self):
        from mmlspark_tpu.core import kernels

        x, y = make_classification(n=300)
        opts = TrainOptions(objective="binary", num_iterations=3, num_leaves=7)
        try:
            kernels.set_kernel_mode("xla")
            bx = Booster.train(x, y, opts)
            kernels.set_kernel_mode("pallas_interpret")
            bp = Booster.train(x, y, opts)
        finally:
            kernels.set_kernel_mode(None)
        np.testing.assert_allclose(bx.predict(x), bp.predict(x), rtol=1e-5,
                                   atol=1e-6)

    def test_mesh_fit_traces_with_the_pallas_kernel(self, mesh8):
        """On the TPU the mesh fit's shard_map body calls pallas_call; CPU
        meshes resolve the scatter kernel and never trace that. Forced
        through the interpreter here: the first four-chip run failed at
        trace time on shard_map's vma check."""
        from mmlspark_tpu.core import kernels

        x, y = make_classification(n=320)
        opts = TrainOptions(objective="binary", num_iterations=2, num_leaves=7)
        try:
            kernels.set_kernel_mode("xla")
            bx = Booster.train(x, y, opts, mesh=mesh8)
            kernels.set_kernel_mode("pallas_interpret")
            bp = Booster.train(x, y, opts, mesh=mesh8)
        finally:
            kernels.set_kernel_mode(None)
        np.testing.assert_allclose(bx.predict(x), bp.predict(x), rtol=1e-5,
                                   atol=1e-6)

    def test_fused_es_stops_and_truncates_on_mesh(self, mesh8):
        # ES must stay on the fused path and give the same model on a mesh
        x, y = make_classification(n=1600)
        opts = TrainOptions(
            objective="binary", num_iterations=120, num_leaves=15,
            early_stopping_round=5,
        )
        b1 = Booster.train(x[:1280], y[:1280], opts, valid=(x[1280:], y[1280:]))
        bm = Booster.train(x[:1280], y[:1280], opts, valid=(x[1280:], y[1280:]),
                           mesh=mesh8)
        assert b1.num_trees < 120 and b1.num_trees == b1.best_iteration + 1
        assert bm.num_trees < 120 and bm.num_trees == bm.best_iteration + 1
