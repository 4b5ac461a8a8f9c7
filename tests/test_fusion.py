"""Whole-pipeline fusion: plan partitioning, byte-identity, fallbacks.

The contract under test everywhere: `fuse()` changes WHERE stages execute
(one XLA program per maximal device-capable run, columns device-resident
between stages), never WHAT they produce. Fused and staged runs are
byte-identical across dtypes, ragged row counts ride the bucket ladder
without steady-state recompiles, non-fusable stages sandwiched
mid-pipeline fall back to the staged path unchanged, and serving /
streaming score through the fused path automatically.
"""

from __future__ import annotations

import functools
import json
import threading
import urllib.request

import numpy as np
import pytest
from conftest import at_device_shapes

from mmlspark_tpu.core import (
    DeviceKernel,
    DeviceTable,
    FusedPipelineModel,
    fuse,
    pipeline_model,
    plan_fusion,
)
from mmlspark_tpu.core.dataplane import ShapeBucketer
from mmlspark_tpu.core.params import Param
from mmlspark_tpu.core.pipeline import PipelineModel, PipelineStage, Timer, Transformer
from mmlspark_tpu.core.schema import Table
from mmlspark_tpu.core.serialize import register_stage
from mmlspark_tpu.nn.models import ModelBundle
from mmlspark_tpu.nn.runner import DeepModelTransformer
from mmlspark_tpu.ops.conversion import DataConversion
from mmlspark_tpu.ops.ensemble import EnsembleByKey
from mmlspark_tpu.ops.featurize import AssembleFeatures
from mmlspark_tpu.ops.missing import CleanMissingData


def _mlp(input_col="features", f=8, outputs=3, **kw):
    t = DeepModelTransformer(input_col=input_col, **kw)
    return t.set_model(ModelBundle.init("mlp", (f,), seed=0, num_outputs=outputs))


def _table(n=50, f=8, seed=0, dtype=np.float64):
    rng = np.random.default_rng(seed)
    return Table({c: rng.normal(size=n).astype(dtype)
                  for c in "abcdefgh"[:f]})


@register_stage
class _DoubleOnHost(Transformer):
    """A deliberately non-fusable stage (no device_kernel)."""

    col = Param("x", "column", ptype=str)

    def _transform(self, table: Table) -> Table:
        return table.with_column(
            self.col_name(), np.asarray(table[self.col_name()]) * 2)

    def col_name(self):
        return self.get("col")


@register_stage
class _AddOneOnDevice(Transformer):
    col = Param("x", "column", ptype=str)

    def _transform(self, table: Table) -> Table:
        c = self.get("col")
        return table.with_column(
            c, np.asarray(table[c], np.float32) + np.float32(1))

    def device_kernel(self):
        c = self.get("col")
        return DeviceKernel(
            fn=lambda p, cols: {c: cols[c].astype("float32") + 1},
            input_cols=(c,), output_cols=(c,), out_dtypes={c: np.float32})


# --------------------------------------------------------------------- #
# planning
# --------------------------------------------------------------------- #


class TestPlanFusion:
    def test_maximal_runs_partition(self):
        plan = plan_fusion([_AddOneOnDevice(), _AddOneOnDevice(),
                            _DoubleOnHost(), _AddOneOnDevice()])
        assert [s.fused for s in plan.segments] == [True, False, True]
        assert [len(s.stages) for s in plan.segments] == [2, 1, 1]
        assert plan.n_fused_stages == 3 and plan.n_stages == 4
        assert plan.fusion_ratio == pytest.approx(0.75)

    def test_reasons_surface_for_host_stages(self):
        plan = plan_fusion([_DoubleOnHost(),
                            EnsembleByKey(keys=["k"], cols=["v"])])
        reasons = [sp.reason for s in plan.segments for sp in s.stages]
        assert "no device kernel declared" in reasons[0]
        assert "data-dependent output shape" in reasons[1]
        assert "HOST" in plan.describe()

    def test_nested_pipeline_models_flatten_into_runs(self):
        inner = pipeline_model(_AddOneOnDevice(), _AddOneOnDevice())
        plan = plan_fusion([_AddOneOnDevice(), inner])
        assert len(plan.segments) == 1 and plan.segments[0].fused
        assert len(plan.segments[0].stages) == 3

    def test_transfer_counts(self):
        plan = plan_fusion([_AddOneOnDevice(), _AddOneOnDevice(),
                            _DoubleOnHost(), _AddOneOnDevice()])
        fused, staged = plan.transfers_per_batch()
        assert fused == 4      # 2 fused segments x (1 in + 1 out)
        assert staged == 6     # 3 device stages x (1 in + 1 out)

    def test_broken_declaration_raises(self):
        # opting out is a returned reason string; an exception is a defect
        # and must not quietly put the stage on the host path
        class Broken(_AddOneOnDevice):
            def device_kernel(self):
                raise RuntimeError("boom")

        with pytest.raises(RuntimeError, match="boom"):
            plan_fusion([Broken()])

    def test_fuse_is_idempotent_and_wraps_bare_transformers(self):
        fm = fuse(pipeline_model(_AddOneOnDevice()))
        assert fuse(fm) is fm
        single = fuse(_AddOneOnDevice())
        assert isinstance(single, FusedPipelineModel)
        with pytest.raises(TypeError):
            fuse(object())


# --------------------------------------------------------------------- #
# DeviceTable
# --------------------------------------------------------------------- #


class TestDeviceTable:
    def test_round_trip_and_with_columns(self):
        dt = DeviceTable.from_host({"x": np.arange(4.0, dtype=np.float32)})
        assert "x" in dt and dt.columns == ["x"] and len(dt) == 1
        dt2 = dt.with_columns({"y": dt["x"] * 2})
        host = dt2.to_host()
        assert host["y"].tolist() == [0.0, 2.0, 4.0, 6.0]
        # derivation never mutates the parent
        assert dt.columns == ["x"]


# --------------------------------------------------------------------- #
# byte identity, fused vs staged
# --------------------------------------------------------------------- #


class TestByteIdentity:
    def _assert_identical(self, staged: Table, fused: Table):
        assert staged.columns == fused.columns
        for c in staged.columns:
            s, f = staged[c], fused[c]
            if isinstance(s, np.ndarray):
                assert s.dtype == f.dtype, c
                assert s.tobytes() == f.tobytes(), c
            else:
                assert list(s) == list(f), c
            assert staged.meta(c) == fused.meta(c), c

    def test_f32_featurize_clean_model_postprocess_chain(self):
        t = _table(57)
        rng = np.random.default_rng(3)
        cat = rng.integers(0, 4, size=57).astype(np.float64)
        t = t.with_column("cat", cat, meta={"category_values": list("wxyz")})
        asm = AssembleFeatures(
            columns_to_featurize=[*"abcdefgh", "cat"]).fit(t)
        nanify = t["a"].copy()
        nanify[::9] = np.nan
        t = t.with_column("a", nanify)
        runner = _mlp(f=12)
        conv = DataConversion(cols=["out"], convert_to="float")
        # CleanMissingData fuses on the float32 features matrix between
        # assembly and the model
        clean = CleanMissingData(
            input_cols=["b"], output_cols=["b"], cleaning_mode="Mean",
        ).fit(Table({"b": t["b"].astype(np.float32)}))
        staged_model = pipeline_model(asm, runner, conv)
        fused_model = fuse(pipeline_model(asm, runner, conv),
                           mini_batch_size=16)
        runner.set(fetch_dict={"out": "logits"})
        staged = staged_model.transform(t)
        fused = fused_model.transform(t)
        assert fused_model.last_stats["segments"][0]["kind"] == "fused"
        self._assert_identical(staged, fused)
        del clean  # float32 clean path covered in test below

    def test_f32_clean_missing_fuses_and_matches(self):
        x = np.arange(40, dtype=np.float32)
        x[::7] = np.nan
        t = Table({"a": x})
        cm = CleanMissingData(input_cols=["a"], output_cols=["a_clean"],
                              cleaning_mode="Median").fit(t)
        fm = fuse(pipeline_model(cm, _AddOneOnDevice(col="a_clean")))
        staged = _AddOneOnDevice(col="a_clean").transform(cm.transform(t))
        fused = fm.transform(t)
        assert fm.last_stats["segments"][0]["kind"] == "fused"
        self._assert_identical(staged, fused)

    def test_f64_clean_missing_falls_back_and_matches(self):
        x = np.arange(40, dtype=np.float64)
        x[::7] = np.nan
        t = Table({"a": x})
        cm = CleanMissingData(input_cols=["a"], output_cols=["a_clean"],
                              cleaning_mode="Mean").fit(t)
        fm = fuse(pipeline_model(cm))
        fused = fm.transform(t)
        seg = fm.last_stats["segments"][0]
        assert seg["kind"] == "host_fallback" and "float64" in seg["reason"]
        self._assert_identical(cm.transform(t), fused)

    def test_bf16_runner_fused_matches_staged(self):
        t = _table(33)
        asm = AssembleFeatures(columns_to_featurize=list("abcdefgh")).fit(t)
        runner = _mlp(bfloat16=True)
        # 33 rows in batches of 8 end in a batch of ONE row: the staged
        # stages run each batch at the fused side's shape (conftest)
        staged = at_device_shapes(functools.cache(
            lambda rows: pipeline_model(asm, _mlp(
                bfloat16=True, mini_batch_size=rows))), t, 8)
        fm = fuse(pipeline_model(asm, runner), mini_batch_size=8)
        fused = fm.transform(t)
        assert fm.last_stats["segments"][0]["kind"] == "fused"
        self._assert_identical(staged, fused)

    def test_int_conversion_fused_matches_staged(self):
        t = Table({"x": np.asarray([1.0, -2.5, 3.9, -0.1, 7.0], np.float32),
                   "y": np.asarray([0, 1, 2, 0, 5], np.int32)})
        for target in ("integer", "short", "byte", "boolean"):
            conv = DataConversion(cols=["x", "y"], convert_to=target)
            fm = fuse(pipeline_model(conv))
            fused = fm.transform(t)
            assert fm.last_stats["segments"][0]["kind"] == "fused", target
            self._assert_identical(conv.transform(t), fused)

    def test_conversion_f64_input_falls_back(self):
        t = Table({"x": np.asarray([1.0, 2.0])})  # float64
        conv = DataConversion(cols=["x"], convert_to="float")
        fm = fuse(pipeline_model(conv))
        fused = fm.transform(t)
        assert fm.last_stats["segments"][0]["kind"] == "host_fallback"
        self._assert_identical(conv.transform(t), fused)

    def test_gbdt_regression_fuses_and_matches(self):
        rng = np.random.default_rng(5)
        # float32-representable float64 features: the binning bit-identity
        # precondition the ready() check enforces
        X = rng.normal(size=(300, 6)).astype(np.float32).astype(np.float64)
        X[::11, 0] = np.nan
        y = 2 * np.nan_to_num(X[:, 0]) + np.sin(X[:, 1])
        t = Table({"features": X, "label": y})
        from mmlspark_tpu.gbdt.estimators import GBDTRegressor

        model = GBDTRegressor(features_col="features", label_col="label",
                              num_iterations=12, num_leaves=15).fit(t)
        fm = fuse(pipeline_model(model), mini_batch_size=128)
        assert fm.plan().segments[0].fused
        fused = fm.transform(t)
        assert fm.last_stats["segments"][0]["kind"] == "fused"
        self._assert_identical(model.transform(t), fused)

    def test_gbdt_classifier_declares_host_reason(self):
        rng = np.random.default_rng(6)
        X = rng.normal(size=(120, 4))
        y = (X[:, 0] > 0).astype(np.float64)
        t = Table({"features": X, "label": y})
        from mmlspark_tpu.gbdt.estimators import GBDTClassifier

        model = GBDTClassifier(features_col="features", label_col="label",
                               num_iterations=5).fit(t)
        plan = plan_fusion([model])
        assert not plan.segments[0].fused
        assert "float64" in plan.segments[0].stages[0].reason
        fm = fuse(pipeline_model(model))
        self._assert_identical(model.transform(t), fm.transform(t))

    def test_empty_table_runs_host_path(self):
        t = Table({"x": np.asarray([], np.float32)})
        fm = fuse(pipeline_model(_AddOneOnDevice()))
        out = fm.transform(t)
        assert out["x"].shape == (0,)
        assert fm.last_stats["segments"][0]["kind"] == "host_fallback"


# --------------------------------------------------------------------- #
# host sandwich / segmentation at runtime
# --------------------------------------------------------------------- #


class TestHostSandwich:
    def test_non_fusable_stage_mid_pipeline(self):
        t = Table({"x": np.arange(20, dtype=np.float32)})
        stages = [_AddOneOnDevice(), _AddOneOnDevice(), _DoubleOnHost(),
                  _AddOneOnDevice()]
        staged = pipeline_model(*stages).transform(t)
        fm = fuse(pipeline_model(*stages))
        fused = fm.transform(t)
        kinds = [s["kind"] for s in fm.last_stats["segments"]]
        assert kinds == ["fused", "host", "fused"]
        assert staged["x"].tobytes() == fused["x"].tobytes()

    def test_serialization_round_trip(self, tmp_path):
        t = Table({"x": np.arange(10, dtype=np.float32)})
        fm = fuse(pipeline_model(_AddOneOnDevice(), _DoubleOnHost(),
                                 _AddOneOnDevice()), mini_batch_size=4)
        expected = fm.transform(t)
        path = str(tmp_path / "fm")
        fm.save(path)
        loaded = PipelineStage.load(path)
        assert isinstance(loaded, FusedPipelineModel)
        assert loaded.get("mini_batch_size") == 4
        assert loaded.transform(t)["x"].tobytes() == expected["x"].tobytes()


# --------------------------------------------------------------------- #
# ragged tails through the bucket ladder
# --------------------------------------------------------------------- #


class TestRaggedLadder:
    def test_ragged_sizes_are_identical_and_stop_recompiling(self):
        runner = _mlp()
        asm_fit = _table(16)
        asm = AssembleFeatures(columns_to_featurize=list("abcdefgh")).fit(
            asm_fit)
        fm = fuse(pipeline_model(asm, runner), mini_batch_size=16)
        # the staged stages at the rung the fused side pads a size to
        staged_at = functools.cache(lambda rows: pipeline_model(
            asm, _mlp(mini_batch_size=rows)))

        # warm the full ladder (every bucket compiles once)
        for n in ShapeBucketer(16).ladder:
            fm.transform(_table(n, seed=n))
        seg = fm._segments[0]
        warm = seg._exec_cache.stats()

        for i, n in enumerate((3, 7, 1, 29, 16, 2, 41, 5)):
            t = _table(n, seed=100 + i)
            s, f = at_device_shapes(staged_at, t, 16), fm.transform(t)
            for c in s.columns:
                assert s[c].tobytes() == f[c].tobytes(), (n, c)
        soaked = seg._exec_cache.stats()
        assert soaked["misses"] == warm["misses"]
        assert soaked["recompiles"] == warm["recompiles"]
        assert soaked["hits"] > warm["hits"]

    def test_buckets_off_pads_to_mini_batch(self):
        fm = fuse(pipeline_model(_AddOneOnDevice()), mini_batch_size=8,
                  shape_buckets=False)
        t = Table({"x": np.arange(13, dtype=np.float32)})
        out = fm.transform(t)
        assert out["x"].tolist() == [float(i + 1) for i in range(13)]

    def test_fully_fusable_chain_moves_two_transfers_per_batch(self):
        # model + postprocess over one input column, one output column:
        # each mini-batch costs exactly 1 upload (features) + 1 download
        # (the score) — the staged path would pay 4 (2 per device stage)
        rng = np.random.default_rng(12)
        t = Table({"features": rng.normal(size=(64, 8)).astype(np.float32)})
        fm = fuse(pipeline_model(
            _mlp(), DataConversion(cols=["output"], convert_to="float")),
            mini_batch_size=16)
        fm.transform(t)
        stats = fm.last_stats
        n_batches = 4
        assert stats["segments"][0]["kind"] == "fused"
        assert stats["uploads"] == n_batches
        assert stats["downloads"] == n_batches
        per_batch = (stats["uploads"] + stats["downloads"]) / n_batches
        assert per_batch <= 2
        _, staged = fm.plan().transfers_per_batch()
        assert staged == 4

    def test_prefetch_depth_zero_is_identical(self):
        t = _table(37)
        asm = AssembleFeatures(columns_to_featurize=list("abcdefgh")).fit(t)
        outs = []
        for depth in (0, 2):
            fm = fuse(pipeline_model(asm, _mlp()), mini_batch_size=8,
                      prefetch_depth=depth)
            outs.append(fm.transform(t)["output"].tobytes())
        assert outs[0] == outs[1]


# --------------------------------------------------------------------- #
# observability
# --------------------------------------------------------------------- #


class TestObservability:
    def test_fusion_ratio_gauge_and_spans(self):
        from mmlspark_tpu.observability.metrics import get_registry
        from mmlspark_tpu.observability.tracing import get_tracer

        fm = fuse(pipeline_model(_AddOneOnDevice(), _DoubleOnHost()),
                  fused_label="ratio-test")
        tracer = get_tracer()
        before = len(tracer.spans())
        fm.transform(Table({"x": np.arange(8, dtype=np.float32)}))
        names = [s.name for s in tracer.spans()[before:]]
        assert "pipeline.fused_segment" in names
        gauge = get_registry().gauge(
            "mmlspark_tpu_pipeline_fusion_ratio",
            labels=("pipeline", "mesh_shape")).labels(
                pipeline="ratio-test", mesh_shape="1")
        assert gauge.value == pytest.approx(0.5)

    def test_timer_reports_device_host_split_for_fused(self):
        fm = fuse(pipeline_model(_AddOneOnDevice(), _DoubleOnHost()))
        timer = Timer(fm)
        timer.transform(Table({"x": np.arange(8, dtype=np.float32)}))
        assert timer.last_segments is not None
        kinds = [s["kind"] for s in timer.last_segments]
        assert kinds == ["fused", "host"]
        fused_seg, host_seg = timer.last_segments
        assert fused_seg["seconds"] == pytest.approx(
            fused_seg["device_seconds"] + fused_seg["host_seconds"])
        assert host_seg["device_seconds"] == 0.0
        assert host_seg["host_seconds"] == host_seg["seconds"]

    def test_timer_plain_stage_has_no_segments(self):
        timer = Timer(_DoubleOnHost())
        timer.transform(Table({"x": np.arange(4.0)}))
        assert timer.last_segments is None


# --------------------------------------------------------------------- #
# serving + streaming integration
# --------------------------------------------------------------------- #


def _post(url: str, payload: dict) -> dict:
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as resp:
        return json.loads(resp.read())


class TestServingIntegration:
    def test_serve_model_auto_fuses_pipeline_models(self):
        from mmlspark_tpu.io_http.serving import serve_model

        model = pipeline_model(_mlp(f=2, outputs=2))
        fm = fuse(model, mini_batch_size=16)
        # warm every ladder bucket deterministically (HTTP batch sizes are
        # timing-dependent) with the same (n, 2) float64 features layout
        # the serving handler stacks
        for n in ShapeBucketer(16).ladder:
            fm.transform(Table({"features": np.ones((n, 2), np.float64)}))
        seg = fm._segments[0]
        warm = seg._exec_cache.stats()
        srv = serve_model(fm, input_cols=["a", "b"], output_col="output",
                          max_batch_size=16)
        try:
            def fire(n):
                errs = []

                def one(i):
                    try:
                        _post(srv.url, {"a": float(i), "b": 1.0})
                    except Exception as e:  # noqa: BLE001
                        errs.append(repr(e))

                ts = [threading.Thread(target=one, args=(i,))
                      for i in range(n)]
                for th in ts:
                    th.start()
                for th in ts:
                    th.join(timeout=30)
                assert not errs, errs

            for n in (1, 4, 8, 3, 7, 12, 16, 2, 9, 5):
                fire(n)
        finally:
            srv.stop()
        soaked = seg._exec_cache.stats()
        # the serving soak acceptance bar: zero steady-state recompiles of
        # the fused segment once the ladder is warm
        assert soaked["misses"] == warm["misses"]
        assert soaked["recompiles"] == warm["recompiles"]
        assert soaked["hits"] > warm["hits"]

    def test_serve_model_fuse_opt_out(self):
        from mmlspark_tpu.io_http import serving as serving_mod

        captured = {}
        orig = serving_mod.ServingServer

        class Capture(orig):
            def __init__(self, handler, **kw):
                captured["handler"] = handler
                super().__init__(handler, **kw)

            def start(self):
                return self

            def stop(self):
                pass

        serving_mod.ServingServer, restore = Capture, orig
        try:
            model = pipeline_model(_AddOneOnDevice())
            serving_mod.serve_model(model, input_cols=["x"],
                                    fuse_pipeline=False)
        finally:
            serving_mod.ServingServer = restore
        assert captured["handler"] is not None


class TestStreamingIntegration:
    def test_query_auto_fuses_and_matches_staged(self):
        from mmlspark_tpu.streaming import MemorySink, MemorySource
        from mmlspark_tpu.streaming.query import StreamingQuery

        model = pipeline_model(_AddOneOnDevice(), _AddOneOnDevice())
        src, sink = MemorySource(), MemorySink()
        q = StreamingQuery(src, model, sink)
        assert isinstance(q.transform, FusedPipelineModel)
        t = Table({"x": np.arange(6, dtype=np.float32)})
        src.add_rows(t)
        assert q.process_all_available() == 1
        staged = model.transform(t)
        assert sink.table()["x"].tobytes() == staged["x"].tobytes()

    def test_query_fuse_opt_out_keeps_model(self):
        from mmlspark_tpu.streaming import MemorySink, MemorySource
        from mmlspark_tpu.streaming.query import StreamingQuery

        model = pipeline_model(_AddOneOnDevice())
        q = StreamingQuery(MemorySource(), model, MemorySink(),
                           fuse_pipeline=False)
        assert q.transform is model


# --------------------------------------------------------------------- #
# ImageTransformer compile-cache quick win
# --------------------------------------------------------------------- #


class TestImageChainCache:
    def test_op_chain_compiles_once_across_transforms(self):
        from mmlspark_tpu.image.transformer import ImageTransformer

        rng = np.random.default_rng(7)
        t = Table({"image": rng.uniform(0, 255, size=(6, 10, 10, 3))})
        it = ImageTransformer(input_col="image", output_col="o") \
            .resize(8, 8).blur(3, 3)
        first = it.transform(t)
        assert it.compile_count == 1
        second = it.transform(t)
        assert it.compile_count == 1  # cached — no re-trace per call
        assert first["o"].tobytes() == second["o"].tobytes()
        # a new shape compiles once more, then is cached too
        t2 = Table({"image": rng.uniform(0, 255, size=(3, 12, 12, 3))})
        it.transform(t2)
        assert it.compile_count == 2
        it.transform(t2)
        assert it.compile_count == 2

    def test_image_chain_fused_matches_staged(self):
        from mmlspark_tpu.image.transformer import ImageTransformer

        rng = np.random.default_rng(8)
        t = Table({"image": rng.uniform(0, 255, size=(9, 10, 10, 3))})
        it = ImageTransformer(input_col="image", output_col="o") \
            .resize(8, 8).gray(keep_channels=True).threshold(90.0)
        staged = it.transform(t)
        fm = fuse(pipeline_model(it), mini_batch_size=4)
        fused = fm.transform(t)
        assert fm.last_stats["segments"][0]["kind"] == "fused"
        assert staged["o"].tobytes() == fused["o"].tobytes()
        assert staged.meta("o") == fused.meta("o")

    def test_ragged_image_column_falls_back(self):
        from mmlspark_tpu.image.transformer import ImageTransformer

        rng = np.random.default_rng(9)
        imgs = [rng.uniform(size=(10, 10, 3)), rng.uniform(size=(12, 12, 3))]
        t = Table({"image": imgs})
        it = ImageTransformer(input_col="image", output_col="o").resize(8, 8)
        fm = fuse(pipeline_model(it))
        fused = fm.transform(t)
        assert fm.last_stats["segments"][0]["kind"] == "host_fallback"
        staged = it.transform(t)
        assert staged["o"].tobytes() == fused["o"].tobytes()
