"""Sharded fused execution (core/fusion.py under a parallel/mesh.py mesh).

The contract layered on top of test_fusion.py's: a mesh changes WHERE a
fused segment's work lands (rows sharded over the data axis, params
replicated or kernel-placed), never WHAT it produces.  Fused-sharded,
fused-single-device, and staged runs are byte-identical — including
ragged tails riding mesh-divisible buckets (a shard answering what one
device answers at the shard's row count) and the tensor-parallel MLP
body on a 2-D data x model mesh.  Mesh shape is part of the executable
cache's family key (a chip-count change is a new family, never a
recompile of an old one), a fixed mesh shape soaks with zero steady-state
compiles, and no mesh / a 1-device mesh is the exact single-chip path.

Runs on the conftest-forced 8 host-platform CPU devices, the same
"multi-chip in one process" harness the reference simulates multi-node
with (partitions-in-one-JVM local[*] sessions).
"""

from __future__ import annotations

import functools

import numpy as np
import pytest
from conftest import at_device_shapes

from mmlspark_tpu.core.dataplane import ExecutableCache, ShapeBucketer
from mmlspark_tpu.core.fusion import FusedPipelineModel, fuse
from mmlspark_tpu.core.pipeline import pipeline_model
from mmlspark_tpu.core.schema import Table
from mmlspark_tpu.nn.models import ModelBundle
from mmlspark_tpu.nn.runner import DeepModelTransformer
from mmlspark_tpu.ops.conversion import DataConversion
from mmlspark_tpu.parallel.mesh import make_mesh


def _mlp(input_col="x", f=16, outputs=4, **kw):
    """Widths all divisible by 2 so the tensor-parallel body qualifies on
    a model axis of 2."""
    t = DeepModelTransformer(input_col=input_col, **kw)
    return t.set_model(ModelBundle.init(
        "mlp", (f,), seed=0, num_outputs=outputs, features=(16, 8)))


def _xtable(n, f=16, seed=3):
    rng = np.random.default_rng(seed)
    return Table({"x": rng.normal(size=(n, f)).astype(np.float32)})


def _stages(bs=32, **mlp_kw):
    return [_mlp(mini_batch_size=bs, **mlp_kw),
            DataConversion(cols=["output"], convert_to="float")]


# --------------------------------------------------------------------- #
# byte-identity
# --------------------------------------------------------------------- #


class TestShardedByteIdentity:
    def test_data_parallel_vs_single_vs_staged_ragged(self, mesh8):
        # 103 = 3 full 32-row chunks + a ragged 7-row tail: the tail pads
        # to a mesh-divisible bucket (multiple of 8) and the padding mask
        # must slice off identically on every shard layout
        table = _xtable(103)
        staged = pipeline_model(*_stages())
        fused1 = fuse(pipeline_model(*_stages()), mini_batch_size=32)
        fused8 = fuse(pipeline_model(*_stages()), mini_batch_size=32,
                      mesh=mesh8)
        out_s = np.asarray(staged.transform(table)["output"])
        out_1 = np.asarray(fused1.transform(table)["output"])
        out_8 = np.asarray(fused8.transform(table)["output"])
        assert out_1.tobytes() == out_s.tobytes()
        # a shard holds 4 rows of a full chunk and ONE of the tail's rung
        # of 8: the mesh answers what one device answers at a shard's shape
        fused1_at = functools.cache(lambda rows: fuse(
            pipeline_model(*_stages()), mini_batch_size=rows))
        out_1_sharded = np.asarray(
            at_device_shapes(fused1_at, table, 32, shards=8)["output"])
        assert out_8.tobytes() == out_1_sharded.tobytes()
        assert fused8.last_stats["mesh_shape"] == "8x1"
        seg = fused8.last_stats["segments"][0]
        assert seg["kind"] == "fused"
        assert seg["mesh_shape"] == "8x1"
        # MLP variables replicate; DataConversion is parameterless
        assert seg["param_placements"] == ["replicated", "none"]

    @pytest.mark.parametrize("outputs", [8, pytest.param(
        4, marks=pytest.mark.xfail(
            reason="XLA:CPU only: the ragged tail leaves 2 rows per data "
            "shard, and a 4-wide head halved over the model axis makes "
            "the per-shard head a (2, K) @ (K, 2) dot, which XLA:CPU "
            "inlines as a mul+add loop while every larger shape takes "
            "its FMA matmul: 1 ulp off the unsharded program. "
            "Byte-identical on the TPU, where chip_smoke.py checks this "
            "shape on a multi-chip host."))])
    def test_tensor_parallel_2d_mesh(self, outputs):
        import jax

        mesh = make_mesh(n_data=4, n_model=2, devices=jax.devices()[:8])
        kw = dict(outputs=outputs, mini_batch_size=32,
                  fetch_dict={"out": "logits", "prob": "probability"})
        t = _mlp(**kw)
        table = _xtable(70, seed=5)
        ref = t.transform(table)
        fused = fuse(_mlp(**kw), mini_batch_size=32, mesh=mesh)
        got = fused.transform(table)
        for c in ("out", "prob"):
            assert np.asarray(got[c]).tobytes() == \
                np.asarray(ref[c]).tobytes()
        seg = fused.last_stats["segments"][0]
        assert seg["mesh_shape"] == "4x2"
        # the kernel's mesh_fn swapped in the column-parallel body and
        # placed the dense params itself
        assert seg["param_placements"] == ["custom"]

    def test_gbdt_rows_sharded_params_replicated(self, mesh8, rng):
        import jax

        from mmlspark_tpu.gbdt.estimators import GBDTRegressor

        model = GBDTRegressor(
            features_col="features", label_col="label", num_iterations=4,
            num_leaves=7,
        ).fit(Table({"features": rng.normal(size=(64, 3)),
                     "label": rng.normal(size=64)}))
        # float32-representable features: the kernel's ready() check
        # refuses anything device binning would re-bucket
        score = Table({"features": rng.normal(
            size=(81, 3)).astype(np.float32).astype(np.float64)})
        ref = np.asarray(model.transform(score)["prediction"])
        fused = fuse(pipeline_model(model), mini_batch_size=32, mesh=mesh8)
        got = np.asarray(fused.transform(score)["prediction"])
        assert got.tobytes() == ref.tobytes()
        seg_stats = fused.last_stats["segments"][0]
        assert seg_stats["param_placements"] == ["custom"]
        # "custom" here must still mean fully replicated: the binning
        # table and tree SoAs live whole on every chip
        seg = fused._ensure_segments()[0]
        for leaf in jax.tree.leaves(seg._device_params):
            assert leaf.sharding.is_fully_replicated

    def test_shard_skew_gauge_recorded(self, mesh8):
        from mmlspark_tpu.observability.metrics import get_registry

        fused = fuse(pipeline_model(*_stages()), mini_batch_size=32,
                     mesh=mesh8, fused_label="skew-test")
        fused.transform(_xtable(64))
        seg = fused.last_stats["segments"][0]
        assert seg["shard_skew_ratio"] >= 1.0
        gauge = get_registry().gauge(
            "mmlspark_tpu_shard_skew_ratio",
            labels=("pipeline", "mesh_shape")).labels(
                pipeline="skew-test", mesh_shape="8x1")
        assert gauge.value >= 1.0


# --------------------------------------------------------------------- #
# cache-key isolation
# --------------------------------------------------------------------- #


class TestCacheKeys:
    def test_family_key_without_mesh_is_the_pr5_key(self):
        base = ("seg", ("x", "float32", (16,)))
        assert ExecutableCache.family_key(base) is base

    def test_family_key_differs_across_mesh_shapes(self):
        base = ("seg", ("x", "float32", (16,)))
        spec = (("mlp", "replicated"), ("x", "P(data)"))
        k8 = ExecutableCache.family_key(
            base, mesh_shape=(("data", 8), ("model", 1)), sharding_spec=spec)
        k4 = ExecutableCache.family_key(
            base, mesh_shape=(("data", 4), ("model", 1)), sharding_spec=spec)
        assert k8 != base and k4 != base and k8 != k4

    def test_segment_keys_carry_mesh_only_when_sharded(self, mesh8):
        import jax

        ins = {"x": np.zeros((32, 16), np.float32)}
        seg_none = fuse(pipeline_model(*_stages()),
                        mini_batch_size=32)._ensure_segments()[0]
        key_none = seg_none._family_key(ins)
        assert key_none[0] == id(seg_none)  # bare PR-5 base, no mesh part

        seg8 = fuse(pipeline_model(*_stages()), mini_batch_size=32,
                    mesh=mesh8)._ensure_segments()[0]
        mesh4 = make_mesh(n_data=4, devices=jax.devices()[:4])
        seg4 = fuse(pipeline_model(*_stages()), mini_batch_size=32,
                    mesh=mesh4)._ensure_segments()[0]
        k8, k4 = seg8._family_key(ins), seg4._family_key(ins)
        # (base, ("mesh", mesh_shape, sharding_spec)); the mesh parts must
        # differ across shapes even though the column contract is the same
        assert k8[1][0] == "mesh" and k4[1][0] == "mesh"
        assert k8[1][1:] != k4[1][1:]

    def test_bucket_ladder_is_mesh_divisible(self):
        for step in ShapeBucketer(32, multiple_of=8).ladder:
            assert step % 8 == 0


# --------------------------------------------------------------------- #
# steady state
# --------------------------------------------------------------------- #


class TestSteadyState:
    def test_zero_recompiles_at_fixed_mesh_shape(self, mesh8):
        fused = fuse(pipeline_model(*_stages()), mini_batch_size=32,
                     mesh=mesh8)
        # warm every bucket the 32-row ladder can mint: full chunks plus
        # ragged tails of 7 (-> 8) and 16 rows
        for n in (103, 80, 64):
            fused.transform(_xtable(n, seed=n))
        seg = fused._ensure_segments()[0]
        warm = seg._exec_cache.stats()
        for n in (103, 80, 64, 40, 96, 7):
            fused.transform(_xtable(n, seed=100 + n))
        after = seg._exec_cache.stats()
        assert after["misses"] == warm["misses"]
        assert after["recompiles"] == warm["recompiles"]
        assert after["hits"] > warm["hits"]


# --------------------------------------------------------------------- #
# fallback: no mesh / trivial mesh is the exact single-chip path
# --------------------------------------------------------------------- #


class TestFallback:
    def test_no_mesh_and_one_device_mesh_are_single_chip(self):
        import jax

        plain = fuse(pipeline_model(*_stages()), mini_batch_size=32)
        trivial = fuse(pipeline_model(*_stages()), mini_batch_size=32,
                       mesh=make_mesh(n_data=1, devices=jax.devices()[:1]))
        table = _xtable(20)
        out_p = np.asarray(plain.transform(table)["output"])
        out_t = np.asarray(trivial.transform(table)["output"])
        assert out_t.tobytes() == out_p.tobytes()
        for fm in (plain, trivial):
            seg = fm._ensure_segments()[0]
            assert fm._mesh is None and seg.mesh is None
            assert set(seg._param_placements) == {"single"}
            assert fm.last_stats["mesh_shape"] == "1"
            assert "param_placements" not in fm.last_stats["segments"][0]
            # bare PR-5 family key: no mesh component at all
            key = seg._family_key({"x": np.zeros((8, 16), np.float32)})
            assert key[0] == id(seg)

    def test_fuse_with_mesh_on_fused_model_reattaches(self, mesh8):
        fm = fuse(pipeline_model(*_stages()), mini_batch_size=32)
        assert fuse(fm) is fm
        assert fuse(fm, mesh=mesh8) is fm
        assert fm._effective_mesh() is mesh8
        fm.set_mesh(None)
        assert fm._effective_mesh() is None


# --------------------------------------------------------------------- #
# mesh threading: serving + streaming
# --------------------------------------------------------------------- #


class TestMeshThreading:
    def test_streaming_query_auto_fuses_under_mesh(self, mesh8):
        from mmlspark_tpu.streaming.query import StreamingQuery
        from mmlspark_tpu.streaming.sources import MemorySource

        q = StreamingQuery(source=MemorySource(),
                           transform=pipeline_model(*_stages()),
                           mesh=mesh8)
        assert isinstance(q.transform, FusedPipelineModel)
        assert q.transform._effective_mesh() is mesh8

    def test_serve_model_threads_mesh(self, mesh8):
        from mmlspark_tpu.io_http.serving import serve_model

        # an already-fused handler gets the mesh attached in place
        fm = fuse(pipeline_model(*_stages()), mini_batch_size=32)
        serve_model(fm, input_cols=["x"], mesh=mesh8)
        assert fm._effective_mesh() is mesh8
