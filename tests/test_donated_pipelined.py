"""Donated buffers, pipelined dispatch, and skew-aware bucketing
(core/fusion.py x core/dataplane.py under a parallel/mesh.py mesh).

The r08 dispatch path adds three throughput levers and this suite pins
the contract that none of them may move a single bit:

* buffer donation (`donate_buffers`) aliases the uploaded batch into the
  executable's workspace — byte-identity at EVERY bucket rung (against
  the staged stages run at that rung's shape), ragged tails included,
  single-device and on the 8-device mesh, because a
  donated program that re-read its input would corrupt exactly the rungs
  the ladder exercises;
* dispatch pipelining (`pipeline_depth`) keeps K+1 batches in flight —
  depths 0/1/K must agree byte-for-byte (reordering or dropping a
  readback is a value bug, not a perf bug);
* the skew-aware ShapeBucketer (`shards=`) balances every rung across
  shards — rungs divisible by the shard count AND the rounding multiple,
  per-shard ladder still geometric, shards=1 exactly the legacy ladder.

Runs on the conftest-forced 8 host-platform CPU devices.
"""

from __future__ import annotations

import functools
import math

import numpy as np
import pytest
from conftest import at_device_shapes

from mmlspark_tpu.core.dataplane import ShapeBucketer
from mmlspark_tpu.core.fusion import fuse
from mmlspark_tpu.core.pipeline import pipeline_model
from mmlspark_tpu.core.schema import Table
from mmlspark_tpu.nn.models import ModelBundle
from mmlspark_tpu.nn.runner import DeepModelTransformer
from mmlspark_tpu.ops.conversion import DataConversion


def _stages(bs=32):
    t = DeepModelTransformer(input_col="x", mini_batch_size=bs)
    t.set_model(ModelBundle.init("mlp", (16,), seed=0, num_outputs=4,
                                 features=(16, 8)))
    return [t, DataConversion(cols=["output"], convert_to="float")]


def _xtable(n, seed=3):
    rng = np.random.default_rng(seed)
    return Table({"x": rng.normal(size=(n, 16)).astype(np.float32)})


@functools.cache
def _staged_at(rows):
    return pipeline_model(*_stages(bs=rows))


# --------------------------------------------------------------------- #
# donation byte-identity
# --------------------------------------------------------------------- #


class TestDonationByteIdentity:
    def _rung_sizes(self, bs, shards):
        """One table size per ladder rung: the rung itself (exact fill)
        and one row less (ragged tail padded up to that rung)."""
        ladder = ShapeBucketer(bs, shards=shards).ladder
        sizes = set()
        for rung in ladder:
            sizes.add(rung)
            if rung > 1:
                sizes.add(rung - 1)
        return sorted(sizes)

    # The staged side runs each batch at the rung the fused side pads it
    # to (on the mesh, at one device's share of it): the same program at
    # the same shape, which is what `_HotPath.warm_rung` holds a resident
    # lane to at serving warm-up, bit for bit.

    def test_every_rung_single_device(self):
        donated = fuse(pipeline_model(*_stages()), mini_batch_size=32,
                       donate_buffers=True)
        plain = fuse(pipeline_model(*_stages()), mini_batch_size=32,
                     donate_buffers=False)
        for n in self._rung_sizes(32, 1):
            table = _xtable(n)
            ref = np.asarray(
                at_device_shapes(_staged_at, table, 32)["output"])
            out_d = np.asarray(donated.transform(table)["output"])
            out_p = np.asarray(plain.transform(table)["output"])
            assert out_d.tobytes() == ref.tobytes(), f"donated != staged @ {n}"
            assert out_p.tobytes() == ref.tobytes(), f"plain != staged @ {n}"

    def test_every_rung_ragged_mesh8(self, mesh8):
        donated = fuse(pipeline_model(*_stages()), mini_batch_size=32,
                       mesh=mesh8, donate_buffers=True)
        plain = fuse(pipeline_model(*_stages()), mini_batch_size=32,
                     mesh=mesh8, donate_buffers=False)
        for n in self._rung_sizes(32, 8):
            table = _xtable(n)
            ref = np.asarray(
                at_device_shapes(_staged_at, table, 32, shards=8)["output"])
            out_d = np.asarray(donated.transform(table)["output"])
            out_p = np.asarray(plain.transform(table)["output"])
            assert out_d.tobytes() == ref.tobytes(), \
                f"donated mesh8 != staged @ {n}"
            assert out_p.tobytes() == ref.tobytes(), \
                f"plain mesh8 != staged @ {n}"

    def test_donation_is_part_of_program_identity(self):
        # a donated (input-aliased) executable is a DIFFERENT XLA program:
        # the family key must separate them or one could be served where
        # the other was compiled
        donated = fuse(pipeline_model(*_stages()), mini_batch_size=32,
                       donate_buffers=True)
        plain = fuse(pipeline_model(*_stages()), mini_batch_size=32,
                     donate_buffers=False)
        ins = {"x": np.zeros((32, 16), np.float32)}
        seg_d = donated._ensure_segments()[0]
        seg_p = plain._ensure_segments()[0]
        kd = tuple(seg_d._family_key(ins)[1:])  # drop id(self)
        kp = tuple(seg_p._family_key(ins)[1:])
        assert kd != kp
        assert seg_d.donate and not seg_p.donate

    def test_stats_report_donation(self):
        fused = fuse(pipeline_model(*_stages()), mini_batch_size=32)
        fused.transform(_xtable(40))
        assert fused.get("donate_buffers") is True  # the shipped default


# --------------------------------------------------------------------- #
# pipelined dispatch
# --------------------------------------------------------------------- #


class TestPipelineDepthEquivalence:
    @pytest.mark.parametrize("depth", [0, 1, 4])
    def test_depth_byte_identity(self, mesh8, depth):
        # 203 rows = 6 full 32-row batches + a 11-row ragged tail: enough
        # batches that a lag-4 window really holds 5 in flight
        table = _xtable(203)
        ref = np.asarray(
            pipeline_model(*_stages()).transform(table)["output"])
        fused = fuse(pipeline_model(*_stages()), mini_batch_size=32,
                     mesh=mesh8, pipeline_depth=depth)
        out = np.asarray(fused.transform(table)["output"])
        assert out.tobytes() == ref.tobytes()
        seg = fused.last_stats["segments"][0]
        assert seg["pipeline_depth"] == depth

    def test_depth_none_inherits_readback_lag(self):
        fused = fuse(pipeline_model(*_stages()), mini_batch_size=32,
                     readback_lag=3)
        fused.transform(_xtable(203))
        assert fused.last_stats["segments"][0]["pipeline_depth"] == 3

    def test_overlap_fraction_reported(self, mesh8):
        fused = fuse(pipeline_model(*_stages()), mini_batch_size=32,
                     mesh=mesh8, pipeline_depth=2)
        fused.transform(_xtable(203))
        seg = fused.last_stats["segments"][0]
        assert 0.0 <= seg["dispatch_overlap_fraction"] <= 1.0
        assert seg["fetched"] == 7  # 6 full + 1 ragged


# --------------------------------------------------------------------- #
# skew-aware bucketer
# --------------------------------------------------------------------- #


class TestSkewAwareBucketer:
    def test_shards1_is_legacy_ladder(self):
        for m in (1, 8, 16):
            legacy = ShapeBucketer(256, multiple_of=m).ladder
            assert ShapeBucketer(256, multiple_of=m, shards=1).ladder \
                == legacy

    @pytest.mark.parametrize("shards", [2, 4, 8])
    @pytest.mark.parametrize("multiple_of", [1, 8, 12])
    def test_rungs_divisible_by_shards_and_multiple(self, shards,
                                                    multiple_of):
        b = ShapeBucketer(512, multiple_of=multiple_of, shards=shards)
        per_m = multiple_of // math.gcd(multiple_of, shards)
        for rung in b.ladder:
            assert rung % shards == 0, f"rung {rung} splits unevenly"
            per_shard = rung // shards
            assert per_shard % per_m == 0, \
                f"per-shard rung {per_shard} breaks multiple_of={multiple_of}"
            assert rung % multiple_of == 0

    def test_per_shard_ladder_balanced_and_geometric(self):
        b = ShapeBucketer(512, shards=8)
        per = b.per_shard_ladder
        assert per == tuple(r // 8 for r in b.ladder)
        # per-shard rungs strictly grow — every rung is one program, and
        # a stalled ladder would mint duplicate families
        assert all(a < z for a, z in zip(per, per[1:]))

    def test_bucket_for_balances_every_shard(self):
        b = ShapeBucketer(512, shards=8)
        for n in (1, 7, 65, 511, 512):
            rung = b.bucket_for(n)
            assert rung >= n
            assert rung % 8 == 0  # every shard gets rung/8 rows exactly

    def test_pad_waste_accounts_shard_padding(self):
        b = ShapeBucketer(512, shards=8)
        rung = b.bucket_for(65)
        b.note_pad(65, rung)
        waste = b.pad_waste()[rung]
        assert waste["rows_real"] == 65
        assert waste["rows_padded"] == rung - 65
        assert waste["ratio"] == pytest.approx((rung - 65) / rung)


# --------------------------------------------------------------------- #
# ring all_gather schedule
# --------------------------------------------------------------------- #


class TestRingAllGather:
    def test_bit_exact_vs_monolithic_gather(self):
        import jax
        import jax.numpy as jnp
        from jax import lax

        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from mmlspark_tpu.parallel.mesh import make_mesh
        from mmlspark_tpu.parallel.tensor_parallel import ring_all_gather

        mesh = make_mesh(n_data=1, n_model=8)
        rng = np.random.default_rng(0)
        y = rng.normal(size=(16, 64)).astype(np.float32)

        def ring(y_):
            return ring_all_gather(y_, "model", axis=-1)

        def mono(y_):
            return lax.all_gather(y_, "model", axis=y_.ndim - 1, tiled=True)

        outs = []
        for body in (ring, mono):
            fn = shard_map(body, mesh=mesh, in_specs=P(None, "model"),
                           out_specs=P(None, "model"))
            outs.append(np.asarray(jax.jit(fn)(jnp.asarray(y))))
        assert outs[0].tobytes() == outs[1].tobytes()

    def test_single_device_axis_is_identity(self):
        import jax
        import jax.numpy as jnp
        from jax import lax  # noqa: F401 — axis helpers used inside body

        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from mmlspark_tpu.parallel.mesh import make_mesh
        from mmlspark_tpu.parallel.tensor_parallel import ring_all_gather

        mesh = make_mesh(n_data=8, n_model=1)
        y = np.arange(32, dtype=np.float32).reshape(8, 4)
        fn = shard_map(lambda y_: ring_all_gather(y_, "model", axis=-1),
                       mesh=mesh, in_specs=P("data", None),
                       out_specs=P("data", None))
        out = np.asarray(jax.jit(fn)(jnp.asarray(y)))
        assert out.tobytes() == y.tobytes()
