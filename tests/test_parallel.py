"""Distribution-layer tests: ring/Ulysses attention vs dense reference,
tensor-parallel matmuls, mesh axes. All on the 8-virtual-device CPU mesh."""

import numpy as np
import pytest

import jax.numpy as jnp

from mmlspark_tpu.parallel import (
    DATA_AXIS,
    SEQ_AXIS,
    dense_attention,
    make_mesh,
    make_ring_attention,
    make_tp_mlp,
    make_ulysses_attention,
)


def qkv(b=2, t=32, h=4, d=8, seed=0):
    rng = np.random.default_rng(seed)
    shape = (b, t, h, d)
    return (jnp.asarray(rng.normal(size=shape), jnp.float32),
            jnp.asarray(rng.normal(size=shape), jnp.float32),
            jnp.asarray(rng.normal(size=shape), jnp.float32))


@pytest.fixture(scope="module")
def seq_mesh():
    return make_mesh(n_data=1, n_seq=8, n_model=1)


class TestRingAttention:
    def test_matches_dense(self, seq_mesh):
        q, k, v = qkv()
        ring = make_ring_attention(seq_mesh, SEQ_AXIS)(q, k, v)
        dense = dense_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(ring), np.asarray(dense),
                                   rtol=2e-4, atol=2e-5)

    def test_causal_matches_dense(self, seq_mesh):
        q, k, v = qkv(seed=1)
        ring = make_ring_attention(seq_mesh, SEQ_AXIS, causal=True)(q, k, v)
        dense = dense_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(ring), np.asarray(dense),
                                   rtol=2e-4, atol=2e-5)

    def test_long_sequence_shape(self, seq_mesh):
        q, k, v = qkv(b=1, t=512, h=2, d=4, seed=2)
        out = make_ring_attention(seq_mesh, SEQ_AXIS)(q, k, v)
        assert out.shape == (1, 512, 2, 4)

    @pytest.mark.parametrize("causal", [False, True])
    def test_local_chunk_matches_dense(self, seq_mesh, causal):
        # t=64 over 8 devices -> t_local=8, folded in chunks of 4: the
        # per-hop score tile halves while the math stays exact
        q, k, v = qkv(t=64, seed=4)
        ring = make_ring_attention(seq_mesh, SEQ_AXIS, causal=causal,
                                   local_chunk=4)(q, k, v)
        dense = dense_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(ring), np.asarray(dense),
                                   rtol=2e-4, atol=2e-5)

    def test_local_chunk_grads_match_dense(self, seq_mesh):
        import jax

        # t=64 over 8 devices -> t_local=8 with chunk 4: the nested chunk
        # scan really runs (t=32 would give t_local=4 and degrade to the
        # one-block path)
        q, k, v = qkv(t=64, seed=5)
        ring_fn = make_ring_attention(seq_mesh, SEQ_AXIS, causal=True,
                                      local_chunk=4)
        gd = jax.grad(lambda q_: (dense_attention(
            q_, k, v, causal=True) ** 2).sum())(q)
        gr = jax.grad(lambda q_: (ring_fn(q_, k, v) ** 2).sum())(q)
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gd),
                                   rtol=5e-4, atol=5e-5)

    def test_local_chunk_must_divide(self, seq_mesh):
        q, k, v = qkv(t=48)  # t_local = 6, chunk 4 does not divide
        with pytest.raises(ValueError, match="local_chunk"):
            make_ring_attention(seq_mesh, SEQ_AXIS, local_chunk=4)(q, k, v)


class TestUlysses:
    def test_matches_dense(self, seq_mesh):
        q, k, v = qkv(h=8)  # heads divisible by 8 shards
        uly = make_ulysses_attention(seq_mesh, SEQ_AXIS)(q, k, v)
        dense = dense_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(uly), np.asarray(dense),
                                   rtol=2e-4, atol=2e-5)

    def test_causal_matches_dense(self, seq_mesh):
        q, k, v = qkv(h=8, seed=3)
        uly = make_ulysses_attention(seq_mesh, SEQ_AXIS, causal=True)(q, k, v)
        dense = dense_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(uly), np.asarray(dense),
                                   rtol=2e-4, atol=2e-5)

    def test_local_chunk_matches_dense(self, seq_mesh):
        """local_chunk swaps the post-all_to_all dense core for the
        chunked online-softmax core: identical output, (c, c)-bounded
        score tiles — the long-context configuration."""
        q, k, v = qkv(h=8, seed=4)
        uly = make_ulysses_attention(
            seq_mesh, SEQ_AXIS, causal=True, local_chunk=8)(q, k, v)
        dense = dense_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(uly), np.asarray(dense),
                                   rtol=2e-4, atol=2e-5)


class TestTensorParallel:
    def test_tp_mlp_matches_local(self):
        mesh = make_mesh(n_data=1, n_model=8)
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)
        w1 = jnp.asarray(rng.normal(size=(32, 64)), jnp.float32)
        b1 = jnp.asarray(rng.normal(size=(64,)), jnp.float32)
        w2 = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
        b2 = jnp.asarray(rng.normal(size=(32,)), jnp.float32)
        import jax

        tp = make_tp_mlp(mesh, "model")(x, w1, b1, w2, b2)
        local = (jax.nn.gelu(x @ w1 + b1) @ w2) + b2
        np.testing.assert_allclose(np.asarray(tp), np.asarray(local),
                                   rtol=2e-4, atol=2e-4)


class TestMeshAxes:
    def test_seq_axis_mesh(self):
        m = make_mesh(n_data=2, n_seq=4)
        assert m.shape[DATA_AXIS] == 2 and m.shape[SEQ_AXIS] == 4

    def test_two_axis_default_unchanged(self):
        m = make_mesh(n_data=8)
        assert SEQ_AXIS not in m.shape


class TestDistributedDeterminism:
    """The reference's replicated-model guarantee: every worker ends up with
    the identical model (LightGBMClassifier.scala:82-85 `.reduce((b1,_)=>b1)`).
    Here: the n-device data-parallel model must equal the single-device model
    — trees compared by serialized text, predictions bit-compared — at
    n ∈ {1, 2, 8}."""

    @staticmethod
    def _gbdt_data(n=256, f=6, seed=0):
        rng = np.random.default_rng(seed)
        x = rng.normal(size=(n, f))
        y = (x[:, 0] - 0.5 * x[:, 1] + 0.25 * x[:, 2] > 0).astype(np.float64)
        return x, y

    def _fit_gbdt(self, x, y, n_devices):
        from mmlspark_tpu.core.schema import Table
        from mmlspark_tpu.gbdt import GBDTClassifier
        from mmlspark_tpu.parallel.mesh import set_default_mesh

        tbl = Table({"features": x, "label": y})
        est = GBDTClassifier(num_iterations=10, num_leaves=15,
                             use_mesh=n_devices is not None)
        if n_devices is None:
            return est.fit(tbl)
        set_default_mesh(make_mesh(n_data=n_devices))
        try:
            return est.fit(tbl)
        finally:
            set_default_mesh(None)

    @pytest.mark.parametrize("n_devices", [1, 2, 8])
    def test_gbdt_model_matches_single_device(self, n_devices):
        x, y = self._gbdt_data()
        ref = self._fit_gbdt(x, y, None)          # plain single-device path
        dist = self._fit_gbdt(x, y, n_devices)    # mesh path
        # identical trees: thresholds, structure, leaf values — via the
        # portable text format (the strongest replicated-model check)
        assert dist.booster.to_text() == ref.booster.to_text()
        np.testing.assert_array_equal(
            np.asarray(dist.booster.predict(x)), np.asarray(ref.booster.predict(x))
        )

    def test_gbdt_regressor_matches_single_device(self):
        from mmlspark_tpu.core.schema import Table
        from mmlspark_tpu.gbdt import GBDTRegressor
        from mmlspark_tpu.parallel.mesh import set_default_mesh

        rng = np.random.default_rng(1)
        x = rng.normal(size=(256, 5))
        y = 2.0 * x[:, 0] - x[:, 1] + 0.1 * rng.normal(size=256)
        tbl = Table({"features": x, "label": y})
        ref = GBDTRegressor(num_iterations=8, num_leaves=15).fit(tbl)
        set_default_mesh(make_mesh(n_data=8))
        try:
            dist = GBDTRegressor(num_iterations=8, num_leaves=15,
                                 use_mesh=True).fit(tbl)
        finally:
            set_default_mesh(None)
        assert dist.booster.to_text() == ref.booster.to_text()

    def test_gbdt_sparse_signal_within_documented_tolerance(self):
        """Adversarial case: sparse, weak-signal features produce near-tie
        splits where float-psum reduction order can flip a branch — the
        documented contract is prediction agreement at 1e-3 relative, not
        byte equality (see _GBDTParams.use_mesh)."""
        from mmlspark_tpu.core.schema import Table
        from mmlspark_tpu.gbdt import GBDTClassifier
        from mmlspark_tpu.parallel.mesh import set_default_mesh

        rng = np.random.default_rng(0)
        x = rng.normal(size=(512, 10)) * (rng.random(size=(512, 10)) < 0.3)
        y = (x[:, 0] + x[:, 1] > 0).astype(np.float64)
        tbl = Table({"features": x, "label": y})
        ref = GBDTClassifier(num_iterations=10, num_leaves=15).fit(tbl)
        set_default_mesh(make_mesh(n_data=8))
        try:
            dist = GBDTClassifier(num_iterations=10, num_leaves=15,
                                  use_mesh=True).fit(tbl)
        finally:
            set_default_mesh(None)
        p_ref = np.asarray(ref.booster.predict(x), np.float64)
        p_dist = np.asarray(dist.booster.predict(x), np.float64)
        np.testing.assert_allclose(p_dist, p_ref, rtol=1e-3, atol=1e-3)
        # same decisions even where a near-tie split flipped
        assert ((p_dist > 0.5) == (p_ref > 0.5)).mean() > 0.99

    def test_voting_parallel_with_large_topk_equals_data_parallel(self):
        """tree_learner=voting_parallel with 2k >= F must select every
        feature, making it byte-identical to data_parallel (the vote is a
        no-op) — validates the vote/merge plumbing end to end."""
        from mmlspark_tpu.core.schema import Table
        from mmlspark_tpu.gbdt import GBDTClassifier
        from mmlspark_tpu.parallel.mesh import set_default_mesh

        x, y = self._gbdt_data()
        tbl = Table({"features": x, "label": y})
        set_default_mesh(make_mesh(n_data=8))
        try:
            data_par = GBDTClassifier(num_iterations=8, num_leaves=15,
                                      use_mesh=True).fit(tbl)
            voting = GBDTClassifier(num_iterations=8, num_leaves=15,
                                    use_mesh=True,
                                    tree_learner="voting_parallel",
                                    top_k=x.shape[1]).fit(tbl)
        finally:
            set_default_mesh(None)
        assert voting.booster.to_text() == data_par.booster.to_text()

    def test_voting_parallel_restricts_and_still_learns(self):
        """With small top_k, each tree splits only on the globally voted 2k
        features, and accuracy stays competitive (voting approximates full
        merge, LightGBM's voting_parallel contract)."""
        from mmlspark_tpu.core.schema import Table
        from mmlspark_tpu.gbdt import GBDTClassifier
        from mmlspark_tpu.parallel.mesh import set_default_mesh

        rng = np.random.default_rng(4)
        x = rng.normal(size=(512, 24))
        y = (x[:, 3] - 0.8 * x[:, 11] > 0).astype(np.float64)
        tbl = Table({"features": x, "label": y})
        set_default_mesh(make_mesh(n_data=8))
        try:
            model = GBDTClassifier(num_iterations=10, num_leaves=15,
                                   use_mesh=True,
                                   tree_learner="voting_parallel",
                                   top_k=2).fit(tbl)
        finally:
            set_default_mesh(None)
        imp = np.asarray(model.get_feature_importances("split"))
        # the two informative features dominate the voted set
        assert imp[3] > 0 and imp[11] > 0
        out = model.transform(tbl)
        acc = (np.asarray(out["prediction"], np.float64) == y).mean()
        assert acc > 0.9, acc

    def test_voting_parallel_restricted_holdout_auc_tracks_data_parallel(self):
        """The ACTUAL contract of restricted voting (LightGBM
        tree_learner=voting_parallel): at top_k ~ F/4 the vote's feature
        pre-selection approximates the full histogram merge, so holdout
        QUALITY must track data-parallel within a small epsilon — not
        merely clear an absolute learning bar (VERDICT r4 #5)."""
        from mmlspark_tpu.core.schema import Table
        from mmlspark_tpu.gbdt import GBDTClassifier
        from mmlspark_tpu.parallel.mesh import set_default_mesh

        rng = np.random.default_rng(9)
        n_tr, n_te, f_dim = 4096, 1024, 16
        x = rng.normal(size=(n_tr + n_te, f_dim))
        # signal spread over 4 features so restricted voting has real work:
        # the voted 2k set must recover all informative columns each tree
        logits = (x[:, 0] - 0.8 * x[:, 5] + 0.6 * x[:, 9]
                  - 0.4 * x[:, 13])
        y = (logits + rng.normal(scale=0.5, size=n_tr + n_te) > 0
             ).astype(np.float64)
        tbl = Table({"features": x[:n_tr], "label": y[:n_tr]})
        cfg = dict(num_iterations=20, num_leaves=15, min_data_in_leaf=10,
                   use_mesh=True)
        set_default_mesh(make_mesh(n_data=8))
        try:
            data_par = GBDTClassifier(**cfg).fit(tbl)
            voting = GBDTClassifier(
                tree_learner="voting_parallel", top_k=f_dim // 4, **cfg
            ).fit(tbl)
        finally:
            set_default_mesh(None)

        from mmlspark_tpu.automl.metrics import auc

        auc_dp = auc(y[n_tr:], np.asarray(data_par.booster.predict(x[n_tr:])))
        auc_v = auc(y[n_tr:], np.asarray(voting.booster.predict(x[n_tr:])))
        assert auc_dp > 0.9, auc_dp          # the baseline itself learned
        assert auc_v >= auc_dp - 0.02, (
            f"restricted voting holdout AUC {auc_v:.4f} trails "
            f"data-parallel {auc_dp:.4f} by more than 0.02"
        )

    @pytest.mark.parametrize("n_devices", [2, 8])
    def test_dnn_step_matches_single_device(self, n_devices):
        """Data-parallel DNN training must match the single-device run on the
        same batches within float-reduction tolerance (the in-process
        equivalent of CNTK's synchronized MPI ring, CommandBuilders.scala:102-128)."""
        import jax
        from mmlspark_tpu.core.schema import Table
        from mmlspark_tpu.nn import DNNLearner
        from mmlspark_tpu.parallel.mesh import set_default_mesh

        rng = np.random.default_rng(2)
        x = rng.normal(size=(128, 8)).astype(np.float32)
        y = (x[:, 0] > 0).astype(np.float64)
        tbl = Table({"features": x, "label": y})

        def fit(use_mesh):
            return DNNLearner(
                architecture="mlp", model_config={"features": (16,)},
                epochs=2, batch_size=64, learning_rate=0.01,
                use_mesh=use_mesh, bfloat16=False, seed=3,
            ).fit(tbl)

        ref = fit(False)
        set_default_mesh(make_mesh(n_data=n_devices))
        try:
            dist = fit(True)
        finally:
            set_default_mesh(None)
        ref_params = jax.tree.leaves(ref.bundle.variables["params"])
        dist_params = jax.tree.leaves(dist.bundle.variables["params"])
        for a, b in zip(ref_params, dist_params):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)


class TestOneProcessPerChip:
    """parallel/chips.py: what a spawned worker's environment must say so
    that no child ever waits on a chip its parent (or a sibling) holds."""

    @pytest.fixture()
    def host(self, monkeypatch):
        """A fake host: set chips / whether this process holds them."""
        from mmlspark_tpu.parallel import chips

        state = {"chips": 0, "holds": False}
        monkeypatch.setattr(chips, "local_tpu_chips", lambda: state["chips"])
        monkeypatch.setattr(chips, "_holds_tpu", lambda: state["holds"])
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        return state

    def test_private_jax_apis_still_answer(self):
        """Unfaked: the two `jax._src` calls chips.py leans on exist and
        answer without bringing a backend up (this box has no TPU)."""
        from mmlspark_tpu.parallel import chips

        assert chips.local_tpu_chips() == 0
        assert chips._holds_tpu() is False

    def test_host_only_worker_is_pinned_to_cpu(self, host):
        from mmlspark_tpu.parallel.chips import worker_env

        host.update(chips=4, holds=True)
        assert worker_env(uses_device=False) == {"JAX_PLATFORMS": "cpu"}

    def test_explicit_cpu_run_and_chipless_host_inherit(self, host,
                                                        monkeypatch):
        from mmlspark_tpu.parallel.chips import worker_env

        assert worker_env(uses_device=True, chip=3) == {}   # no chips
        host.update(chips=1, holds=True)
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        assert worker_env(uses_device=True, chip=3) == {}

    def test_device_worker_gets_one_chip_by_index(self, host):
        from mmlspark_tpu.parallel.chips import worker_env

        host.update(chips=4)
        env = worker_env(uses_device=True, chip=2)
        assert env["TPU_VISIBLE_DEVICES"] == "2"
        assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"

    def test_impossible_seatings_fail_with_the_reason(self, host):
        from mmlspark_tpu.parallel.chips import worker_env

        host.update(chips=1)
        with pytest.raises(RuntimeError, match="one process per chip"):
            worker_env(uses_device=True, chip=1)
        host.update(chips=4, holds=True)
        with pytest.raises(RuntimeError, match="holds the host's 4 TPU"):
            worker_env(uses_device=True, chip=0)

    def test_fleet_refuses_at_start_before_spawning(self, host):
        from mmlspark_tpu.io_http.serving import ServingFleet

        host.update(chips=1)
        fleet = ServingFleet(lambda: None, n_hosts=2, rendezvous=False)
        with pytest.raises(RuntimeError, match="this host has 1"):
            fleet.start()
        assert fleet._procs == []

    def test_partition_workers_are_classified_by_their_chain(
            self, monkeypatch):
        """Streaming partition workers: a chain of stateful operators only
        is host-only (numpy); any other stage may score through JAX."""
        from mmlspark_tpu.core.pipeline import Transformer, pipeline_model
        from mmlspark_tpu.io_http import serving
        from mmlspark_tpu.streaming import (
            GroupedAggregator, KeyedShuffle, MemorySink, MemorySource,
            ParallelStreamingQuery)

        seen = []

        class FakeFleet:
            urls: list = []

            def __init__(self, factory, n_hosts, **kw):
                seen.append(kw["device_workers"])

            def watch(self, cb):
                pass

            def start(self):
                return self

            def stop(self):
                pass

        class Scorer(Transformer):
            def _transform(self, table):
                return table

        monkeypatch.setattr(serving, "ServingFleet", FakeFleet)
        agg = GroupedAggregator(group_col="key", value_col="value",
                                agg="sum", output_col="total")
        for chain in ([agg], [Scorer(), agg]):
            q = ParallelStreamingQuery(
                MemorySource(), pipeline_model(
                    KeyedShuffle(key_col="key", num_partitions=2), *chain),
                MemorySink(), workers="fleet")
            q._ensure_workers()
            q.stop()
        assert seen == [False, True]

    def test_spawn_env_patches_and_restores(self, monkeypatch):
        import os

        from mmlspark_tpu.parallel.chips import spawn_env

        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        monkeypatch.delenv("TPU_VISIBLE_DEVICES", raising=False)
        with spawn_env({"JAX_PLATFORMS": "tpu", "TPU_VISIBLE_DEVICES": "1"}):
            assert os.environ["JAX_PLATFORMS"] == "tpu"
            assert os.environ["TPU_VISIBLE_DEVICES"] == "1"
        assert os.environ["JAX_PLATFORMS"] == "cpu"
        assert "TPU_VISIBLE_DEVICES" not in os.environ
