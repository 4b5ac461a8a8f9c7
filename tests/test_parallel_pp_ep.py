"""Pipeline- and expert-parallel tests (8 virtual CPU devices).

Both capabilities go beyond the reference (SURVEY.md §2.2 lists PP/EP as
absent there); correctness is asserted against single-device references —
the same replicated-model-vs-sharded-model equality discipline the GBDT
suite uses for data parallelism.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from mmlspark_tpu.parallel.moe import (
    EXPERT_AXIS,
    init_moe,
    moe_ffn_local,
    moe_ffn_sharded,
)
from mmlspark_tpu.parallel.pipeline_parallel import (
    PIPE_AXIS,
    make_pipe_mesh,
    pipeline_forward,
)


def _stage_fn(params, x):
    w, b = params
    return x + jnp.tanh(x @ w + b)


class TestPipelineParallel:
    @pytest.mark.parametrize("n_micro", [1, 4, 8])
    def test_matches_sequential(self, n_micro, rng):
        n_stages, b, d = 8, 16, 12
        ws = rng.normal(size=(n_stages, d, d)).astype(np.float32) * 0.3
        bs = rng.normal(size=(n_stages, d)).astype(np.float32) * 0.1
        x = rng.normal(size=(b, d)).astype(np.float32)

        expected = x
        for i in range(n_stages):
            expected = np.asarray(_stage_fn((ws[i], bs[i]), expected))

        mesh = make_pipe_mesh(n_stages)
        out = pipeline_forward(
            _stage_fn, (jnp.asarray(ws), jnp.asarray(bs)),
            jnp.asarray(x), n_micro=n_micro, mesh=mesh,
        )
        np.testing.assert_allclose(np.asarray(out), expected, rtol=2e-5,
                                   atol=1e-6)

    def test_4_stage_pipe_on_8_devices(self, rng):
        n_stages, b, d = 4, 8, 6
        ws = rng.normal(size=(n_stages, d, d)).astype(np.float32) * 0.3
        bs = np.zeros((n_stages, d), np.float32)
        x = rng.normal(size=(b, d)).astype(np.float32)
        expected = x
        for i in range(n_stages):
            expected = np.asarray(_stage_fn((ws[i], bs[i]), expected))
        mesh = make_pipe_mesh(n_stages)
        out = pipeline_forward(
            _stage_fn, (jnp.asarray(ws), jnp.asarray(bs)),
            jnp.asarray(x), n_micro=2, mesh=mesh,
        )
        np.testing.assert_allclose(np.asarray(out), expected, rtol=2e-5,
                                   atol=1e-6)

    def test_batch_not_divisible_raises(self, rng):
        mesh = make_pipe_mesh(2)
        with pytest.raises(ValueError, match="not divisible"):
            pipeline_forward(
                _stage_fn,
                (jnp.zeros((2, 4, 4)), jnp.zeros((2, 4))),
                jnp.zeros((7, 4)), n_micro=3, mesh=mesh,
            )


class TestExpertParallel:
    def test_sharded_matches_local_and_dense(self, rng):
        n_shards, d, h, e = 8, 8, 16, 8
        t_local = 16
        t = n_shards * t_local
        params = init_moe(jax.random.PRNGKey(0), d, h, e)
        x = jnp.asarray(rng.normal(size=(t, d)).astype(np.float32))

        # dense reference: every token scored by its own top-1 expert
        scores = jax.nn.softmax(x @ params.w_gate, axis=-1)
        eid = jnp.argmax(scores, axis=-1)
        gate = jnp.max(scores, axis=-1)
        hid = jax.nn.gelu(
            jnp.einsum("td,tdh->th", x, params.w1[eid]) + params.b1[eid]
        )
        dense = (jnp.einsum("th,thd->td", hid, params.w2[eid])
                 + params.b2[eid]) * gate[:, None]

        # generous capacity so no token drops: all paths must agree exactly
        cf = float(e)  # capacity = t_local per expert locally
        local = moe_ffn_local(params, x, capacity_factor=cf)
        np.testing.assert_allclose(np.asarray(local), np.asarray(dense),
                                   rtol=1e-4, atol=1e-5)

        from jax.sharding import Mesh

        mesh = Mesh(np.asarray(jax.devices()[:n_shards]), (EXPERT_AXIS,))
        spec = type(params)(
            w_gate=P(),
            w1=P(EXPERT_AXIS), b1=P(EXPERT_AXIS),
            w2=P(EXPERT_AXIS), b2=P(EXPERT_AXIS),
        )
        fn = jax.jit(shard_map(
            lambda p, xx: moe_ffn_sharded(p, xx, capacity_factor=cf),
            mesh=mesh, in_specs=(spec, P(EXPERT_AXIS)),
            out_specs=P(EXPERT_AXIS),
        ))
        sharded = fn(params, x)
        np.testing.assert_allclose(np.asarray(sharded), np.asarray(dense),
                                   rtol=1e-4, atol=1e-5)

    def test_capacity_drops_are_bounded(self, rng):
        # tight capacity: output for dropped tokens is 0 (standard Switch
        # behavior); no NaNs, shape preserved
        d, h, e, t = 4, 8, 4, 32
        params = init_moe(jax.random.PRNGKey(1), d, h, e)
        x = jnp.asarray(rng.normal(size=(t, d)).astype(np.float32))
        out = moe_ffn_local(params, x, capacity_factor=0.25)
        arr = np.asarray(out)
        assert arr.shape == (t, d) and np.isfinite(arr).all()
        # some token must actually drop at cf=0.25 with skewed routing
        dropped = np.all(arr == 0.0, axis=1)
        assert dropped.sum() >= 1

    def test_bf16_routing_ranks_exact_past_256(self, rng):
        # regression: capacity ranks must be int32 — a bf16 cumsum cannot
        # count past 256, silently merging two tokens into one slot
        d, h, e, t = 4, 8, 2, 600
        params = init_moe(jax.random.PRNGKey(3), d, h, e)
        # steer everything to expert 0 so one expert sees >256 tokens
        params = params._replace(
            w_gate=jnp.zeros_like(params.w_gate).at[:, 0].set(1.0)
        )
        xf = rng.normal(size=(t, d)).astype(np.float32)
        out32 = np.asarray(moe_ffn_local(params, jnp.asarray(xf),
                                         capacity_factor=float(e)))
        p16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
        out16 = np.asarray(moe_ffn_local(
            p16, jnp.asarray(xf, jnp.bfloat16), capacity_factor=float(e)
        )).astype(np.float32)
        # bf16 arithmetic is coarse but every token must keep ITS OWN
        # expert output; slot merging produces O(1) errors and zero rows
        assert not np.any(np.all(out16 == 0.0, axis=1))
        np.testing.assert_allclose(out16, out32, rtol=0.15, atol=0.05)

    def test_gradients_flow(self, rng):
        d, h, e, t = 4, 8, 4, 16
        params = init_moe(jax.random.PRNGKey(2), d, h, e)
        x = jnp.asarray(rng.normal(size=(t, d)).astype(np.float32))

        def loss(p):
            return jnp.mean(moe_ffn_local(p, x, capacity_factor=4.0) ** 2)

        g = jax.grad(loss)(params)
        for leaf in jax.tree.leaves(g):
            assert np.isfinite(np.asarray(leaf)).all()
        assert float(jnp.abs(g.w1).sum()) > 0
