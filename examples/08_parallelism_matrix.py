"""The parallelism matrix on an 8-virtual-device mesh.

The reference's only distribution story is data parallelism with fully
replicated models (SURVEY.md §2.2). This example runs every axis the TPU
build adds — all on CPU virtual devices, the same code a real multi-chip
mesh runs:

1. data-parallel GBDT (psum histogram merge, replicated model),
2. pipeline-parallel forward (GPipe microbatch schedule),
3. expert-parallel MoE training step (all_to_all dispatch/combine).
"""

import _backend  # noqa: F401 — honors JAX_PLATFORMS=cpu + 8 virtual devices

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import shard_map  # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402

from mmlspark_tpu.core.schema import Table  # noqa: E402
from mmlspark_tpu.gbdt import GBDTClassifier  # noqa: E402
from mmlspark_tpu.parallel import (  # noqa: E402
    EXPERT_AXIS,
    init_moe,
    make_mesh,
    make_pipe_mesh,
    moe_ffn_sharded,
    pipeline_forward,
    use_mesh,
)


def stage(params, h):
    w, b = params
    return h + jnp.tanh(h @ w + b)


def main():
    n_dev = len(jax.devices())
    print(f"devices: {n_dev} x {jax.devices()[0].device_kind}")
    if n_dev < 2:
        raise SystemExit(
            "need >= 2 devices to demonstrate anything — run with "
            "JAX_PLATFORMS=cpu for an 8-virtual-device mesh"
        )

    # -- 1. data-parallel GBDT --------------------------------------------
    rng = np.random.default_rng(0)
    x = rng.normal(size=(2048, 8))
    y = (x[:, 0] - 0.5 * x[:, 1] + 0.3 * rng.normal(size=2048) > 0).astype(float)
    tbl = Table({"features": x, "label": y})
    single = GBDTClassifier(num_iterations=10, num_leaves=15).fit(tbl)
    with use_mesh(make_mesh(n_data=n_dev)):
        dist = GBDTClassifier(num_iterations=10, num_leaves=15,
                              use_mesh=True).fit(tbl)
    # the documented determinism contract (docs/parallel.md): identical
    # tree structure; leaf values within float-psum tolerance (reduction
    # order differs from the single-device fit)
    same = (
        np.array_equal(dist.booster.feature, single.booster.feature)
        and np.array_equal(dist.booster.left, single.booster.left)
        and np.allclose(dist.booster.predict(x), single.booster.predict(x),
                        rtol=1e-3, atol=1e-5)
    )
    print(f"1. data-parallel GBDT over {n_dev} devices: "
          f"structure identical + predictions within tolerance = {same}")

    # -- 2. pipeline-parallel forward -------------------------------------
    d = 16
    ws = jnp.asarray(rng.normal(size=(n_dev, d, d)) * 0.3, jnp.float32)
    bs = jnp.zeros((n_dev, d), jnp.float32)
    xp = jnp.asarray(rng.normal(size=(32, d)), jnp.float32)
    out = pipeline_forward(stage, (ws, bs), xp, n_micro=4,
                           mesh=make_pipe_mesh(n_dev))
    expected = xp
    for i in range(n_dev):
        expected = stage((ws[i], bs[i]), expected)
    err = float(jnp.abs(out - expected).max())
    print(f"2. {n_dev}-stage pipeline (4 microbatches): "
          f"max |pipeline - sequential| = {err:.2e}")

    # -- 3. expert-parallel MoE step --------------------------------------
    params = init_moe(jax.random.PRNGKey(0), d, 32, n_dev)
    xt = jnp.asarray(rng.normal(size=(16 * n_dev, d)), jnp.float32)
    yt = jnp.asarray(rng.normal(size=(16 * n_dev, d)), jnp.float32)
    spec = type(params)(w_gate=P(), w1=P(EXPERT_AXIS), b1=P(EXPERT_AXIS),
                        w2=P(EXPERT_AXIS), b2=P(EXPERT_AXIS))
    e_mesh = Mesh(np.asarray(jax.devices()), (EXPERT_AXIS,))

    def step(p, xx, yy):
        def loss_fn(p):
            o = moe_ffn_sharded(p, xx, capacity_factor=4.0)
            return jax.lax.pmean(jnp.mean((o - yy) ** 2), EXPERT_AXIS)

        loss, g = jax.value_and_grad(loss_fn)(p)
        g = g._replace(w_gate=jax.lax.psum(g.w_gate, EXPERT_AXIS))
        return jax.tree.map(lambda a, b: a - 0.1 * b, p, g), loss

    fn = jax.jit(shard_map(step, mesh=e_mesh,
                           in_specs=(spec, P(EXPERT_AXIS), P(EXPERT_AXIS)),
                           out_specs=(spec, P())))
    p1, l1 = fn(params, xt, yt)
    _, l2 = fn(p1, xt, yt)
    print(f"3. {n_dev}-expert MoE (all_to_all dispatch): "
          f"loss {float(l1):.4f} -> {float(l2):.4f} (decreasing)")
    assert same and err < 1e-4 and float(l2) < float(l1)
    print("parallelism matrix OK")


if __name__ == "__main__":
    main()
