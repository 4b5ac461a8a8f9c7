"""Worker for the multi-host rendezvous test (run as a subprocess).

Exercises the product path: `initialize_runtime` (the jax.distributed
rendezvous that replaces the reference's driver-socket handshake and
ssh/MPI, SURVEY.md §5.8) -> global mesh over ALL processes' devices ->
cross-process psum on the data axis.
"""

import os
import sys


def main() -> None:
    rank, n_procs, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=2"
    )
    import jax

    jax.config.update("jax_platforms", "cpu")

    from mmlspark_tpu.parallel.mesh import initialize_runtime, make_mesh

    initialize_runtime(
        coordinator_address=f"127.0.0.1:{port}",
        num_processes=n_procs,
        process_id=rank,
    )

    import numpy as np
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    devs = jax.devices()                     # global across processes
    mesh = make_mesh(n_data=len(devs))
    psum = jax.jit(shard_map(
        lambda x: jax.lax.psum(x, "data"), mesh=mesh,
        in_specs=P("data"), out_specs=P(),
    ))
    # per-process local shards -> one global row-sharded array
    sharding = NamedSharding(mesh, P("data"))
    shards = [
        jax.device_put(np.full((1, 1), float(rank + 1), np.float32), d)
        for d in jax.local_devices()
    ]
    garr = jax.make_array_from_single_device_arrays(
        (len(devs), 1), sharding, shards
    )
    out = psum(garr)
    val = float(np.asarray(out.addressable_data(0))[0, 0])

    # -- distributed GBDT fit over the cross-process mesh ----------------
    # The reference's data-parallel tree learner guarantees every worker
    # ends with an identical model (LightGBMClassifier.scala:82-85); here
    # the same guarantee must hold across real process boundaries: the
    # 4-device 2-process fit must equal the plain local fit.
    from mmlspark_tpu.core.schema import Table
    from mmlspark_tpu.gbdt.estimators import GBDTClassifier
    from mmlspark_tpu.parallel.mesh import use_mesh

    rng = np.random.default_rng(0)           # identical data on every rank
    x = rng.normal(size=(256, 6))
    yl = (x[:, 0] - 0.5 * x[:, 1] + 0.2 * rng.normal(size=256) > 0)
    tbl = Table({"features": x, "label": yl.astype(np.float64)})
    single = GBDTClassifier(num_iterations=2, num_leaves=7).fit(tbl)
    with use_mesh(mesh):
        dist = GBDTClassifier(num_iterations=2, num_leaves=7,
                              use_mesh=True).fit(tbl)
    struct_ok = bool(
        np.array_equal(dist.booster.feature, single.booster.feature)
        and np.array_equal(dist.booster.left, single.booster.left)
    )
    pred_ok = bool(np.allclose(
        np.asarray(dist.booster.predict(x)),
        np.asarray(single.booster.predict(x)), rtol=1e-3, atol=1e-5,
    ))
    # byte-level model identity across ranks (thresholds + leaf values, not
    # just structure): hash of the serialized model text
    import hashlib

    model_hash = hashlib.sha256(dist.booster.to_text().encode()).hexdigest()[:16]

    print(f"RESULT rank={rank} n_devices={len(devs)} "
          f"n_local={len(jax.local_devices())} psum={val} "
          f"gbdt_struct={int(struct_ok)} gbdt_pred={int(pred_ok)} "
          f"model_hash={model_hash}", flush=True)


if __name__ == "__main__":
    main()
