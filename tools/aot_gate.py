#!/usr/bin/env python
"""Pallas AOT-compile gate: prove every shipped Pallas kernel, and every
bucket-ladder program serving can mint, compiles on REAL Mosaic/XLA:TPU.

Interpret-mode parity is NOT compile evidence: a kernel can pass the
interpreter and still be refused by Mosaic (the flash forward's lse block
broke the (8, 128) block rule for as long as only the interpreter ran it).
This gate AOT-compiles each kernel at its SHIPPED tile config via
jit(...).lower(...).compile() — no input data, no timed execution — and
prints one OK/FAIL verdict per kernel.

It needs a TPU: on any other backend it refuses to run (on CPU every
Pallas verdict would be FAIL by construction). Exit code is 1 when any
kernel fails, 2 when no TPU is present, 0 only when every verdict is OK.
"""
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

VERDICTS = []


def gate(name, build):
    """build() -> (fn, abstract_args); compile and record the verdict.
    `fn` may already be jitted (e.g. with in_shardings for the sharded
    ladder) — then its own lower() is used instead of re-wrapping."""
    t0 = time.time()
    try:
        fn, args = build()
        lowerable = fn if hasattr(fn, "lower") else jax.jit(fn)
        lowerable.lower(*args).compile()
        VERDICTS.append((name, "OK", time.time() - t0, ""))
        print(f"AOT {name}: OK ({time.time() - t0:.1f}s)", flush=True)
    except Exception as e:  # noqa: BLE001 — each kernel gets its own verdict
        first = str(e).strip().splitlines()[0] if str(e).strip() else repr(e)
        VERDICTS.append((name, "FAIL", time.time() - t0, first))
        print(f"AOT {name}: FAIL ({time.time() - t0:.1f}s) — {first}",
              flush=True)
        traceback.print_exc(limit=3)


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def hist_build(group=None, fused=False, bins_dtype=jnp.int32):
    """Histogram kernel at the bench's shipped shape: F=28 (Higgs-family
    feature count), B=256 bins (max_bin=255), C=2 grad/hess columns."""
    os.environ.pop("MMLSPARK_TPU_HIST_GROUP", None)
    os.environ.pop("MMLSPARK_TPU_FUSED_HIST", None)
    if group:
        os.environ["MMLSPARK_TPU_HIST_GROUP"] = str(group)
    if fused:
        os.environ["MMLSPARK_TPU_FUSED_HIST"] = "1"
    from mmlspark_tpu.gbdt.hist_kernel import histogram_pallas

    n, f, b, c = 8192, 28, 256, 2
    return (lambda bins, stats: histogram_pallas(bins, stats, b),
            (sds((n, f), bins_dtype), sds((n, c), jnp.float32)))


def flash_build(t, grad=False):
    """Flash attention at the bench transformer's shipped head geometry
    (d_model=512 / 8 heads -> D=64, bf16, at the tile the kernel chooses).
    Batch is small: the Mosaic kernel is identical per block; grid count
    doesn't change it."""
    from mmlspark_tpu.nn.attention import flash_attention

    q = sds((2, t, 8, 64), jnp.bfloat16)
    if grad:
        def loss(q_, k_, v_):
            return flash_attention(q_, k_, v_, causal=True).astype(
                jnp.float32).sum()

        return jax.grad(loss, argnums=(0, 1, 2)), (q, q, q)
    return (lambda q_, k_, v_: flash_attention(q_, k_, v_, causal=True),
            (q, q, q))


def runner_bucket_build(n):
    """Pipelined model-runner forward at ONE shape-bucket ladder size.

    The async data plane (core/dataplane.py) pads ragged tails to a pow-2
    bucket ladder instead of the full batch, so at serve time any ladder
    shape may be dispatched — each one is a distinct XLA program and must
    compile. Gating every bucket here is what makes "zero steady-state
    recompiles" a pre-verified fact rather than a first-request surprise."""
    from mmlspark_tpu.nn.models import ModelBundle
    from mmlspark_tpu.nn.runner import DeepModelTransformer

    t = DeepModelTransformer(input_col="x", fused_dispatch=False)
    t.set_model(ModelBundle.init("mlp", (8,), seed=0, num_outputs=3))
    fwd = t._forward_fn(("logits",))
    return fwd, (t.bundle.variables, sds((n, 8), jnp.float32))


def runner_sharded_build(n, n_data, n_model=1):
    """One (bucket shape x mesh shape) cell of the SHARDED ladder.

    Under a mesh the fusion engine pads to buckets that are multiples of
    the data-axis size, and a different mesh shape is a different program
    (the executable-cache family key includes it) — so every combination
    the sharded ladder can mint must compile, or a chip-count change means
    a steady-state recompile. n_model > 1 compiles the tensor-parallel
    (column-parallel + all_gather) forward, the same body the fused
    DeepModelTransformer kernel swaps in via mesh_fn."""
    from mmlspark_tpu.nn.models import ModelBundle
    from mmlspark_tpu.nn.runner import DeepModelTransformer
    from mmlspark_tpu.parallel.mesh import (data_sharding, make_mesh,
                                            replicated_sharding)

    mesh = make_mesh(n_data=n_data, n_model=n_model,
                     devices=jax.devices()[: n_data * n_model])
    t = DeepModelTransformer(input_col="x", fused_dispatch=False)
    # feature/output widths divisible by the model axis so TP qualifies
    t.set_model(ModelBundle.init("mlp", (8,), seed=0, num_outputs=4,
                                 features=(16, 8)))
    x = sds((n, 8), jnp.float32)
    if n_model > 1:
        fwd, shardings = t._tp_forward_fn(("logits",), mesh)
        jfn = jax.jit(fwd, in_shardings=(shardings,
                                         data_sharding(mesh, None)))
    else:
        jfn = jax.jit(t._forward_fn(("logits",)),
                      in_shardings=(replicated_sharding(mesh),
                                    data_sharding(mesh, None)))
    return jfn, (t.bundle.variables, x)


# one fitted model + fused executor shared by every serving gate below —
# training per (bucket x mesh) cell would swamp the gate's wall clock
_RESIDENT = {}


def _resident_executor(n_data=0, donate=True):
    """A ResidentExecutor over a tiny fitted GBDT model, fused under a
    `n_data x 1` mesh (0 = single device). Cached per (mesh, donation)
    cell: a donated (input-aliased) executable is a DIFFERENT XLA program
    from the non-donated one, and serve_model can mint either
    (donate_buffers defaults on, users may disable it)."""
    key = (n_data, bool(donate))
    if key in _RESIDENT:
        return _RESIDENT[key]
    import numpy as np

    from mmlspark_tpu.core.fusion import fuse
    from mmlspark_tpu.core.pipeline import PipelineModel
    from mmlspark_tpu.core.schema import Table
    from mmlspark_tpu.gbdt.estimators import GBDTRegressor

    if "model" not in _RESIDENT:
        rng = np.random.default_rng(3)
        X = rng.normal(size=(256, 8)).astype(np.float32).astype(np.float64)
        y = X @ rng.normal(size=8)
        _RESIDENT["model"] = GBDTRegressor(
            num_iterations=5, num_leaves=7).fit(
            Table({"features": X, "label": y}))
    mesh = None
    if n_data:
        from mmlspark_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(n_data=n_data, n_model=1,
                         devices=jax.devices()[:n_data])
    fused = fuse(PipelineModel([_RESIDENT["model"]]), mesh=mesh,
                 donate_buffers=donate)
    rex = fused.resident_executor()
    if isinstance(rex, str):
        raise RuntimeError(f"no resident executor: {rex}")
    _RESIDENT[key] = rex
    return rex


def serving_resident_build(n, n_data=0, donate=True):
    """The serving hot path's resident executable at ONE bucket rung.

    Since the fused decode->bin->traverse rewrite this program is ONE
    jitted body from the raw f32 feature matrix to scores: vmapped
    `searchsorted` against device-pinned adjusted bin keys, then the
    fixed-depth gather walk over the SoA node arrays — no separate
    binning dispatch exists anymore, so this gate IS the compile
    evidence for the fused kernel across (bucket x mesh x donation).

    io_http/serving.py routes live request batches straight onto these
    programs (params pinned on device, one upload per batch), and its
    warmup refuses to flip /readyz until the full ladder is compiled —
    so every rung the batcher can mint must AOT-compile, single-device
    and under each mesh shape this host can form, donated and not.
    (Pipeline depth needs no axis of its own: lag-K readback re-dispatches
    the SAME executable — depth only changes how many results are in
    flight on the host, never the lowered program.)"""
    import numpy as np

    rex = _resident_executor(n_data, donate)
    return rex.aot_args({"features": np.zeros((1, 8), np.float64)}, n)


def _sar_resident_executor(n_data=0):
    """A ResidentExecutor over a tiny fitted SAR top-k scorer, fused under
    a `n_data x 1` mesh (0 = single device). Cached per mesh shape."""
    key = ("sar", n_data)
    if key in _RESIDENT:
        return _RESIDENT[key]
    import numpy as np

    from mmlspark_tpu.core.fusion import fuse
    from mmlspark_tpu.core.pipeline import PipelineModel
    from mmlspark_tpu.core.schema import Table
    from mmlspark_tpu.recommendation import SAR, SARTopKScorer

    if "sar_model" not in _RESIDENT:
        rng = np.random.default_rng(5)
        rows = [(float(u), float(i), 1.0)
                for u in range(32) for i in rng.choice(24, 6, replace=False)]
        arr = np.asarray(rows, np.float64)
        _RESIDENT["sar_model"] = SAR(support_threshold=1).fit(Table({
            "user": arr[:, 0], "item": arr[:, 1], "rating": arr[:, 2]}))
    mesh = None
    if n_data:
        from mmlspark_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(n_data=n_data, n_model=1,
                         devices=jax.devices()[:n_data])
    scorer = SARTopKScorer.from_model(_RESIDENT["sar_model"], k=10)
    fused = fuse(PipelineModel([scorer]), mesh=mesh)
    rex = fused.resident_executor()
    if isinstance(rex, str):
        raise RuntimeError(f"no resident executor: {rex}")
    _RESIDENT[key] = rex
    return rex


def sar_resident_build(n, n_data=0):
    """The SAR recommender hot path's resident executable at ONE rung.

    serve_recommender pins user-affinity and item-similarity on device and
    routes decoded user-id batches onto these fused
    gather -> matmul -> seen-mask -> top_k programs; warmup compiles the
    full ladder before /readyz flips, so every rung must AOT-compile."""
    import numpy as np

    rex = _sar_resident_executor(n_data)
    return rex.aot_args({"features": np.zeros((1, 1), np.float64)}, n)


def main():
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {getattr(dev, 'device_kind', '?')} "
          f"x{len(jax.devices())}", flush=True)
    if dev.platform != "tpu":
        print("aot_gate: no TPU — Mosaic verdicts exist only on the chip; "
              "refusing to run", file=sys.stderr, flush=True)
        return 2

    gate("hist_per_feature_int32", lambda: hist_build())
    gate("hist_per_feature_uint8",
         lambda: hist_build(bins_dtype=jnp.uint8))
    gate("hist_grouped_g4_uint8",
         lambda: hist_build(group=4, bins_dtype=jnp.uint8))
    gate("hist_fused_uint8", lambda: hist_build(fused=True,
                                                bins_dtype=jnp.uint8))
    os.environ.pop("MMLSPARK_TPU_HIST_GROUP", None)
    os.environ.pop("MMLSPARK_TPU_FUSED_HIST", None)
    gate("flash_fwd_seq512", lambda: flash_build(512))
    gate("flash_fwd_seq4096", lambda: flash_build(4096))
    gate("flash_fwd_bwd_seq512", lambda: flash_build(512, grad=True))

    from mmlspark_tpu.core.dataplane import ShapeBucketer
    for bucket in ShapeBucketer(64).ladder:
        gate(f"runner_bucket_b{bucket}",
             lambda n=bucket: runner_bucket_build(n))

    # sharded ladder: every (bucket shape x mesh shape) the fused engine
    # can mint on this host's devices, incl. one 2-D data x model mesh.
    # Ladders come from ShapeBucketer(shards=...) — the skew-aware
    # per-shard-balanced rungs serve_model and the fused engine actually
    # mint under a mesh (NOT the old multiple_of= rounding).
    n_dev = len(jax.devices())
    mesh_shapes = [(d, 1) for d in (2, 4, 8) if d <= n_dev]
    if n_dev >= 8:
        mesh_shapes.append((4, 2))
    elif n_dev >= 4:
        mesh_shapes.append((2, 2))
    for n_data, n_model in mesh_shapes:
        for bucket in ShapeBucketer(64, shards=n_data).ladder:
            gate(f"runner_bucket_b{bucket}_mesh{n_data}x{n_model}",
                 lambda n=bucket, d=n_data, m=n_model:
                 runner_sharded_build(n, d, m))

    # serving hot path: the resident executor's bucket ladder (the exact
    # programs serve_model warmup compiles before /readyz flips),
    # single-device and sharded over each pure-data mesh, in BOTH
    # donation states — an input-aliased executable is a different
    # program, and donate_buffers is a user-settable Param
    for bucket in ShapeBucketer(64).ladder:
        gate(f"serving_resident_b{bucket}",
             lambda n=bucket: serving_resident_build(n))
        gate(f"serving_resident_b{bucket}_nodonate",
             lambda n=bucket: serving_resident_build(n, donate=False))
    for n_data, n_model in mesh_shapes:
        if n_model != 1:
            continue  # the GBDT kernel shards rows over data only
        for bucket in ShapeBucketer(64, shards=n_data).ladder:
            gate(f"serving_resident_b{bucket}_mesh{n_data}x1",
                 lambda n=bucket, d=n_data: serving_resident_build(n, d))
            gate(f"serving_resident_b{bucket}_mesh{n_data}x1_nodonate",
                 lambda n=bucket, d=n_data:
                 serving_resident_build(n, d, donate=False))

    # SAR recommender hot path: the device-resident top-k ladder
    # (recommendation/resident.py), single-device and sharded over each
    # pure-data mesh — same contract as the GBDT rungs above
    for bucket in ShapeBucketer(64).ladder:
        gate(f"sar_resident_b{bucket}",
             lambda n=bucket: sar_resident_build(n))
    for n_data, n_model in mesh_shapes:
        if n_model != 1:
            continue  # the SAR kernel shards rows over data only
        for bucket in ShapeBucketer(64, shards=n_data).ladder:
            gate(f"sar_resident_b{bucket}_mesh{n_data}x1",
                 lambda n=bucket, d=n_data: sar_resident_build(n, d))

    n_fail = sum(1 for _, v, _, _ in VERDICTS if v == "FAIL")
    print(f"\nAOT GATE SUMMARY: {len(VERDICTS) - n_fail}/{len(VERDICTS)} "
          f"kernels compile on {dev.platform}", flush=True)
    for name, verdict, secs, err in VERDICTS:
        print(f"  {name:28s} {verdict:4s} {secs:6.1f}s {err}", flush=True)
    return 1 if n_fail else 0


if __name__ == "__main__":
    sys.exit(main())
