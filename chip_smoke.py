#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the three main paths once, in one process, through the entry points
a user calls, at the full width of the configurations the repo names:

1. GBDT — `Table` -> `GBDTClassifier.fit` at the Higgs shape (2^20 rows x
   28 features, 255 bins, 63 leaves, uint8 bins; boosting rounds cut) ->
   `model.transform` over the whole table (the jitted device traversal) ->
   `serve_model` answering HTTP requests; then a `GBDTRegressor` of the
   same widths behind `serve_model`'s device-resident hot path (the
   classifier has no fused plan: its sigmoid runs in float64 on the host).
2. Runner — `DeepModelTransformer.transform`, ResNet-50 at 224 px in
   bfloat16, batches of 128, checked against a float32 run of the same
   weights.
3. Trainer — `DNNLearner.fit`, ResNet-50 at 224 px, batch 32, on the
   fused-epoch path and on the streamed path.

Then two short checks of what production code can select besides:

4. Lanes — the other two device-resident serving lanes, SAR top-k and the
   fused runner: enabled, byte-matched against the handler at every ladder
   rung during warm-up, and answering the same over HTTP.
5. Attention — the Pallas flash kernel (`attention_impl="flash"`) against
   `dense_attention`, forward and `jax.grad`, bfloat16, D=64, at T=512 and
   T=4096.

With more than one device the GBDT fit and the resident serving lane also
run over a mesh of all of them (same trees as the single-device fit, rows
observed on every device), the trainer shards its batch over them, and the
tensor-parallel runner body is byte-compared on a data x model mesh.

Weights and data are random, made from SEED. Nothing is caught: the first
failed check or exception ends the run with a non-zero exit code. Without
a TPU it exits 2 at once and prints no result. The last line of standard
output is the result, one JSON object.
"""

from __future__ import annotations

import contextlib
import json
import sys
import time
import urllib.request

import numpy as np

SEED = 0


class SmokeFailure(Exception):
    pass


def require(cond, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return time.perf_counter() - t0, out


def report(phase: str, **fields) -> None:
    body = " ".join(f"{k}={v:.3f}" if isinstance(v, float) else f"{k}={v}"
                    for k, v in fields.items())
    print(f"[{phase}] {body}", flush=True)


# --------------------------------------------------------------------- #
# serving helpers                                                       #
# --------------------------------------------------------------------- #

def _post(url: str, payload: dict) -> dict:
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as r:
        return json.loads(r.read())


def _get(url: str) -> dict:
    with urllib.request.urlopen(url, timeout=60) as r:
        return json.loads(r.read())


def _wait_ready(srv, timeout_s: float = 600.0) -> None:
    deadline = time.monotonic() + timeout_s
    while not srv.ready:
        require(srv.warmup_error is None,
                f"serving warm-up failed: {srv.warmup_error}")
        require(time.monotonic() < deadline,
                f"server not ready after {timeout_s:.0f}s; "
                f"health={srv.health()}")
        time.sleep(0.05)


@contextlib.contextmanager
def _serving(model, warm: "dict | None" = None, **kw):
    """`serve_model`, warmed on the request `warm` (whose keys name the
    input columns) when the model takes one; yields (server, seconds until
    /readyz flipped)."""
    from mmlspark_tpu.io_http import serve_model
    from mmlspark_tpu.io_http.schema import HTTPRequestData

    if warm is not None:
        kw.update(input_cols=list(warm),
                  warmup_request=HTTPRequestData.from_json("/", warm))
    t0 = time.perf_counter()
    srv = serve_model(model, max_batch_size=64, **kw)
    try:
        _wait_ready(srv)
        yield srv, time.perf_counter() - t0
    finally:
        srv.stop()


def _ask(srv, payloads) -> "tuple[float, list]":
    return timed(lambda: [_post(srv.url, p) for p in payloads])


def _check_hot_path(srv, to_ready_s, payloads, expect, label: str) -> None:
    """The device-resident lane compiled, ran and byte-matched at every
    ladder rung, and answers `payloads` with exactly `expect`."""
    dt, got = _ask(srv, payloads)
    require(got == expect, f"{label}: served replies differ from the model's")
    hp = _get(srv.url)["hot_path"]
    require(hp is not None, f"{label}: serve_model built no hot path")
    require(hp["enabled"] and hp["disabled_reason"] is None,
            f"{label}: hot path disabled: {hp['disabled_reason']}")
    lane = hp["resident_label"]
    for rung in srv.bucketer.ladder:
        require(lane in hp["timings_ms"].get(str(rung), {}),
                f"{label}: no {lane} timing at rung {rung}: "
                f"{hp['timings_ms']}")
    if not hp["paths"][lane]:
        # the measured crossover chose the native walk at the rungs these
        # requests landed on; drive the resident lane itself once
        srv.hot_path.force_path = lane
        _, got = _ask(srv, payloads)
        srv.hot_path.force_path = None
        require(got == expect,
                f"{label}: resident replies differ from the model's")
        hp = _get(srv.url)["hot_path"]
    require(hp["paths"][lane] >= 1,
            f"{label}: no request was counted on the {lane} path")
    report(label, to_ready_s=to_ready_s, requests_s=dt,
           crossover=hp["crossover"], timings_ms=hp["timings_ms"],
           paths=hp["paths"])


def _handler_replies(srv, payloads) -> list:
    """What the handler route answers: the oracle warm-up holds a lane to."""
    srv.hot_path.force_path = "host"
    _, got = _ask(srv, payloads)
    srv.hot_path.force_path = None
    return got


# --------------------------------------------------------------------- #
# phase 1: GBDT                                                         #
# --------------------------------------------------------------------- #

def higgs_like(n: int, f: int):
    """Seeded stand-in at the Higgs shape: float32-representable features
    (the serving lane bins on the device in float32), a binary label from
    a non-linear score plus noise."""
    rng = np.random.default_rng(SEED + 9)
    x = rng.normal(size=(n, f)).astype(np.float32)
    score = x[:, 0] - 0.6 * x[:, 1] + 0.3 * x[:, 2] * x[:, 3] + 0.2 * x[:, 4]
    y = (score + rng.normal(scale=0.9, size=n) > 0).astype(np.float64)
    return x.astype(np.float64), y


def gbdt_phase(n_dev: int, n_rows: int = 1 << 20, n_features: int = 28,
               rounds: int = 10, serve_rounds: int = 5) -> None:
    import jax

    from mmlspark_tpu.core import kernels
    from mmlspark_tpu.core.schema import Table
    from mmlspark_tpu.gbdt import GBDTClassifier, GBDTRegressor
    from mmlspark_tpu.gbdt.booster import Booster
    from mmlspark_tpu.gbdt.hist_kernel import histogram_pallas

    require(kernels.resolve("gbdt_histogram") is histogram_pallas,
            "the histogram kernel did not resolve to the Pallas kernel")
    widths = dict(num_leaves=63, max_bin=255, bin_dtype="uint8")
    x, y = higgs_like(n_rows, n_features)
    table = Table({"features": x, "label": y})
    cols = [f"f{i}" for i in range(n_features)]

    def fit():
        return GBDTClassifier(num_iterations=rounds, **widths).fit(table)

    cold, model = timed(fit)
    warm, again = timed(fit)
    text = model.booster.to_text()
    require(again.booster.to_text() == text,
            "two fits of the same table gave different models")
    report("gbdt.fit", rows=n_rows, features=n_features, rounds=rounds,
           trees=model.booster.num_trees, cold_s=cold, warm_s=warm)

    require(n_rows > Booster.HOST_PREDICT_MAX_ROWS,
            "table small enough for the host walk; the device traversal "
            "would not run")
    cold, scored = timed(lambda: model.transform(table))
    warm, _ = timed(lambda: model.transform(table))
    prob = np.asarray(scored["probability"])
    require(prob.shape == (n_rows, 2) and np.isfinite(prob).all(),
            f"probabilities malformed: shape {prob.shape}")
    acc = float((np.asarray(scored["prediction"]) == y).mean())
    constant = float(max(y.mean(), 1.0 - y.mean()))
    require(acc > constant + 0.05,
            f"train accuracy {acc:.4f} does not beat the constant "
            f"predictor {constant:.4f}")
    # the device traversal against the host tree walk on a small input
    # (the repo's contract: identical float32 accumulation order)
    small = x[: Booster.HOST_PREDICT_MAX_ROWS]
    host = np.asarray(model.booster.predict_raw(small, device="host"))
    dev = np.asarray(scored["raw_prediction"])[: len(small), 1]
    require(np.array_equal(host, dev.astype(host.dtype)),
            "device traversal and host walk disagree: max |diff| "
            f"{np.abs(host - dev).max():.3e}")
    report("gbdt.transform", rows=n_rows, accuracy=acc, constant=constant,
           cold_s=cold, warm_s=warm)

    # the classifier serves through the handler (serve_model says why)
    rows = x[:8]
    payloads = [dict(zip(cols, r.tolist())) for r in rows]
    with _serving(model, payloads[0],
                  output_col="probability") as (srv, to_ready_s):
        dt, got = _ask(srv, payloads)
        expect = np.asarray(
            model.transform(Table({"features": rows}))["probability"])
        require([g["probability"] for g in got] == expect.tolist(),
                "classifier replies differ from model.transform")
        require(_get(srv.url)["hot_path"] is None,
                "the classifier grew a hot path; extend the smoke to it")
        report("gbdt.serve_classifier", route="handler",
               to_ready_s=to_ready_s, requests_s=dt)

    # same widths behind the device-resident lane
    cold, reg = timed(lambda: GBDTRegressor(
        num_iterations=serve_rounds, **widths).fit(table))
    pred = np.asarray(reg.transform(table)["prediction"])
    mse, var = float(((pred - y) ** 2).mean()), float(y.var())
    require(np.isfinite(pred).all() and mse < 0.95 * var,
            f"regressor mse {mse:.4f} does not beat the constant "
            f"predictor {var:.4f}")
    report("gbdt.fit_regressor", rounds=serve_rounds, mse=mse,
           constant=var, cold_s=cold)
    expect = [{"prediction": v} for v in np.asarray(
        reg.transform(Table({"features": rows}))["prediction"]).tolist()]
    with _serving(reg, payloads[0]) as (srv, to_ready_s):
        _check_hot_path(srv, to_ready_s, payloads, expect,
                        "gbdt.serve_resident")

    if n_dev == 1:
        return
    # ---- the same fit over a mesh of every device ---------------------
    from mmlspark_tpu.parallel.mesh import (make_mesh, set_default_mesh,
                                            shard_rows)

    mesh = make_mesh(n_data=n_dev)
    sharded, _ = shard_rows(x, mesh)
    placed = {s.device for s in sharded.addressable_shards}
    require(len(placed) == n_dev and all(
        s.data.shape[0] == n_rows // n_dev
        for s in sharded.addressable_shards),
        f"rows sit on {len(placed)} of {n_dev} devices")
    set_default_mesh(mesh)
    try:
        cold, mesh_model = timed(lambda: GBDTClassifier(
            num_iterations=rounds, use_mesh=True, **widths).fit(table))
    finally:
        set_default_mesh(None)
    # the repo's replicated-model contract (`_GBDTParams.use_mesh`, checked
    # the same way by `__graft_entry__.dryrun_multichip`): the same trees,
    # leaf values within float-psum tolerance, the same decisions
    one, many = model.booster, mesh_model.booster
    require(np.array_equal(many.feature, one.feature)
            and np.array_equal(many.left, one.left),
            f"the {n_dev}-device trees differ in structure from the "
            "single-device fit")
    p_one = np.asarray(one.predict(x))
    p_many = np.asarray(many.predict(x))
    close = float(np.isclose(p_many, p_one, rtol=1e-3, atol=1e-5).mean())
    agree = float(((p_many > 0.5) == (p_one > 0.5)).mean())
    require(close > 0.99 and agree > 0.995,
            f"the {n_dev}-device model diverged: {close:.4f} of "
            f"predictions close, {agree:.4f} of decisions equal")
    peaks = [d.memory_stats()["peak_bytes_in_use"] for d in jax.devices()]
    require(min(peaks) > n_rows * n_features // n_dev,
            f"a device never held its shard of the bins: peaks {peaks}")
    report("gbdt.fit_mesh", devices=n_dev, cold_s=cold,
           predictions_close=close, decisions_equal=agree,
           to_text_equal=many.to_text() == text, peak_bytes=peaks)
    with _serving(reg, payloads[0], mesh=mesh) as (srv, to_ready_s):
        feats = np.repeat(rows, n_dev, axis=0)
        outs = srv.hot_path.executor.dispatch({"features": feats})
        spread = {s.device for o in jax.tree.leaves(outs)
                  for s in o.addressable_shards}
        require(len(spread) == n_dev,
                f"resident scores sit on {len(spread)} of {n_dev} devices")
        _check_hot_path(srv, to_ready_s, payloads, expect,
                        "gbdt.serve_resident_mesh")


# --------------------------------------------------------------------- #
# phase 2: runner                                                       #
# --------------------------------------------------------------------- #

def runner_phase(n_dev: int, side: int = 224, batch: int = 128,
                 n_batches: int = 3, n_ref: int = 8,
                 tolerance: float = 0.02) -> None:
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.core.schema import Table
    from mmlspark_tpu.nn.models import ModelBundle
    from mmlspark_tpu.nn.runner import DeepModelTransformer

    preprocess = {"mean": 127.5, "std": 63.75}
    bundle = ModelBundle.init("resnet50", (side, side, 3), seed=SEED,
                              preprocess=preprocess, dtype=jnp.bfloat16)
    # flax zero-initialises each block's last BatchNorm scale, which would
    # multiply every residual branch by 0 and hide its numerics from the
    # comparison below: give those a non-zero value
    bundle.variables = jax.tree.map(
        lambda a: jnp.full_like(a, 0.5) if not np.asarray(a).any() else a,
        bundle.variables)
    rng = np.random.default_rng(SEED + 3)
    images = rng.integers(0, 256, size=(batch * n_batches, side, side, 3),
                          dtype=np.uint8)
    table = Table({"image": images})
    runner = DeepModelTransformer(
        input_col="image", mini_batch_size=batch, bfloat16=True,
    ).set_model(bundle)
    cold, out = timed(lambda: runner.transform(table))
    warm, out2 = timed(lambda: runner.transform(table))
    logits = np.asarray(out["output"])
    require(logits.shape == (len(images), 1000)
            and np.isfinite(logits).all(),
            f"logits malformed: shape {logits.shape}")
    require(np.array_equal(logits, np.asarray(out2["output"])),
            "two transforms of the same table differ")

    ref_bundle = ModelBundle(
        architecture="resnet50", config={}, variables=bundle.variables,
        input_shape=bundle.input_shape, preprocess=preprocess)
    ref = np.asarray(DeepModelTransformer(
        input_col="image", mini_batch_size=n_ref,
    ).set_model(ref_bundle).transform(
        Table({"image": images[:n_ref]}))["output"])
    scale = float(np.abs(ref).max())
    err = float(np.abs(logits[:n_ref] - ref).max()) / scale
    require(scale > 0 and err < tolerance,
            f"bfloat16 logits differ from float32 by {err:.4f} of the "
            f"largest logit (limit {tolerance})")
    report("runner", images=len(images), side=side, batch=batch,
           cold_s=cold, warm_s=warm, bf16_vs_f32_rel_err=err)

    if n_dev < 4 or n_dev % 2:
        return
    # ---- the tensor-parallel body on a data x model mesh --------------
    # 70 rows in batches of 32 over a 4-output head: the ragged tail and
    # the halved head give the smallest per-shard dots, the shape XLA:CPU
    # cannot hold byte-identical (tests/test_sharded_fusion.py xfails it)
    from mmlspark_tpu.core.fusion import fuse
    from mmlspark_tpu.parallel.mesh import make_mesh

    def mlp():
        return DeepModelTransformer(
            input_col="x", mini_batch_size=32,
            fetch_dict={"out": "logits", "prob": "probability"},
        ).set_model(ModelBundle.init(
            "mlp", (16,), seed=SEED, num_outputs=4, features=(16, 8)))

    small = Table({"x": rng.normal(size=(70, 16)).astype(np.float32)})
    ref = mlp().transform(small)
    fused = fuse(mlp(), mini_batch_size=32,
                 mesh=make_mesh(n_data=n_dev // 2, n_model=2))
    got = fused.transform(small)
    seg = fused.last_stats["segments"][0]
    require(seg["mesh_shape"] == f"{n_dev // 2}x2"
            and seg["param_placements"] == ["custom"],
            f"the tensor-parallel body did not run: {seg}")
    for col in ("out", "prob"):
        require(np.asarray(got[col]).tobytes()
                == np.asarray(ref[col]).tobytes(),
                f"tensor-parallel {col!r} differs from the unsharded run")
    report("runner.tensor_parallel", mesh=seg["mesh_shape"], outputs=4,
           byte_identical=True)


# --------------------------------------------------------------------- #
# phase 3: trainer                                                      #
# --------------------------------------------------------------------- #

def trainer_phase(n_dev: int, side: int = 224, n_images: int = 128,
                  batch: int = 32, epochs: int = 3,
                  architecture: str = "resnet50") -> None:
    import jax

    from mmlspark_tpu.core.schema import Table
    from mmlspark_tpu.nn.trainer import DNNLearner
    from mmlspark_tpu.observability.tracing import get_tracer

    classes = 10
    rng = np.random.default_rng(SEED + 5)
    labels = rng.integers(0, classes, size=n_images)
    # brightness carries the class, so a few steps can lower the loss
    images = (rng.integers(0, 64, size=(n_images, side, side, 3))
              + 19 * labels[:, None, None, None]).astype(np.uint8)
    table = Table({"features": images, "label": labels.astype(np.float64)})
    steps = n_images // batch
    for fused in (True, False):
        tracer = get_tracer()
        tracer.clear()
        learner = DNNLearner(
            architecture=architecture, batch_size=batch, epochs=epochs,
            learning_rate=1e-3, fused_epochs=fused, seed=SEED)
        total, model = timed(lambda: learner.fit(table))
        spans = [s for s in tracer.spans() if s.name == "trainer.epoch"]
        require(len(spans) == epochs
                and all(s.args["fused"] is fused for s in spans)
                and all(s.args["steps"] == steps for s in spans),
                f"expected {epochs} epochs of {steps} steps with "
                f"fused={fused}; saw {[s.args for s in spans]}")
        losses = [float(s.args["loss"]) for s in spans]
        require(all(np.isfinite(losses)) and losses[-1] < losses[0],
                f"loss did not fall (fused={fused}): {losses}")
        leaves = jax.tree.leaves(model.bundle.variables)
        require(all(np.isfinite(np.asarray(a)).all() for a in leaves),
                "non-finite parameter after training")
        report("trainer", path="fused_epoch" if fused else "streamed",
               devices=n_dev, steps_per_epoch=steps, batch=batch, side=side,
               losses=[round(v, 4) for v in losses], total_s=total,
               cold_epoch_s=spans[0].dur_us / 1e6,
               warm_epoch_s=spans[-1].dur_us / 1e6)


# --------------------------------------------------------------------- #
# phase 4: the other device-resident serving lanes                      #
# --------------------------------------------------------------------- #

def lanes_phase(n_users: int = 4000, n_items: int = 1000, per_user: int = 30,
                width: int = 256) -> None:
    from mmlspark_tpu.core.pipeline import pipeline_model
    from mmlspark_tpu.core.schema import Table
    from mmlspark_tpu.nn.models import ModelBundle
    from mmlspark_tpu.nn.runner import DeepModelTransformer
    from mmlspark_tpu.recommendation import SAR

    rng = np.random.default_rng(SEED + 11)
    items = np.concatenate([rng.choice(n_items, size=per_user, replace=False)
                            for _ in range(n_users)])
    cold, sar = timed(lambda: SAR(support_threshold=1).fit(Table({
        "user": np.repeat(np.arange(n_users), per_user).astype(np.float64),
        "item": items.astype(np.float64),
        "rating": np.ones(len(items))})))
    payloads = [{"user": int(u)} for u in rng.integers(0, n_users, size=8)]
    with _serving(sar) as (srv, to_ready_s):
        expect = _handler_replies(srv, payloads)
        require(all(len(e["recommendations"]) == len(e["ratings"]) > 0
                    and np.isfinite(e["ratings"]).all() for e in expect),
                f"SAR replies malformed: {expect[0]}")
        _check_hot_path(srv, to_ready_s, payloads, expect, "lanes.sar")
    report("lanes.sar_fit", users=n_users, items=n_items, cold_s=cold)

    runner = DeepModelTransformer(
        input_col="features", mini_batch_size=64, bfloat16=True,
    ).set_model(ModelBundle.init(
        "mlp", (width,), seed=SEED, num_outputs=16,
        features=(2 * width, width)))
    cols = [f"f{i}" for i in range(width)]
    rows = rng.normal(size=(8, width)).astype(np.float32)
    payloads = [dict(zip(cols, r.tolist())) for r in rows]
    with _serving(pipeline_model(runner), payloads[0],
                  output_col="output") as (srv, to_ready_s):
        expect = _handler_replies(srv, payloads)
        require(all(np.shape(e["output"]) == (16,)
                    and np.isfinite(e["output"]).all() for e in expect),
                f"runner replies malformed: {expect[0]}")
        _check_hot_path(srv, to_ready_s, payloads, expect, "lanes.runner")


# --------------------------------------------------------------------- #
# phase 5: the Pallas attention kernel                                  #
# --------------------------------------------------------------------- #

def attention_phase(heads: int = 8, dim: int = 64, fwd_limit: float = 2e-2,
                    grad_limit: float = 3e-2) -> None:
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.nn.attention import dense_attention, flash_attention

    def loss(attend, weight):
        return lambda q, k, v: (attend(q, k, v, causal=True).astype(
            jnp.float32) * weight).sum()

    for batch, seq in ((2, 512), (1, 4096)):
        keys = jax.random.split(jax.random.PRNGKey(SEED + seq), 4)
        q, k, v, weight = (jax.random.normal(
            key, (batch, seq, heads, dim), jnp.float32) for key in keys)
        low = [a.astype(jnp.bfloat16) for a in (q, k, v)]
        # the reference: dense attention in float32 on the same rounded inputs
        exact = [a.astype(jnp.float32) for a in low]
        ref = jax.jit(lambda *a: dense_attention(*a, causal=True))(*exact)
        cold, out = timed(lambda: jax.block_until_ready(jax.jit(
            lambda *a: flash_attention(*a, causal=True))(*low)))
        out = out.astype(jnp.float32)
        fwd_err = float(jnp.abs(out - ref).max())
        require(out.shape == ref.shape and bool(jnp.isfinite(out).all())
                and fwd_err < fwd_limit,
                f"flash forward at T={seq}: max |err| {fwd_err:.3e} "
                f"(limit {fwd_limit})")
        g_ref = jax.jit(jax.grad(loss(dense_attention, weight),
                                 argnums=(0, 1, 2)))(*exact)
        g_out = jax.jit(jax.grad(loss(flash_attention, weight),
                                 argnums=(0, 1, 2)))(*low)
        grad_err = max(float(jnp.abs(a.astype(jnp.float32) - b).max()
                             / jnp.abs(b).max())
                       for a, b in zip(g_out, g_ref))
        require(grad_err < grad_limit,
                f"flash grad at T={seq}: relative err {grad_err:.3e} "
                f"(limit {grad_limit})")
        report("attention.flash", seq=seq, heads=heads, dim=dim,
               fwd_max_err=fwd_err, grad_rel_err=grad_err, cold_s=cold)


# --------------------------------------------------------------------- #

def main() -> int:
    import mmlspark_tpu  # noqa: F401 — places the compile cache first
    import jax
    import jaxlib

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform={dev.platform!r}); "
              "this script only runs on the chip", file=sys.stderr)
        return 2
    from importlib.metadata import version

    import mmlspark_tpu.gbdt  # noqa: F401 — registers the histogram kernels
    from mmlspark_tpu import native
    from mmlspark_tpu.core import kernels

    n_dev = len(devices)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": n_dev}
    print(f"platform={dev.platform} device_kind={dev.device_kind!r} "
          f"count={n_dev}", flush=True)
    print(f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
          f"libtpu={version('libtpu')}", flush=True)
    print(f"native.available={native.available()} "
          f"gbdt_histogram={kernels.resolve('gbdt_histogram').__name__} "
          f"compile_cache={jax.config.jax_compilation_cache_dir}",
          flush=True)

    t0 = time.perf_counter()
    gbdt_phase(n_dev)
    runner_phase(n_dev)
    trainer_phase(n_dev)
    lanes_phase()
    attention_phase()
    print(f"[done] total_s={time.perf_counter() - t0:.1f}", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
