"""Headline benchmarks for the north-star paths (BASELINE.md):

1. GBDT fit throughput (rows/sec) on an Adult-Census-scale binary
   classification workload — the reference's `LightGBMClassifier.fit`
   (LightGBMClassifier.scala:47-94) on the `LightGBM - Quickstart` notebook.
2. Deep-model-runner inference throughput (images/sec) on a CIFAR10-scale
   ResNet forward — the reference's `CNTKModel.transform`
   (CNTKModel.scala:497-503) on the CIFAR10 notebook.
3. DNN training throughput (images/sec) on a ResNet-50 fine-tune —
   BASELINE config #4, the reference's `CNTKLearner.fit` via mpirun+CNTK
   (CNTKLearner.scala:169-183, CommandBuilders.scala:241-243).
4. Continuous-serving latency p50/p99 — the reference's ~1 ms claim
   (docs/mmlspark-serving.md:10-11).

Utilization is first-class: every compute-bound family reports achieved
TFLOP/s and MFU (model FLOPs utilization = achieved / chip peak bf16), and
the memory-bound GBDT fit reports a modeled HBM traffic figure against the
chip's bandwidth. FLOPs come from XLA's own cost analysis of the exact
compiled program where available, with analytic fallbacks.

There is no fallback: every family runs on the backend JAX selects and a
failure in any of them fails the bench. Without a TPU the bench refuses to
run unless `JAX_PLATFORMS=cpu` is set explicitly (the CI smoke), so a CPU
number can never land under a device's name by accident.

Prints ONE JSON line on stdout:
  {"metric", "value", "unit", "vs_baseline", "extra": {...}}
The headline metric is GBDT fit throughput; every other family, the MFU
fields, and the backend actually used ride in "extra".
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np

# Proxy for the reference's LightGBM-on-Spark CPU fit on Adult Census
# (no absolute published numbers exist; BASELINE.md): 32.6k rows x 100
# boosting rounds in ~3.3 s on a local[*] CI machine ≈ 1.0e6 rows/sec.
BASELINE_ROWS_PER_SEC = 1.0e6
# Proxy for the reference's CNTKModel CIFAR10 ResNet inference: CNTK-era
# ResNet-20 CIFAR10 forward on a CPU Spark executor sustains O(1k) img/s;
# a representative notebook-scale figure is ~2k images/sec (BASELINE.md
# publishes no absolute number either).
BASELINE_IMAGES_PER_SEC = 2.0e3
# Proxy for the reference's CNTKLearner ResNet-50 fine-tune (BASELINE
# config #4): CNTK-era single-GPU ResNet-50 ImageNet-size training
# sustained ~200 images/sec on a K80-class device.
BASELINE_TRAIN_IMAGES_PER_SEC = 2.0e2

N_ROWS = int(os.environ.get("MMLSPARK_TPU_BENCH_ROWS", 32768))
N_FEATURES = 14
NUM_ITERATIONS = 100
NUM_LEAVES = 31

IMG_BATCH = int(os.environ.get("MMLSPARK_TPU_BENCH_IMG_BATCH", 1024))
N_IMAGES = 8192         # CIFAR10-scale eval slice

# Orchestrator plumbing (see main()): a chip belongs to ONE process at a
# time — a second process fails or hangs in backend init until the first
# exits — so the families run as SEQUENTIAL child processes, each with a
# hard timeout. A native-code compile hang cannot be interrupted from
# inside the process (signals only fire between bytecodes), so the
# watchdog must live in a parent that never touches the device.
_SKIP_TRAINER_ENV = "MMLSPARK_TPU_BENCH_SKIP_TRAINER"
_SKIP_LARGE_ENV = "MMLSPARK_TPU_BENCH_SKIP_GBDT_LARGE"
_SKIP_TRANSFORMER_ENV = "MMLSPARK_TPU_BENCH_SKIP_TRANSFORMER"
_CORE_TIMEOUT_ENV = "MMLSPARK_TPU_BENCH_CORE_TIMEOUT"
_TRAINER_TIMEOUT_ENV = "MMLSPARK_TPU_BENCH_TRAINER_TIMEOUT"
_TRANSFORMER_TIMEOUT_ENV = "MMLSPARK_TPU_BENCH_TRANSFORMER_TIMEOUT"
_LARGE_TIMEOUT_ENV = "MMLSPARK_TPU_BENCH_GBDT_LARGE_TIMEOUT"
_MULTICHIP_TIMEOUT_ENV = "MMLSPARK_TPU_BENCH_MULTICHIP_TIMEOUT"
_MULTICHIP_ARTIFACT = "MULTICHIP_r08.json"


# --------------------------------------------------------------------- #
# chip model: peak numbers + XLA cost analysis                          #
# --------------------------------------------------------------------- #

# (substring of device_kind lower) -> (peak bf16 TFLOP/s, HBM GB/s) per chip.
# Public TPU spec-sheet numbers; v5e reports "TPU v5 lite" and v6e
# "TPU v6 lite". Every key names one part: no bare generation key that
# would hand an unlisted part a sibling's peak.
_CHIP_PEAKS = [
    ("v6 lite", (918.0, 1640.0)),
    ("v6e", (918.0, 1640.0)),
    ("v5 lite", (197.0, 819.0)),
    ("v5e", (197.0, 819.0)),
    ("v5p", (459.0, 2765.0)),
    ("v3", (123.0, 900.0)),
    ("v2", (45.0, 700.0)),
]


def chip_peaks() -> "tuple[str, float | None, float | None]":
    """(device_kind, peak bf16 TFLOP/s, HBM GB/s); Nones on the CPU
    backend. A non-CPU device that is not in the table is an error: a
    utilization against a guessed peak is worse than none."""
    import jax

    dev = jax.devices()[0]
    kind = str(getattr(dev, "device_kind", dev.platform))
    if dev.platform == "cpu":
        return kind, None, None
    low = kind.lower()
    for key, peaks in _CHIP_PEAKS:
        if key in low:
            return kind, peaks[0], peaks[1]
    raise RuntimeError(
        f"bench: no peak numbers for device_kind {kind!r}; add it to "
        "_CHIP_PEAKS with its source before benchmarking on it")


def flops_of(jitted, *args) -> "float | None":
    """XLA's own FLOP count for the exact compiled program (None when the
    backend doesn't report cost analysis)."""
    try:
        cost = jitted.lower(*args).compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        f = float(cost.get("flops", 0.0))
        return f if f > 0 else None
    except Exception:  # noqa: BLE001 — cost analysis is best-effort
        return None


def _mfu(tflops_achieved: "float | None", peak: "float | None") -> "float | None":
    if not tflops_achieved or not peak:
        return None
    return round(tflops_achieved / peak, 4)


def flops_sane(measured: "float | None", analytic: "float | None",
               label: str = "") -> "float | None":
    """Cross-check XLA's cost-analysis FLOPs against the analytic count.

    Some backends report padded/fused counts (a conv padded from 16 to 128
    lanes books 8x the maths that exists), which silently inflates MFU.
    Use the measured count when it's within a 1.5x ratio of the analytic
    model either way; otherwise trust the model and say so on stderr."""
    if measured is None:
        return analytic
    if analytic is None:
        return measured
    if measured > 1.5 * analytic or measured < analytic / 1.5:
        print(f"bench: cost-analysis flops {measured:.3e} vs analytic "
              f"{analytic:.3e} for {label}; using analytic",
              file=sys.stderr)
        return analytic
    return measured


def median_timed(fn, reps: int = 3) -> float:
    """Median wall-clock of `fn()` over reps — one host stall must not
    define a throughput number."""
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def require_tpu_or_explicit_cpu() -> str:
    """The platform this process runs on. No TPU is an error unless the
    caller asked for the CPU by name (`JAX_PLATFORMS=cpu`, the CI smoke)."""
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit(
            f"bench: no TPU (platform={platform!r}); set JAX_PLATFORMS=cpu "
            "explicitly for a CPU smoke run")
    return platform


def make_dataset(n: int, f: int, seed: int = 7):
    """Synthetic stand-in for Adult Census (zero-egress environment): mixed
    informative numeric features, binary label with label noise."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, f))
    x[:, 3] = np.round(np.abs(x[:, 3]) * 5)          # discrete-ish columns
    x[:, 7] = np.round(np.abs(x[:, 7]) * 3)
    logits = (
        x[:, 0] - 0.7 * x[:, 1] + 0.4 * x[:, 2] * x[:, 4] + 0.2 * x[:, 3]
    )
    y = (logits + rng.normal(scale=0.8, size=n) > 0).astype(np.float64)
    return x, y


# --------------------------------------------------------------------- #
# families                                                              #
# --------------------------------------------------------------------- #


def _auc(y_true: np.ndarray, scores: np.ndarray) -> "float | None":
    """Rank-based ROC-AUC (Mann-Whitney U with tie correction)."""
    pos = y_true > 0.5
    n_pos, n_neg = int(pos.sum()), int((~pos).sum())
    if n_pos == 0 or n_neg == 0:
        return None
    order = np.argsort(scores, kind="mergesort")
    ranks = np.empty(len(scores))
    ranks[order] = np.arange(1, len(scores) + 1)
    # average ranks over ties
    sorted_scores = scores[order]
    i = 0
    while i < len(sorted_scores):
        j = i
        while j + 1 < len(sorted_scores) and sorted_scores[j + 1] == sorted_scores[i]:
            j += 1
        if j > i:
            ranks[order[i:j + 1]] = (i + j + 2) / 2.0
        i = j + 1
    u = ranks[pos].sum() - n_pos * (n_pos + 1) / 2.0
    return float(u / (n_pos * n_neg))


N_VALID = 8192


def bench_gbdt(hbm_peak_gbps: "float | None") -> dict:
    from mmlspark_tpu.gbdt.booster import Booster, TrainOptions

    # held-out split: a perf change that silently broke learning must fail
    # the bench, not just the later test gates (valid AUC is the canary)
    x_all, y_all = make_dataset(N_ROWS + N_VALID, N_FEATURES)
    x, y = x_all[:N_ROWS], y_all[:N_ROWS]
    x_valid, y_valid = x_all[N_ROWS:], y_all[N_ROWS:]
    opts = TrainOptions(
        objective="binary",
        num_iterations=NUM_ITERATIONS,
        num_leaves=NUM_LEAVES,
        learning_rate=0.1,
    )

    from mmlspark_tpu.utils.profiling import device_trace

    # warm-up with IDENTICAL options: the fused boosting loop is one XLA
    # program whose shape includes num_iterations, so only an identical run
    # hits the compile cache (first TPU compile ~20-40s)
    Booster.train(x, y, opts)

    # set MMLSPARK_TPU_TRACE_DIR to capture an xprof trace of the timed fit
    with device_trace(None):
        t0 = time.perf_counter()
        booster = Booster.train(x, y, opts)
        elapsed = time.perf_counter() - t0

    # sanity: the model must actually learn (guards against benchmarking a no-op)
    pred = booster.predict(x)
    acc = float(((pred > 0.5) == (y > 0.5)).mean())
    assert acc > 0.7, f"model failed to learn (acc={acc:.3f})"
    valid_pred = np.asarray(booster.predict(x_valid))
    valid_auc = _auc(y_valid, valid_pred)
    assert valid_auc is not None and valid_auc > 0.75, (
        f"model failed to generalize (valid AUC={valid_auc})"
    )

    # The algorithm's irreducible traffic is re-reading the (n, F) binned
    # matrix (int32) + grad/hess for the histogram build of each split step
    # ((num_leaves-1) masked full passes per tree). Reporting that modeled
    # traffic against the chip's bandwidth shows where this config sits:
    # at Adult-Census scale the whole matrix is ~2 MB, so the fit is
    # dispatch/serialization-bound, NOT bandwidth-bound — the large-config
    # fit below is where the bandwidth story (and rows/sec) scales up.
    bins_bytes = N_ROWS * N_FEATURES * 4
    per_pass = bins_bytes + N_ROWS * 4 * 2           # bins + grad + hess
    modeled_gb = NUM_ITERATIONS * (NUM_LEAVES - 1) * per_pass / 1e9
    gbps = modeled_gb / elapsed
    rows_per_sec = N_ROWS * NUM_ITERATIONS / elapsed
    return {
        "rows_per_sec": rows_per_sec,
        "fit_seconds": elapsed,
        "acc": acc,
        "valid_auc": valid_auc,
        "modeled_hbm_gbps": gbps,
        "modeled_hbm_frac_of_peak": (
            round(gbps / hbm_peak_gbps, 4) if hbm_peak_gbps else None
        ),
    }


def bench_gbdt_large(hbm_peak_gbps: "float | None") -> "dict | None":
    """Higgs-scale fit (1M rows x 28 features, the reference's
    docs/lightgbm.md:17-21 workload shape): rows/sec at a size where the
    per-split fixed costs amortize and HBM traffic is the real limiter.
    Device-only — an explicit CPU smoke skips it (minutes for no insight)."""
    import jax

    if jax.default_backend() == "cpu":
        return None
    from mmlspark_tpu.gbdt.booster import Booster, TrainOptions

    n, f, iters, leaves = 1 << 20, 28, 50, 63
    n_valid = 65536
    x_all, y_all = make_dataset_wide(n + n_valid, f)
    x, y = x_all[:n], y_all[:n]
    x_valid, y_valid = x_all[n:], y_all[n:]
    # uint8 bin storage (4x narrower histogram HBM read) + on-device
    # binning (the host binary search costs ~2 s at this scale)
    bin_dtype, dev_bin = "uint8", True
    opts = TrainOptions(objective="binary", num_iterations=iters,
                        num_leaves=leaves, learning_rate=0.1,
                        bin_dtype=bin_dtype, device_binning=dev_bin)
    Booster.train(x, y, opts)                        # compile warm-up
    t0 = time.perf_counter()
    booster = Booster.train(x, y, opts)
    elapsed = time.perf_counter() - t0
    pred = booster.predict(x[:65536])
    acc = float(((pred > 0.5) == (y[:65536] > 0.5)).mean())
    valid_auc = _auc(y_valid, np.asarray(booster.predict(x_valid)))

    # batch scoring throughput — the reference predicts ONE ROW PER JNI
    # CALL (LightGBMBooster.scala:38-113, SURVEY.md §3.1's named perf
    # sink); here it is one jitted blocked traversal over all 1M rows.
    # Two tiers, like the runner family: end-to-end (host binning + h2d +
    # traversal + d2h; predict_raw synchronizes internally) and
    # device-resident (binned matrix already on device).
    import jax.numpy as jnp

    booster.predict_raw(x, device="device")   # compile+warm at this shape
    dt = median_timed(lambda: booster.predict_raw(x, device="device"))
    predict_e2e_rows = n / dt
    binned_dev = jnp.asarray(
        booster.bin_mapper.transform(x).astype(np.int32))
    traverse = booster._traverse_fn()
    jax.block_until_ready(traverse(binned_dev))      # compile + warm
    dt = median_timed(
        lambda: jax.block_until_ready(traverse(binned_dev)))
    predict_resident_rows = n / dt
    bin_bytes = 1 if bin_dtype == "uint8" else 4
    per_pass = n * f * bin_bytes + n * 4 * 2
    gbps = iters * (leaves - 1) * per_pass / 1e9 / elapsed
    return {
        "rows_per_sec": n * iters / elapsed,
        "fit_seconds": elapsed,
        "acc": acc,
        "valid_auc": valid_auc,
        "bin_dtype": bin_dtype,
        "device_binning": dev_bin,
        "predict_rows_per_sec": predict_e2e_rows,
        "predict_resident_rows_per_sec": predict_resident_rows,
        "modeled_hbm_gbps": gbps,
        "modeled_hbm_frac_of_peak": (
            round(gbps / hbm_peak_gbps, 4) if hbm_peak_gbps else None
        ),
    }


def bench_gbdt_dart() -> "dict | None":
    """dart-mode fit throughput (VERDICT r3 item 8: the fused dart loop —
    drop bookkeeping carried in the scan — must keep dart at O(1)
    dispatches per fit like the other modes; this row measures it)."""
    from mmlspark_tpu.gbdt.booster import Booster, TrainOptions

    x, y = make_dataset(N_ROWS, N_FEATURES)
    opts = TrainOptions(
        objective="binary", boosting_type="dart",
        num_iterations=NUM_ITERATIONS, num_leaves=NUM_LEAVES,
        learning_rate=0.1, drop_rate=0.1,
    )
    Booster.train(x, y, opts)                        # compile warm-up
    t0 = time.perf_counter()
    booster = Booster.train(x, y, opts)
    elapsed = time.perf_counter() - t0
    acc = float(((booster.predict(x) > 0.5) == (y > 0.5)).mean())
    return {
        "rows_per_sec": N_ROWS * NUM_ITERATIONS / elapsed,
        "fit_seconds": elapsed,
        "acc": acc,
    }


def make_dataset_wide(n: int, f: int, seed: int = 9):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, f)).astype(np.float32)
    logits = x[:, 0] - 0.6 * x[:, 1] + 0.3 * x[:, 2] * x[:, 3] + 0.2 * x[:, 4]
    y = (logits + rng.normal(scale=0.9, size=n) > 0).astype(np.float64)
    return x.astype(np.float64), y


def bench_model_runner(peak_tflops: "float | None") -> dict:
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.core.schema import Table
    from mmlspark_tpu.nn.models import ModelBundle
    from mmlspark_tpu.nn.runner import DeepModelTransformer

    bundle = ModelBundle.init(
        "resnet20_cifar", input_shape=(32, 32, 3), seed=0,
        preprocess={"mean": 127.5, "std": 63.75},
    )
    # bfloat16 forward: MXU-native (the reference's CNTK evaluator runs
    # f32 on GPU; bf16 is the TPU-idiomatic inference dtype)
    runner = DeepModelTransformer(
        input_col="image", mini_batch_size=IMG_BATCH, bfloat16=True,
    ).set_model(bundle)

    # images ship as uint8 (what decode produces) and are normalized ON
    # DEVICE via bundle.preprocess — 4x fewer host->device bytes, which is
    # the dominant cost of a batched transform (HBM/transfer-bound, not
    # MXU-bound: see the resident_* ceiling fields)
    rng = np.random.default_rng(3)
    images = rng.integers(0, 256, size=(N_IMAGES, 32, 32, 3), dtype=np.uint8)
    table = Table({"image": images})

    from mmlspark_tpu.utils.profiling import device_trace

    # async data plane: the same transform streamed at the STAGE'S default
    # settings (mini_batch_size=64, f32, prefetch_depth=2, shape_buckets)
    # with host prepare/upload and readback overlapping device compute;
    # fused dispatch off so the pipelined loop — not the one-dispatch
    # scan — is what's measured
    pipelined_runner = DeepModelTransformer(
        input_col="image", fused_dispatch=False,
    ).set_model(bundle)

    # compute ceiling: the same bf16 forward on device-RESIDENT data — the
    # gap to the end-to-end number is host<->device transfer, not MXU time
    bf16_vars = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16)
        if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating) else a,
        bundle.variables,
    )

    @jax.jit
    def fwd(v, xb):
        xf = (xb.astype(jnp.float32) - 127.5) / 63.75
        return bundle.module.apply(v, xf.astype(jnp.bfloat16), train=False)

    # fused scan over resident batches — the SAME dispatch pattern as the
    # e2e transform (a per-batch Python loop here measured 0.9x the e2e
    # path: 8 dispatches + host concat, not the forward's ceiling)
    @jax.jit
    def fwd_scan(v, xall):
        def body(_, xb):
            return 0, fwd(v, xb)

        _, outs = jax.lax.scan(body, 0, xall)
        return outs

    nb = N_IMAGES // IMG_BATCH
    xd = jax.device_put(images[:nb * IMG_BATCH].reshape(
        nb, IMG_BATCH, *images.shape[1:]))

    # warm-up / compile all three paths, and check the e2e output once
    out = runner.transform(table)
    probs = np.asarray(out["output"])
    assert probs.shape[0] == N_IMAGES and np.isfinite(probs).all()
    pipelined_runner.transform(table)
    jax.block_until_ready(fwd_scan(bf16_vars, xd))

    def timed(fn):
        t0 = time.perf_counter()
        fn()
        return time.perf_counter() - t0

    # INTERLEAVED reps: the sequential/pipelined/resident comparison is
    # the point of these rows, so each rep of each path runs under the
    # same machine-load window — five paired passes, not one-sided
    # samples taken minutes apart. Each row reports its MIN: external
    # load only ever slows a pass down, so the minimum is the robust
    # estimate of what the path costs (timeit's rationale)
    seq_t, pipe_t, res_t = [], [], []
    rows = [
        # each rep materializes host arrays, so it includes the full
        # device->host sync
        (seq_t, lambda: np.asarray(runner.transform(table)["output"])),
        (pipe_t, lambda: pipelined_runner.transform(table)),
        (res_t, lambda: np.asarray(fwd_scan(bf16_vars, xd))),
    ]
    with device_trace(None):
        for rep in range(5):
            # rotate the within-pass order so no row systematically gets
            # the coolest (or most contended) slot of each pass
            for acc, fn in rows[rep % 3:] + rows[:rep % 3]:
                acc.append(timed(fn))
    elapsed = min(seq_t)
    pipe_elapsed = min(pipe_t)
    pipe_stats = pipelined_runner.last_pipeline_stats or {}
    resident = (nb * IMG_BATCH) / min(res_t)
    # the pipelined-vs-sequential comparison is PAIRED: both rows ran in
    # every pass, so the per-pass ratio cancels that pass's machine-load
    # noise; the median over passes is the robust comparison (a ratio of
    # independent mins pairs each row's luckiest window against the
    # other's and swings with whichever row noise favored)
    pass_ratios = sorted(s / p for s, p in zip(seq_t, pipe_t))
    pipe_vs_seq = pass_ratios[len(pass_ratios) // 2]

    # FLOPs from XLA's cost model of the exact compiled forward, sanity-
    # checked against the analytic count: ResNet-20 CIFAR forward ~= 8.2e7
    # FLOPs/img (2 * ~41M MACs)
    step_flops = flops_of(fwd, bf16_vars, xd[0])
    per_img = flops_sane(step_flops / IMG_BATCH if step_flops else None,
                         8.2e7, "runner fwd")
    tflops = resident * per_img / 1e12
    return {
        "images_per_sec": N_IMAGES / elapsed,
        "transform_seconds": elapsed,
        "pipelined_images_per_sec": N_IMAGES / pipe_elapsed,
        "pipelined_vs_sequential": pipe_vs_seq,
        "pipeline_overlap_fraction": pipe_stats.get("overlap_fraction", 0.0),
        "pipeline_bucket_ladder": pipe_stats.get("bucket_ladder"),
        "resident_images_per_sec": resident,
        "resident_tflops": tflops,
        "resident_mfu": _mfu(tflops, peak_tflops),
        "flops_per_image": per_img,
    }


def bench_transformer(peak_tflops: "float | None") -> dict:
    """Transformer encoder throughput (tokens/sec + MFU) — the
    beyond-reference sequence family (SURVEY.md §5.7: the reference has no
    sequence models at all). Three measurements:

    * forward, XLA dense attention vs the Pallas flash kernel
      (nn/attention.py) head-to-head at seq 512 — the kernel's value is a
      measured claim, not a design claim;
    * fused-scan training (all steps in ONE dispatch, the DNNLearner
      dispatch pattern) with the chunked O(T) attention core;
    * a long-sequence forward (seq 4096) on the flash kernel, where dense
      attention's (T,T) score materialization starts paying real HBM.

    Transformer MFU is the honest utilization probe: the FLOPs are large
    matmuls, so achieved/peak here reflects the framework, not conv
    shapes. CPU runs are tiny smokes and report null throughput, same
    policy as bench_trainer."""
    import jax
    import jax.numpy as jnp
    import optax

    from mmlspark_tpu.nn.models import make_model

    on_cpu = jax.default_backend() == "cpu"
    if on_cpu:
        layers, d_model, heads, d_ff, vocab = 2, 64, 4, 128, 512
        seq, bs_fwd, bs_train, long_seq, long_bs = 64, 8, 4, 256, 1
        fwd_batches, train_steps = 2, 2
    else:
        layers, d_model, heads, d_ff, vocab = 8, 512, 8, 2048, 16384
        seq, bs_fwd, bs_train, long_seq, long_bs = 512, 64, 32, 4096, 4
        fwd_batches, train_steps = 16, 8

    rng = np.random.default_rng(11)

    def toks(b, t):
        return jnp.asarray(rng.integers(0, vocab, size=(b, t)), jnp.int32)

    def model(impl, max_len):
        return make_model(
            "transformer", num_layers=layers, d_model=d_model,
            num_heads=heads, d_ff=d_ff, vocab_size=vocab, num_outputs=8,
            max_len=max_len, attention_impl=impl, dtype=jnp.bfloat16)

    base = model("dense", max(seq, long_seq))
    xb = toks(bs_fwd, seq)
    variables = base.init(jax.random.PRNGKey(0), xb)

    def analytic_per_tok(t):
        # per layer: qkvo projections 2*4*d^2, MLP 2*2*d*d_ff, attention
        # score+value matmuls 2*2*t*d per token; embed/head negligible
        return layers * (2 * (4 * d_model ** 2 + 2 * d_model * d_ff)
                         + 4 * t * d_model)

    def timed_fwd(impl, x, n_batches, want_flops=False):
        m = model(impl, max(seq, long_seq))
        fwd = jax.jit(lambda v, xb_: m.apply(v, xb_))
        jax.block_until_ready(fwd(variables, x))

        def one_pass():
            outs = [fwd(variables, x) for _ in range(n_batches)]
            jax.block_until_ready(outs[-1])

        dt = median_timed(one_pass)
        tokens = n_batches * x.shape[0] * x.shape[1]
        # flops_of re-lowers + re-compiles outside the jit cache — only pay
        # that for the one call whose FLOP count is actually used
        fl = flops_of(fwd, variables, x) if want_flops else None
        per = flops_sane(fl / (x.shape[0] * x.shape[1]) if fl else None,
                         analytic_per_tok(x.shape[1]),
                         "transformer fwd") if want_flops else None
        return tokens / dt, per

    fwd_dense_tps, per_tok = timed_fwd("dense", xb, fwd_batches,
                                       want_flops=True)
    fwd_flash_tps = timed_fwd("flash", xb, fwd_batches)[0]
    long_tps = timed_fwd("flash", toks(long_bs, long_seq), fwd_batches)[0]

    # training: chunked attention core, all steps fused in one scan dispatch
    m_train = model("chunked", seq)
    m_train_flash = model("flash", seq)
    xt, yt = toks(bs_train, seq), jnp.asarray(
        rng.integers(0, 8, size=bs_train), jnp.int32)
    tvars = m_train.init(jax.random.PRNGKey(1), xt)
    tx = optax.adamw(1e-4)
    opt0 = tx.init(tvars["params"])

    def make_epoch(mod):
        def step(params, opt_state):
            def loss_fn(p):
                logits = mod.apply({"params": p}, xt, train=True)
                return optax.softmax_cross_entropy_with_integer_labels(
                    logits.astype(jnp.float32), yt).mean()

            loss, grads = jax.value_and_grad(loss_fn)(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        def epoch(params, opt_state):
            def body(carry, _):
                p, o = carry
                p, o, loss = step(p, o)
                return (p, o), loss

            (p, o), losses = jax.lax.scan(
                body, (params, opt_state), None, length=train_steps)
            return p, o, losses[-1]

        return jax.jit(epoch)

    ep = make_epoch(m_train)
    out = ep(tvars["params"], opt0)
    jax.block_until_ready(out)
    dt = median_timed(
        lambda: jax.block_until_ready(ep(tvars["params"], opt0)))
    train_tps = train_steps * bs_train * seq / dt
    sf = flops_of(ep, tvars["params"], opt0)
    train_per_tok = flops_sane(
        sf / (train_steps * bs_train * seq) if sf else None,
        3 * analytic_per_tok(seq), "transformer train")

    # flash-core training (Pallas fwd + custom_vjp XLA bwd)
    epf = make_epoch(m_train_flash)
    jax.block_until_ready(epf(tvars["params"], opt0))
    dtf = median_timed(
        lambda: jax.block_until_ready(epf(tvars["params"], opt0)))
    train_flash_tps = train_steps * bs_train * seq / dtf

    measurable = not on_cpu
    fwd_tflops = (fwd_flash_tps * per_tok / 1e12
                  if measurable and per_tok and fwd_flash_tps else None)
    train_tflops = (train_tps * train_per_tok / 1e12
                    if measurable and train_per_tok else None)
    return {
        "fwd_dense_tokens_per_sec": fwd_dense_tps if measurable else None,
        "fwd_flash_tokens_per_sec": fwd_flash_tps if measurable else None,
        "fwd_mfu": _mfu(fwd_tflops, peak_tflops),
        "longseq_tokens_per_sec": long_tps if measurable else None,
        "train_tokens_per_sec": train_tps if measurable else None,
        "train_mfu": _mfu(train_tflops, peak_tflops),
        "train_flash_tokens_per_sec": (
            train_flash_tps if measurable else None),
        "seq_len": seq,
        "long_seq_len": long_seq,
        "smoke_only": on_cpu,
    }


def bench_trainer(peak_tflops: "float | None") -> dict:
    """ResNet-50 fine-tune throughput (images/sec) — BASELINE config #4
    (the reference trains out-of-band via mpirun+CNTK,
    CNTKLearner.scala:169-183; here it is one jitted epoch scan per
    dispatch, bf16 compute / f32 params). Timed as fit(1+k) - fit(1): the
    compile cost appears in both and cancels, leaving k steady-state
    epochs. The real measurement (224x224 inputs, CIFAR-style 10-class
    head) runs on the device; an explicit CPU run is a small 32x32 smoke,
    not a meaningful throughput number."""
    import jax
    import jax.numpy as jnp
    import optax

    from mmlspark_tpu.core.schema import Table
    from mmlspark_tpu.nn.trainer import DNNLearner

    on_cpu = jax.default_backend() == "cpu"
    side = 32 if on_cpu else 224
    n = 64 if on_cpu else 512
    # the orchestrator's trainer timeout guards the compile
    bs = 32 if on_cpu else 64
    extra_epochs = 1 if on_cpu else 2
    classes = 10
    rng = np.random.default_rng(5)
    # uint8 images: 4x smaller host table (fits the fused-epoch on-device
    # budget at 224x224), cast to compute dtype inside the model
    x = rng.integers(0, 256, size=(n, side, side, 3), dtype=np.uint8)
    y = rng.integers(0, classes, size=n).astype(np.float64)
    tbl = Table({"features": x, "label": y})

    def fit(epochs):
        learner = DNNLearner(
            architecture="resnet50", epochs=epochs, batch_size=bs,
            model_config={"num_outputs": classes},
            use_mesh=False, seed=0, bfloat16=True,
        )
        t0 = time.perf_counter()
        learner.fit(tbl)
        return time.perf_counter() - t0

    t1 = fit(1)
    tn = fit(1 + extra_epochs)
    steady = tn - t1
    # Timing-resolution floor: fit(1+k)-fit(1) subtracts two large
    # compile-dominated times, so on a smoke run the difference can land
    # inside timing noise (round-3 artifact: a clamped 1e-9 denominator
    # produced trainer_images_per_sec=6.4e10). Below the floor — or on the
    # CPU smoke config, whose number is meaningless anyway — report null
    # rather than a nonsense throughput.
    measurable = (not on_cpu) and steady > 0.05
    img_per_sec = (n * extra_epochs / steady) if measurable else None

    # train-step FLOPs: XLA cost analysis of a same-shape value_and_grad
    # step on the same module (the learner's internal step is identical
    # math); analytic fallback ~3x the 4.1 GFLOP fwd at 224 (scaled by
    # side^2) per image.
    from mmlspark_tpu.nn.models import make_model

    module = make_model("resnet50", num_outputs=classes, dtype=jnp.bfloat16)
    xb = jnp.asarray(x[:bs])
    variables = module.init(jax.random.PRNGKey(0), xb.astype(jnp.float32))
    params, batch_stats = variables["params"], variables.get("batch_stats", {})
    yb = jnp.asarray(y[:bs], jnp.int32)

    def loss_fn(p):
        logits, _ = module.apply(
            {"params": p, "batch_stats": batch_stats},
            xb.astype(jnp.float32), train=True, mutable=["batch_stats"],
        )
        return optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), yb
        ).mean()

    step = jax.jit(jax.value_and_grad(loss_fn))
    step_flops = flops_of(step, params)
    per_img = flops_sane(step_flops / bs if step_flops else None,
                         3 * 4.1e9 * (side / 224) ** 2, "trainer step")
    tflops = (img_per_sec * per_img / 1e12) if img_per_sec else None
    return {
        "train_images_per_sec": img_per_sec,
        "epoch1_seconds": t1,
        "steady_epochs_seconds": steady,
        "train_tflops": tflops,
        "train_mfu": _mfu(tflops, peak_tflops),
        "image_side": side,
        "smoke_only": on_cpu,
    }


def bench_trainer_checkpoint_overhead() -> dict:
    """The elastic-training paired row: steady-state DNN epoch time with
    per-epoch checkpointing ON (checkpoint_dir + checkpoint_every_n=1:
    every epoch serializes params/opt-state and lands them through
    atomic_write + manifest update) vs OFF. Same estimator as
    bench_trainer — fit(1+k) - fit(1) cancels the compile — and the two
    arms alternate within each pass so host noise hits both equally; the
    reported ratio is the median of per-pass ratios. Acceptance bar
    (ISSUE 14): checkpointed/plain <= 1.05."""
    import tempfile

    from mmlspark_tpu.core.schema import Table
    from mmlspark_tpu.nn.trainer import DNNLearner

    rng = np.random.default_rng(9)
    # sized so one epoch is O(500ms): the checkpoint cost is a FIXED
    # ~10ms per-snapshot tax (serialize + payload fsync + manifest
    # fsync), so a toy epoch would measure fsync latency against
    # nothing — the ratio is only meaningful when the epoch does real
    # work, as any actual training run does
    n, d, classes = 16384, 256, 10
    x = rng.normal(size=(n, d))
    y = rng.integers(0, classes, size=n).astype(np.float64)
    tbl = Table({"features": x, "label": y})
    extra_epochs = 4

    def fit_seconds(epochs: int, ckpt_dir: "str | None") -> float:
        kw = dict(checkpoint_dir=ckpt_dir, checkpoint_every_n=1) \
            if ckpt_dir else {}
        learner = DNNLearner(
            architecture="mlp", epochs=epochs, batch_size=128,
            model_config={"features": (512, 256), "num_outputs": classes},
            use_mesh=False, seed=0, **kw)
        t0 = time.perf_counter()
        learner.fit(tbl)
        return time.perf_counter() - t0

    ratios, plain_s, ckpt_s = [], [], []
    for _ in range(3):
        with tempfile.TemporaryDirectory() as ck:
            # a fresh dir per pass: the checkpointed arm must WRITE every
            # epoch, not resume past the work the plain arm does
            t_off = max(fit_seconds(1 + extra_epochs, None)
                        - fit_seconds(1, None), 1e-9)
            with tempfile.TemporaryDirectory() as ck1:
                t_on = max(fit_seconds(1 + extra_epochs, ck)
                           - fit_seconds(1, ck1), 1e-9)
        plain_s.append(t_off)
        ckpt_s.append(t_on)
        ratios.append(t_on / t_off)
    return {
        "ratio_checkpointed": float(np.median(ratios)),
        "plain_epoch_seconds": float(np.median(plain_s)) / extra_epochs,
        "checkpointed_epoch_seconds": float(
            np.median(ckpt_s)) / extra_epochs,
    }


def bench_serving() -> dict:
    """Continuous-mode serving latency (p50/p99 ms) on a warm jitted model —
    the measured counterpart of the reference's ~1 ms claim
    (docs/mmlspark-serving.md:10-11)."""
    import http.client

    from mmlspark_tpu.core.schema import Table
    from mmlspark_tpu.gbdt.estimators import GBDTClassifier
    from mmlspark_tpu.io_http.serving import serve_model

    x, y = make_dataset(2048, 8, seed=11)
    model = GBDTClassifier(num_iterations=10, num_leaves=15).fit(
        Table({"features": x, "label": y})
    )
    # default max_latency_ms=0: greedy drain + backpressure batching — a
    # collection window would add its full length to p50 at this
    # single-client load (measured: 1.00 -> 0.59 ms server p50)
    srv = serve_model(model, input_cols=[f"f{j}" for j in range(8)])
    try:
        row = {f"f{j}": float(x[0, j]) for j in range(8)}
        body = json.dumps(row).encode()
        # persistent HTTP/1.1 connection: the server keeps one thread per
        # connection, so steady-state latency excludes TCP/thread setup
        conn = http.client.HTTPConnection(srv.host, srv.port, timeout=30)

        def post():
            conn.request("POST", srv.api_path, body=body,
                         headers={"Content-Type": "application/json"})
            r = conn.getresponse()
            r.read()
            assert r.status == 200, f"serving returned {r.status}"

        for _ in range(20):          # warm-up: compile the scoring step
            post()
        srv.reset_latency_stats()
        # measure BOTH sides: the server's enqueue->reply-written window
        # and the client's full round trip — a transport stall (the Nagle/
        # delayed-ACK class of bug) is invisible to the first and dominant
        # in the second
        rtt = []
        for _ in range(200):
            t0 = time.perf_counter()
            post()
            rtt.append(time.perf_counter() - t0)
        stats = srv.latency_stats()
        rtt_ms = np.asarray(rtt) * 1e3
        conn.close()
    finally:
        srv.stop()
    return {"p50_ms": stats["p50_ms"], "p99_ms": stats["p99_ms"],
            "client_rtt_p50_ms": float(np.percentile(rtt_ms, 50)),
            "client_rtt_p99_ms": float(np.percentile(rtt_ms, 99))}


def bench_serving_degraded() -> dict:
    """Continuous-mode serving latency under chaos: ~10% of requests hit a
    seeded injected-fault burst (FaultInjector 503s) while the server runs
    the resilience shedding config (bounded queue + per-request deadline).
    Tracks healthy-path client p50/p99 and the observed error rate — the
    number that shows load shedding keeps the tail flat when a dependency
    burns instead of timing every caller out at once."""
    import http.client

    from mmlspark_tpu.io_http.schema import HTTPResponseData
    from mmlspark_tpu.io_http.serving import ServingServer
    from mmlspark_tpu.resilience import FaultInjector

    # ~10% of requests overall: a 7% trigger rate with burst=2 (real
    # outages are correlated runs, not independent coin flips)
    fi = FaultInjector(seed=23, status_prob=0.07, status_code=503,
                       status_burst=2, retry_after_s=0.05)
    ok = HTTPResponseData(200, "OK",
                          headers={"Content-Type": "application/json"},
                          entity=b'{"prediction": 1.0}')
    injected = HTTPResponseData(503, "injected fault",
                                headers={"Retry-After": "0.05"}, entity=b"{}")

    def handler(table):
        return table.with_column(
            "reply", [injected if fi.decide() == "status" else ok
                      for _ in range(table.num_rows)])

    srv = ServingServer(handler, max_pending=64,
                        request_deadline_s=5.0).start()
    try:
        body = b'{"f0": 0.5}'
        conn = http.client.HTTPConnection(srv.host, srv.port, timeout=30)

        def post():
            conn.request("POST", srv.api_path, body=body,
                         headers={"Content-Type": "application/json"})
            r = conn.getresponse()
            r.read()
            return r.status

        for _ in range(20):          # warm-up outside the timed window
            post()
        statuses, rtt = [], []
        for _ in range(300):
            t0 = time.perf_counter()
            statuses.append(post())
            rtt.append(time.perf_counter() - t0)
        conn.close()
    finally:
        srv.stop()
    healthy_ms = np.asarray(
        [t for t, s in zip(rtt, statuses) if s == 200]) * 1e3
    return {
        "p50_ms": float(np.percentile(healthy_ms, 50)),
        "p99_ms": float(np.percentile(healthy_ms, 99)),
        "error_rate": sum(1 for s in statuses if s != 200) / len(statuses),
        "faults_injected": fi.injected["status"],
        "requests_shed": srv.requests_shed,
        "requests_expired": srv.requests_expired,
    }


def bench_streaming() -> dict:
    """Micro-batch engine throughput (batches/sec, rows/sec): a fitted GBDT
    model scoring MemorySource batches through StreamingQuery into a
    MemorySink. The driver loop is host-side Python, so this row tracks
    per-batch engine overhead, NOT accelerator throughput — it is reported
    as a CPU number regardless of platform. The model transform itself is
    the compile-once/stream-forever path: batch 0 compiles, every later
    batch replays the cached executable."""
    from mmlspark_tpu.core.schema import Table
    from mmlspark_tpu.gbdt.estimators import GBDTClassifier
    from mmlspark_tpu.streaming import MemorySink, MemorySource, StreamingQuery

    x, y = make_dataset(4096, 8, seed=13)
    model = GBDTClassifier(num_iterations=10, num_leaves=15).fit(
        Table({"features": x, "label": y})
    )
    rows_per_batch, n_batches = 512, 50
    rng = np.random.default_rng(17)
    batches = [Table({"features": rng.normal(size=(rows_per_batch, 8))})
               for _ in range(n_batches)]

    source, sink = MemorySource(), MemorySink()
    q = StreamingQuery(source, model, sink, name="bench")
    # warm-up batch: compile the scoring step outside the timed window
    source.add_rows(batches[0])
    q.process_next()
    t0 = time.perf_counter()
    for b in batches[1:]:
        source.add_rows(b)
        q.process_next()
    elapsed = time.perf_counter() - t0
    q.stop()
    timed = n_batches - 1
    assert q.batches_processed == n_batches, (
        f"expected {n_batches} micro-batches, ran {q.batches_processed}")
    return {
        "batches_per_sec": timed / elapsed,
        "rows_per_sec": timed * rows_per_batch / elapsed,
        "rows_per_batch": rows_per_batch,
    }


def bench_pipeline_fusion() -> dict:
    """Whole-pipeline fusion (core/fusion.py): the SAME three-stage image
    scoring pipeline (ImageTransformer -> CNN -> DataConversion) run
    staged — per-stage transforms, a host materialization at every stage
    boundary — vs fused into one jitted composition with columns
    device-resident between stages. The comparison is paired like
    runner_pipelined_vs_sequential: both paths run in each of five
    interleaved passes, the per-pass ratio cancels that pass's machine
    load, and the median over passes is the reported speedup. Transfer
    counts come from the fused model's own upload/download accounting vs
    the plan's analytic staged count (2 per device stage per batch)."""
    from mmlspark_tpu.core.fusion import fuse
    from mmlspark_tpu.core.pipeline import pipeline_model
    from mmlspark_tpu.core.schema import Table
    from mmlspark_tpu.image.transformer import ImageTransformer
    from mmlspark_tpu.nn.models import ModelBundle
    from mmlspark_tpu.nn.runner import DeepModelTransformer
    from mmlspark_tpu.ops.conversion import DataConversion

    n_images, bs = 2048, 256
    rng = np.random.default_rng(11)
    table = Table({"image": rng.integers(
        0, 256, size=(n_images, 16, 16, 3), dtype=np.uint8).astype(
            np.float64)})
    stages = [
        ImageTransformer(input_col="image", output_col="image")
        .resize(8, 8).gray(keep_channels=True),
        DeepModelTransformer(
            input_col="image", mini_batch_size=bs).set_model(
                ModelBundle.init("simple_cnn", (8, 8, 3), seed=0,
                                 num_outputs=10)),
        DataConversion(cols=["output"], convert_to="float"),
    ]
    staged = pipeline_model(*stages)
    fused = fuse(pipeline_model(*stages), mini_batch_size=bs)
    plan = fused.plan()

    # warm-up: compile both paths and check equivalence once — fusion
    # changes WHERE stages run, never what they produce
    out_s = np.asarray(staged.transform(table)["output"])
    out_f = np.asarray(fused.transform(table)["output"])
    assert out_s.tobytes() == out_f.tobytes(), "fused != staged"
    assert fused.last_stats["segments"][0]["kind"] == "fused"

    def timed(fn):
        t0 = time.perf_counter()
        fn()
        return time.perf_counter() - t0

    staged_t, fused_t = [], []
    rows = [
        (staged_t, lambda: np.asarray(staged.transform(table)["output"])),
        (fused_t, lambda: np.asarray(fused.transform(table)["output"])),
    ]
    for rep in range(5):
        # rotate within-pass order so neither path owns the cooler slot
        for acc, fn in rows[rep % 2:] + rows[:rep % 2]:
            acc.append(timed(fn))
    pass_ratios = sorted(s / f for s, f in zip(staged_t, fused_t))
    speedup = pass_ratios[len(pass_ratios) // 2]

    n_batches = -(-n_images // bs)
    stats = fused.last_stats
    # column-granular count from the fused model's own accounting (3 here:
    # the in-place image column's final value + the score come back; the
    # staged path pays a full host round-trip at every stage boundary)
    fused_transfers = (stats["uploads"] + stats["downloads"]) / n_batches
    boundary_transfers, staged_transfers = plan.transfers_per_batch()
    return {
        "fused_vs_staged": speedup,
        "fused_images_per_sec": n_images / min(fused_t),
        "staged_images_per_sec": n_images / min(staged_t),
        "fusion_ratio": plan.fusion_ratio,
        "fused_transfers_per_batch": fused_transfers,
        "fused_boundary_transfers_per_batch": float(boundary_transfers),
        "staged_transfers_per_batch": float(staged_transfers),
    }


def _forced_host_devices() -> bool:
    """True when this process's jax "chips" are forced host-platform CPU
    devices time-slicing ONE machine's cores (how CI runs the multichip
    family), i.e. the devices do not own independent silicon."""
    import jax

    return (jax.default_backend() == "cpu"
            and "host_platform_device_count" in os.environ.get(
                "XLA_FLAGS", ""))


def _fused_sharded_ladder(n_rows: int, bs: int, devs,
                          with_attribution: bool = True) -> list:
    """One fused-sharded ladder (shared by the realistic and the legacy
    small-batch workloads): the SAME two-stage scoring pipeline
    (MLP -> DataConversion) fused on one device vs fused on an n-device
    data-parallel mesh, at n = 1/2/4/8 of this process's devices.
    Pairing follows bench_pipeline_fusion: both paths run in each of five
    interleaved passes, the per-pass ratio cancels that pass's machine
    load, and the median over passes is the reported ratio.
    Byte-identity vs BOTH the single-device fused output and the staged
    (unfused) path is asserted at every mesh size, and the timed passes
    must add ZERO executable-cache misses after warmup — a steady-state
    recompile at fixed mesh shape fails the family.

    Per-chip normalization: `per_chip_rows_per_sec` is always the raw
    rate/n.  `per_chip_vs_single_chip` divides it by the single-chip rate
    TIMES each chip's `silicon_share` — 1.0 on real multi-chip hardware
    (each shard owns its own silicon; the raw ROADMAP definition), 1/n on
    forced host-platform devices where the n "chips" time-slice the one
    CPU that produced the single-chip figure (raw per-chip there is
    mechanically ~1/n regardless of how well the dispatch path scales,
    so the raw ratio would grade the box, not the design).  The artifact
    records `silicon_share` and `forced_host` so the normalization is
    auditable, never silent."""
    from mmlspark_tpu.core.fusion import fuse
    from mmlspark_tpu.core.pipeline import pipeline_model
    from mmlspark_tpu.core.schema import Table
    from mmlspark_tpu.nn.models import ModelBundle
    from mmlspark_tpu.nn.runner import DeepModelTransformer
    from mmlspark_tpu.ops.conversion import DataConversion
    from mmlspark_tpu.parallel.mesh import make_mesh

    n_batches = -(-n_rows // bs)
    forced_host = _forced_host_devices()
    rng = np.random.default_rng(7)
    table = Table({"x": rng.normal(size=(n_rows, 32)).astype(np.float32)})

    def stages():
        return [
            DeepModelTransformer(input_col="x", mini_batch_size=bs).set_model(
                ModelBundle.init("mlp", (32,), seed=0, num_outputs=8,
                                 features=(64, 32))),
            DataConversion(cols=["output"], convert_to="float"),
        ]

    def build(mesh):
        # donation ON and a 2-deep dispatch pipeline: the steady-state
        # serving configuration this ladder is meant to certify
        return fuse(pipeline_model(*stages()), mini_batch_size=bs,
                    pipeline_depth=2, mesh=mesh)

    def timed(fn):
        t0 = time.perf_counter()
        fn()
        return time.perf_counter() - t0

    single = build(None)
    ref = np.asarray(single.transform(table)["output"])
    ref_staged = np.asarray(
        pipeline_model(*stages()).transform(table)["output"])
    assert ref.tobytes() == ref_staged.tobytes(), \
        "single-device fused != staged path"

    ladder = []
    single_rate = None
    for nd in (1, 2, 4, 8):
        if nd > len(devs):
            continue
        mesh = None if nd == 1 else make_mesh(n_data=nd, devices=devs[:nd])
        fused = single if nd == 1 else build(mesh)
        out = np.asarray(fused.transform(table)["output"])  # compile + warm
        assert out.tobytes() == ref.tobytes(), \
            f"fused on {nd}-device mesh != single-device fused (and staged)"
        warm = dict(fused.last_stats["segments"][0])

        t_single, t_nd = [], []
        rows = [
            (t_single, lambda: np.asarray(single.transform(table)["output"])),
            (t_nd, lambda: np.asarray(fused.transform(table)["output"])),
        ]
        for rep in range(5):
            # rotate within-pass order so neither path owns the cooler slot
            for acc, fn in rows[rep % 2:] + rows[:rep % 2]:
                acc.append(timed(fn))
        ratios = sorted(s / t for s, t in zip(t_single, t_nd))

        seg = fused.last_stats["segments"][0]
        steady_misses = seg["misses"] - warm["misses"]
        steady_recompiles = seg["recompiles"] - warm["recompiles"]
        assert steady_misses == 0 and steady_recompiles == 0, (
            f"steady-state compile at fixed mesh {seg['mesh_shape']}: "
            f"+{steady_misses} misses / +{steady_recompiles} recompiles")
        rate = n_rows / min(t_nd)
        if single_rate is None:
            single_rate = rate
        share = (1.0 / nd) if forced_host else 1.0
        row = {
            "n_devices": nd,
            "mesh_shape": seg["mesh_shape"],
            "sharded_vs_single_paired_median": ratios[len(ratios) // 2],
            "rows_per_sec": rate,
            "per_chip_rows_per_sec": rate / nd,
            "silicon_share": share,
            "per_chip_vs_single_chip": (rate / nd) / (single_rate * share),
            "uploads_per_batch": seg["uploads"] / n_batches,
            "downloads_per_batch": seg["downloads"] / n_batches,
            "steady_state_misses": steady_misses,
            "steady_state_recompiles": steady_recompiles,
            "donate_buffers": bool(fused.get("donate_buffers")),
            "pipeline_depth": seg.get("pipeline_depth"),
            "dispatch_overlap_fraction": seg.get(
                "dispatch_overlap_fraction"),
        }
        if "shard_skew_ratio" in seg:
            row["shard_skew_ratio"] = seg["shard_skew_ratio"]
        if with_attribution:
            # one ARMED pass after the timed ones (arming serializes
            # dispatch on device results, so it never times the ratio
            # rows): the per-phase, per-shard attribution diagnose --perf
            # renders — which shard was slowest at this mesh size and how
            # many rows it held
            from mmlspark_tpu.observability.profiler import (
                Profiler, get_profiler, set_default_profiler)

            prev_prof = get_profiler()
            prof = Profiler(enabled=True)
            set_default_profiler(prof)
            try:
                np.asarray(fused.transform(table)["output"])
            finally:
                set_default_profiler(prev_prof)
            attr = prof.attribution()
            if attr:
                row["attribution"] = attr[0]
        ladder.append(row)
    return ladder


def _bench_tp_gather_schedules(devs, n_rows: int, bs: int) -> "dict | None":
    """Tensor-parallel all_gather schedule check on a (4 data x 2 model)
    mesh: time the fused TP pipeline under XLA's monolithic `all_gather`
    and under the hand-scheduled collective-permute ring
    (parallel.tensor_parallel.ring_all_gather — same bytes, each permute
    step independently schedulable), both byte-identical to single-device.

    The phase ledger cannot see inside an XLA program, so "did the gather
    overlap compute" is judged by its observable: `dispatch_overlap_
    fraction` (batches whose results were already complete at fetch) and
    the paired throughput of the two schedules.  When the ring schedule
    wins, XLA was NOT hiding the collective on this mesh and
    MMLSPARK_TPU_RING_GATHER=1 is the documented remedy."""
    if len(devs) < 8:
        return None
    from mmlspark_tpu.core.fusion import fuse
    from mmlspark_tpu.core.pipeline import pipeline_model
    from mmlspark_tpu.core.schema import Table
    from mmlspark_tpu.nn.models import ModelBundle
    from mmlspark_tpu.nn.runner import DeepModelTransformer
    from mmlspark_tpu.ops.conversion import DataConversion
    from mmlspark_tpu.parallel.mesh import make_mesh

    rng = np.random.default_rng(7)
    table = Table({"x": rng.normal(size=(n_rows, 32)).astype(np.float32)})

    def build(mesh):
        stages = [
            DeepModelTransformer(input_col="x", mini_batch_size=bs).set_model(
                ModelBundle.init("mlp", (32,), seed=0, num_outputs=8,
                                 features=(64, 32))),
            DataConversion(cols=["output"], convert_to="float"),
        ]
        return fuse(pipeline_model(*stages), mini_batch_size=bs,
                    pipeline_depth=2, mesh=mesh)

    single = build(None)
    ref = np.asarray(single.transform(table)["output"])

    schedules = {}
    for name in ("xla", "ring"):
        prev = os.environ.get("MMLSPARK_TPU_RING_GATHER")
        os.environ["MMLSPARK_TPU_RING_GATHER"] = "1" if name == "ring" else "0"
        try:
            mesh = make_mesh(n_data=4, n_model=2, devices=devs[:8])
            fused = build(mesh)
            out = np.asarray(fused.transform(table)["output"])  # warm
            assert out.tobytes() == ref.tobytes(), \
                f"TP ({name} gather) != single-device fused"
            times = []
            for _ in range(5):
                t0 = time.perf_counter()
                np.asarray(fused.transform(table)["output"])
                times.append(time.perf_counter() - t0)
            seg = fused.last_stats["segments"][0]
            schedules[name] = {
                "rows_per_sec": n_rows / min(times),
                "dispatch_overlap_fraction": seg.get(
                    "dispatch_overlap_fraction"),
                "mesh_shape": seg["mesh_shape"],
            }
        finally:
            if prev is None:
                os.environ.pop("MMLSPARK_TPU_RING_GATHER", None)
            else:
                os.environ["MMLSPARK_TPU_RING_GATHER"] = prev
    winner = max(schedules, key=lambda k: schedules[k]["rows_per_sec"])
    return {"mesh_shape": "4x2", "rows": n_rows, "batch_size": bs,
            "schedules": schedules, "gather_schedule": winner,
            "xla_gather_overlaps": winner == "xla"}


def bench_fused_sharded() -> dict:
    """Sharded fused execution (core/fusion.py under a parallel/mesh.py
    mesh), two workloads:

    * `fused_sharded_vs_single` — the REALISTIC ladder (>=512k rows,
      >=32k batch): row counts that can amortize collectives and keep
      every chip's dispatch queue full, so the ladder measures the
      donated/pipelined/skew-aware design rather than fixed per-dispatch
      overhead.  The ROADMAP per-chip criterion is judged here (with the
      silicon-share normalization `_fused_sharded_ladder` documents).
    * `fused_sharded_vs_single_smallbatch` — the pre-r08 4096-row/512-
      batch workload carried forward unchanged, so the trajectory of the
      small-batch regime (where fixed overhead DOES dominate) stays
      comparable across rounds.

    Plus `tp_gather`: the tensor-parallel all_gather schedule check
    (XLA's monolithic gather vs the hand-scheduled collective-permute
    ring) on the 4x2 mesh."""
    import jax

    devs = jax.devices()
    n_rows, bs = 524288, 32768
    small_rows, small_bs = 4096, 512
    out = {
        "fused_sharded_vs_single": _fused_sharded_ladder(
            n_rows, bs, devs, with_attribution=True),
        "fused_sharded_vs_single_smallbatch": _fused_sharded_ladder(
            small_rows, small_bs, devs, with_attribution=False),
        "rows": n_rows, "batch_size": bs,
        "smallbatch_rows": small_rows, "smallbatch_batch_size": small_bs,
        "forced_host": _forced_host_devices(),
        "devices_available": len(devs),
    }
    tp = _bench_tp_gather_schedules(devs, n_rows // 4, bs)
    if tp is not None:
        out["tp_gather"] = tp
    return out


def bench_instrumentation() -> dict:
    """Per-iteration cost of the telemetry layer on a runner-style loop
    (counter + histogram.time + span around each step), as a slowdown
    ratio over the uninstrumented loop — once with live instruments, once
    with a DISABLED registry/tracer (the no-op fast path).

    Estimator: paired difference. The instrument cost per iteration is
    (instrumented empty-body loop - bare empty-body loop), both floors of
    several passes — this difference is stable because neither term holds
    a workload. The workload floor (an elementwise numpy op, hundreds of
    us) is timed separately and the ratio is (work + instr_cost) / work.
    Timing a workload+instrument loop directly CANNOT resolve this: host
    noise on a shared CPU is bursty at +-5% per pass while the true
    overhead is under 1%, so the direct ratio measures the scheduler, not
    the library. disabled ~1.0 is the fast path working; enabled <= 1.05
    is the acceptance bar."""
    from mmlspark_tpu.observability import MetricsRegistry, Tracer

    clock = time.perf_counter

    def floor_per_call(body, calls: int, passes: int = 5) -> float:
        best = float("inf")
        for _ in range(passes):
            t0 = clock()
            for _ in range(calls):
                body()
            best = min(best, clock() - t0)
        return best / calls

    def make_step(reg, tracer, work):
        count = reg.counter("mmlspark_tpu_bench_instr_iters_total",
                            "instrumented bench-loop iterations")
        hist = reg.histogram("mmlspark_tpu_bench_instr_step_seconds",
                             "instrumented bench-loop step time")

        def step():
            with tracer.start_span("bench.step"):
                with hist.time():
                    work()
            count.inc()
        return step

    def nop():
        pass

    # 1) instrument cost per iteration (empty-body paired difference)
    k = 20_000
    base = floor_per_call(nop, k)
    cost_enabled = max(
        floor_per_call(make_step(MetricsRegistry(), Tracer(), nop), k)
        - base, 0.0)
    cost_disabled = max(
        floor_per_call(make_step(MetricsRegistry(enabled=False),
                                 Tracer(enabled=False), nop), k)
        - base, 0.0)

    # 2) representative per-iteration workload floor
    a = np.random.default_rng(23).normal(size=500_000)

    def work():
        _ = np.multiply(a, a).sum()

    work_floor = floor_per_call(work, 100, passes=7)

    return {
        "ratio_enabled": (work_floor + cost_enabled) / work_floor,
        "ratio_disabled": (work_floor + cost_disabled) / work_floor,
        "enabled_cost_us_per_iter": cost_enabled * 1e6,
        "disabled_cost_us_per_iter": cost_disabled * 1e6,
        "workload_us_per_iter": work_floor * 1e6,
    }


def bench_recorder_overhead() -> dict:
    """The flight-recorder paired row: serving-hot-path p50 with the
    black box ARMED (ring + exemplar-stamped latency observation +
    metric-delta tick check per request) vs DISABLED (the one-attribute
    no-op path). Same estimator as bench_instrumentation: the per-request
    recorder cost is a paired difference of empty-body loop floors (host
    noise cannot resolve a <2% delta on direct server timings), and the
    p50 under load comes from a real keep-alive request loop against a
    ServingServer. Acceptance bar: armed/disabled p50 ratio <= 1.02."""
    import http.client
    import json as _json
    import urllib.parse

    from mmlspark_tpu.core.schema import Table
    from mmlspark_tpu.io_http.schema import make_reply, parse_request
    from mmlspark_tpu.io_http.serving import ServingServer
    from mmlspark_tpu.observability import MetricsRegistry
    from mmlspark_tpu.observability.recorder import FlightRecorder

    def handler(table: Table) -> Table:
        t = parse_request(table)
        return make_reply(
            t.with_column("y", np.asarray(t["x"], dtype=float) * 2), "y")

    # 1) real p50 under serving load (keep-alive, continuous batcher)
    srv = ServingServer(handler, metrics=MetricsRegistry(),
                        exemplars=False).start()
    try:
        p = urllib.parse.urlsplit(srv.url)
        conn = http.client.HTTPConnection(p.hostname, p.port, timeout=30)
        body = _json.dumps({"x": 2.0}).encode()
        lat = []
        for i in range(240):
            t0 = time.perf_counter()
            conn.request("POST", p.path or "/", body=body,
                         headers={"Content-Type": "application/json"})
            conn.getresponse().read()
            if i >= 40:  # warm-up excluded
                lat.append(time.perf_counter() - t0)
        conn.close()
    finally:
        srv.stop()
    p50 = float(np.percentile(lat, 50))

    # 2) per-request recorder cost, paired empty-body difference
    clock = time.perf_counter

    def floor_per_call(body, calls: int = 20_000, passes: int = 5) -> float:
        best = float("inf")
        for _ in range(passes):
            t0 = clock()
            for _ in range(calls):
                body()
            best = min(best, clock() - t0)
        return best / calls

    def make_step(armed: bool):
        reg = MetricsRegistry()
        rec = FlightRecorder(enabled=armed, tick_interval_s=3600.0,
                             registry=reg)
        child = reg.histogram(
            "mmlspark_tpu_serving_latency_seconds", "latency",
            labels=("server",), exemplars=armed).labels(server="bench")
        ex = ({"trace_id": "ab" * 16, "route": "resident", "bucket": "8"}
              if armed else None)

        def step():
            child.observe(1e-3, exemplar=ex)
            rec.record_request(trace_id="ab" * 16, route="resident",
                               bucket=8, queue_depth=0, latency_s=1e-3,
                               status=200)
            rec.maybe_tick(reg)
        return step

    def nop():
        pass

    base = floor_per_call(nop)
    cost_armed = max(floor_per_call(make_step(True)) - base, 0.0)
    cost_disabled = max(floor_per_call(make_step(False)) - base, 0.0)
    return {
        "serving_p50_ms": p50 * 1e3,
        "ratio_armed": (p50 + cost_armed) / max(p50 + cost_disabled, 1e-12),
        "armed_cost_us_per_request": cost_armed * 1e6,
        "disabled_cost_us_per_request": cost_disabled * 1e6,
    }


def bench_timeline_overhead() -> dict:
    """The telemetry-timeline paired row: serving p50 with a
    TimelineRecorder ARMED beside the server (background sampling loop:
    registry snapshot -> delta-encode -> checksummed atomic segment
    rewrite, at `interval_s` cadence) vs DISABLED (no recorder at all).
    The recorder never touches the request path, so its per-request cost
    is the amortized share of one sample a single request carries:
    sample_cost * (p50 / interval_s). The sample cost itself is a
    min-of-passes loop floor over real `sample()` calls against the
    loaded serving registry (fsync + rewrite included — that IS the
    cost), and the p50 comes from the same out-of-process-style
    keep-alive loop as bench_recorder_overhead. Acceptance bar:
    armed/disabled p50 ratio <= 1.02."""
    import http.client
    import json as _json
    import shutil
    import tempfile
    import urllib.parse

    from mmlspark_tpu.core.schema import Table
    from mmlspark_tpu.io_http.schema import make_reply, parse_request
    from mmlspark_tpu.io_http.serving import ServingServer
    from mmlspark_tpu.observability import MetricsRegistry
    from mmlspark_tpu.observability.timeline import TimelineRecorder

    def handler(table: Table) -> Table:
        t = parse_request(table)
        return make_reply(
            t.with_column("y", np.asarray(t["x"], dtype=float) * 2), "y")

    interval_s = 5.0
    reg = MetricsRegistry()
    # 1) real p50 under serving load, recorder sampling in background at
    #    its production cadence (its thread steal, if any, is in the p50)
    tmp = tempfile.mkdtemp(prefix="mml_bench_timeline_")
    srv = ServingServer(handler, metrics=reg, exemplars=False).start()
    rec = TimelineRecorder(os.path.join(tmp, "segments"), reg,
                           interval_s=interval_s, keep=4)
    rec.start()
    try:
        p = urllib.parse.urlsplit(srv.url)
        conn = http.client.HTTPConnection(p.hostname, p.port, timeout=30)
        body = _json.dumps({"x": 2.0}).encode()
        lat = []
        for i in range(240):
            t0 = time.perf_counter()
            conn.request("POST", p.path or "/", body=body,
                         headers={"Content-Type": "application/json"})
            conn.getresponse().read()
            if i >= 40:  # warm-up excluded
                lat.append(time.perf_counter() - t0)
        conn.close()
    finally:
        rec.stop()
        srv.stop()
    p50 = float(np.percentile(lat, 50))

    # 2) cost of ONE sample against the loaded registry (loop floor)
    clock = time.perf_counter

    def sample_floor(calls: int = 50, passes: int = 3) -> float:
        best = float("inf")
        for _ in range(passes):
            t0 = clock()
            for _ in range(calls):
                rec.sample()
            best = min(best, clock() - t0)
        return best / calls

    sample_cost = sample_floor()
    shutil.rmtree(tmp, ignore_errors=True)
    # a request's amortized share of the background cadence
    cost_armed = sample_cost * (p50 / interval_s)
    return {
        "serving_p50_ms": p50 * 1e3,
        "ratio_armed": (p50 + cost_armed) / p50,
        "armed_cost_us_per_request": cost_armed * 1e6,
        "disabled_cost_us_per_request": 0.0,
        "sample_cost_us": sample_cost * 1e6,
    }


def bench_profiler_overhead() -> dict:
    """The perf-attribution paired row: serving p50 with the phase
    ledger ARMED (real per-request ledger: queue/prepare/pad/compute
    brackets + async pooled commit into labeled histograms + recorder)
    vs DISABLED (the NULL_LEDGER one-attribute-check path). Same
    estimator as bench_recorder_overhead: the per-request ledger cost is
    a paired difference of loop floors (min-of-passes — deterministic;
    direct A/B p50s on a shared CI host cannot resolve a <2% delta)
    stacked on one real p50 measured by an OUT-OF-PROCESS client (an
    in-process client shares the GIL with the server and the profiler's
    committer, absorbing background commit work a real client never
    sees). The handler runs a dense forward pass per batch so the p50
    sits at the scale of the repo's real model-serving rows (~1 ms)
    rather than an empty echo — the bar is overhead relative to MODEL
    serving. The loop floor deliberately includes the committer's
    amortized CPU steal, not just the enqueue. Acceptance bar:
    armed/disabled p50 ratio <= 1.02."""
    import subprocess
    import urllib.parse

    from mmlspark_tpu.core.schema import Table
    from mmlspark_tpu.io_http.schema import make_reply, parse_request
    from mmlspark_tpu.io_http.serving import ServingServer
    from mmlspark_tpu.observability import MetricsRegistry
    from mmlspark_tpu.observability.profiler import (Profiler,
                                                     get_profiler,
                                                     set_default_profiler)

    rng = np.random.default_rng(7)
    w1 = rng.standard_normal((64, 1024)).astype(np.float32) * 0.05
    w2 = rng.standard_normal((1024, 1024)).astype(np.float32) * 0.05
    w3 = rng.standard_normal((1024, 256)).astype(np.float32) * 0.05
    w4 = rng.standard_normal((256, 1)).astype(np.float32) * 0.05

    def handler(table: Table) -> Table:
        t = parse_request(table)
        x = np.asarray(t["x"], dtype=np.float32)
        feats = np.outer(x, np.ones(w1.shape[0], dtype=np.float32))
        h = np.tanh(np.tanh(feats @ w1) @ w2)
        y = np.tanh(h @ w3) @ w4
        return make_reply(t.with_column("y", y[:, 0].astype(float)), "y")

    client_src = (
        "import http.client, json, sys, time\n"
        "host, port, path, n = (sys.argv[1], int(sys.argv[2]),\n"
        "                       sys.argv[3], int(sys.argv[4]))\n"
        "conn = http.client.HTTPConnection(host, port, timeout=30)\n"
        "body = json.dumps({'x': 2.0}).encode()\n"
        "out = []\n"
        "for _ in range(n):\n"
        "    t0 = time.perf_counter()\n"
        "    conn.request('POST', path, body=body,\n"
        "                 headers={'Content-Type': 'application/json'})\n"
        "    conn.getresponse().read()\n"
        "    out.append(time.perf_counter() - t0)\n"
        "conn.close()\n"
        "print(' '.join(f'{x:.9f}' for x in out))\n"
    )

    prof = Profiler(registry=MetricsRegistry(), enabled=False)
    prev = get_profiler()
    set_default_profiler(prof)
    srv = ServingServer(handler, metrics=MetricsRegistry(),
                        exemplars=False).start()
    lat: dict[bool, list[float]] = {False: [], True: []}
    try:
        p = urllib.parse.urlsplit(srv.url)

        def chunk(n: int, sink: "list | None") -> None:
            res = subprocess.run(
                [sys.executable, "-c", client_src, p.hostname,
                 str(p.port), p.path or "/", str(n)],
                capture_output=True, text=True, timeout=120)
            vals = [float(x) for x in res.stdout.split()]
            if sink is not None:
                sink.extend(vals[4:])  # drop per-connection warm-up

        chunk(40, None)  # warm-up
        for armed in (False, True):
            prof.enabled = armed
            for _ in range(2):
                chunk(60, lat[armed])
            prof.flush()
    finally:
        srv.stop()
        prof.disarm()
        set_default_profiler(prev)
    p50_off = float(np.percentile(lat[False], 50))
    p50_on = float(np.percentile(lat[True], 50))

    # paired loop floor: the deterministic per-request ledger cost
    # (enqueue brackets + the committer's amortized GIL steal)
    clock = time.perf_counter

    def floor_per_call(body, calls: int = 20_000, passes: int = 5) -> float:
        best = float("inf")
        for _ in range(passes):
            t0 = clock()
            for _ in range(calls):
                body()
            best = min(best, clock() - t0)
        return best / calls

    def make_step(armed: bool):
        step_prof = Profiler(registry=MetricsRegistry(), enabled=armed)

        def step():
            led = step_prof.ledger("request", "host",
                                   server="bench", bucket="8")
            if led.armed:
                led.add("queue", 1e-6)
                led.add("prepare", 1e-6)
                led.note_pad(7, 8)
                with led.phase("compute"):
                    pass
                led.done(rtt_s=1e-3)
        return step

    def nop():
        pass

    base = floor_per_call(nop)
    cost_armed = max(floor_per_call(make_step(True)) - base, 0.0)
    cost_disabled = max(floor_per_call(make_step(False)) - base, 0.0)
    return {
        "serving_p50_ms": p50_off * 1e3,
        "serving_p50_armed_ms": p50_on * 1e3,
        "ratio_armed": ((p50_off + cost_armed)
                        / max(p50_off + cost_disabled, 1e-12)),
        "armed_cost_us_per_request": cost_armed * 1e6,
        "disabled_cost_us_per_request": cost_disabled * 1e6,
    }


def bench_fleet_scrape() -> dict:
    """Cost of the fleet-observability aggregation path: scrape every
    replica's /metrics over real HTTP, parse, merge, and re-render the
    fleet exposition — at n_hosts = 1, 2, 4 in-process ServingServers
    (each with a PRIVATE registry, so the series sets are disjoint and
    realistic). Reported: per-n aggregate latency floor, plus the
    overhead ratio of the n=4 aggregate over a single-replica scrape —
    how much the federation layer adds on top of just fetching one
    exposition."""
    import json as _json
    import urllib.request

    from mmlspark_tpu.core.schema import Table
    from mmlspark_tpu.io_http.schema import make_reply, parse_request
    from mmlspark_tpu.io_http.serving import ServingServer
    from mmlspark_tpu.observability import MetricsRegistry
    from mmlspark_tpu.observability.fleet import MetricsAggregator

    def handler(table: Table) -> Table:
        t = parse_request(table)
        return make_reply(
            t.with_column("y", np.asarray(t["x"], dtype=float) * 2), "y")

    servers = []
    try:
        for _ in range(4):
            srv = ServingServer(handler, metrics=MetricsRegistry()).start()
            servers.append(srv)
            for i in range(4):  # populate counters + latency histogram
                req = urllib.request.Request(
                    srv.url, data=_json.dumps({"x": float(i)}).encode(),
                    headers={"Content-Type": "application/json"},
                    method="POST")
                urllib.request.urlopen(req, timeout=10).read()

        def aggregate_floor(n: int, passes: int = 7) -> float:
            agg = MetricsAggregator(
                urls={str(i): f"{s.url.rstrip('/')}/metrics"
                      for i, s in enumerate(servers[:n])})
            best = float("inf")
            for _ in range(passes):
                t0 = time.perf_counter()
                agg.scrape()
                text = agg.render()
                best = min(best, time.perf_counter() - t0)
            assert text  # the exposition actually rendered
            return best

        def single_scrape_floor(passes: int = 7) -> float:
            url = f"{servers[0].url.rstrip('/')}/metrics"
            best = float("inf")
            for _ in range(passes):
                t0 = time.perf_counter()
                with urllib.request.urlopen(url, timeout=10) as r:
                    r.read()
                best = min(best, time.perf_counter() - t0)
            return best

        single = single_scrape_floor()
        by_n = {n: aggregate_floor(n) for n in (1, 2, 4)}
    finally:
        for srv in servers:
            srv.stop()
    return {
        "aggregate_ms_by_n": {n: v * 1e3 for n, v in by_n.items()},
        "single_scrape_ms": single * 1e3,
        "overhead_vs_single_scrape": by_n[4] / max(single, 1e-9),
    }


def _fleet_gateway_handler(table):
    from mmlspark_tpu.io_http.schema import make_reply, parse_request

    t = parse_request(table)
    return make_reply(
        t.with_column("y", np.asarray(t["x"], dtype=float) * 2), "y")


def _fleet_gateway_factory():
    # module-level so the spawn-context fleet worker can pickle it
    return _fleet_gateway_handler


def bench_fleet_gateway() -> dict:
    """Routing-gateway cost and crash behavior: client p50/p99 through a
    ServingGateway in front of a 2-replica ServingFleet vs the same
    requests sent straight at one replica, then the client-visible error
    rate while one replica is HARD-KILLED mid-bench — the gateway's
    connection-failure hedge should make the crash cost a retry, not an
    error (the row the self-healing claim is judged on)."""
    import http.client
    import urllib.parse

    from mmlspark_tpu.io_http.gateway import ServingGateway
    from mmlspark_tpu.io_http.serving import ServingFleet

    fleet = ServingFleet(_fleet_gateway_factory, n_hosts=2,
                         device_workers=False).start()
    gw = ServingGateway()
    gw.attach_fleet(fleet)
    gw.start()
    try:
        body = json.dumps({"x": 2.0}).encode()

        def timed_posts(url, n):
            """(latencies_s, statuses) over n keep-alive POSTs to url."""
            p = urllib.parse.urlsplit(url)
            conn = http.client.HTTPConnection(
                p.hostname, p.port, timeout=30)
            lat, statuses = [], []
            try:
                for _ in range(n):
                    t0 = time.perf_counter()
                    try:
                        conn.request(
                            "POST", p.path or "/", body=body,
                            headers={"Content-Type": "application/json"})
                        r = conn.getresponse()
                        r.read()
                        statuses.append(r.status)
                    except OSError:
                        # a dropped keep-alive socket is a client-visible
                        # failure for this row; reconnect and carry on
                        statuses.append(0)
                        conn.close()
                        conn = http.client.HTTPConnection(
                            p.hostname, p.port, timeout=30)
                    lat.append(time.perf_counter() - t0)
            finally:
                conn.close()
            return lat, statuses

        # warm both paths outside the timed windows (compile + keep-alive)
        timed_posts(fleet.urls[0], 20)
        timed_posts(gw.url, 20)

        direct_lat, direct_st = timed_posts(fleet.urls[0], 200)
        assert all(s == 200 for s in direct_st), "direct path errored"
        gw_lat, gw_st = timed_posts(gw.url, 200)
        assert all(s == 200 for s in gw_st), "gateway path errored"

        # kill window: 100 requests, then fleet._procs[1] dies WITHOUT the
        # fleet/gateway being told (unlike fleet.kill, which unpublishes) —
        # the gateway keeps routing at the corpse until the hedge ejects it
        _, st_a = timed_posts(gw.url, 100)
        fleet._procs[1].kill()
        fleet._procs[1].join(timeout=10)
        _, st_b = timed_posts(gw.url, 200)
        kill_st = st_a + st_b
        errors = sum(1 for s in kill_st if s != 200)
    finally:
        gw.stop()
        fleet.stop()
    gw_ms = np.asarray(gw_lat) * 1e3
    direct_ms = np.asarray(direct_lat) * 1e3
    return {
        "gateway_p50_ms": float(np.percentile(gw_ms, 50)),
        "gateway_p99_ms": float(np.percentile(gw_ms, 99)),
        "direct_p50_ms": float(np.percentile(direct_ms, 50)),
        "direct_p99_ms": float(np.percentile(direct_ms, 99)),
        "kill_error_rate": errors / len(kill_st),
        "kill_requests": len(kill_st),
    }


def bench_serving_hot_path() -> dict:
    """Device-resident hot path vs today's handler path, PAIRED: the same
    model served twice (`hot_path=False` is exactly the pre-hot-path
    serve_model), driven at client concurrency 1/32/256 so the continuous
    batcher actually coalesces at the upper sizes. Reports server p50/p99
    and client-RTT medians per concurrency plus which route the measured
    crossover picked — at batch 1 the auto-pick is allowed to choose the
    native walk (that IS the policy working); at 32/256 the resident
    executor must pull ahead on device-backed runs."""
    import http.client

    from mmlspark_tpu.core.schema import Table
    from mmlspark_tpu.gbdt.estimators import GBDTRegressor
    from mmlspark_tpu.io_http.schema import HTTPRequestData
    from mmlspark_tpu.io_http.serving import serve_model

    x, y = make_dataset(2048, 8, seed=11)
    # f32-representable features: live batches stay resident-eligible
    x = x.astype(np.float32).astype(np.float64)
    model = GBDTRegressor(num_iterations=10, num_leaves=15).fit(
        Table({"features": x, "label": y.astype(np.float64)}))
    cols = [f"f{j}" for j in range(8)]
    warm = HTTPRequestData.from_json(
        "/", {c: float(x[0, j]) for j, c in enumerate(cols)})
    bodies = [json.dumps({c: float(x[i, j]) for j, c in enumerate(cols)}
                         ).encode() for i in range(64)]

    def wait_ready(srv, timeout_s=180.0):
        deadline = time.monotonic() + timeout_s
        while not srv.ready:
            if time.monotonic() > deadline:
                raise TimeoutError("serving server never became ready")
            time.sleep(0.02)

    def drive(srv, n_clients, per_client):
        """n_clients keep-alive connections posting concurrently; returns
        every client-side RTT in seconds."""
        rtt, errors = [], []
        # all connections established BEFORE anyone posts: the measured
        # window is scoring under concurrency, not a TCP connect storm
        barrier = threading.Barrier(n_clients)

        def client(k):
            conn = http.client.HTTPConnection(srv.host, srv.port,
                                              timeout=60)
            try:
                conn.connect()
                barrier.wait()
                for i in range(per_client):
                    body = bodies[(k * per_client + i) % len(bodies)]
                    t0 = time.perf_counter()
                    for attempt in (0, 1):
                        try:
                            conn.request("POST", srv.api_path, body=body,
                                         headers={"Content-Type":
                                                  "application/json"})
                            r = conn.getresponse()
                            r.read()
                            break
                        except (OSError, http.client.HTTPException):
                            # the server's idle keep-alive window can drop
                            # a parked connection under high concurrency;
                            # a reconnect (timed) is the honest client cost
                            conn.close()
                            conn = http.client.HTTPConnection(
                                srv.host, srv.port, timeout=60)
                            if attempt:
                                raise
                    if r.status != 200:
                        errors.append(r.status)
                    rtt.append(time.perf_counter() - t0)
            except Exception as e:  # noqa: BLE001 — surfaced below
                errors.append(repr(e))
            finally:
                conn.close()

        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise RuntimeError(f"hot-path bench clients failed: "
                               f"{errors[:3]} (+{max(len(errors)-3, 0)})")
        return rtt

    servers = {
        "handler": serve_model(model, cols, hot_path=False,
                               max_batch_size=256, warmup_request=warm),
        "hot": serve_model(model, cols, max_batch_size=256,
                           warmup_request=warm),
    }
    per_concurrency = {}
    try:
        for srv in servers.values():
            wait_ready(srv)
        hp = servers["hot"].hot_path
        if hp is None or hp.disabled is not None:
            raise RuntimeError(
                "hot path unavailable: "
                + (hp.disabled if hp else "no resident executor"))
        for n_clients in (1, 32, 256):
            per_client = max(2, 512 // n_clients) if n_clients > 1 else 200
            row = {}
            for name, srv in servers.items():
                drive(srv, min(n_clients, 8), 3)   # warm the connections
                srv.reset_latency_stats()
                before = (dict(hp.path_requests) if name == "hot" else None)
                rtt_ms = np.asarray(
                    drive(srv, n_clients, per_client)) * 1e3
                stats = srv.latency_stats()
                row[f"{name}_p50_ms"] = stats["p50_ms"]
                row[f"{name}_p99_ms"] = stats["p99_ms"]
                row[f"{name}_rtt_p50_ms"] = float(np.percentile(rtt_ms, 50))
                row[f"{name}_rtt_p99_ms"] = float(np.percentile(rtt_ms, 99))
                if before is not None:
                    delta = {p: hp.path_requests[p] - before.get(p, 0)
                             for p in hp.path_requests}
                    row["hot_route"] = max(delta, key=delta.get)
            row["hot_vs_handler_rtt_p50"] = (
                row["handler_rtt_p50_ms"] / max(row["hot_rtt_p50_ms"], 1e-9))
            per_concurrency[n_clients] = row
    finally:
        for srv in servers.values():
            srv.stop()
    return {"per_concurrency": per_concurrency,
            "crossover": servers["hot"].hot_path.snapshot()["crossover"]}


def bench_serving_binary_wire() -> dict:
    """Binary wire protocol vs JSON on the SAME hot-path server, PAIRED:
    identical feature rows posted over persistent connections as framed
    binary (io_http/wire.py — no JSON parse, no decimal float round
    trip) and as JSON, at client concurrency 1/32/256. Rows are client
    RTT p50/p99 per protocol, keyed per concurrency so bench_gate
    tracks each rung; the json_vs_binary ratios are the headline."""
    import http.client

    from mmlspark_tpu.core.schema import Table
    from mmlspark_tpu.gbdt.estimators import GBDTRegressor
    from mmlspark_tpu.io_http import wire
    from mmlspark_tpu.io_http.schema import HTTPRequestData
    from mmlspark_tpu.io_http.serving import serve_model

    x, y = make_dataset(2048, 8, seed=13)
    x = x.astype(np.float32).astype(np.float64)
    model = GBDTRegressor(num_iterations=10, num_leaves=15).fit(
        Table({"features": x, "label": y.astype(np.float64)}))
    cols = [f"f{j}" for j in range(8)]
    warm = HTTPRequestData.from_json(
        "/", {c: float(x[0, j]) for j, c in enumerate(cols)})
    json_bodies = [json.dumps(
        {c: float(x[i, j]) for j, c in enumerate(cols)}).encode()
        for i in range(64)]
    bin_bodies = [wire.encode_features_request(x[i:i + 1])
                  for i in range(64)]
    json_hdrs = {"Content-Type": "application/json"}
    bin_hdrs = {"Content-Type": wire.WIRE_CONTENT_TYPE,
                "Accept": wire.WIRE_CONTENT_TYPE}

    srv = serve_model(model, cols, max_batch_size=256, warmup_request=warm)

    def drive(bodies, headers, n_clients, per_client):
        rtt, errors = [], []
        barrier = threading.Barrier(n_clients)

        def client(k):
            conn = http.client.HTTPConnection(srv.host, srv.port,
                                              timeout=60)
            try:
                conn.connect()
                barrier.wait()
                for i in range(per_client):
                    body = bodies[(k * per_client + i) % len(bodies)]
                    t0 = time.perf_counter()
                    for attempt in (0, 1):
                        try:
                            conn.request("POST", srv.api_path, body=body,
                                         headers=headers)
                            r = conn.getresponse()
                            r.read()
                            break
                        except (OSError, http.client.HTTPException):
                            conn.close()
                            conn = http.client.HTTPConnection(
                                srv.host, srv.port, timeout=60)
                            if attempt:
                                raise
                    if r.status != 200:
                        errors.append(r.status)
                    rtt.append(time.perf_counter() - t0)
            except Exception as e:  # noqa: BLE001 — surfaced below
                errors.append(repr(e))
            finally:
                conn.close()

        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise RuntimeError(f"binary-wire bench clients failed: "
                               f"{errors[:3]} (+{max(len(errors)-3, 0)})")
        return np.asarray(rtt) * 1e3

    out: dict = {}
    try:
        deadline = time.monotonic() + 180.0
        while not srv.ready:
            if time.monotonic() > deadline:
                raise TimeoutError("serving server never became ready")
            time.sleep(0.02)
        for n_clients in (1, 32, 256):
            per_client = max(2, 512 // n_clients) if n_clients > 1 else 100
            # two alternating passes per protocol, best-of: clock drift
            # on a busy box would otherwise bias whichever ran second
            for proto, bodies, hdrs in 2 * (
                    ("binary", bin_bodies, bin_hdrs),
                    ("json", json_bodies, json_hdrs)):
                drive(bodies, hdrs, min(n_clients, 8), 3)   # warm conns
                ms = drive(bodies, hdrs, n_clients, per_client)
                for q, tag in ((50, "p50"), (99, "p99")):
                    key = f"{proto}_c{n_clients}_rtt_{tag}_ms"
                    val = float(np.percentile(ms, q))
                    out[key] = min(out.get(key, val), val)
            out[f"json_vs_binary_c{n_clients}_rtt_p50"] = (
                out[f"json_c{n_clients}_rtt_p50_ms"]
                / max(out[f"binary_c{n_clients}_rtt_p50_ms"], 1e-9))
        # the protocol counter must agree that both wires were exercised
        protos = srv.protocol_counts()
        out["binary_requests"] = int(protos.get("binary", 0))
        out["json_requests"] = int(protos.get("json", 0))
    finally:
        srv.stop()
    return out


def bench_gateway_tier() -> dict:
    """One gateway process vs an SO_REUSEPORT tier of N workers on the
    SAME backend fleet: sustained throughput over many keep-alive client
    connections (the kernel balances the tier by CONNECTION, so the
    drive spreads sockets), then a kill window where a tier worker is
    SIGKILLed mid-drive and every request goes through the pooled
    product client — the stale-socket retry must absorb the death, so
    the honest error count is 0."""
    import http.client
    import os as _os
    import urllib.parse

    from mmlspark_tpu.io_http.clients import http_send
    from mmlspark_tpu.io_http.gateway import GatewayTier, ServingGateway
    from mmlspark_tpu.io_http.schema import HTTPRequestData
    from mmlspark_tpu.io_http.serving import ServingFleet

    n_workers = max(2, min(8, _os.cpu_count() or 1))
    fleet = ServingFleet(_fleet_gateway_factory, n_hosts=2,
                         device_workers=False).start()
    body = json.dumps({"x": 2.0}).encode()

    def throughput(url, n_conns=16, seconds=3.0):
        p = urllib.parse.urlsplit(url)
        stop_at = [0.0]
        counts = [0] * n_conns
        barrier = threading.Barrier(n_conns)

        def client(k):
            conn = http.client.HTTPConnection(p.hostname, p.port,
                                              timeout=30)
            try:
                conn.connect()
                barrier.wait()
                if k == 0:
                    stop_at[0] = time.monotonic() + seconds
                while not stop_at[0]:
                    time.sleep(0.001)
                while time.monotonic() < stop_at[0]:
                    try:
                        conn.request("POST", p.path or "/", body=body,
                                     headers={"Content-Type":
                                              "application/json"})
                        r = conn.getresponse()
                        r.read()
                        if r.status == 200:
                            counts[k] += 1
                    except (OSError, http.client.HTTPException):
                        conn.close()
                        conn = http.client.HTTPConnection(
                            p.hostname, p.port, timeout=30)
            finally:
                conn.close()

        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(n_conns)]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = max(time.monotonic() - t0, 1e-9)
        return sum(counts) / wall

    gw = ServingGateway(urls=fleet.urls).start()
    tier = None
    try:
        single_rps = throughput(gw.url)
        gw.stop()
        gw = None
        tier = GatewayTier(urls=fleet.urls, n_workers=n_workers).start()
        throughput(tier.url, seconds=1.0)          # warm all workers
        tier_rps = throughput(tier.url)

        # kill window: product client (pool + stale retry) under threads,
        # one tier worker SIGKILLed mid-window, then respawned
        statuses: list = []
        lock = threading.Lock()

        def pooled_client():
            for _ in range(40):
                r = http_send(HTTPRequestData.from_json(
                    tier.url, {"x": 2.0}))
                with lock:
                    statuses.append(r.status_code)

        threads = [threading.Thread(target=pooled_client)
                   for _ in range(4)]
        killer = threading.Timer(0.05, tier.kill_worker, args=(1,))
        killer.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        killer.join()
        tier.respawn_worker(1)
        kill_errors = sum(1 for s in statuses if s != 200)
        alive = sum(1 for w in tier.workers() if w["alive"])
    finally:
        if gw is not None:
            gw.stop()
        if tier is not None:
            tier.stop()
        fleet.stop()
    return {
        "single_requests_per_sec": single_rps,
        "tier_requests_per_sec": tier_rps,
        "tier_vs_single_x": tier_rps / max(single_rps, 1e-9),
        "tier_workers": n_workers,
        "kill_errors": kill_errors,
        "kill_requests": len(statuses),
        "workers_alive_after_respawn": alive,
    }


def bench_recommendation_topk() -> dict:
    """Device-resident SAR top-k serving vs the handler path, PAIRED: the
    same fitted model served twice (`hot_path=False` is exactly the
    handler-only server), 32 keep-alive clients posting user ids, the hot
    server forced onto the `sar_resident` route. Reports requests/sec and
    client RTT p50/p99 per server, the offline
    `recommend_for_all_users` sweep as the batch-throughput ceiling, and
    warmup's paired per-rung timings (the byte-compare pass times BOTH
    engines on every ladder rung) as resident-vs-host ratios."""
    import http.client

    from mmlspark_tpu.core.schema import Table
    from mmlspark_tpu.recommendation import SAR
    from mmlspark_tpu.recommendation.resident import serve_recommender

    rng = np.random.default_rng(11)
    n_users, n_items, per_user, k = 512, 256, 24, 10
    users = np.repeat(np.arange(n_users, dtype=np.float64), per_user)
    items = np.concatenate([
        rng.choice(n_items, size=per_user, replace=False)
        for _ in range(n_users)]).astype(np.float64)
    model = SAR(support_threshold=1).fit(Table({
        "user": users, "item": items, "rating": np.ones_like(users)}))

    model.recommend_for_all_users(k=k)         # compile + device upload
    t0 = time.perf_counter()
    model.recommend_for_all_users(k=k)
    offline_rows_per_sec = n_users / (time.perf_counter() - t0)

    bodies = [json.dumps({"user": i % n_users}).encode() for i in range(64)]

    def wait_ready(srv, timeout_s=180.0):
        deadline = time.monotonic() + timeout_s
        while not srv.ready:
            if time.monotonic() > deadline:
                raise TimeoutError("recommender server never became ready")
            time.sleep(0.02)

    def drive(srv, n_clients, per_client):
        rtt, errors = [], []
        barrier = threading.Barrier(n_clients)

        def client(kk):
            conn = http.client.HTTPConnection(srv.host, srv.port,
                                              timeout=60)
            try:
                conn.connect()
                barrier.wait()
                for i in range(per_client):
                    body = bodies[(kk * per_client + i) % len(bodies)]
                    t0 = time.perf_counter()
                    for attempt in (0, 1):
                        try:
                            conn.request("POST", srv.api_path, body=body,
                                         headers={"Content-Type":
                                                  "application/json"})
                            r = conn.getresponse()
                            r.read()
                            break
                        except (OSError, http.client.HTTPException):
                            conn.close()
                            conn = http.client.HTTPConnection(
                                srv.host, srv.port, timeout=60)
                            if attempt:
                                raise
                    if r.status != 200:
                        errors.append(r.status)
                    rtt.append(time.perf_counter() - t0)
            except Exception as e:  # noqa: BLE001 — surfaced below
                errors.append(repr(e))
            finally:
                conn.close()

        threads = [threading.Thread(target=client, args=(kk,))
                   for kk in range(n_clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        if errors:
            raise RuntimeError(f"recommendation bench clients failed: "
                               f"{errors[:3]} (+{max(len(errors)-3, 0)})")
        return rtt, wall

    servers = {
        "handler": serve_recommender(model, k=k, hot_path=False,
                                     max_batch_size=256),
        "hot": serve_recommender(model, k=k, max_batch_size=256),
    }
    out = {"offline_rows_per_sec": offline_rows_per_sec}
    try:
        for srv in servers.values():
            wait_ready(srv)
        hp = servers["hot"].hot_path
        if hp is None or hp.disabled is not None:
            raise RuntimeError(
                "sar hot path unavailable: "
                + (hp.disabled if hp else "no resident executor"))
        hp.force_path = "sar_resident"
        for name, srv in servers.items():
            drive(srv, 8, 3)                    # warm the connections
            rtt, wall = drive(srv, 32, 16)
            rtt_ms = np.asarray(rtt) * 1e3
            out[f"{name}_rows_per_sec"] = len(rtt) / wall
            out[f"{name}_rtt_p50_ms"] = float(np.percentile(rtt_ms, 50))
            out[f"{name}_rtt_p99_ms"] = float(np.percentile(rtt_ms, 99))
        out["resident_vs_handler_rtt_p50"] = (
            out["handler_rtt_p50_ms"] / max(out["hot_rtt_p50_ms"], 1e-9))
        snap = hp.snapshot()
        assert snap["paths"]["sar_resident"] >= 512, snap["paths"]
        # paired per-rung ladder: the SAME decoded batch scored through
        # the full handler path and through the resident executor,
        # best-of-3 each — the rung-resolution view behind the RTT medians
        from mmlspark_tpu.core.schema import Table as _T
        from mmlspark_tpu.io_http.schema import HTTPRequestData

        hot = servers["hot"]
        req0 = HTTPRequestData.from_json("/", {"user": 0})

        def best_of(fn, reps=3):
            best = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter()
                fn()
                best = min(best, time.perf_counter() - t0)
            return best

        by_rung = {}
        for rung in hot.bucketer.ladder:
            reqs = [req0] * rung
            feats = hp.decoder.decode(reqs, rung)
            t_host = best_of(lambda: hot.handler(_T({"request": reqs})))
            t_res = best_of(lambda: hp.resident_values(feats, rung))
            by_rung[str(rung)] = round(t_host / max(t_res, 1e-9), 3)
        out["resident_vs_host_by_rung"] = by_rung
        out["crossover"] = snap["crossover"]
    finally:
        for srv in servers.values():
            srv.stop()
    return out


def _write_metrics_snapshot() -> None:
    """Dump the process-default registry next to the bench output so the
    run's counters (executable-cache hits, serving counts, streaming rows)
    ride along with the JSON line. Path: MMLSPARK_TPU_BENCH_METRICS_PATH
    (default bench_metrics.json in the working directory)."""
    try:
        from mmlspark_tpu.observability import get_registry

        path = os.environ.get("MMLSPARK_TPU_BENCH_METRICS_PATH",
                              "bench_metrics.json")
        with open(path, "w", encoding="utf-8") as fh:
            json.dump(get_registry().snapshot(), fh, indent=2, sort_keys=True)
        print(f"bench: metrics snapshot -> {path}", file=sys.stderr)
    except Exception as e:  # noqa: BLE001 — snapshot must not cost the line
        print(f"bench: metrics snapshot failed ({e!r})", file=sys.stderr)


def _resolve_kernel_name() -> str:
    from mmlspark_tpu.core.kernels import resolve

    return resolve("gbdt_histogram").__name__


# --------------------------------------------------------------------- #
# orchestration                                                         #
# --------------------------------------------------------------------- #


def _r1(d: "dict | None", key: str) -> "float | None":
    v = d.get(key) if d else None
    return round(v, 1) if v is not None else None


def _trainer_extra(trainer: "dict | None") -> dict:
    """Trainer fields of the JSON line — shared by _run_suite and the
    orchestrator's post-hoc merge of the trainer child's output."""
    ips = trainer.get("train_images_per_sec") if trainer else None
    return {
        "trainer_images_per_sec": round(ips, 1) if ips else None,
        "trainer_vs_baseline": round(
            ips / BASELINE_TRAIN_IMAGES_PER_SEC, 3) if ips else None,
        "trainer_baseline_images_per_sec": BASELINE_TRAIN_IMAGES_PER_SEC,
        "trainer_tflops": round(
            trainer["train_tflops"], 3)
            if trainer and trainer.get("train_tflops") else None,
        "trainer_mfu": trainer.get("train_mfu") if trainer else None,
        "trainer_image_side": trainer.get("image_side") if trainer else None,
        "trainer_smoke_only": trainer.get("smoke_only") if trainer else None,
    }


def _gbdt_large_extra(gbdt_large: "dict | None") -> dict:
    """Higgs-scale-family fields of the JSON line — shared by _run_suite
    and the orchestrator's post-hoc merge of the gbdt_large child."""
    g = (gbdt_large or {}).get
    return {
        "gbdt_large_rows_per_sec": _r1(gbdt_large, "rows_per_sec"),
        "gbdt_large_fit_seconds": (
            round(g("fit_seconds"), 3)
            if g("fit_seconds") is not None else None),
        "gbdt_large_train_acc": (
            round(g("acc"), 4) if g("acc") is not None else None),
        "gbdt_large_valid_auc": (
            round(g("valid_auc"), 4) if g("valid_auc") is not None else None),
        "gbdt_large_modeled_hbm_gbps": (
            round(g("modeled_hbm_gbps"), 2)
            if g("modeled_hbm_gbps") is not None else None),
        "gbdt_large_modeled_hbm_frac_of_peak": g("modeled_hbm_frac_of_peak"),
        "gbdt_large_bin_dtype": g("bin_dtype"),
        "gbdt_large_device_binning": g("device_binning"),
        "gbdt_predict_rows_per_sec": _r1(gbdt_large, "predict_rows_per_sec"),
        "gbdt_predict_resident_rows_per_sec": _r1(
            gbdt_large, "predict_resident_rows_per_sec"),
    }


def _transformer_extra(transformer: "dict | None") -> dict:
    """Transformer fields of the JSON line — shared by _run_suite and the
    orchestrator's post-hoc merge of the transformer child's output."""
    g = (transformer or {}).get
    return {
        "transformer_fwd_dense_tokens_per_sec": _r1(
            transformer, "fwd_dense_tokens_per_sec"),
        "transformer_fwd_flash_tokens_per_sec": _r1(
            transformer, "fwd_flash_tokens_per_sec"),
        "transformer_fwd_mfu": g("fwd_mfu"),
        "transformer_longseq_tokens_per_sec": _r1(
            transformer, "longseq_tokens_per_sec"),
        "transformer_train_tokens_per_sec": _r1(
            transformer, "train_tokens_per_sec"),
        "transformer_train_mfu": g("train_mfu"),
        "transformer_train_flash_tokens_per_sec": _r1(
            transformer, "train_flash_tokens_per_sec"),
        "transformer_seq_len": g("seq_len"),
        "transformer_long_seq_len": g("long_seq_len"),
        "transformer_smoke_only": g("smoke_only"),
    }


def bench_automl_sweep() -> dict:
    """Distributed-sweep rows: the SAME 6-trial 2-rung hyperband sweep
    (GBDT, shared binned dataset) run serially (P=1) and across 4
    preemptible worker processes (P=4), plus a third P=4 run where a
    chaos hook SIGKILLs a worker mid-trial — the preemption recovery
    overhead is that run's wall time over the undisturbed P=4 time.
    Rung barriers make the computed fit set parallelism-invariant, so
    all three runs must land the byte-identical SweepResult digest.

    Each worker's XLA is pinned to one thread — the deployment model is
    one execution slot (chip) per worker, so P=1 must not get a 4-core
    head start over the per-worker slots. Even so this is NOT a
    CPU-speedup claim: on a host with fewer cores than workers (CI runs
    on one) P=4 CANNOT beat P=1, and the paired trials/min rows exist to
    track regressions in sweep orchestration cost (claim/heartbeat/
    barrier overhead) while `speedup_p4` is ungated diagnostics; real
    speedup needs a device per worker."""
    import tempfile

    from mmlspark_tpu.automl.sweep import HyperbandPruner, SweepScheduler
    from mmlspark_tpu.core.schema import Table
    from mmlspark_tpu.gbdt import GBDTClassifier

    rng = np.random.default_rng(17)
    # sized so one fold fit is O(1s): worker spawn (~1-2s/process) and
    # rung-barrier idling must be a tax on real work, not the whole
    # measurement — a toy fit would benchmark process startup
    x = rng.normal(size=(2048, 16))
    y = (x[:, 0] + 0.5 * x[:, 1] > 0).astype(np.float64)
    table = Table({"features": x, "label": y})
    est = GBDTClassifier(features_col="features", label_col="label",
                         num_iterations=8, num_leaves=15, seed=7)
    space = [{"learning_rate": lr, "num_leaves": nl}
             for lr in (0.05, 0.1, 0.2) for nl in (4, 8)]

    def run(workers: int, ckpt: str, chaos: "dict | None" = None):
        sched = SweepScheduler(
            [est], trials=[(0, p) for p in space],
            evaluation_metric="accuracy", label_col="label", num_folds=2,
            seed=0, checkpoint_dir=ckpt, workers=workers,
            pruner=HyperbandPruner(min_resource=4, max_resource=8, eta=2),
            rung_timeout_s=240.0, chaos=chaos,
            # CPU workers by design (see the XLA_FLAGS pin below): these
            # rows track orchestration cost, and this driver holds the chip
            fleet_kw={"device_workers": False})
        t0 = time.perf_counter()
        res = sched.run(table)
        return res, time.perf_counter() - t0

    # spawned workers read env at jax import; the driver's own backend
    # is already initialized, so only the workers are pinned
    old_flags = os.environ.get("XLA_FLAGS")
    os.environ["XLA_FLAGS"] = ((old_flags + " ") if old_flags else "") + \
        "--xla_cpu_multi_thread_eigen=false intra_op_parallelism_threads=1"
    try:
        with tempfile.TemporaryDirectory() as d:
            r1, s1 = run(1, os.path.join(d, "p1"))
            r4, s4 = run(4, os.path.join(d, "p4"))
            rc, sc = run(4, os.path.join(d, "chaos"),
                         chaos={"nth": 3, "mode": "before_save"})
    finally:
        if old_flags is None:
            os.environ.pop("XLA_FLAGS", None)
        else:
            os.environ["XLA_FLAGS"] = old_flags
    if not (r1.digest == r4.digest == rc.digest):
        raise RuntimeError("sweep digests diverged across parallelism")
    fits = len(r1.results)
    return {
        "fits": fits,
        "p1_trials_per_sec": fits / s1,
        "p4_trials_per_sec": fits / s4,
        "p1_trials_per_min": 60.0 * fits / s1,
        "p4_trials_per_min": 60.0 * fits / s4,
        "speedup_p4": s1 / s4,
        "recovery_overhead": sc / s4,
        "resumed_trials": rc.resumed_trials,
    }


def bench_trainer_elastic() -> dict:
    """Elastic data-parallel training rows: the SAME GBDT fit over 2
    REAL fleet worker processes at a fixed world, and again with a
    forced world resize every 3 boosting rounds (kill a worker at one
    boundary, respawn it at the next) — paired wall times plus the
    re-shard barrier cost per membership event. Byte-identity of the two
    final models is asserted: the elastic contract says the membership
    schedule must never change the bits, so any divergence here is a
    correctness failure, not noise. Like the sweep rows this is NOT a
    speedup claim on CI hosts — the paired rows exist to track the
    orchestration cost (drain + checkpoint + configure) per re-shard."""
    import tempfile

    from mmlspark_tpu.resilience.elastic_fleet import ElasticGBDTFit

    rng = np.random.default_rng(23)
    x = rng.normal(size=(2048, 12))
    y = x[:, 0] * 2.0 - x[:, 1] + 0.1 * rng.normal(size=2048)
    rounds = 10

    def run(d, hook=None):
        fit = ElasticGBDTFit(
            d, objective="regression", num_iterations=rounds,
            num_leaves=15, max_bin=63, min_data_in_leaf=5, seed=3,
            n_workers=2, num_virtual=16, step_hook=hook,
            request_timeout_s=120.0)
        t0 = time.perf_counter()
        fit.fit(x, y)
        return fit, time.perf_counter() - t0

    with tempfile.TemporaryDirectory() as d:
        fit_fixed, s_fixed = run(os.path.join(d, "fixed"))

        state = {"last": -1}

        def hook(fit):
            # one membership change per 3rd step boundary: kill slot 0,
            # then respawn it at the next trigger, alternating
            if fit.step and fit.step % 3 == 0 and fit.step != state["last"]:
                state["last"] = fit.step
                dead = fit.fleet.dead_slots()
                if dead:
                    fit.fleet.respawn(dead[0])
                else:
                    fit.fleet.kill(0)

        fit_resize, s_resize = run(os.path.join(d, "resize"), hook)

    if fit_fixed.model_digest() != fit_resize.model_digest():
        raise RuntimeError(
            "elastic digests diverged across world-size schedules")
    # the initial world formation is a join too; resize events are the rest
    n_events = max(len(fit_resize.reshards) - 1, 1)
    return {
        "rounds": rounds,
        "fixed_steps_per_sec": rounds / s_fixed,
        "resize_steps_per_sec": rounds / s_resize,
        "resize_events": n_events,
        "resize_overhead": s_resize / s_fixed,
        "reshard_cost_seconds": max(s_resize - s_fixed, 0.0) / n_events,
    }


def bench_streaming_parallel() -> dict:
    """Partition-parallel streaming speedup: the SAME keyed stateful
    pipeline run at P=1 (plain StreamingQuery) and P=2/P=4
    (ParallelStreamingQuery, thread workers), paired over identical
    batches, plus the shuffle split+merge overhead as a fraction of P=4
    wall time. The chain models an external per-batch call (feature-store
    enrichment) with a GIL-releasing block proportional to rows — the
    speedup is honest LATENCY HIDING of that blocking work across
    partitions, which is the single-host analogue of fleet workers; it is
    NOT a CPU-parallelism claim (this runs on however many cores the host
    has, including one). Byte-identity of all three outputs is asserted,
    and a stream-stream join at P=4 is checked against its P=1 oracle."""
    from mmlspark_tpu.core.params import Param
    from mmlspark_tpu.core.pipeline import Transformer, pipeline_model
    from mmlspark_tpu.core.schema import Table
    from mmlspark_tpu.streaming import (
        GroupedAggregator, KeyedShuffle, MemorySink, MemorySource,
        ParallelStreamingQuery, StreamingQuery, StreamStreamJoin)

    class IoBoundEnrichment(Transformer):
        """Stand-in for a per-batch external call: blocks (releasing the
        GIL) for seconds_per_row * num_rows, passes rows through."""

        seconds_per_row = Param(5e-5, "simulated external-call latency "
                                "per row", ptype=float)

        def _transform(self, table):
            time.sleep(self.get("seconds_per_row") * table.num_rows)
            return table

    rows_per_batch, n_batches, n_keys = 256, 30, 32
    rng = np.random.default_rng(23)
    batches = [
        Table({"key": [f"k{int(i)}" for i in
                       rng.integers(0, n_keys, rows_per_batch)],
               "value": rng.normal(size=rows_per_batch)})
        for _ in range(n_batches)]

    def run(P):
        src, sink = MemorySource(), MemorySink()
        chain = [IoBoundEnrichment(),
                 GroupedAggregator(group_col="key", value_col="value",
                                   agg="sum", output_col="total")]
        if P == 1:
            q = StreamingQuery(src, pipeline_model(*chain), sink,
                               name="par1")
        else:
            q = ParallelStreamingQuery(
                src, pipeline_model(
                    KeyedShuffle(key_col="key", num_partitions=P),
                    *chain),
                sink, name=f"par{P}")
        src.add_rows(batches[0])
        q.process_next()        # warm-up: spin up workers untimed
        t0 = time.perf_counter()
        for b in batches[1:]:
            src.add_rows(b)
            q.process_next()
        elapsed = time.perf_counter() - t0
        out = sink.table()
        shuffle_s = getattr(q, "shuffle_seconds", 0.0)
        q.stop()
        return elapsed, out, shuffle_s

    e1, t1, _ = run(1)
    e2, t2, _ = run(2)
    e4, t4, sh4 = run(4)
    identical = t1.equals(t2) and t1.equals(t4)
    assert identical, "partitioned output diverged from the P=1 run"

    # stream-stream join: P=4 output must match the single-partition oracle
    jdata = [
        Table({"key": [f"k{int(i)}" for i in rng.integers(0, 8, 64)],
               "time": np.round(rng.uniform(b * 10, b * 10 + 12, 64), 3),
               "side": [("left" if x < 0.5 else "right")
                        for x in rng.random(64)],
               "value": np.round(rng.uniform(0, 10, 64), 3)})
        for b in range(4)]

    def run_join(P):
        src, sink = MemorySource(), MemorySink()
        join = StreamStreamJoin(key_col="key", join_window_s=5.0,
                                watermark_delay_s=2.0)
        if P == 1:
            q = StreamingQuery(src, join, sink, name="join1")
        else:
            q = ParallelStreamingQuery(
                src, pipeline_model(
                    KeyedShuffle(key_col="key", num_partitions=P), join),
                sink, name=f"join{P}")
        for b in jdata:
            src.add_rows(b)
            q.process_all_available()
        q.stop()
        return sink.table()

    join_ok = run_join(1).equals(run_join(4))
    timed_rows = (n_batches - 1) * rows_per_batch
    return {
        "p1_rows_per_sec": timed_rows / e1,
        "p2_rows_per_sec": timed_rows / e2,
        "p4_rows_per_sec": timed_rows / e4,
        "speedup_p2_vs_p1": e1 / e2,
        "speedup_p4_vs_p1": e1 / e4,
        "shuffle_overhead_fraction": sh4 / e4 if e4 else 0.0,
        "outputs_identical": bool(identical),
        "join_matches_oracle": bool(join_ok),
    }


def _streaming_extra(streaming: "dict | None") -> dict:
    """Streaming-engine fields of the JSON line. The micro-batch driver is
    host-side Python: these are CPU numbers on every platform (the label
    keeps a TPU run's trend line from being read as accelerator work)."""
    g = (streaming or {}).get
    return {
        "streaming_batches_per_sec": _r1(streaming, "batches_per_sec"),
        "streaming_rows_per_sec": _r1(streaming, "rows_per_sec"),
        "streaming_rows_per_batch": g("rows_per_batch"),
        "streaming_backend": "cpu (host-side driver, non-TPU)"
        if streaming else None,
    }


def _streaming_parallel_extra(par: "dict | None") -> dict:
    """Partition-parallel streaming fields. The speedup is latency
    hiding of a GIL-releasing external-call model across partitions —
    a host-side concurrency number on every platform, never TPU work."""
    g = (par or {}).get
    return {
        "streaming_parallel_p1_rows_per_sec": _r1(par, "p1_rows_per_sec"),
        "streaming_parallel_p2_rows_per_sec": _r1(par, "p2_rows_per_sec"),
        "streaming_parallel_p4_rows_per_sec": _r1(par, "p4_rows_per_sec"),
        "streaming_parallel_speedup_p2_vs_p1": round(
            par["speedup_p2_vs_p1"], 3) if par else None,
        "streaming_parallel_speedup_p4_vs_p1": round(
            par["speedup_p4_vs_p1"], 3) if par else None,
        "streaming_parallel_shuffle_overhead_fraction": round(
            par["shuffle_overhead_fraction"], 4) if par else None,
        "streaming_parallel_outputs_identical": g("outputs_identical"),
        "streaming_parallel_join_matches_oracle": g("join_matches_oracle"),
        "streaming_parallel_backend": "cpu (io-overlap across thread "
        "partitions, non-TPU)" if par else None,
    }


def _run_suite(platform: str) -> dict:
    """Every core family, in order, with no per-family insurance: a family
    that raises fails the bench (a null or 0.0 row under a device's name is
    worse than no artifact). The three heavy families run here too unless
    the orchestrator moved them into their own watched children."""
    chip, peak_tflops, peak_gbps = chip_peaks()

    gbdt = bench_gbdt(peak_gbps)
    gbdt_large = (None if os.environ.get(_SKIP_LARGE_ENV)
                  else bench_gbdt_large(peak_gbps))
    dart = bench_gbdt_dart()
    runner = bench_model_runner(peak_tflops)
    transformer = (None if os.environ.get(_SKIP_TRANSFORMER_ENV)
                   else bench_transformer(peak_tflops))
    trainer = (None if os.environ.get(_SKIP_TRAINER_ENV)
               else bench_trainer(peak_tflops))
    serving = bench_serving()
    degraded = bench_serving_degraded()
    streaming = bench_streaming()
    streaming_parallel = bench_streaming_parallel()
    fusion = bench_pipeline_fusion()
    instrumentation = bench_instrumentation()
    recorder = bench_recorder_overhead()
    profiler = bench_profiler_overhead()
    timeline_bench = bench_timeline_overhead()
    ckpt_overhead = bench_trainer_checkpoint_overhead()
    fleet_scrape = bench_fleet_scrape()
    fleet_gateway = bench_fleet_gateway()
    hot_serving = bench_serving_hot_path()
    binary_wire = bench_serving_binary_wire()
    gateway_tier = bench_gateway_tier()
    rec_topk = bench_recommendation_topk()
    automl_sweep = bench_automl_sweep()
    trainer_elastic = bench_trainer_elastic()
    _write_metrics_snapshot()

    resident = runner.get("resident_images_per_sec", 0.0)
    mfu_note = (
        f"runner resident MFU {runner.get('resident_mfu')}"
        if runner.get("resident_mfu") is not None else "MFU n/a off-TPU"
    )
    return {
        "metric": "gbdt_fit_throughput",
        "value": round(gbdt["rows_per_sec"], 1),
        "unit": "rows/sec",
        "vs_baseline": round(gbdt["rows_per_sec"] / BASELINE_ROWS_PER_SEC, 3),
        "extra": {
            "platform": platform,
            "chip": chip,
            "chip_peak_bf16_tflops": peak_tflops,
            "chip_peak_hbm_gbps": peak_gbps,
            "gbdt_histogram_kernel": _resolve_kernel_name(),
            "gbdt_fit_seconds": round(gbdt["fit_seconds"], 3),
            "gbdt_train_acc": round(gbdt["acc"], 4),
            "gbdt_valid_auc": round(gbdt["valid_auc"], 4),
            "gbdt_baseline_rows_per_sec": BASELINE_ROWS_PER_SEC,
            "gbdt_modeled_hbm_gbps": round(gbdt["modeled_hbm_gbps"], 2),
            "gbdt_modeled_hbm_frac_of_peak": gbdt["modeled_hbm_frac_of_peak"],
            **_gbdt_large_extra(gbdt_large),
            "gbdt_dart_rows_per_sec": round(
                dart["rows_per_sec"], 1) if dart else None,
            "gbdt_dart_fit_seconds": round(
                dart["fit_seconds"], 3) if dart else None,
            "gbdt_dart_train_acc": round(dart["acc"], 4) if dart else None,
            "model_runner_images_per_sec": round(runner["images_per_sec"], 1),
            "model_runner_vs_baseline": round(
                runner["images_per_sec"] / BASELINE_IMAGES_PER_SEC, 3),
            "model_runner_baseline_images_per_sec": BASELINE_IMAGES_PER_SEC,
            "runner_pipelined_images_per_sec": round(
                runner.get("pipelined_images_per_sec", 0.0), 1),
            # paired per-pass median from bench_model_runner; falls back
            # to the ratio of independently-minimized rates
            "runner_pipelined_vs_sequential": round(
                runner.get("pipelined_vs_sequential")
                or (runner.get("pipelined_images_per_sec", 0.0)
                    / max(runner["images_per_sec"], 1e-9)), 3),
            "runner_pipeline_overlap_fraction": round(
                runner.get("pipeline_overlap_fraction", 0.0), 3),
            "runner_pipeline_bucket_ladder": runner.get(
                "pipeline_bucket_ladder"),
            "model_runner_resident_images_per_sec": round(resident, 1),
            "model_runner_resident_tflops": round(
                runner.get("resident_tflops", 0.0), 3),
            "model_runner_resident_mfu": runner.get("resident_mfu"),
            "model_runner_flops_per_image": round(
                runner.get("flops_per_image", 0.0)),
            **_trainer_extra(trainer),
            **_transformer_extra(transformer),
            "serving_p50_ms": round(serving["p50_ms"], 3) if serving else None,
            "serving_p99_ms": round(serving["p99_ms"], 3) if serving else None,
            "serving_client_rtt_p50_ms": round(
                serving["client_rtt_p50_ms"], 3) if serving else None,
            "serving_client_rtt_p99_ms": round(
                serving["client_rtt_p99_ms"], 3) if serving else None,
            "serving_degraded_p50_ms": round(
                degraded["p50_ms"], 3) if degraded else None,
            "serving_degraded_p99_ms": round(
                degraded["p99_ms"], 3) if degraded else None,
            "serving_degraded_error_rate": round(
                degraded["error_rate"], 4) if degraded else None,
            **_streaming_extra(streaming),
            **_streaming_parallel_extra(streaming_parallel),
            # paired per-pass median, like runner_pipelined_vs_sequential
            "pipeline_fused_vs_staged": round(
                fusion["fused_vs_staged"], 3) if fusion else None,
            "pipeline_fused_images_per_sec": round(
                fusion["fused_images_per_sec"], 1) if fusion else None,
            "pipeline_staged_images_per_sec": round(
                fusion["staged_images_per_sec"], 1) if fusion else None,
            "pipeline_fusion_ratio": round(
                fusion["fusion_ratio"], 3) if fusion else None,
            "pipeline_fused_transfers_per_batch": round(
                fusion["fused_transfers_per_batch"], 2) if fusion else None,
            "pipeline_fused_boundary_transfers_per_batch": round(
                fusion["fused_boundary_transfers_per_batch"], 2)
                if fusion else None,
            "pipeline_staged_transfers_per_batch": round(
                fusion["staged_transfers_per_batch"], 2) if fusion else None,
            "instrumentation_overhead": round(
                instrumentation["ratio_enabled"], 3)
                if instrumentation else None,
            "instrumentation_overhead_disabled": round(
                instrumentation["ratio_disabled"], 3)
                if instrumentation else None,
            "recorder_overhead": round(
                recorder["ratio_armed"], 4) if recorder else None,
            "recorder_serving_p50_ms": round(
                recorder["serving_p50_ms"], 3) if recorder else None,
            "recorder_armed_cost_us": round(
                recorder["armed_cost_us_per_request"], 3)
                if recorder else None,
            "recorder_disabled_cost_us": round(
                recorder["disabled_cost_us_per_request"], 3)
                if recorder else None,
            "profiler_overhead": round(
                profiler["ratio_armed"], 4) if profiler else None,
            "profiler_serving_p50_ms": round(
                profiler["serving_p50_ms"], 3) if profiler else None,
            "profiler_armed_cost_us": round(
                profiler["armed_cost_us_per_request"], 3)
                if profiler else None,
            "profiler_disabled_cost_us": round(
                profiler["disabled_cost_us_per_request"], 3)
                if profiler else None,
            "timeline_overhead": round(
                timeline_bench["ratio_armed"], 4)
                if timeline_bench else None,
            "timeline_serving_p50_ms": round(
                timeline_bench["serving_p50_ms"], 3)
                if timeline_bench else None,
            "timeline_armed_cost_us": round(
                timeline_bench["armed_cost_us_per_request"], 3)
                if timeline_bench else None,
            "timeline_sample_cost_us": round(
                timeline_bench["sample_cost_us"], 3)
                if timeline_bench else None,
            "trainer_checkpoint_overhead": round(
                ckpt_overhead["ratio_checkpointed"], 4)
                if ckpt_overhead else None,
            "trainer_checkpoint_epoch_ms": round(
                ckpt_overhead["checkpointed_epoch_seconds"] * 1e3, 3)
                if ckpt_overhead else None,
            "trainer_plain_epoch_ms": round(
                ckpt_overhead["plain_epoch_seconds"] * 1e3, 3)
                if ckpt_overhead else None,
            "fleet_scrape_aggregate_ms": {
                str(n): round(v, 3) for n, v in
                fleet_scrape["aggregate_ms_by_n"].items()}
                if fleet_scrape else None,
            "fleet_scrape_single_ms": round(
                fleet_scrape["single_scrape_ms"], 3)
                if fleet_scrape else None,
            "fleet_scrape_overhead_vs_single": round(
                fleet_scrape["overhead_vs_single_scrape"], 3)
                if fleet_scrape else None,
            "fleet_gateway_p50_ms": round(
                fleet_gateway["gateway_p50_ms"], 3)
                if fleet_gateway else None,
            "fleet_gateway_p99_ms": round(
                fleet_gateway["gateway_p99_ms"], 3)
                if fleet_gateway else None,
            "fleet_gateway_direct_p50_ms": round(
                fleet_gateway["direct_p50_ms"], 3)
                if fleet_gateway else None,
            "fleet_gateway_direct_p99_ms": round(
                fleet_gateway["direct_p99_ms"], 3)
                if fleet_gateway else None,
            "fleet_gateway_kill_error_rate": round(
                fleet_gateway["kill_error_rate"], 4)
                if fleet_gateway else None,
            "fleet_gateway_kill_requests": (
                fleet_gateway["kill_requests"] if fleet_gateway else None),
            "serving_hot_path": ({
                str(b): {k: (round(v, 3) if isinstance(v, float) else v)
                         for k, v in row.items()}
                for b, row in hot_serving["per_concurrency"].items()}
                if hot_serving else None),
            "serving_hot_path_crossover": (
                hot_serving["crossover"] if hot_serving else None),
            "serving_binary_wire": ({
                k: (round(v, 3) if isinstance(v, float) else v)
                for k, v in binary_wire.items()}
                if binary_wire else None),
            "gateway_tier_single_requests_per_sec": round(
                gateway_tier["single_requests_per_sec"], 1)
                if gateway_tier else None,
            "gateway_tier_requests_per_sec": round(
                gateway_tier["tier_requests_per_sec"], 1)
                if gateway_tier else None,
            "gateway_tier_vs_single_x": round(
                gateway_tier["tier_vs_single_x"], 3)
                if gateway_tier else None,
            "gateway_tier_workers": (
                gateway_tier["tier_workers"] if gateway_tier else None),
            "gateway_tier_kill_errors": (
                gateway_tier["kill_errors"] if gateway_tier else None),
            "gateway_tier_kill_requests": (
                gateway_tier["kill_requests"] if gateway_tier else None),
            "recommendation_topk_rows_per_sec": _r1(
                rec_topk, "hot_rows_per_sec"),
            "recommendation_topk_client_rtt_p50_ms": round(
                rec_topk["hot_rtt_p50_ms"], 3) if rec_topk else None,
            "recommendation_topk_client_rtt_p99_ms": round(
                rec_topk["hot_rtt_p99_ms"], 3) if rec_topk else None,
            "recommendation_topk_handler_rows_per_sec": _r1(
                rec_topk, "handler_rows_per_sec"),
            "recommendation_topk_handler_rtt_p50_ms": round(
                rec_topk["handler_rtt_p50_ms"], 3) if rec_topk else None,
            "recommendation_topk_resident_vs_handler_rtt_p50": round(
                rec_topk["resident_vs_handler_rtt_p50"], 3)
                if rec_topk else None,
            "recommendation_topk_offline_rows_per_sec": _r1(
                rec_topk, "offline_rows_per_sec"),
            "recommendation_topk_resident_vs_host_by_rung": (
                rec_topk["resident_vs_host_by_rung"] if rec_topk else None),
            "automl_sweep_p1_trials_per_sec": round(
                automl_sweep["p1_trials_per_sec"], 3)
                if automl_sweep else None,
            "automl_sweep_p4_trials_per_sec": round(
                automl_sweep["p4_trials_per_sec"], 3)
                if automl_sweep else None,
            "automl_sweep_p1_trials_per_min": round(
                automl_sweep["p1_trials_per_min"], 1)
                if automl_sweep else None,
            "automl_sweep_p4_trials_per_min": round(
                automl_sweep["p4_trials_per_min"], 1)
                if automl_sweep else None,
            "automl_sweep_speedup_p4": round(
                automl_sweep["speedup_p4"], 3) if automl_sweep else None,
            "automl_sweep_preemption_recovery_overhead": round(
                automl_sweep["recovery_overhead"], 3)
                if automl_sweep else None,
            "automl_sweep_fits": (
                automl_sweep["fits"] if automl_sweep else None),
            "trainer_elastic_fixed_steps_per_sec": round(
                trainer_elastic["fixed_steps_per_sec"], 3)
                if trainer_elastic else None,
            "trainer_elastic_resize_steps_per_sec": round(
                trainer_elastic["resize_steps_per_sec"], 3)
                if trainer_elastic else None,
            "trainer_elastic_resize_overhead": round(
                trainer_elastic["resize_overhead"], 3)
                if trainer_elastic else None,
            "trainer_elastic_reshard_cost_seconds": round(
                trainer_elastic["reshard_cost_seconds"], 3)
                if trainer_elastic else None,
            "trainer_elastic_resize_events": (
                trainer_elastic["resize_events"]
                if trainer_elastic else None),
            "headroom_note": (
                "gbdt fit is HBM-bound (see gbdt_modeled_hbm_* vs chip peak); "
                "end-to-end runner throughput is host->device transfer bound: "
                f"the device-resident bf16 forward runs "
                f"{resident / max(runner['images_per_sec'], 1):.1f}x faster; "
                f"{mfu_note}"
            ),
        },
    }


def _family_core_main() -> None:
    """Everything except the heavy solo families, in this process. Emits
    the full JSON line with their fields null; the orchestrator fills
    them in."""
    platform = require_tpu_or_explicit_cpu()
    import jax

    print(f"bench: running on {platform} ({len(jax.devices())} device(s))",
          file=sys.stderr)
    print(json.dumps(_run_suite(platform)))


def _family_solo_main(bench_fn) -> None:
    """One heavy family alone (trainer / transformer / gbdt_large). Runs
    in its own process because a big compile cannot be interrupted
    in-process; the orchestrator kills the child on timeout."""
    require_tpu_or_explicit_cpu()
    _, peak_tflops, _ = chip_peaks()
    print(json.dumps(bench_fn(peak_tflops)))


def _family_multichip_main() -> None:
    """Sharded-fusion family, on whatever devices this process has: the
    chips of a multi-chip host, or the forced host-platform devices of an
    explicit `JAX_PLATFORMS=cpu` run (the artifact records which)."""
    require_tpu_or_explicit_cpu()
    import jax

    print(f"bench: multichip family on {len(jax.devices())} "
          f"{jax.devices()[0].platform} device(s)", file=sys.stderr)
    print(json.dumps(bench_fused_sharded()))


def _multichip_orchestrator() -> None:
    """Run the multichip family watched and write the MULTICHIP artifact.

    Rounds 1-5 recorded only whether the dryrun exited 0 ({n_devices, rc,
    ok, ...} with no numbers), which left the ROADMAP per-chip-throughput
    criterion unmeasurable. The artifact keeps those fields and adds the
    fused_sharded_vs_single ladder the criterion is judged on."""
    idx = sys.argv.index("--multichip") + 1
    path = (sys.argv[idx]
            if idx < len(sys.argv) and not sys.argv[idx].startswith("-")
            else _MULTICHIP_ARTIFACT)
    timeout = float(os.environ.get(_MULTICHIP_TIMEOUT_ENV, 900))
    rc, out, err = _run_watched(
        [sys.executable, os.path.abspath(__file__), "--family", "multichip"],
        dict(os.environ), timeout)
    sys.stderr.write(err[-20000:])
    result = _last_json_line(out) if rc == 0 else None
    record = {
        "n_devices": (result or {}).get("devices_available"),
        "rc": rc,
        "ok": rc == 0 and result is not None,
        "skipped": False,
        "tail": "" if rc == 0 else (err or out)[-2000:],
    }
    if result is not None:
        record.update(result)
    with open(path, "w") as fh:
        json.dump(record, fh, indent=2)
        fh.write("\n")
    print(json.dumps(record))
    if not record["ok"]:
        raise SystemExit(1)


def _bench_gbdt_large_solo(_peak_tflops):
    """Solo-family adapter: the large family keys off HBM peak, not FLOPs."""
    _, _, peak_gbps = chip_peaks()
    return bench_gbdt_large(peak_gbps)


def _run_watched(args: list, env: dict,
                 timeout: float) -> "tuple[int | None, str, str]":
    """Run a child in its own process group and return (rc, stdout, stderr);
    rc is None on timeout. Killing the GROUP matters: families start
    worker processes of their own (fleets, gateway tiers), and a plain
    child-kill would orphan them."""
    import signal

    proc = subprocess.Popen(args, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True,
                            start_new_session=True)
    try:
        out, err = proc.communicate(timeout=timeout)
        return proc.returncode, out or "", err or ""
    except subprocess.TimeoutExpired:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            proc.kill()
        out, err = proc.communicate()
        return None, out or "", err or ""


def _last_json_line(stdout: str) -> "dict | None":
    for text in reversed((stdout or "").strip().splitlines()):
        try:
            return json.loads(text)
        except ValueError:
            continue
    return None


def main() -> None:
    if "--family" in sys.argv:
        idx = sys.argv.index("--family") + 1
        family = sys.argv[idx] if idx < len(sys.argv) else "<missing>"
        if family == "core":
            return _family_core_main()
        if family == "trainer":
            return _family_solo_main(bench_trainer)
        if family == "transformer":
            return _family_solo_main(bench_transformer)
        if family == "gbdt_large":
            return _family_solo_main(_bench_gbdt_large_solo)
        if family == "multichip":
            return _family_multichip_main()
        raise SystemExit(f"bench: unknown family {family!r}")

    if "--multichip" in sys.argv:
        return _multichip_orchestrator()

    # Orchestrator: never imports jax (a chip belongs to one process;
    # holding it here would lock the children out). Core families first —
    # they carry the headline metric — then each heavy family (largest
    # compiles) under its own compile-hang timeout. Any child that fails
    # or times out fails the bench.
    here = os.path.abspath(__file__)
    core_timeout = float(os.environ.get(_CORE_TIMEOUT_ENV, 1800))
    solo_timeouts = {
        "transformer": float(os.environ.get(_TRANSFORMER_TIMEOUT_ENV, 900)),
        "trainer": float(os.environ.get(_TRAINER_TIMEOUT_ENV, 900)),
        "gbdt_large": float(os.environ.get(_LARGE_TIMEOUT_ENV, 1200)),
    }

    def run_family(family: str, env: dict, timeout: float) -> "dict | None":
        rc, out, err = _run_watched(
            [sys.executable, here, "--family", family], env, timeout)
        sys.stderr.write(err[-20000:])
        if rc != 0:
            reason = (f"exceeded {timeout:.0f}s" if rc is None
                      else f"rc={rc}")
            raise SystemExit(f"bench: {family} family failed ({reason})")
        return _last_json_line(out)

    line = run_family(
        "core", dict(os.environ, **{_SKIP_TRAINER_ENV: "1",
                                    _SKIP_TRANSFORMER_ENV: "1",
                                    _SKIP_LARGE_ENV: "1"}), core_timeout)
    if line is None:
        raise SystemExit("bench: core families printed no JSON line")
    merges = {"transformer": _transformer_extra, "trainer": _trainer_extra,
              "gbdt_large": _gbdt_large_extra}
    for family, to_extra in merges.items():
        result = run_family(family, dict(os.environ), solo_timeouts[family])
        # a family may print null where it does not apply (gbdt_large on
        # an explicit CPU smoke); its fields then stay null
        if result is not None:
            line["extra"].update(to_extra(result))
    print(json.dumps(line))


if __name__ == "__main__":
    main()
