"""Sweep histogram-kernel variants on the real chip.

Times each variant on the bench workload shape (n=32768, F=14, B=256, C=3)
as a jitted scan of SPLITS sequential builds with changing masks — the same
dependency structure as a real tree grow — and prints per-build microseconds and
the projected 100-iteration fit seconds.

Usage: python tools/sweep_hist.py            # real device
       JAX_PLATFORMS=cpu python tools/sweep_hist.py
"""

from __future__ import annotations

import contextlib
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

N, F, B, C = 32768, 14, 256, 3
SPLITS = 30          # one tree's worth of sequential hist builds
REPS = 3


def make_inputs(seed=0):
    rng = np.random.default_rng(seed)
    bins = jnp.asarray(rng.integers(0, B, size=(N, F)), jnp.int32)
    stats = jnp.asarray(rng.normal(size=(N, C)), jnp.float32)
    return bins, stats


def run(name, hist_fn, bins, stats):
    """Scan SPLITS dependent builds (mask derived from prior output)."""

    def body(mask, _):
        s = stats * mask[:, None]
        h = hist_fn(bins, s, B)
        # fold the result into the next mask so builds are truly sequential
        new_mask = jnp.where(
            (jnp.arange(N) % 7).astype(jnp.float32) < (h[0, 0, 2] % 7.0),
            mask, 1.0 - mask)
        return new_mask, h[0, 0, 0]

    @jax.jit
    def tree(mask0):
        return jax.lax.scan(body, mask0, None, length=SPLITS)

    mask0 = jnp.ones((N,), jnp.float32)
    out = tree(mask0)
    jax.block_until_ready(out)
    ts = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(tree(mask0))
        ts.append(time.perf_counter() - t0)
    per_build_us = min(ts) / SPLITS * 1e6
    fit_s = per_build_us * 1e-6 * SPLITS * 100   # 100 trees
    print(f"{name:34s} {per_build_us:9.1f} us/build   projected fit {fit_s:6.3f} s")
    return per_build_us


# ---------------------------------------------------------------- variants --

def v_current_pallas(chunk):
    from mmlspark_tpu.gbdt import hist_kernel as hk

    def fn(bins, stats, num_bins):
        old = hk._PALLAS_CHUNK
        hk._PALLAS_CHUNK = chunk
        try:
            # pin BOTH opt-ins off so this row times the per-feature kernel
            # even if the operator exported the env vars for other rows
            with _with_env("MMLSPARK_TPU_FUSED_HIST", "0"), \
                    _with_env("MMLSPARK_TPU_HIST_GROUP", "1"):
                return hk._histogram_pallas(bins, stats, num_bins,
                                            interpret=False)
        finally:
            hk._PALLAS_CHUNK = old
    return fn


@contextlib.contextmanager
def _with_env(key, value):
    """Temporarily set an env var, restoring any prior value."""
    old = os.environ.get(key)
    os.environ[key] = value
    try:
        yield
    finally:
        if old is None:
            os.environ.pop(key, None)
        else:
            os.environ[key] = old


def _force_fused():
    return _with_env("MMLSPARK_TPU_FUSED_HIST", "1")


def v_fused_auto():
    from mmlspark_tpu.gbdt import hist_kernel as hk

    def fn(bins, stats, num_bins):
        with _force_fused():
            return hk._histogram_pallas(bins, stats, num_bins, interpret=False)
    return fn


def v_fused_budget(budget_mb):
    from mmlspark_tpu.gbdt import hist_kernel as hk

    def fn(bins, stats, num_bins):
        old = hk._FUSED_MASK_VMEM_BYTES
        hk._FUSED_MASK_VMEM_BYTES = budget_mb * 2**20
        try:
            with _force_fused():
                return hk._histogram_pallas(bins, stats, num_bins,
                                            interpret=False)
        finally:
            hk._FUSED_MASK_VMEM_BYTES = old
    return fn


def v_grouped(group, chunk=1024):
    from mmlspark_tpu.gbdt import hist_kernel as hk

    def fn(bins, stats, num_bins):
        old = hk._PALLAS_CHUNK
        hk._PALLAS_CHUNK = chunk
        try:
            with _with_env("MMLSPARK_TPU_FUSED_HIST", "0"), \
                    _with_env("MMLSPARK_TPU_HIST_GROUP", str(group)):
                return hk._histogram_pallas(bins, stats, num_bins,
                                            interpret=False)
        finally:
            hk._PALLAS_CHUNK = old
    return fn


def v_materialized_oh(bins, stats, num_bins):
    """One-hot materialized once (closure cache) + single big dot per build."""
    # build OH outside the timed region is not possible here; emulate by
    # computing OH inside jit — XLA hoists it out of the scan as a loop
    # invariant, which is exactly the per-fit amortization we'd implement.
    n, f = bins.shape
    oh = jax.nn.one_hot(bins, num_bins, dtype=jnp.bfloat16)  # (n, F, B)
    oh = oh.reshape(n, f * num_bins)
    h = jax.lax.dot_general(
        stats, oh, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    return h.reshape(stats.shape[1], f, num_bins).transpose(1, 2, 0)


def _chunk_of(budget_mb: int) -> int:
    """The fused chunk a given VMEM budget yields at the sweep shape."""
    from mmlspark_tpu.gbdt import hist_kernel as hk

    old = hk._FUSED_MASK_VMEM_BYTES
    hk._FUSED_MASK_VMEM_BYTES = budget_mb * 2**20
    try:
        return hk._fused_chunk(F, B)
    finally:
        hk._FUSED_MASK_VMEM_BYTES = old


def full_fit_ab():
    """FULL-FIT A/B at the Adult-Census bench shape (VERDICT r4 #3): the
    µs/build sweep above ranks kernels in isolation, but the decision to
    flip the default needs END-TO-END fit seconds — binning, growth, and
    the histogram stream together — plus the valid-AUC guard that a
    faster kernel didn't silently break learning. One row per candidate
    configuration; the winner's numbers go to PERF.md and
    the default flip happens on this table, not on µs/build."""
    from mmlspark_tpu.automl.metrics import auc as roc_auc
    from mmlspark_tpu.core.kernels import set_kernel_mode
    from mmlspark_tpu.gbdt.booster import Booster, TrainOptions
    from tools.build_zoo import make_dataset

    n_fit, n_valid, f_dim = 200_000, 8_192, 28
    x, y = make_dataset(n_fit + n_valid, f_dim)
    x, x_v, y, y_v = x[:n_fit], x[n_fit:], y[:n_fit], y[n_fit:]
    base = dict(objective="binary", num_iterations=50, num_leaves=63,
                learning_rate=0.1)

    configs = [
        # (label, kernel mode, env overrides, TrainOptions extras)
        ("pallas per-feature int32", "pallas", {}, {}),
        ("pallas per-feature uint8", "pallas", {}, {"bin_dtype": "uint8"}),
        ("xla uint8", "xla", {}, {"bin_dtype": "uint8"}),
        ("pallas grouped G=4 uint8", "pallas",
         {"MMLSPARK_TPU_HIST_GROUP": "4"}, {"bin_dtype": "uint8"}),
        ("pallas fused uint8", "pallas",
         {"MMLSPARK_TPU_FUSED_HIST": "1"}, {"bin_dtype": "uint8"}),
        ("pallas per-feature uint8+devbin", "pallas", {},
         {"bin_dtype": "uint8", "device_binning": True}),
    ]
    print(f"\n== FULL-FIT A/B (n={n_fit}, F={f_dim}, 50 iters, 63 leaves; "
          "fit seconds include binning) ==")
    rows = []
    for label, mode, env, extra in configs:
        try:
            set_kernel_mode(mode)
            ctxs = [_with_env(k, v) for k, v in env.items()]
            with contextlib.ExitStack() as stack:
                for c in ctxs:
                    stack.enter_context(c)
                # cold pass includes compile; the warm pass (fresh train,
                # cached lowering) is the steady-state number the default
                # flip must rank on — compile-time deltas between pallas/
                # xla/fused lowerings would otherwise pick the winner
                t0 = time.perf_counter()
                Booster.train(x, y, TrainOptions(**base, **extra))
                cold_s = time.perf_counter() - t0
                t0 = time.perf_counter()
                b = Booster.train(x, y, TrainOptions(**base, **extra))
                fit_s = time.perf_counter() - t0
            auc = roc_auc(y_v, np.asarray(b.predict(x_v)))
            rows.append((label, fit_s, auc))
            print(f"{label:34s} warm {fit_s:7.2f} s "
                  f"(cold {cold_s:6.2f})   {n_fit / fit_s:12,.0f} rows/s"
                  f"   valid AUC {auc:.4f}")
        except Exception as e:  # noqa: BLE001 — per-config verdicts
            print(f"{label:34s} FAILED: {type(e).__name__}: "
                  f"{str(e).splitlines()[0][:90]}")
        finally:
            set_kernel_mode(None)
    if rows:
        # the winner must LEARN, not just finish: a fast config with a
        # silently broken kernel (AUC collapse) can never take the table
        best_auc = max(r[2] for r in rows)
        sound = [r for r in rows if r[2] >= max(0.75, best_auc - 0.01)]
        if sound:
            best = min(sound, key=lambda r: r[1])
            print(f"FULL-FIT WINNER: {best[0]} ({best[1]:.2f} s, "
                  f"AUC {best[2]:.4f})")
        else:
            print("FULL-FIT WINNER: none — every config failed the "
                  "AUC soundness floor")


def main():
    print(f"device: {jax.devices()[0].device_kind}")
    bins, stats = make_inputs()
    from mmlspark_tpu.gbdt.hist_kernel import histogram_xla

    ref = None
    # uint8 bin storage (bin_dtype="uint8"): 4x narrower HBM read of the
    # dominant stream; kernels cast to int32 inside VMEM. Sweeping both
    # dtypes decides whether uint8 becomes the default next round.
    bins_u8 = bins.astype(jnp.uint8)
    variants = [
        ("xla one-hot scan (fallback)",
         lambda b, s, nb: histogram_xla(b, s, nb), bins),
        ("pallas per-feature chunk=1024", v_current_pallas(1024), bins),
        ("pallas per-feature chunk=2048", v_current_pallas(2048), bins),
        ("pallas grouped G=2 chunk=1024", v_grouped(2), bins),
        ("pallas grouped G=4 chunk=1024", v_grouped(4), bins),
        ("pallas grouped G=7 chunk=1024", v_grouped(7), bins),
        ("pallas grouped G=4 chunk=512", v_grouped(4, 512), bins),
        (f"pallas fused auto (4MB->{_chunk_of(4)})", v_fused_auto(), bins),
        (f"pallas fused budget 2MB ({_chunk_of(2)})", v_fused_budget(2), bins),
        (f"pallas fused budget 8MB ({_chunk_of(8)})", v_fused_budget(8), bins),
        ("materialized one-hot bf16 dot", v_materialized_oh, bins),
        ("xla one-hot scan (uint8 bins)",
         lambda b, s, nb: histogram_xla(b, s, nb), bins_u8),
        ("pallas fused auto (uint8 bins)", v_fused_auto(), bins_u8),
    ]
    for name, fn, b_in in variants:
        try:
            h = np.asarray(jax.jit(lambda b, s: fn(b, s, B))(b_in, stats))
            if ref is None:
                ref = h
            err = float(np.abs(h - ref).max())
            run(name, fn, b_in, stats)
            if err > 1e-3:
                print(f"    WARNING {name}: max abs err vs reference "
                      f"variant = {err:.2e}")
        except Exception as e:  # noqa: BLE001
            print(f"{name:34s} FAILED: {type(e).__name__}: {e}")

    if jax.devices()[0].platform == "cpu":
        print("\nfull-fit A/B skipped on CPU (pallas non-interpret cannot "
              "run here; the decision table needs the real chip)")
    elif os.environ.get("MMLSPARK_TPU_SWEEP_FULLFIT", "1") != "0":
        full_fit_ab()


if __name__ == "__main__":
    main()
