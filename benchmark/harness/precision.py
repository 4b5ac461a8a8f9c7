"""A tree of float32 weights rounded through a lower precision: the
control's side of `correct` (the reference computed from such weights, put
in the program's place, has to fail a limit).

A matrix goes down in one compiled program and comes back in another, so
the array of the lower type exists in memory between them. Inside ONE
program the TPU's compiler takes float32 -> fp8 -> float32 for the
identity (read on a v5e, PR 26: a control rounded so returned the
reference to seven digits), and a control that rounds nothing fails
nothing."""

from __future__ import annotations

import functools


def _down(x, precision: str):
    """-> (the matrix in the lower type, its scale)."""
    import jax.numpy as jnp

    if precision == "bfloat16":
        return x.astype(jnp.bfloat16), jnp.float32(1.0)
    top = jnp.maximum(jnp.abs(x).max(), 1e-30)
    if precision == "int8":           # symmetric, one scale for the tensor
        scale = top / 127.0
        return jnp.clip(jnp.round(x / scale), -127, 127).astype(
            jnp.int8), scale
    if precision == "fp8":            # e4m3, the tensor scaled to its range
        scale = top / 448.0
        return (x / scale).astype(jnp.float8_e4m3fn), scale
    raise ValueError(f"no such precision: {precision!r}")


def _up(low, scale):
    import jax.numpy as jnp

    return low.astype(jnp.float32) * scale


@functools.lru_cache(maxsize=None)
def _programs():
    import jax

    return jax.jit(_down, static_argnums=1), jax.jit(_up)


def through(weights, precision: str):
    """Every matrix of `weights` (two axes or more: kernels, embeddings)
    rounded through `precision`, back in float32; vectors stay, as a
    quantised model keeps its biases and norm scales. `weights` is SPENT:
    each float32 matrix is freed once its lower copy exists, because a
    tree that fills most of the device has no room for a second one."""
    import jax

    down, up = _programs()

    def one(x):
        if x.ndim < 2:
            return x
        low, scale = down(x, precision)
        low.block_until_ready()
        x.delete()
        return up(low, scale)

    return jax.tree.map(one, weights)
