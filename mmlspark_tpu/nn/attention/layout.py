"""What every core shares: the tier that runs, the layout the Pallas kernels
speak, and the projections that exist only to produce that layout.

Two decisions are made here and nowhere else: `tier` (what a model's
"flash" means on this backend) and `_lanes_whole` (which heads a kernel
reads in place; `head_projection` and `out_projection` choose a module by
it, and the tests take it away to compare the two layouts).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..lanes import NEG_INF as _NEG_INF  # noqa: F401  (the cores import it from here)


def _known(impl: str) -> str:
    """`impl` if it names a tier; the one place that refuses another."""
    if impl not in ("flash", "chunked", "dense"):
        raise ValueError(f"unknown attention impl {impl!r}; have 'flash', "
                         "'chunked', 'dense'")
    return impl


def tier(impl: str) -> str:
    """The tier that runs where a model states `impl`: "flash" is the
    Pallas kernels, and the chunked tier on the CPU, where Mosaic cannot
    lower (so a CPU test loads the same model file); on any other backend
    the kernel is used and a failure to compile it propagates. "chunked"
    and "dense" are what they say everywhere; any other name is an error
    that lists the three. The functions that take a LITERAL tier
    (`causal_attention`, `latent_attention`, `eva_attention`,
    `flash_attention(interpret=)`) do not come through here: the CPU tests
    run the kernels interpreted through them."""
    if _known(impl) == "flash" and jax.default_backend() == "cpu":
        return "chunked"
    return impl


def _pad_seq(x, mult):
    t = x.shape[1]
    pad = (-t) % mult
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
    return x, t


def _lanes_whole(*widths: int) -> bool:
    """The rule, by shape: a head whose channels are whole lane blocks
    (multiples of 128) can be named as a block inside (B, T, H x D), the
    array its projection wrote; any other width (64, a test's 8) cannot,
    and takes the head-major copy."""
    return all(w % 128 == 0 for w in widths)


def _rows(x, in_place: bool):
    """(B, T, H, D) as a kernel's grid reads it. In place: (B, T, H x D),
    the same bytes in row-major order, head j's channels lane block j.
    Head-major: (B x H, T, D), a transposed copy in HBM."""
    b, t, h, d = x.shape
    if in_place:
        return x.reshape(b, t, h * d)
    return jnp.moveaxis(x, 2, 1).reshape(b * h, t, d)


def _heads(x, b: int, h: int, in_place: bool):
    """`_rows`' way back: (B, T, H, D) of a kernel's output."""
    if in_place:
        return x.reshape(b, x.shape[1], h, x.shape[2] // h)
    return jnp.moveaxis(x.reshape(b, h, x.shape[1], x.shape[2]), 1, 2)


def _block_at(in_place: bool, heads: int):
    """(row b, head j, block i along the sequence) -> the index of that
    (1, positions, D) block in `_rows`' array of `heads` heads."""
    if in_place:
        return lambda b_, j, i: (b_, i, j)
    return lambda b_, j, i: (b_ * heads + j, i, 0)


class HeadsDense(nn.Module):
    """x (.., d) projected to `heads` heads of `width` channels, (..,
    heads, width). Parameter-compatible with `nn.DenseGeneral((heads,
    width))` (kernel (d, heads, width), bias (heads, width), its
    initialisers), and the same sums; but computed as ONE product to (..,
    heads x width), bias added there, and reshaped. That three-
    dimensional array is the one the TPU's compiler lays out, channels in
    lanes and positions in sublanes: the array a kernel reads in place. A
    product to four dimensions it writes positions-minor (PERF.md, PR
    34), and a copy to the kernel's layout follows. `parts`: widths that
    split every head's channels (latent attention's own and rotary query
    channels): a product and an array each, from the kernel's columns."""

    heads: int
    width: int
    use_bias: bool = True
    parts: tuple[int, ...] = ()
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        d, flat = x.shape[-1], self.heads * self.width

        def kernel_init(rng, shape, dtype=jnp.float32):
            return nn.initializers.lecun_normal()(
                rng, (d, flat), dtype).reshape(shape)

        kernel = self.param("kernel", kernel_init,
                            (d, self.heads, self.width), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros_init(),
                          (self.heads, self.width),
                          jnp.float32) if self.use_bias else None
        x, kernel, bias = nn.dtypes.promote_dtype(x, kernel, bias,
                                                  dtype=self.dtype)
        outs, first = [], 0
        for width in self.parts or (self.width,):
            columns = slice(first, first + width)
            out = jnp.dot(x, kernel[:, :, columns].reshape(d, -1))
            if bias is not None:
                out = out + bias[:, columns].reshape(-1)
            outs.append(out.reshape(x.shape[:-1] + (self.heads, width)))
            first += width
        return tuple(outs) if self.parts else outs[0]


class HeadsOut(nn.Module):
    """`HeadsDense`'s way back: o (.., heads, width) projected to
    `features` channels. Parameter-compatible with `nn.DenseGeneral(
    features, axis=(-2, -1))` (kernel (heads, width, features), no bias,
    its initialiser), and the same sums; but computed as ONE product of
    (.., heads x width), the array a kernel wrote in place, by the kernel
    as (heads x width, features). Contracted over two dimensions the TPU's
    compiler copies that array heads-in-sublanes first: a `copy` of
    (.., heads x width) in the compiled program, which `tests/
    test_chipless_compile.py` holds absent (PERF.md section 6, PR 40)."""

    features: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, o):
        heads, width = o.shape[-2:]

        def kernel_init(rng, shape, dtype=jnp.float32):
            return nn.initializers.lecun_normal()(
                rng, (heads * width, self.features), dtype).reshape(shape)

        kernel = self.param("kernel", kernel_init,
                            (heads, width, self.features), jnp.float32)
        o, kernel = nn.dtypes.promote_dtype(o, kernel, dtype=self.dtype)
        return jnp.dot(o.reshape(o.shape[:-2] + (heads * width,)),
                       kernel.reshape(heads * width, self.features))


def head_projection(heads: int, width: int, dtype, name: str):
    """The module that projects to `heads` heads of `width` channels, no
    bias, by shape: `HeadsDense` where a head is whole lane blocks (a
    kernel then reads what it wrote in place), `nn.DenseGeneral` at any
    other width. The same parameters under the same name either way."""
    if _lanes_whole(width):
        return HeadsDense(heads, width, use_bias=False, dtype=dtype,
                          name=name)
    return nn.DenseGeneral((heads, width), use_bias=False, dtype=dtype,
                           name=name)


def out_projection(features: int, width: int, dtype, name: str):
    """`head_projection`'s way back, from heads of `width` channels to
    `features`: `HeadsOut` where a kernel wrote the heads in place,
    `nn.DenseGeneral` over both axes elsewhere."""
    if _lanes_whole(width):
        return HeadsOut(features, dtype=dtype, name=name)
    return nn.DenseGeneral(features, axis=(-2, -1), use_bias=False,
                           dtype=dtype, name=name)
