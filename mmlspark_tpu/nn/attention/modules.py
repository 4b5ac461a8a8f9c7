"""What a model's attention module calls: `SelfAttention`, the encoder's
parameter-compatible module, and `decoder_attention`, a decoder's core. Both
ask `layout.tier` what runs and look the cores up in their home modules."""

from __future__ import annotations

import functools
from typing import Any

import jax.numpy as jnp
from flax import linen as nn

from ...parallel.ring_attention import dense_attention
from . import eva, flash, layout, xla


class SelfAttention(nn.Module):
    """Multi-head self-attention with a selectable attention core.

    Parameter tree is IDENTICAL to flax's nn.MultiHeadDotProductAttention
    (submodules query/key/value/out with the same DenseGeneral layouts) so
    checkpoints, the serialize registry, and the HF import spec
    (import_weights.TRANSFORMER_SPEC -> params/attn_i/query/kernel ...)
    are impl-agnostic.

    impl: "dense" (reference math), "chunked" (O(T) scan, differentiable),
    "flash" (Pallas TPU kernel, differentiable via custom_vjp; what runs
    for it on this backend is `layout.tier`'s answer).
    """

    num_heads: int
    dtype: Any = jnp.float32
    impl: str = "dense"
    causal: bool = False

    @nn.compact
    def __call__(self, x, train: bool = False):
        d_model = x.shape[-1]
        if d_model % self.num_heads:
            raise ValueError(f"d_model={d_model} not divisible by "
                             f"num_heads={self.num_heads}")
        head_dim = d_model // self.num_heads
        proj = functools.partial(layout.HeadsDense, self.num_heads, head_dim,
                                 dtype=self.dtype)
        q = proj(name="query")(x)
        k = proj(name="key")(x)
        v = proj(name="value")(x)

        impl = layout.tier(self.impl)
        if impl == "dense":
            out = dense_attention(q, k, v, causal=self.causal)
        elif impl == "chunked":
            out = xla.chunked_attention(q, k, v, causal=self.causal)
        else:
            out = flash.flash_attention(q, k, v, causal=self.causal)
        return nn.DenseGeneral(features=d_model, axis=(-2, -1),
                               dtype=self.dtype, name="out")(out)


def decoder_attention(q, k, v, impl: str, dtype, pooling=None,
                      window: int = 0, chunk: int = 0, band=None):
    """The one entry a decoder's attention module calls, for a model that
    states the tier `impl` (`layout.tier`: "flash" is the Pallas kernels,
    chunked on the CPU). With a `window`, a query reads its own window
    exactly and the windows before it a summary a `chunk`, pooled with
    `pooling` (phi, mu) (`eva.eva_attention`, which takes plain causal
    attention for a row of at most one window). With a `band`, a query
    reads the `band` keys that end with its own (a window that slides with
    it: `flash.causal_attention`'s `window`)."""
    impl = layout.tier(impl)
    if window:
        return eva.eva_attention(q, k, v, *pooling, window, chunk,
                                 impl=impl).astype(dtype)
    return flash.causal_attention(q, k, v, impl, window=band).astype(dtype)
