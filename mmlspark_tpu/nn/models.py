"""Model architectures + the ModelBundle container.

Reference: the CNTK side ships opaque serialized `Function` graphs
(src/cntk-model/src/main/scala/SerializableFunction.scala:85+) whose layers
are addressed by name for transfer learning (`ImageFeaturizer.scala:92-135`
cutOutputLayers/layerNames). TPU-first equivalent: flax modules with
deterministic layer naming; intermediates are captured by flax's
`capture_intermediates` and addressed with the same dotted-path idea.

All models run NHWC with channel dims that map well to the MXU's 128-lane
tiling; compute in bfloat16 with float32 params/accumulations is handled by
the `dtype` argument (the standard flax mixed-precision recipe).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Callable, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..observability.metrics import get_registry
from ..observability.tracing import get_tracer
from . import attention, loglik, scan

__all__ = [
    "MLP",
    "SimpleCNN",
    "ResNet",
    "TransformerEncoder",
    "MLAMoEDecoder",
    "HybridMoEDecoder",
    "EvaDecoder",
    "LatentAttention",
    "GroupedQueryAttention",
    "EvaAttention",
    "ShortConv",
    "StateSpaceMixer",
    "SSMHybridDecoder",
    "SelectiveMixer",
    "GatedMemoryUnit",
    "DifferentialAttention",
    "DecoderHybridDecoder",
    "ExpertLayer",
    "GatedFFN",
    "RMSNorm",
    "LayerNorm",
    "resnet20_cifar",
    "resnet50",
    "ARCHITECTURES",
    "make_model",
    "ModelBundle",
]


class MLP(nn.Module):
    """Plain fully-connected classifier/regressor."""

    features: Sequence[int] = (128, 64)
    num_outputs: int = 2
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = x.reshape((x.shape[0], -1)).astype(self.dtype)
        for i, f in enumerate(self.features):
            x = nn.Dense(f, dtype=self.dtype, name=f"dense_{i}")(x)
            x = nn.relu(x)
        return nn.Dense(self.num_outputs, dtype=self.dtype, name="head")(x)


class SimpleCNN(nn.Module):
    """Small conv net (the role of the reference's ConvNet notebook model,
    `DeepLearning - CIFAR10 Convolutional Network.ipynb`)."""

    num_outputs: int = 10
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = x.astype(self.dtype)
        for i, f in enumerate((64, 128, 256)):
            x = nn.Conv(f, (3, 3), dtype=self.dtype, name=f"conv_{i}")(x)
            x = nn.relu(x)
            x = nn.max_pool(x, (2, 2), strides=(2, 2))
        x = x.reshape((x.shape[0], -1))
        x = nn.Dense(256, dtype=self.dtype, name="dense_0")(x)
        x = nn.relu(x)
        return nn.Dense(self.num_outputs, dtype=self.dtype, name="head")(x)


class ResNetBlock(nn.Module):
    filters: int
    strides: tuple[int, int] = (1, 1)
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        residual = x
        y = nn.Conv(self.filters, (3, 3), self.strides, use_bias=False,
                    dtype=self.dtype, name="conv1")(x)
        y = nn.BatchNorm(use_running_average=not train, dtype=self.dtype,
                         name="bn1")(y)
        y = nn.relu(y)
        y = nn.Conv(self.filters, (3, 3), use_bias=False, dtype=self.dtype,
                    name="conv2")(y)
        y = nn.BatchNorm(use_running_average=not train, dtype=self.dtype,
                         scale_init=nn.initializers.zeros_init(), name="bn2")(y)
        if residual.shape != y.shape:
            residual = nn.Conv(self.filters, (1, 1), self.strides,
                               use_bias=False, dtype=self.dtype,
                               name="proj_conv")(residual)
            residual = nn.BatchNorm(use_running_average=not train,
                                    dtype=self.dtype, name="proj_bn")(residual)
        return nn.relu(residual + y)


class BottleneckBlock(nn.Module):
    filters: int
    strides: tuple[int, int] = (1, 1)
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        residual = x
        y = nn.Conv(self.filters, (1, 1), use_bias=False, dtype=self.dtype,
                    name="conv1")(x)
        y = nn.BatchNorm(use_running_average=not train, dtype=self.dtype,
                         name="bn1")(y)
        y = nn.relu(y)
        y = nn.Conv(self.filters, (3, 3), self.strides, use_bias=False,
                    dtype=self.dtype, name="conv2")(y)
        y = nn.BatchNorm(use_running_average=not train, dtype=self.dtype,
                         name="bn2")(y)
        y = nn.relu(y)
        y = nn.Conv(self.filters * 4, (1, 1), use_bias=False, dtype=self.dtype,
                    name="conv3")(y)
        y = nn.BatchNorm(use_running_average=not train, dtype=self.dtype,
                         scale_init=nn.initializers.zeros_init(), name="bn3")(y)
        if residual.shape != y.shape:
            residual = nn.Conv(self.filters * 4, (1, 1), self.strides,
                               use_bias=False, dtype=self.dtype,
                               name="proj_conv")(residual)
            residual = nn.BatchNorm(use_running_average=not train,
                                    dtype=self.dtype, name="proj_bn")(residual)
        return nn.relu(residual + y)


class ResNet(nn.Module):
    """ResNet family. `stage_sizes`/`bottleneck` select the variant:
    resnet20 CIFAR (3,3,3 basic), resnet50 (3,4,6,3 bottleneck), etc."""

    stage_sizes: Sequence[int] = (3, 3, 3)
    num_outputs: int = 10
    num_filters: int = 16
    bottleneck: bool = False
    stem_strides: int = 1          # 1 for CIFAR-size inputs, 2 for ImageNet
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = x.astype(self.dtype)
        block = BottleneckBlock if self.bottleneck else ResNetBlock
        if self.stem_strides == 1:
            x = nn.Conv(self.num_filters, (3, 3), use_bias=False,
                        dtype=self.dtype, name="stem_conv")(x)
        else:
            x = nn.Conv(self.num_filters, (7, 7), (2, 2), use_bias=False,
                        dtype=self.dtype, name="stem_conv")(x)
        x = nn.BatchNorm(use_running_average=not train, dtype=self.dtype,
                         name="stem_bn")(x)
        x = nn.relu(x)
        if self.stem_strides != 1:
            x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        for i, size in enumerate(self.stage_sizes):
            for j in range(size):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = block(self.num_filters * 2**i, strides=strides,
                          dtype=self.dtype, name=f"stage{i}_block{j}")(x, train)
        x = jnp.mean(x, axis=(1, 2), keepdims=False)
        self.sow("intermediates", "pooled_features", x)
        return nn.Dense(self.num_outputs, dtype=jnp.float32, name="head")(x)


class TransformerEncoder(nn.Module):
    """Sequence classifier/regressor: pre-LN transformer encoder blocks
    over (batch, seq, feat) inputs — the sequence-model family the
    reference lacks entirely (SURVEY.md §5.7). Token-id inputs embed via
    `vocab_size`; continuous inputs project via a Dense stem. Attention is
    standard dense MHA here; the sharded ring/Ulysses variants in
    `parallel.ring_attention` drop into the same block shape for long
    sequences (they implement identical math)."""

    num_layers: int = 2
    d_model: int = 64
    num_heads: int = 4
    d_ff: int = 128
    num_outputs: int = 2
    vocab_size: int = 0             # >0: int token inputs, embed; 0: project
    max_len: int = 512
    dropout_rate: float = 0.0
    # attention core (nn/attention/): "dense" (reference math),
    # "chunked" (O(T) online-softmax scan), "flash" (Pallas TPU kernel,
    # differentiable via custom_vjp; the chunked tier on the CPU).
    # Param trees are identical across impls, so a model trained with one
    # loads and serves with any other.
    attention_impl: str = "dense"
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        if self.vocab_size > 0:
            h = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype,
                         name="embed")(x.astype(jnp.int32))
        else:
            if x.ndim == 2:          # (batch, seq) scalars -> (batch, seq, 1)
                x = x[:, :, None]
            h = nn.Dense(self.d_model, dtype=self.dtype, name="stem")(
                x.astype(self.dtype))
        if h.shape[1] > self.max_len:
            raise ValueError(
                f"sequence length {h.shape[1]} exceeds max_len={self.max_len}; "
                "raise max_len in the model config"
            )
        # param stays float32 (the mixed-precision recipe: f32 params, cast
        # at use) — creating it in bf16 would also optimize it in bf16 and
        # tiny position updates would round to zero
        pos = self.param(
            "pos_embed", nn.initializers.normal(0.02),
            (self.max_len, self.d_model), jnp.float32,
        )
        h = h + pos[: h.shape[1]][None, :, :].astype(self.dtype)
        if self.attention_impl != "dense" and self.dropout_rate > 0:
            raise ValueError(
                "attention dropout is only implemented for the dense core; "
                f"got attention_impl={self.attention_impl!r} with "
                f"dropout_rate={self.dropout_rate}")
        for i in range(self.num_layers):
            y = nn.LayerNorm(dtype=self.dtype, name=f"ln_attn_{i}")(h)
            if self.attention_impl == "dense":
                y = nn.MultiHeadDotProductAttention(
                    num_heads=self.num_heads, dtype=self.dtype,
                    dropout_rate=self.dropout_rate, deterministic=not train,
                    name=f"attn_{i}",
                )(y)
            else:
                y = attention.SelfAttention(
                    num_heads=self.num_heads, dtype=self.dtype,
                    impl=self.attention_impl, name=f"attn_{i}",
                )(y, train=train)
            h = h + y
            y = nn.LayerNorm(dtype=self.dtype, name=f"ln_mlp_{i}")(h)
            y = nn.Dense(self.d_ff, dtype=self.dtype, name=f"mlp_up_{i}")(y)
            y = nn.gelu(y)
            y = nn.Dense(self.d_model, dtype=self.dtype, name=f"mlp_down_{i}")(y)
            h = h + y
        h = nn.LayerNorm(dtype=self.dtype, name="ln_final")(h)
        pooled = h.mean(axis=1)
        self.sow("intermediates", "pooled_features", pooled)
        return nn.Dense(self.num_outputs, dtype=jnp.float32, name="head")(pooled)


def _times(x, multiplier: float):
    """x under a model's fixed scalar: the product in float32, rounded once
    to x's type; a multiplier of 1 is no operation at all, so a family
    without multipliers lowers to what it did."""
    if multiplier == 1.0:
        return x
    return (x.astype(jnp.float32) * multiplier).astype(x.dtype)


class RMSNorm(nn.Module):
    """x / rms(x) * scale: the statistics and the product in float32."""

    eps: float = 1e-5
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True)
                                + self.eps)
        return (y * scale.astype(jnp.float32)).astype(self.dtype)


class LayerNorm(nn.Module):
    """(x - mean(x)) / sqrt(var(x) + eps) * scale + bias: the statistics and
    the products in float32 (`RMSNorm`'s signature, a family's other kind
    of norm)."""

    eps: float = 1e-5
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale, bias = (self.param(name, init, (x.shape[-1],), jnp.float32)
                       for name, init in (("scale", nn.initializers.ones),
                                          ("bias", nn.initializers.zeros)))
        x32 = x.astype(jnp.float32)
        centred = x32 - x32.mean(-1, keepdims=True)
        y = centred * jax.lax.rsqrt(
            (centred * centred).mean(-1, keepdims=True) + self.eps)
        return (y * scale + bias).astype(self.dtype)


class LatentAttention(nn.Module):
    """Causal multi-head latent attention (arXiv 2405.04434, section 2.1):
    queries of `qk_nope + qk_rope` channels a head; ONE down-projection of
    the input to a latent of `kv_lora_rank` channels and one rotary key of
    `qk_rope` channels for all heads; the latent, RMS-normed, projected up
    to every head's `qk_nope` key channels and `v_head` value channels;
    scores over sqrt(qk_nope + qk_rope).

    What goes in and comes out of the core (`attention.latent_attention`),
    each as its projection leaves it, (B, T, heads x width) in memory: a
    head's own query channels and its rotary ones from two products of
    `q_proj`'s columns (`HeadsDense`), the rotary ones rotated where they
    lie; `kv_b_proj`'s output whole, a head's key channels then its
    values; the one rotary key (B, T, qk_rope). On the "flash" tier at
    the published widths (128 + 64, 128) nothing is sliced, concatenated,
    broadcast or transposed before the kernel, and the output projection
    reads what it wrote; every other tier and shape builds q and k of
    qk_nope + qk_rope channels as before."""

    num_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float = 10000.0
    eps: float = 1e-5
    impl: str = "flash"
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, y):
        dt, heads, lat = self.dtype, self.num_heads, self.kv_lora_rank
        nope, rope, vd = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                          self.v_head_dim)
        d = y.shape[-1]
        with jax.named_scope("mla.project"):
            q_nope, q_rope = attention.HeadsDense(
                heads, nope + rope, use_bias=False, parts=(nope, rope),
                dtype=dt, name="q_proj")(y)
            q_rope = attention.rotary_positions(q_rope, self.rope_theta,
                                                self.impl)
            kv = nn.Dense(lat + rope, use_bias=False, dtype=dt,
                          name="kv_a_proj")(y)
            # the one rotary key is a slice of a plain product, no head a
            # `HeadsDense` wrote
            k_pe = attention.rotary_xla(kv[:, :, None, lat:],
                                        self.rope_theta)[:, :, 0]
            c = RMSNorm(self.eps, dt, name="kv_a_norm")(kv[..., :lat])
            kvb = attention.HeadsDense(heads, nope + vd, use_bias=False,
                                       dtype=dt, name="kv_b_proj")(c)
        # the innermost scope names the plain flash call in a device trace;
        # the latent forward of whole lanes names itself (`mla_attn_n<nope>
        # r<rope>`: lowered once a shape, so no layer's name is in it)
        with jax.named_scope("mla.attend"), jax.named_scope(self.name):
            o = attention.latent_attention(
                q_nope, q_rope, kvb, k_pe,
                attention.tier(self.impl)).astype(dt)
        with jax.named_scope("mla.project"):
            return nn.DenseGeneral(d, axis=(-2, -1), use_bias=False,
                                   dtype=dt, name="out")(o)


class GroupedQueryAttention(nn.Module):
    """Causal attention whose `num_heads` query heads share `num_kv_heads`
    key/value heads (query head j reads head j // group; K and V are never
    repeated: `nn/attention/`), scores over sqrt(head width). No biases.
    A head is `head_dim` channels wide (None: the input's width over
    `num_heads`; with a width of its own the projections need not be
    square: 28 heads of 128 on an input of 2560). `qk_norm`: an RMSNorm
    over the channels of every query and of every key head before the
    rotary positions (one scale vector for all query heads, one for all
    key heads). `rotary`: rotary positions over the whole head (False: the
    layer has no positional encoding). `window`: a query reads the keys
    `window - 1` behind it to its own and none further (None: every key at
    or before it). How the heads are projected, rotated and projected back
    is the package's rule by shape (`attention.head_projection`,
    `rotary_heads`, `out_projection`): heads of whole lanes stay where the
    kernel reads them in place. `key_multiplier`: a fixed scalar on the key
    projection's output (a family trained under fixed multipliers; 1 is no
    operation at all)."""

    num_heads: int
    num_kv_heads: int
    rope_theta: float = 10000.0
    eps: float = 1e-5
    impl: str = "flash"
    dtype: Any = jnp.float32
    head_dim: int | None = None
    qk_norm: bool = True
    rotary: bool = True
    window: int | None = None
    key_multiplier: float = 1.0

    @nn.compact
    def __call__(self, y):
        dt, d = self.dtype, y.shape[-1]
        if self.num_heads % self.num_kv_heads or (
                self.head_dim is None and d % self.num_heads):
            raise ValueError(
                f"{self.num_heads} query heads over {self.num_kv_heads} "
                f"key/value heads do not divide a width of {d}")
        width = self.head_dim or d // self.num_heads

        def heads(n, name):
            return attention.head_projection(n, width, dt, name)(y)

        def placed(x, name):
            if self.qk_norm:
                x = RMSNorm(self.eps, dt, name=name)(x)
            if self.rotary:
                x = attention.rotary_heads(x, self.rope_theta, self.impl)
            return x

        with jax.named_scope("gqa.project"):
            q = placed(heads(self.num_heads, "q_proj"), "q_norm")
            k = placed(_times(heads(self.num_kv_heads, "k_proj"),
                              self.key_multiplier), "k_norm")
            v = heads(self.num_kv_heads, "v_proj")
        # the innermost scope names the plain Pallas call in a device trace;
        # the banded forward names itself (`swa_attn_w<window>`: lowered
        # once a shape, so no layer's name is in it)
        with jax.named_scope("gqa.attend"), jax.named_scope(
                self.name or "gqa_attn"):
            o = attention.decoder_attention(q, k, v, self.impl, dt,
                                            band=self.window)
        with jax.named_scope("gqa.project"):
            return attention.out_projection(d, width, dt, "out")(o)


class EvaAttention(nn.Module):
    """Causal attention that reads its own window exactly and everything
    before it as one summary a chunk (EVA, arXiv 2302.04542, as EvaByte
    runs it): `num_heads` heads of d / num_heads channels, rotary over the
    whole head, no biases; two learned vectors a head, `phi` (the weights
    inside a chunk: softmax over its positions of k . phi / sqrt(width))
    and `mu` (added to the pooled key), float32. Query t in window
    w = t // window_size attends, in ONE softmax, to the keys of window w
    at or before it and to the summaries of every chunk of windows 0 ..
    w - 1 (`nn/attention/eva.py` `eva_summaries`, `eva_attention`). Heads of
    whole lanes (128 channels) on the "flash" tier are projected, rotated
    and attended to as (B, T, heads x width) arrays, nothing laid out
    again between the projections and the output projection."""

    num_heads: int
    window_size: int = 2048
    chunk_size: int = 16
    rope_theta: float = 100000.0
    impl: str = "flash"
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, y):
        dt, d = self.dtype, y.shape[-1]
        if d % self.num_heads:
            raise ValueError(f"{self.num_heads} heads do not divide a "
                             f"width of {d}")
        width = d // self.num_heads

        def heads(name):
            return attention.HeadsDense(self.num_heads, width, use_bias=False,
                                        dtype=dt, name=name)(y)

        def placed(name):
            return attention.rotary_positions(heads(name), self.rope_theta,
                                              self.impl)

        with jax.named_scope("eva.project"):
            q, k, v = placed("q_proj"), placed("k_proj"), heads("v_proj")
        phi, mu = (self.param(name, nn.initializers.normal(1.0),
                              (self.num_heads, width), jnp.float32)
                   for name in ("phi", "mu"))
        # `eva.summarise` and `eva.attend` are opened inside
        with jax.named_scope(self.name or "eva_attn"):
            o = attention.decoder_attention(
                q, k, v, self.impl, dt, (phi, mu), self.window_size,
                self.chunk_size)
        with jax.named_scope("eva.project"):
            return nn.DenseGeneral(d, axis=(-2, -1), use_bias=False,
                                   dtype=dt, name="out")(o)


def _causal_taps(z, kernel):
    """c[t] = sum_j kernel[:, j] z[t - (taps - 1) + j] a channel, z zero
    before a row's first token: a depthwise causal convolution as shifted
    multiply-adds (the last tap meets the newest token, as torch's Conv1d
    lays them). z (B, T, C), padded in its own type and read in float32;
    kernel (C, taps) float32."""
    taps, t = kernel.shape[1], z.shape[1]
    z = jnp.pad(z, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(kernel[:, j] * z[:, j:j + t].astype(jnp.float32)
               for j in range(taps))


class ShortConv(nn.Module):
    """The gated short convolution (LFM2, arXiv 2511.23404, section 2):
    [B, C, u] = y W_in; z = B * u; c[t] = sum_j w[:, j] z[t - (taps-1) + j]
    per channel, z zero before a row's first token (a depthwise causal
    convolution); out = (C * c) W_out. No activation, no bias, no state
    across rows. The taps are shifted multiply-adds that XLA fuses with
    both gates into one pass over the (T, 3 d) projection: the mix is
    bound by memory, float32 inside and rounded once."""

    taps: int = 3
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, y):
        dt, d = self.dtype, y.shape[-1]
        with jax.named_scope("conv.project"):
            gated = nn.Dense(3 * d, use_bias=False, dtype=dt,
                             name="in_proj")(y)
        kernel = self.param("conv_kernel",
                            nn.initializers.normal(self.taps ** -0.5),
                            (d, self.taps), jnp.float32)
        with jax.named_scope("conv.mix"):
            gate_in, gate_out, u = (
                gated[..., i * d:(i + 1) * d].astype(jnp.float32)
                for i in range(3))
            mixed = (gate_out * _causal_taps(gate_in * u, kernel)).astype(dt)
        with jax.named_scope("conv.project"):
            return nn.Dense(d, use_bias=False, dtype=dt,
                            name="out_proj")(mixed)


class StateSpaceMixer(nn.Module):
    """The Mamba-2 mixer (arXiv 2405.21060, as Falcon-H1 runs it): ONE
    input projection, no bias, to [z | x B C | dt]: a gate of `num_heads` x
    `head_dim` channels, the scan's input of as many with `n_groups` groups
    of `d_state` channels each for B and for C, and a step a head, each part
    under its own fixed scalar (`projection_multipliers`, over z, x, B, C,
    dt in that order); [x B C] through a depthwise causal convolution of
    `conv_taps` taps with a bias and a SiLU, zero before a row's first
    token; dt_j = softplus(dt_j + dt_bias_j), A_j = -exp(A_log_j); the
    selective scan (`nn/scan.py`: S_t = exp(dt_t A) S_{t-1} + dt_t x_t
    B_t^T, y_t = S_t C_t + D x_t, head j reading group j // (heads /
    groups), no state across rows); y * silu(z) through an RMSNorm in
    `n_groups` groups of channels with one weight a channel (the gate
    BEFORE the norm); the output projection, no bias.

    The convolution writes (B, T, channels) and the scan's kernel tier reads
    a head's x and a group's B and C out of that array in place; the step,
    the decays and the state are float32. `scan_name` is the kernel's own in
    a device trace."""

    num_heads: int
    head_dim: int
    n_groups: int
    d_state: int
    conv_taps: int = 4
    projection_multipliers: tuple = (1.0, 1.0, 1.0, 1.0, 1.0)
    eps: float = 1e-5
    dtype: Any = jnp.float32
    scan_name: str = "ssd_scan"

    @nn.compact
    def __call__(self, y):
        f32, dt_, d = jnp.float32, self.dtype, y.shape[-1]
        heads, groups, state = self.num_heads, self.n_groups, self.d_state
        inner, bc = heads * self.head_dim, groups * state
        mixed = inner + 2 * bc
        if heads % groups or inner % groups:
            raise ValueError(f"{groups} groups do not divide {heads} heads "
                             f"of {self.head_dim} channels")
        m_z, m_x, m_b, m_c, m_dt = self.projection_multipliers
        with jax.named_scope("ssm.project"):
            p = nn.Dense(inner + mixed + heads, use_bias=False, dtype=dt_,
                         name="in_proj")(y)

        def vector(name, init, n):
            return self.param(name, init, (n,), jnp.float32)

        taps = self.param("conv_kernel",
                          nn.initializers.normal(self.conv_taps ** -0.5),
                          (mixed, self.conv_taps), jnp.float32)
        conv_bias = vector("conv_bias", nn.initializers.zeros, mixed)
        dt_bias = vector("dt_bias", nn.initializers.zeros, heads)
        a_log = vector("A_log", nn.initializers.zeros, heads)
        skip = vector("D", nn.initializers.ones, heads)
        gate_scale = vector("norm_scale", nn.initializers.ones, inner)
        with jax.named_scope("ssm.conv"):
            # each part under its multiplier, a channel's in one vector; a
            # channel's scalar commutes with its own taps, so it scales
            # them: conv(m x) = (m taps) x, and the projection's slice is
            # read once, as it lies
            by_channel = jnp.concatenate([
                jnp.full((inner,), m_x, f32), jnp.full((bc,), m_b, f32),
                jnp.full((bc,), m_c, f32)])
            xbc = nn.silu(_causal_taps(
                p[..., inner:inner + mixed], taps * by_channel[:, None])
                + conv_bias).astype(dt_)
        with jax.named_scope("ssm.scan"):
            step = jax.nn.softplus(
                p[..., inner + mixed:].astype(f32) * m_dt + dt_bias)
            scanned = scan.selective_scan(
                xbc, step, -jnp.exp(a_log), skip, heads=heads,
                width=self.head_dim, groups=groups, state=state,
                name=self.scan_name)
        with jax.named_scope("ssm.gate"):
            gated = scanned.astype(f32) * nn.silu(
                p[..., :inner].astype(f32) * m_z)
            grouped = gated.reshape(*gated.shape[:-1], groups,
                                    inner // groups)
            normed = grouped * jax.lax.rsqrt(
                (grouped * grouped).mean(-1, keepdims=True) + self.eps)
            normed = (normed.reshape(gated.shape) * gate_scale).astype(dt_)
        with jax.named_scope("ssm.project"):
            return nn.Dense(d, use_bias=False, dtype=dt_,
                            name="out_proj")(normed)


class SelectiveMixer(nn.Module):
    """The Mamba-1 mixer (arXiv 2312.00752): [x | z] = y W_in, `inner`
    channels each, no bias; x through a depthwise causal convolution of
    `conv_taps` taps with a bias and a SiLU, zero before a row's first
    token; [r | B | C] = x W_x, `dt_rank` + 2 x `d_state` channels, no
    bias; dt = softplus(r W_dt + dt_bias) a CHANNEL, float32; A =
    -exp(A_log), (inner, d_state); the selective scan whose decay is a
    (channel, state) pair (`nn/scan.py`'s second form: S_t[c, n] =
    exp(dt_t[c] A[c, n]) S_{t-1}[c, n] + dt_t[c] B_t[n] x_t[c], y_t[c] =
    sum_n C_t[n] S_t[c, n] + D[c] x_t[c], B and C shared by all channels,
    no state across rows); (y * silu(z)) W_out, no bias. -> (the output,
    y): the scan's output with the skip, BEFORE the gate and the output
    projection, is what a layer may keep for a later layer's
    `GatedMemoryUnit`. `scan_name` is the kernel's own in a device trace."""

    inner: int
    dt_rank: int
    d_state: int = 16
    conv_taps: int = 4
    dtype: Any = jnp.float32
    scan_name: str = "sel_scan"

    @nn.compact
    def __call__(self, y):
        f32, dt_, d = jnp.float32, self.dtype, y.shape[-1]
        inner, rank, state = self.inner, self.dt_rank, self.d_state
        dense = functools.partial(nn.Dense, use_bias=False, dtype=dt_)
        with jax.named_scope("selscan.project"):
            p = dense(2 * inner, name="in_proj")(y)
        taps = self.param("conv_kernel",
                          nn.initializers.normal(self.conv_taps ** -0.5),
                          (inner, self.conv_taps), f32)
        conv_bias = self.param("conv_bias", nn.initializers.zeros, (inner,),
                               f32)
        dt_kernel = self.param("dt_kernel",
                               nn.initializers.normal(rank ** -0.5),
                               (rank, inner), f32)
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (inner,), f32)
        a_log = self.param("A_log", nn.initializers.zeros, (inner, state),
                           f32)
        skip = self.param("D", nn.initializers.ones, (inner,), f32)
        with jax.named_scope("selscan.conv"):
            x = nn.silu(_causal_taps(p[..., :inner], taps)
                        + conv_bias).astype(dt_)
        with jax.named_scope("selscan.project"):
            rbc = dense(rank + 2 * state, name="x_proj")(x)
            step = jax.nn.softplus(jnp.dot(
                rbc[..., :rank], dt_kernel.astype(dt_),
                preferred_element_type=f32) + dt_bias)
        with jax.named_scope("selscan.scan"):
            scanned = scan.channel_scan(
                x, step, -jnp.exp(a_log), rbc[..., rank:rank + state],
                rbc[..., rank + state:], skip, name=self.scan_name)
        with jax.named_scope("selscan.gate"):
            gated = (scanned.astype(f32)
                     * nn.silu(p[..., inner:].astype(f32))).astype(dt_)
        with jax.named_scope("selscan.project"):
            return dense(d, name="out_proj")(gated), scanned


class GatedMemoryUnit(nn.Module):
    """(silu(y W_1) * memory) W_2, no bias: a projection of the layer's
    input gates ANOTHER layer's array at the same row and position
    (`memory`, (B, T, inner): the scan output a `SelectiveMixer` kept; the
    gated memory unit of arXiv 2507.06607). The gate is float32, rounded
    once."""

    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, y, memory):
        dense = functools.partial(nn.Dense, use_bias=False, dtype=self.dtype)
        gate = dense(memory.shape[-1], name="in_proj")(y)
        with jax.named_scope("gmu.gate"):
            gated = (nn.silu(gate.astype(jnp.float32))
                     * memory.astype(jnp.float32)).astype(self.dtype)
        return dense(y.shape[-1], name="out_proj")(gated)


class DifferentialAttention(nn.Module):
    """Causal differential attention (arXiv 2410.05258; the core and its
    layout are `nn/attention/diff.py`'s): `num_heads` query heads over
    `num_kv_heads` key/value heads of d / num_heads channels, heads 2j and
    2j + 1 a pair; a pair's two softmaxes over one value twice a head wide,
    o_j = (P1 - lambda P2) v, lambda = exp(lq1 . lk1) - exp(lq2 . lk2) +
    lambda_init with four learned vectors a layer and lambda_init = 0.8 -
    0.6 exp(-0.3 `depth`); o_j through an RMSNorm a pair (ONE weight of
    twice a head's channels a layer) and (1 - lambda_init); the output
    projection. Biases on the projections; no positional encoding.
    `window`: a query reads the `window` keys that end with its own (None:
    every key at or before it). `cross`: the layer owns a query and an
    output projection, its lambda vectors and its norm's weight, and NO key
    or value projection: it reads the `keys` another layer made. -> (the
    output, the keys and values as this layer read them: `diff.key_pairs`'
    layout, which a layer may keep for such layers). The softmaxes, lambda
    and the norm's statistics are float32."""

    num_heads: int
    num_kv_heads: int
    depth: int = 0
    window: int | None = None
    cross: bool = False
    eps: float = 1e-5
    impl: str = "flash"
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, y, keys=None):
        f32, dt, d = jnp.float32, self.dtype, y.shape[-1]
        if d % self.num_heads or self.num_heads % self.num_kv_heads or (
                self.num_kv_heads % 2):
            raise ValueError(
                f"{self.num_heads} query heads over {self.num_kv_heads} "
                f"key/value heads are no pairs that divide a width of {d}")
        width = d // self.num_heads
        dense = functools.partial(nn.Dense, dtype=dt)
        with jax.named_scope("diff.project"):
            q = dense(d, name="q_proj")(y)
            if not self.cross:
                wide = self.num_kv_heads * width
                keys = attention.key_pairs(dense(wide, name="k_proj")(y),
                                           dense(wide, name="v_proj")(y),
                                           self.num_kv_heads)
        lq1, lk1, lq2, lk2 = (
            self.param(name, nn.initializers.normal(0.1), (width,), f32)
            for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"))
        scale = self.param("norm_scale", nn.initializers.ones, (2 * width,),
                           f32)
        first = attention.diff.lambda_init(self.depth)
        lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + first
        with jax.named_scope("diff.attend"):
            o = attention.differential_attention(
                q, keys, lam, attention.tier(self.impl), self.window,
                self.name or "diff_attn")
        with jax.named_scope("diff.norm"):
            o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + self.eps)
            o = (o * (scale * (1.0 - first))).astype(dt)
        with jax.named_scope("diff.project"):
            return dense(d, name="out")(o.reshape(*o.shape[:2], d)), keys


class GatedFFN(nn.Module):
    """down(silu(gate y) * up y), no biases. `multipliers` (gate, down):
    fixed scalars on the gate's pre-activation and on the output, m_down *
    down(silu(m_gate * gate y) * up y); (1, 1) is no operation at all."""

    width: int
    dtype: Any = jnp.float32
    multipliers: tuple = (1.0, 1.0)

    @nn.compact
    def __call__(self, y):
        dense = functools.partial(nn.Dense, use_bias=False, dtype=self.dtype)
        m_gate, m_down = self.multipliers
        gate = _times(dense(self.width, name="gate")(y).astype(jnp.float32),
                      m_gate)
        hidden = nn.silu(gate).astype(self.dtype) * dense(
            self.width, name="up")(y)
        return _times(dense(y.shape[-1], name="down")(hidden), m_down)


class Router(nn.Module):
    """A router apart from its experts: scores over all
    `n_routed_experts` from the input it is GIVEN, which need not be the
    experts' (a model that routes before its attention hands it the
    attention's normed input, so that an expert's weights can be fetched
    while the attention computes). No selection bias, no scaling factor,
    the weights over the picks' own sum. -> (picked (T, k) int32, weights
    (T, k) float32) of `parallel.moe.route_top_k`, which an `ExpertLayer`
    takes as `routed`."""

    n_routed_experts: int
    top_k: int
    scoring: str

    @nn.compact
    def __call__(self, a):
        from ..parallel.moe import route_top_k

        d = a.shape[-1]
        kernel = self.param("kernel", nn.initializers.normal(d ** -0.5),
                            (d, self.n_routed_experts), jnp.float32)
        return route_top_k(a.reshape(-1, d), kernel, None, self.top_k,
                           scoring=self.scoring)


class ExpertLayer(nn.Module):
    """Routed experts (the `experts_held` of `n_routed_experts`, top-k,
    dropless: `parallel.moe.moe_ffn_dropless`) plus the shared feed-forward
    every token takes, where the model has one (`n_shared_experts` 0
    builds none). The router reads the layer's own input, scored by
    `scoring`, unless the picks come with the call (`routed`, a `Router`'s
    output: the layer then holds no router of its own). `activation` is the
    gate's in the routed experts. -> (output, picks (held,) int32)."""

    n_routed_experts: int
    experts_held: tuple
    top_k: int
    width: int
    n_shared_experts: int = 1
    scaling: float = 1.0
    normalise: bool = True
    dtype: Any = jnp.float32
    epsilon: float = 1e-20          # added to the sum the weights are over
    scoring: str = "sigmoid"
    activation: str = "silu"

    @nn.compact
    def __call__(self, y, routed=None):
        from ..parallel.moe import moe_ffn_dropless

        d, w, held = y.shape[-1], self.width, int(self.experts_held[1])

        def kernel(name, shape, fan_in):
            return self.param(name, nn.initializers.normal(fan_in ** -0.5),
                              shape, jnp.float32)

        flat = y.reshape(-1, d)
        router = bias = None
        if routed is None:
            router = kernel("router_kernel", (d, self.n_routed_experts), d)
            # the selection bias (a checkpoint's `e_score_correction_bias`)
            bias = self.param("router_bias", nn.initializers.zeros,
                              (self.n_routed_experts,), jnp.float32)
        out, picks = moe_ffn_dropless(
            flat, router, bias,
            kernel("experts_gate", (held, d, w), d),
            kernel("experts_up", (held, d, w), d),
            kernel("experts_down", (held, w, d), w),
            n_routed_experts=self.n_routed_experts,
            experts_held=tuple(self.experts_held), top_k=self.top_k,
            scaling=self.scaling, normalise=self.normalise,
            epsilon=self.epsilon, dtype=self.dtype, scoring=self.scoring,
            activation=self.activation, routed=routed)
        if self.n_shared_experts:
            if self.activation != "silu":
                raise ValueError("the shared feed-forward is gated by silu; "
                                 f"the experts by {self.activation!r}")
            with jax.named_scope("moe.shared"):
                out = out + GatedFFN(self.n_shared_experts * w, self.dtype,
                                     name="shared")(flat)
        return out.reshape(y.shape), picks


class ExitGate(nn.Module):
    """sigmoid(h . w + b), one number a token: a step's chance of being a
    token's last, given that the token reached it. The product takes
    `dtype` inputs; the sum, the bias and the sigmoid are float32."""

    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h):
        d = h.shape[-1]
        kernel = self.param("kernel", nn.initializers.normal(d ** -0.5),
                            (d, 1), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (1,), jnp.float32)
        logit = jnp.dot(h, kernel.astype(self.dtype),
                        preferred_element_type=jnp.float32)[..., 0]
        return jax.nn.sigmoid(logit + bias.astype(jnp.float32))


class _ScoringDecoder(nn.Module):
    """What the decoder families share, written once: the block loop over
    token ids (h = x + Op(RMSNorm(x)), out = h + FF(RMSNorm(h)); FF a
    gated feed-forward in the leading dense layers, then an `ExpertLayer`
    in the layers that are left, if any), the final RMSNorm, the chunked
    log-likelihood head and what is sown per batch. A family states its
    sizes as attributes under the names used here and gives
    `_dense_layers`, the layers that lead with a dense feed-forward (all
    of them in a family without experts), and `_operator(i)`, layer i's
    mixer with the name of the norm before it.

    Scoring output: the module returns, and sows as `token_logprobs`, each
    next token's log-probability, (rows, length - 1): the head (`loglik`)
    folds tiles of `head_chunk` tokens against the vocabulary and keeps the
    target's log-probability alone, in ONE kernel or a chunk at a time, so
    the (rows x length x vocabulary) logits never exist. `output="logits"`
    returns them instead (short rows, tests). A family whose head makes
    several predictions a position states `num_pred_heads`: the head's
    kernel is (d, num_pred_heads x vocab_size), prediction p in columns
    p x vocab_size onwards; the scoring output reads prediction 0, the
    next token, and "logits" returns all, (rows, length, num_pred_heads,
    vocab_size). Predictions past the next token are held and never read.

    A chip may hold a share of the model. `experts_held` (first index,
    count) says which routed experts' weights this module has; routing is
    always over all `n_routed_experts` and the layer adds up what its own
    experts give (no capacity, no token ever dropped). `vocab_size` is the
    rows of the embedding and of the head held. Per batch the module also
    sows `moe_picks`, int32 (expert layers, experts held): the picks each
    held expert received (`batch_counters` names it for the runner, which
    reads it back with the batch and hands a call's counters to
    `call_span_arguments`).

    A family trained under fixed scalars states them
    (`embedding_multiplier` on the embedding's rows, `mlp_multipliers` on a
    dense feed-forward's gate and output, `lm_head_multiplier` on the logits);
    at 1 none of them is an operation.

    Three seats that a family may fill and most leave empty.
    `total_ut_steps` T > 1 runs the whole stack, the final norm included,
    T times over the SAME parameters (h_t = RMSNorm_final(stack(h_{t-1}));
    the next step reads h_t and so does the head): the steps are one
    `nn.scan` with the parameters broadcast, so the lowered program holds
    every layer once whatever T is, and nothing is sown inside it.
    `sandwich_norms` puts an RMSNorm of its own AFTER each operator and
    each feed-forward, before the residual add (`<norm before>_post_<i>`
    in the tree). `exit_gate` reads every step's h_t through an `ExitGate`
    (lambda_t, the chance of leaving at step t having reached it); the
    module then sows `exit_pdf`, float32 (rows, length, T): p_t =
    lambda_t prod_{j<t} (1 - lambda_j), the last step taking what is left;
    the head reads each token's state at its exit step, the first t whose
    running sum of p reaches `early_exit_threshold` (T where that is 1 or
    more, as published; no step's compute is skipped either way), and
    `loop_exit_at`, int32 (T,), counts the batch's tokens by that step.

    Two more. `norm_kind` "layer" makes every norm of the skeleton, the
    final one included, a `LayerNorm` with a weight and a bias.
    `layers_share`: a layer may KEEP arrays for later layers and a later
    layer's operator may READ them: `_stack` carries a dict of what was
    kept, an operator is called `operator(a, kept)` and returns (its
    output, {name: array} to keep), and nothing is copied a layer.

    Precision: products take `dtype` inputs and accumulate in float32; the
    router's scores, the top-k, every softmax, sigmoid and log-sum-exp,
    the exit distribution and every norm's statistics are float32."""

    # what a family may state as an attribute of its own
    route_epsilon = 1e-20           # added to the sum of a token's weights
    tie_embeddings = False          # the head is the embedding, transposed
    num_pred_heads = 1              # predictions a position the head makes
    router_scoring = "sigmoid"      # or "softmax" (`route_top_k`)
    expert_activation = "silu"      # the gate's in the routed experts
    # what a layer's router reads: "experts" (the experts' own input) or
    # "operator" (the operator's normed input: the picks are made before
    # the attention runs, by a `Router` named `router_<i>`)
    router_input = "experts"
    # a family trained under fixed scalars states them; 1 is no operation
    embedding_multiplier = 1.0      # on the embedding's rows
    lm_head_multiplier = 1.0        # on the logits
    mlp_multipliers = (1.0, 1.0)    # a dense feed-forward's (gate, down)
    total_ut_steps = 1              # passes of the stack, one set of weights
    sandwich_norms = False          # an RMSNorm after each operator as well
    exit_gate = False               # an exit distribution over the steps
    early_exit_threshold = 1.0      # running sum of it a token leaves at
    # every norm's kind: "rms" (`RMSNorm`, eps `rms_norm_eps`) or "layer"
    # (`LayerNorm`, a weight and a bias, eps `layer_norm_eps`)
    norm_kind = "rms"
    # layers keep arrays for later layers: an operator is then called with
    # what was kept so far and returns (its output, what it keeps)
    layers_share = False

    @property
    def batch_counters(self) -> tuple:
        """int32 arrays sown per batch that the runner reads back beside
        the fetched outputs (`nn/runner.py`) and hands, stacked over a
        call's batches, to `call_span_arguments`."""
        return (("moe_picks",) if self.num_layers > self._dense_layers
                else ()) + (("loop_exit_at",) if self.exit_gate else ())

    def call_span_arguments(self, counted: dict, scored: list,
                            row_shape: tuple) -> dict:
        """What a call reports about itself -> the arguments of its root
        span (`runner.transform`); the registry's counters are added to
        here. `counted`: `batch_counters`' arrays by name, each stacked over
        the call's batches; `scored`: the rows each batch scored, padding
        included (the device computed them); `row_shape`: a row's shape. A
        counter's arrays are told apart by NAME, and a name nothing here
        knows is left."""
        arguments = {}
        if "moe_picks" in counted:
            per_row = int(np.prod(row_shape))
            arguments.update(self._expert_load(
                counted["moe_picks"], [rows * per_row for rows in scored]))
        if "loop_exit_at" in counted:
            arguments.update(self._loop_passes(counted["loop_exit_at"]))
        return arguments

    def _loop_passes(self, exit_at: np.ndarray) -> dict:
        """`exit_at`: int (batches, steps), each batch's tokens (padding
        rows' included) by the step of the looped stack they leave at. ->
        the span's arguments: that, with the steps and the layer passes the
        call ran (every batch passes every layer once a step), which the
        registry counts too."""
        steps = int(exit_at.shape[1])
        passes = len(exit_at) * int(self.num_layers) * steps
        get_registry().counter(
            "mmlspark_tpu_loop_layer_passes_total",
            "layer passes of a looped stack: batches x layers x steps",
        ).inc(float(passes))
        return dict(loop_steps=steps, loop_layer_passes=passes,
                    loop_exit_at=[int(n) for n in exit_at.sum(axis=0)])

    def _expert_load(self, picks: np.ndarray, tokens: list) -> dict:
        """`picks`: int (batches, expert layers, experts held), the picks
        each held expert received in each batch, padding rows' included;
        `tokens`: each batch's tokens. Added to the registry's counters by
        layer -> the span's arguments, with the (layer, batch) pairs whose
        picks outgrew the expert layer's dispatch buffer
        (`moe_ffn_dropless` then runs the whole T x k)."""
        from ..parallel.moe import dropless_buffer_rows

        k, held = self.num_experts_per_tok, picks.shape[2]
        whole = np.asarray(tokens) * k
        buffer = np.asarray([dropless_buffer_rows(
            t, k, held, self.n_routed_experts) for t in tokens])
        outgrown = ((buffer < whole) & (picks.sum(axis=2).T >= buffer)).sum(
            axis=1)                                        # by layer
        registry = get_registry()
        held_total = registry.counter(
            "mmlspark_tpu_moe_picks_held_total",
            "picks routed to the experts this module holds, by expert layer",
            labels=("layer",))
        whole_total = registry.counter(
            "mmlspark_tpu_moe_whole_buffer_total",
            "batches whose picks outgrew the dispatch buffer, by expert layer",
            labels=("layer",))
        call = picks.sum(axis=0)                           # (layers, held)
        for layer, here in enumerate(call.sum(axis=1)):
            held_total.labels(layer=layer).inc(float(here))
            whole_total.labels(layer=layer).inc(float(outgrown[layer]))
        return dict(
            moe_picks=int(whole.sum() * picks.shape[1]),
            moe_picks_held=int(call.sum()),
            moe_whole_buffer=int(outgrown.sum()),
            # the busiest held expert of a layer over that layer's mean,
            # the largest over the layers
            moe_load_max_over_mean=float(
                (call.max(axis=1) / np.maximum(call.mean(axis=1), 1e-30))
                .max()))

    def _logits(self, h, head):
        """h @ head in float32, under the family's scalar on the logits."""
        return _times(jnp.dot(h, head, preferred_element_type=jnp.float32),
                      self.lm_head_multiplier)

    def _token_logprobs(self, h, ids, head, embedding=None):
        """log_softmax(h @ head)[next token] for every position but a
        row's last: `loglik.token_logprobs`, ONE Pallas call over all the
        batch's tokens where its rule takes the shapes, else `head_chunk`
        tokens at a time. `embedding`: (V, d), where the head is tied to
        it."""
        b, t, d = h.shape
        # the last position of a row scores a target that is cut off below
        target = jnp.concatenate([ids[:, 1:], ids[:, :1]], 1).reshape(b * t)
        out = loglik.token_logprobs(
            h.reshape(b * t, d), target, head, embedding=embedding,
            multiplier=self.lm_head_multiplier, chunk=self.head_chunk)
        return out.reshape(b, t)[:, :t - 1]

    def _stack(self, h, norm):
        """One pass through the layers -> (h, [an expert layer's picks])."""
        dt = self.dtype
        picks = []
        shared = {}         # what layers kept for later ones, by name
        early = self.router_input == "operator"
        for i in range(self.num_layers):
            before, operator = self._operator(i)
            a = norm(name=before)(h)
            routed = None
            if early and i >= self._dense_layers:
                routed = Router(
                    self.n_routed_experts, self.num_experts_per_tok,
                    self.router_scoring, name=f"router_{i}")(a)
            if self.layers_share:
                out, keeps = operator(a, shared)
                shared = {**shared, **keeps}
            else:
                out = operator(a)
            if self.sandwich_norms:
                out = norm(name=f"{before[:before.rindex('_')]}_post_{i}")(
                    out)
            h = h + out
            y = norm(name=f"ln_mlp_{i}")(h)
            if i < self._dense_layers:
                out = GatedFFN(self.d_ff_dense, dt,
                               tuple(self.mlp_multipliers),
                               name=f"mlp_{i}")(y)
            else:
                out, n = ExpertLayer(
                    self.n_routed_experts, tuple(self.experts_held),
                    self.num_experts_per_tok, self.d_ff_expert,
                    self.n_shared_experts, self.routed_scaling_factor,
                    self.norm_topk_prob, dt, self.route_epsilon,
                    self.router_scoring, self.expert_activation,
                    name=f"moe_{i}")(y, routed)
                picks.append(n)
            if self.sandwich_norms:
                out = norm(name=f"ln_mlp_post_{i}")(out)
            h = h + out
        return h, picks

    def _leave(self, h, leave, states):
        """The exit distribution from every step's lambda_t (`leave`,
        (T, rows, length) float32), sown as `exit_pdf` with the batch's
        `loop_exit_at` -> the state the head reads: each token's at its
        exit step (`states`, (T, rows, length, d)), or `h`, the last
        step's, where the threshold is 1 or more."""
        steps = leave.shape[0]
        # prod_{j<t} (1 - lambda_j): the chance of reaching step t
        reach = jnp.concatenate([jnp.ones_like(leave[:1]),
                                 jnp.cumprod(1.0 - leave, 0)[:-1]])
        pdf = jnp.moveaxis(jnp.concatenate(
            [(leave * reach)[:-1], reach[-1:]]), 0, -1)
        self.sow("intermediates", "exit_pdf", pdf)
        if states is None:
            at = jnp.full(pdf.shape[:-1], steps - 1, jnp.int32)
        else:
            reached = (jnp.cumsum(pdf, -1) >= self.early_exit_threshold
                       ).at[..., -1].set(True)
            at = jnp.argmax(reached, -1).astype(jnp.int32)
            h = jnp.take_along_axis(states, at[None, ..., None], 0)[0]
        self.sow("intermediates", "loop_exit_at", (
            at[..., None] == jnp.arange(steps)).sum((0, 1), dtype=jnp.int32))
        return h

    @nn.compact
    def __call__(self, x, train: bool = False):
        """The forward of every family."""
        ids = x.astype(jnp.int32)
        if ids.ndim != 2:
            raise ValueError("the decoder takes (rows, length) token ids, "
                             f"got {ids.shape}")
        if ids.shape[1] > self.max_len:
            raise ValueError(
                f"sequence length {ids.shape[1]} exceeds max_len="
                f"{self.max_len}; raise max_len in the model config")
        dt, d = self.dtype, self.d_model
        norm = (functools.partial(LayerNorm, self.layer_norm_eps, dt)
                if self.norm_kind == "layer"
                else functools.partial(RMSNorm, self.rms_norm_eps, dt))
        embed = nn.Embed(self.vocab_size, d, dtype=dt,
                         embedding_init=nn.initializers.normal(1.0),
                         name="embed")
        h = _times(embed(ids), self.embedding_multiplier)
        steps = int(self.total_ut_steps)
        # a token may leave before the last step: every step's state is kept
        select = self.exit_gate and self.early_exit_threshold < 1

        def step(mdl, h, _):
            """One pass of the stack and the final norm -> (h_t, what the
            steps stack: the picks, lambda_t and, under `select`, h_t)."""
            h, picks = mdl._stack(h, norm)
            h = norm(name="ln_final")(h)
            picks = jnp.stack(picks) if picks else None
            leave = (ExitGate(dt, name="exit_gate")(h) if mdl.exit_gate
                     else None)
            return h, (picks, leave, h if select else None)

        if steps == 1:
            h, (picks, leave, states) = step(self, h, None)
            # as a scan of one step would stack them
            leave, states = (a if a is None else a[None]
                             for a in (leave, states))
        else:
            # ONE traced and lowered body for all steps, its parameters the
            # module's own tree; nothing inside it is sown. flax traces the
            # body a second time to find what it gives that no step changes,
            # which only `init` needs (the parameters are made in there)
            h, (picks, leave, states) = nn.scan(
                step, variable_broadcast="params",
                split_rngs={"params": False}, length=steps,
                check_constancy_invariants=self.is_initializing())(
                    self, h, None)
            picks = picks if picks is None else picks.sum(0)
        if self.exit_gate:
            with jax.named_scope("loop.exit"):
                h = self._leave(h, leave, states)
        self.sow("intermediates", "hidden", h)
        if picks is not None:
            self.sow("intermediates", "moe_picks", picks)
        embedding = None
        if self.tie_embeddings:
            # the kernel reads it where the parameters keep it, (V, d);
            # only XLA's path and the logits read the transpose
            embedding = embed.embedding.astype(dt)
            head = embedding.T
        else:
            head = self.param(
                "head_kernel", nn.initializers.normal(d ** -0.5),
                (d, self.num_pred_heads * self.vocab_size),
                jnp.float32).astype(dt)
        with jax.named_scope("loglik.head"):
            # the next token is prediction 0's columns
            logprobs = self._token_logprobs(
                h, ids, head[:, :self.vocab_size]
                if self.num_pred_heads > 1 else head, embedding)
            self.sow("intermediates", "token_logprobs", logprobs)
            if self.output == "logits":
                logits = self._logits(h, head)
                return logits if self.num_pred_heads == 1 else logits.reshape(
                    *logits.shape[:2], self.num_pred_heads, self.vocab_size)
        if self.output != "token_logprobs":
            raise ValueError(f"unknown output {self.output!r}")
        return logprobs


class MLAMoEDecoder(_ScoringDecoder):
    """Causal decoder over token ids: latent attention, gated
    feed-forwards (dense in the leading layers, then routed experts with a
    shared one), RMSNorm, rotary positions on part of a head, an untied
    head (the DeepSeek-V2/V3 block: arXiv 2405.04434 section 2.1, arXiv
    2412.19437 section 2.1.2). The block loop, the head, the outputs and
    the share of a model a chip may hold are `_ScoringDecoder`'s."""

    num_layers: int = 2
    d_model: int = 64
    num_heads: int = 4
    kv_lora_rank: int = 32
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    d_ff_dense: int = 128
    first_k_dense: int = 1
    n_routed_experts: int = 8
    experts_held: tuple = (0, 8)    # (first index, count)
    num_experts_per_tok: int = 3
    d_ff_expert: int = 32
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    vocab_size: int = 256
    max_len: int = 8192
    # "flash": the Pallas kernel (chunked off-TPU), "chunked", "dense"
    attention_impl: str = "flash"
    head_chunk: int = 1024          # tokens of one block of the head
    output: str = "token_logprobs"  # or "logits"
    dtype: Any = jnp.float32

    @property
    def _dense_layers(self) -> int:
        return self.first_k_dense

    def _operator(self, i: int):
        return f"ln_attn_{i}", LatentAttention(
            self.num_heads, self.kv_lora_rank, self.qk_nope_head_dim,
            self.qk_rope_head_dim, self.v_head_dim, self.rope_theta,
            self.rms_norm_eps, self.attention_impl, self.dtype,
            name=f"mla_attn_{i}")


class HybridMoEDecoder(_ScoringDecoder):
    """Causal decoder over token ids whose layers are told apart by a
    list (the LFM2 block, arXiv 2511.23404): `layer_types[i]` is "conv"
    (`ShortConv`, a gated depthwise convolution of `conv_taps` taps) or
    "full_attention" (`GroupedQueryAttention`, `num_heads` query heads over
    `num_kv_heads` key/value heads, an RMSNorm on every query and key head,
    rotary over the whole head); `num_dense_layers` leading gated
    feed-forwards, then routed experts with no shared one and 1e-6 under
    the weights' sum; a head tied to the embedding. The block loop, the
    head, the outputs and the share of a model a chip may hold are
    `_ScoringDecoder`'s."""

    layer_types: tuple = ("conv", "full_attention")
    d_model: int = 64
    num_heads: int = 4
    num_kv_heads: int = 2
    conv_taps: int = 3
    d_ff_dense: int = 128
    num_dense_layers: int = 1
    n_routed_experts: int = 8
    experts_held: tuple = (0, 8)    # (first index, count)
    num_experts_per_tok: int = 2
    d_ff_expert: int = 32
    n_shared_experts: int = 0
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    route_epsilon: float = 1e-6
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1000000.0
    vocab_size: int = 256
    tie_embeddings: bool = True
    max_len: int = 16384
    # "flash": the Pallas kernel (chunked off-TPU), "chunked", "dense"
    attention_impl: str = "flash"
    head_chunk: int = 1024          # tokens of one block of the head
    output: str = "token_logprobs"  # or "logits"
    dtype: Any = jnp.float32

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def _dense_layers(self) -> int:
        return self.num_dense_layers

    def _operator(self, i: int):
        kind = self.layer_types[i]
        if kind == "full_attention":
            operator = GroupedQueryAttention(
                self.num_heads, self.num_kv_heads, self.rope_theta,
                self.rms_norm_eps, self.attention_impl, self.dtype,
                name=f"gqa_attn_{i}")
        elif kind == "conv":
            operator = ShortConv(self.conv_taps, self.dtype,
                                 name=f"conv_{i}")
        else:
            raise ValueError(f"unknown layer type {kind!r} at layer {i}: "
                             "'conv' or 'full_attention'")
        return f"ln_op_{i}", operator


class EvaDecoder(_ScoringDecoder):
    """Causal decoder over bytes (EvaByte): every layer `EvaAttention`
    (its own window of `window_size` exactly, the windows before it a
    summary a chunk of `chunk_size`) and a gated feed-forward, no expert
    layer, so nothing is sown per batch for the runner to read back; an
    untied head of `num_pred_heads` predictions a position, of which the
    scoring output reads the next byte's. RMSNorm scales are stored as
    they multiply (a checkpoint's `norm_add_unit_offset` is met at import).
    The block loop, the head and the outputs are `_ScoringDecoder`'s."""

    num_layers: int = 2
    d_model: int = 64
    num_heads: int = 4
    window_size: int = 2048
    chunk_size: int = 16
    d_ff_dense: int = 128
    rms_norm_eps: float = 1e-5
    rope_theta: float = 100000.0
    vocab_size: int = 320
    num_pred_heads: int = 8
    max_len: int = 32768
    # "flash": the Pallas kernel (chunked off-TPU), "chunked", "dense"
    attention_impl: str = "flash"
    head_chunk: int = 1024          # tokens of one block of the head
    output: str = "token_logprobs"  # or "logits"
    dtype: Any = jnp.float32

    @property
    def _dense_layers(self) -> int:
        return self.num_layers

    def _operator(self, i: int):
        return f"ln_attn_{i}", EvaAttention(
            self.num_heads, self.window_size, self.chunk_size,
            self.rope_theta, self.attention_impl, self.dtype,
            name=f"eva_attn_{i}")


def _band_tile_pairs(impl: str, dtype, window: int, length: int, calls: int):
    """-> (computed, needed): the (query block, key block) tiles the banded
    forward computes for `calls` forwards (rows x heads x banded layers) of
    `length` positions behind a band of `window` keys, in tiles and
    FRACTIONS of one (floats: an edge tile folded in parts counts the part
    of it that is computed, `attention.band_tile_pairs`), and the pairs the
    band itself holds in tiles of that size; from shapes alone. None where
    no banded kernel runs: off the "flash" tier, or a row no longer than the
    window."""
    if attention.tier(impl) != "flash" or length <= window:
        return None
    computed, needed = attention.band_tile_pairs(
        length, window, *attention.band_tiles(length, window, dtype))
    return computed * calls, needed * calls


def _band_tile_arguments(module, scored: list, row_shape: tuple) -> dict:
    """What a family with banded layers adds to a call's root span: what the
    banded kernel computed over the call and what the band needed
    (`module.window_tile_pairs`, summed over the batches). Only a tracer
    that keeps spans has a reader for it, only rows of token ids have a
    length, and only where that kernel ran."""
    if not get_tracer().enabled or len(row_shape) != 1:
        return {}
    pairs = [p for p in (module.window_tile_pairs(rows, row_shape[0])
                         for rows in scored) if p]
    if not pairs:
        return {}
    return dict(
        attn_window_tile_pairs=float(sum(p[0] for p in pairs)),
        attn_window_tile_pairs_needed=float(sum(p[1] for p in pairs)))


class WindowMoEDecoder(_ScoringDecoder):
    """Causal decoder over token ids whose attention layers are told apart
    by a list (the SmallThinker block, arXiv 2507.20984): `layer_types[i]`
    is "global" (every key at or before the query, NO positional encoding)
    or "sliding" (the `window_size` keys that end with the query's own,
    rotary positions over the whole head); both `GroupedQueryAttention`
    with heads of `head_dim` channels, a width of their own, and no norm
    on a head. Every layer has routed experts gated by ReLU, no shared one
    and no dense layer; a layer's router reads the ATTENTION's normed input
    (`router_input`: the picks are made before the attention runs) and
    weighs its picks by a softmax over their logits; an untied head. The
    block loop, the head, the outputs and the share of a model a chip may
    hold are `_ScoringDecoder`'s."""

    layer_types: tuple = ("global", "sliding")
    d_model: int = 64
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 16
    window_size: int = 4096
    n_routed_experts: int = 8
    experts_held: tuple = (0, 8)    # (first index, count)
    num_experts_per_tok: int = 2
    d_ff_expert: int = 32
    n_shared_experts: int = 0
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1500000.0
    vocab_size: int = 256
    max_len: int = 16384
    # "flash": the Pallas kernels (chunked off-TPU), "chunked", "dense"
    attention_impl: str = "flash"
    head_chunk: int = 1024          # tokens of one block of the head
    output: str = "token_logprobs"  # or "logits"
    dtype: Any = jnp.float32

    # what the family states and no configuration changes
    router_scoring = "softmax"      # over the picked logits, no bias
    norm_topk_prob = True           # ... which is over the picks' own sum
    routed_scaling_factor = 1.0
    expert_activation = "relu"
    router_input = "operator"       # the attention's normed input

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def _dense_layers(self) -> int:
        return 0

    def _operator(self, i: int):
        kind = self.layer_types[i]
        if kind not in ("global", "sliding"):
            raise ValueError(f"unknown layer type {kind!r} at layer {i}: "
                             "'global' or 'sliding'")
        sliding = kind == "sliding"
        # a global layer's plain forward is named by the module, `gqa_attn_
        # <i>`, as the grouped-query layers of `hybrid_moe_decoder` are; a
        # sliding layer's is `swa_attn_<i>` where the row fits its window
        # and the banded forward's own name, `swa_attn_w<window>`, past it
        return f"ln_attn_{i}", GroupedQueryAttention(
            self.num_heads, self.num_kv_heads, self.rope_theta,
            self.rms_norm_eps, self.attention_impl, self.dtype,
            head_dim=self.head_dim, qk_norm=False, rotary=sliding,
            window=self.window_size if sliding else None,
            name=f"swa_attn_{i}" if sliding else f"gqa_attn_{i}")

    def window_tile_pairs(self, rows: int, length: int):
        """`_band_tile_pairs` for a batch of `rows` rows of `length`
        positions, over every head and sliding layer."""
        return _band_tile_pairs(
            self.attention_impl, self.dtype, self.window_size, length,
            rows * self.num_heads * self.layer_types.count("sliding"))

    def call_span_arguments(self, counted: dict, scored: list,
                            row_shape: tuple) -> dict:
        """The family's own beside the skeleton's: the banded kernel's
        tiles (`_band_tile_arguments`)."""
        arguments = super().call_span_arguments(counted, scored, row_shape)
        arguments.update(_band_tile_arguments(self, scored, row_shape))
        return arguments


class LoopedDecoder(_ScoringDecoder):
    """Causal decoder over token ids whose ONE stack of layers is run
    `total_ut_steps` times over the same weights (the Ouro block: "Scaling
    Latent Reasoning via Looped Language Models", arXiv 2510.25741): every
    layer plain causal attention (`GroupedQueryAttention`: `num_heads`
    query heads over `num_kv_heads` key/value heads of `head_dim` channels,
    no norm on a head, rotary over the whole head, the same positions in
    every step) and a gated feed-forward, each between TWO RMSNorms, one
    before it and one after it before the residual add (four a layer); the
    final norm inside the loop; a gate a step whose exit distribution is
    sown beside the log-probabilities (`exit_pdf`, `loop_exit_at`); an
    untied head over the state each token leaves with. No expert layer.
    The steps, the norms' seats, the gate, the head and the outputs are
    `_ScoringDecoder`'s."""

    num_layers: int = 2
    total_ut_steps: int = 4
    early_exit_threshold: float = 1.0
    d_model: int = 64
    num_heads: int = 4
    num_kv_heads: int = 4
    head_dim: int = 16
    d_ff_dense: int = 128
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    vocab_size: int = 256
    max_len: int = 65536
    # "flash": the Pallas kernel (chunked off-TPU), "chunked", "dense"
    attention_impl: str = "flash"
    head_chunk: int = 1024          # tokens of one block of the head
    output: str = "token_logprobs"  # or "logits"
    dtype: Any = jnp.float32

    # what the family states and no configuration changes
    sandwich_norms = True
    exit_gate = True

    @property
    def _dense_layers(self) -> int:
        return self.num_layers

    def _operator(self, i: int):
        # named as the other families' plain causal layers are, so that a
        # device trace tells this attention by the same name
        return f"ln_attn_{i}", GroupedQueryAttention(
            self.num_heads, self.num_kv_heads, self.rope_theta,
            self.rms_norm_eps, self.attention_impl, self.dtype,
            head_dim=self.head_dim, qk_norm=False, name=f"gqa_attn_{i}")


class SSMHybridDecoder(_ScoringDecoder):
    """Causal decoder over token ids whose every layer runs TWO mixers side
    by side on the same normed input (the Falcon-H1 block): a Mamba-2
    state-space mixer (`StateSpaceMixer`: `ssm_heads` heads of
    `ssm_head_dim` channels, `ssm_groups` groups of `ssm_state` state
    channels, a convolution of `conv_taps` taps) and grouped-query
    attention (`num_heads` query heads over `num_kv_heads` key/value heads
    of `head_dim` channels, no norm on a head, rotary over the whole head),
    h <- h + m_ssm_out SSM(m_ssm_in x) + m_attn_out Attn(m_attn_in x), then
    a gated feed-forward; all of it under the family's fixed scalars: on
    the embedding, the key projection, both mixers' inputs and outputs, the
    five parts of the state-space projection (`ssm_multipliers`: z, x, B, C,
    dt), the feed-forward's gate and output (`mlp_multipliers`) and the
    logits. No expert layer; an untied head. The scan's tier is
    `scan.tier`'s to pick (the backend and the widths), no option here. The
    block loop, the final norm, the head and the outputs are
    `_ScoringDecoder`'s."""

    num_layers: int = 2
    d_model: int = 64
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 16
    ssm_heads: int = 4
    ssm_head_dim: int = 16
    ssm_groups: int = 2
    ssm_state: int = 32
    conv_taps: int = 4
    d_ff_dense: int = 128
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e11
    embedding_multiplier: float = 1.0
    key_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: tuple = (1.0, 1.0, 1.0, 1.0, 1.0)
    mlp_multipliers: tuple = (1.0, 1.0)
    lm_head_multiplier: float = 1.0
    vocab_size: int = 256
    max_len: int = 32768
    # "flash": the Pallas kernel (chunked off-TPU), "chunked", "dense"
    attention_impl: str = "flash"
    head_chunk: int = 1024          # tokens of one block of the head
    output: str = "token_logprobs"  # or "logits"
    dtype: Any = jnp.float32

    @property
    def _dense_layers(self) -> int:
        return self.num_layers

    def _operator(self, i: int):
        dt = self.dtype
        ssm = StateSpaceMixer(
            self.ssm_heads, self.ssm_head_dim, self.ssm_groups,
            self.ssm_state, self.conv_taps, tuple(self.ssm_multipliers),
            self.rms_norm_eps, dt, f"ssd_scan_{i}", name=f"ssm_{i}")
        # named as the other families' plain causal layers are, so that a
        # device trace tells this attention by the same name
        attend = GroupedQueryAttention(
            self.num_heads, self.num_kv_heads, self.rope_theta,
            self.rms_norm_eps, self.attention_impl, dt,
            head_dim=self.head_dim, qk_norm=False,
            key_multiplier=self.key_multiplier, name=f"gqa_attn_{i}")

        def both(x):
            """The two branches' sum, each under its scalars, in float32
            and rounded once."""
            f32 = jnp.float32
            state_space = ssm(_times(x, self.ssm_in_multiplier))
            attended = attend(_times(x, self.attention_in_multiplier))
            return (state_space.astype(f32) * self.ssm_out_multiplier
                    + attended.astype(f32) * self.attention_out_multiplier
                    ).astype(dt)

        return f"ln_op_{i}", both

    def call_span_arguments(self, counted: dict, scored: list,
                            row_shape: tuple) -> dict:
        """The family's own beside the skeleton's: `ssd_steps`, the chunks
        the call's scans stepped through (rows x heads x chunks, padding
        rows' included, over the layers and the batches), which the
        registry counts too. It follows from the batches' shapes, so it is
        reckoned here and nothing is read back for it; only rows of token
        ids have a length."""
        arguments = super().call_span_arguments(counted, scored, row_shape)
        if len(row_shape) == 1:
            total = self.num_layers * sum(
                scan.scan_steps(rows, row_shape[0], self.ssm_heads)
                for rows in scored)
            get_registry().counter(
                "mmlspark_tpu_ssd_steps_total",
                "chunks a state-space scan stepped through: rows x heads "
                "x chunks, over the layers and the batches",
            ).inc(float(total))
            arguments["ssd_steps"] = total
        return arguments


def hybrid_layer_kinds(layers: int) -> tuple:
    """Which operator each layer of a decoder-hybrid-decoder has, a function
    of the depth alone (a multiple of 4): the first half alternates "mamba"
    and "sliding" (differential attention behind a window); layer N / 2 is
    "mamba_keeps" (its scan output is kept), layer N / 2 + 1 "full_keeps"
    (full causal differential attention whose keys and values are kept);
    after them "gmu" (reads the scan output) and "cross" (reads the keys and
    values) alternate."""
    if layers < 4 or layers % 4:
        raise ValueError(
            f"a decoder-hybrid-decoder has a multiple of 4 layers, not "
            f"{layers}: its halves alternate two kinds of layer each")
    half = layers // 2
    first = ("mamba", "sliding") * (half // 2)
    return first + ("mamba_keeps", "full_keeps") + ("gmu", "cross") * (
        half // 2 - 1)


class DecoderHybridDecoder(_ScoringDecoder):
    """Causal decoder over token ids whose SECOND half reads what two layers
    of it made (the SambaY shape of arXiv 2507.06607, as Phi-4-mini-flash
    runs it; `hybrid_layer_kinds` is the published rule): the first half
    alternates a Mamba-1 mixer (`SelectiveMixer`: `mamba_inner` channels,
    `mamba_state` states, a step of rank `mamba_dt_rank`) and differential
    attention behind a window of `window_size` keys (`DifferentialAttention`,
    `num_heads` over `num_kv_heads` heads in pairs); layer N / 2 is a Mamba
    mixer whose scan output M is KEPT, layer N / 2 + 1 full causal
    differential attention whose keys and values are KEPT; every later
    even layer is a `GatedMemoryUnit` over M, every later odd one
    differential attention whose queries are its own and whose keys and
    values are layer N / 2 + 1's (no key or value projection in its tree,
    nothing copied a layer). Every layer then a gated feed-forward. LayerNorm
    with a bias as every norm, biases on the attention's projections, no
    positional encoding, a tied head, no multiplier. Scoring runs every
    layer on every position. The scan's tier is `nn/scan.py`'s rule to pick.
    The block loop, the kept arrays' seat, the head and the outputs are
    `_ScoringDecoder`'s."""

    num_layers: int = 8
    d_model: int = 64
    num_heads: int = 4
    num_kv_heads: int = 2
    mamba_inner: int = 128
    mamba_state: int = 16
    mamba_dt_rank: int = 4
    conv_taps: int = 4
    window_size: int = 512
    d_ff_dense: int = 128
    layer_norm_eps: float = 1e-5
    vocab_size: int = 256
    tie_embeddings: bool = True
    max_len: int = 32768
    # "flash": the Pallas kernels (chunked off-TPU), "chunked", "dense"
    attention_impl: str = "flash"
    head_chunk: int = 1024          # tokens of one block of the head
    output: str = "token_logprobs"  # or "logits"
    dtype: Any = jnp.float32

    # what the family states and no configuration changes
    norm_kind = "layer"
    layers_share = True

    @property
    def _dense_layers(self) -> int:
        return self.num_layers

    def _operator(self, i: int):
        kind, dt = hybrid_layer_kinds(self.num_layers)[i], self.dtype
        if kind in ("mamba", "mamba_keeps"):
            mixer = SelectiveMixer(
                self.mamba_inner, self.mamba_dt_rank, self.mamba_state,
                self.conv_taps, dt, f"sel_scan_{i}", name=f"mamba_{i}")

            def operator(a, kept):
                out, scanned = mixer(a)
                return out, ({"memory": scanned} if kind == "mamba_keeps"
                             else {})
        elif kind == "gmu":
            unit = GatedMemoryUnit(dt, name=f"gmu_{i}")

            def operator(a, kept):
                return unit(a, kept["memory"]), {}
        else:
            # a device trace tells the layers by these names: the plain
            # causal calls `diff_attn_<i>`; a sliding layer's `diff_swa_<i>`
            # where the row fits its window and the banded forward's own
            # name, `diff_swa_w<window>`, past it
            sliding = kind == "sliding"
            attend = DifferentialAttention(
                self.num_heads, self.num_kv_heads, i,
                self.window_size if sliding else None, kind == "cross",
                self.layer_norm_eps, self.attention_impl, dt,
                name=f"diff_swa_{i}" if sliding else f"diff_attn_{i}")

            def operator(a, kept):
                out, keys = attend(a, kept["keys"] if kind == "cross"
                                   else None)
                return out, ({"keys": keys} if kind == "full_keeps" else {})
        return f"ln_op_{i}", operator

    def window_tile_pairs(self, rows: int, length: int):
        """`_band_tile_pairs` for a batch of `rows` rows of `length`
        positions over the sliding layers: two softmaxes a pair of heads,
        so `num_heads` forwards a row a layer."""
        return _band_tile_pairs(
            self.attention_impl, self.dtype, self.window_size, length,
            rows * self.num_heads * hybrid_layer_kinds(
                self.num_layers).count("sliding"))

    def call_span_arguments(self, counted: dict, scored: list,
                            row_shape: tuple) -> dict:
        """The family's own beside the skeleton's, each reckoned from the
        batches' shapes (nothing is read back) and counted by the registry
        too: `sel_scan_steps`, the grid steps of the call's scans (rows x
        channel blocks x chunks, padding rows' included, over the Mamba
        layers and the batches); `shared_reads`, the (layer, batch) pairs
        that read an array another layer kept; and, where a tracer keeps
        spans and the banded kernel ran, its tiles as `window_moe_decoder`
        writes them. Only rows of token ids have a length."""
        arguments = super().call_span_arguments(counted, scored, row_shape)
        if len(row_shape) != 1:
            return arguments
        kinds = hybrid_layer_kinds(self.num_layers)
        mixers = sum(kind.startswith("mamba") for kind in kinds)
        steps = mixers * sum(
            scan.sel_scan_steps(rows, row_shape[0], self.mamba_inner)
            for rows in scored)
        reads = (kinds.count("gmu") + kinds.count("cross")) * len(scored)
        registry = get_registry()
        registry.counter(
            "mmlspark_tpu_sel_scan_steps_total",
            "grid steps of a channel-decay selective scan: rows x channel "
            "blocks x chunks, over the layers and the batches",
        ).inc(float(steps))
        registry.counter(
            "mmlspark_tpu_shared_reads_total",
            "(layer, batch) pairs that read an array an earlier layer kept",
        ).inc(float(reads))
        arguments.update(sel_scan_steps=steps, shared_reads=reads)
        arguments.update(_band_tile_arguments(self, scored, row_shape))
        return arguments


def resnet20_cifar(num_outputs: int = 10, dtype=jnp.float32) -> ResNet:
    return ResNet(stage_sizes=(3, 3, 3), num_filters=16,
                  num_outputs=num_outputs, dtype=dtype)


def resnet50(num_outputs: int = 1000, dtype=jnp.float32) -> ResNet:
    return ResNet(stage_sizes=(3, 4, 6, 3), num_filters=64, bottleneck=True,
                  stem_strides=2, num_outputs=num_outputs, dtype=dtype)


def _hashable(config: dict) -> dict:
    """`experts_held` and `layer_types` arrive as lists from a JSON config;
    a module's attributes are hashable."""
    return {k: tuple(v) if isinstance(v, list) else v
            for k, v in config.items()}


# Architecture registry: name -> factory(**config). The zoo's ModelSchema
# references architectures by name (the reference's ModelSchema carries a
# remote URI instead, downloader/Schema.scala:30+). Families: `mlp`,
# `simple_cnn` and the `resnet*` over images or features; over token ids the
# `transformer` encoder and seven causal decoders on one skeleton,
# `mla_moe_decoder`, `hybrid_moe_decoder`, `eva_decoder`,
# `window_moe_decoder`, `looped_decoder`, `ssm_hybrid_decoder` and
# `decoder_hybrid_decoder`.
ARCHITECTURES: dict[str, Callable[..., nn.Module]] = {
    "mlp": lambda **kw: MLP(**kw),
    "simple_cnn": lambda **kw: SimpleCNN(**kw),
    "resnet20_cifar": lambda **kw: resnet20_cifar(**kw),
    "resnet50": lambda **kw: resnet50(**kw),
    "resnet": lambda **kw: ResNet(**kw),
    "transformer": lambda **kw: TransformerEncoder(**kw),
    "mla_moe_decoder": lambda **kw: MLAMoEDecoder(**_hashable(kw)),
    "hybrid_moe_decoder": lambda **kw: HybridMoEDecoder(**_hashable(kw)),
    "eva_decoder": lambda **kw: EvaDecoder(**kw),
    "window_moe_decoder": lambda **kw: WindowMoEDecoder(**_hashable(kw)),
    "looped_decoder": lambda **kw: LoopedDecoder(**kw),
    "ssm_hybrid_decoder": lambda **kw: SSMHybridDecoder(**_hashable(kw)),
    "decoder_hybrid_decoder": lambda **kw: DecoderHybridDecoder(**kw),
}


def make_model(architecture: str, **config) -> nn.Module:
    if architecture not in ARCHITECTURES:
        raise ValueError(
            f"unknown architecture {architecture!r}; have {sorted(ARCHITECTURES)}"
        )
    return ARCHITECTURES[architecture](**config)


@dataclass
class ModelBundle:
    """A saved/loadable model: architecture name + config + variables.

    Role of the reference's serialized CNTK Function + ModelSchema metadata
    (SerializableFunction.scala:85+, downloader/Schema.scala:30+)."""

    architecture: str
    config: dict[str, Any]
    variables: dict[str, Any]          # {"params": ..., "batch_stats": ...}
    input_shape: tuple[int, ...] = ()  # per-example shape, e.g. (32, 32, 3)
    class_labels: list | None = None
    preprocess: dict[str, Any] = field(default_factory=dict)  # mean/std etc.

    _module: nn.Module | None = None

    @property
    def module(self) -> nn.Module:
        if self._module is None:
            cfg = dict(self.config)
            if cfg.get("dtype") == "bfloat16":
                cfg["dtype"] = jnp.bfloat16
            elif cfg.get("dtype") == "float32":
                cfg["dtype"] = jnp.float32
            self._module = make_model(self.architecture, **cfg)
        return self._module

    @staticmethod
    def init(architecture: str, input_shape: tuple[int, ...], seed: int = 0,
             class_labels=None, preprocess=None, **config) -> "ModelBundle":
        bundle = ModelBundle(
            architecture=architecture,
            config=config,
            variables={},
            input_shape=tuple(input_shape),
            class_labels=class_labels,
            preprocess=dict(preprocess or {}),
        )
        x = jnp.zeros((1, *input_shape), jnp.float32)
        bundle.variables = bundle.module.init(jax.random.PRNGKey(seed), x)
        return bundle

    def save(self, path: str) -> None:
        import json
        from flax import serialization

        cfg = {
            k: ("bfloat16" if v is jnp.bfloat16 else "float32" if v is jnp.float32 else v)
            for k, v in self.config.items()
        }
        header = json.dumps({
            "architecture": self.architecture,
            "config": cfg,
            "input_shape": list(self.input_shape),
            "class_labels": self.class_labels,
            "preprocess": self.preprocess,
        }).encode()
        blob = serialization.to_bytes(self.variables)
        with open(path, "wb") as fh:
            fh.write(len(header).to_bytes(8, "little"))
            fh.write(header)
            fh.write(blob)

    @staticmethod
    def load(path: str) -> "ModelBundle":
        import json
        from flax import serialization

        with open(path, "rb") as fh:
            hlen = int.from_bytes(fh.read(8), "little")
            header = json.loads(fh.read(hlen).decode())
            blob = fh.read()
        bundle = ModelBundle(
            architecture=header["architecture"],
            config=header["config"],
            variables={},
            input_shape=tuple(header["input_shape"]),
            class_labels=header.get("class_labels"),
            preprocess=header.get("preprocess", {}),
        )
        x = jnp.zeros((1, *bundle.input_shape), jnp.float32)
        template = bundle.module.init(jax.random.PRNGKey(0), x)
        bundle.variables = serialization.from_bytes(template, blob)
        return bundle

    def layer_names(self) -> list[str]:
        """Dotted paths of all submodules (the reference's layerNames,
        ImageFeaturizer.scala:92-135)."""
        x = jnp.zeros((1, *self.input_shape), jnp.float32)
        _, state = self.module.apply(
            self.variables, x, train=False,
            capture_intermediates=True, mutable=["intermediates"],
        )
        names: list[str] = []

        def walk(tree, prefix):
            for k, v in tree.items():
                p = f"{prefix}.{k}" if prefix else k
                if isinstance(v, dict):
                    walk(v, p)
                else:
                    # "__call__" leaves name the module; sown values (e.g.
                    # pooled_features) name themselves
                    names.append(prefix if k == "__call__" else p)

        walk(state["intermediates"], "")
        # dedupe, keep order; drop the root module's own output ("") — that
        # is just the logits, addressable as "logits"
        seen: dict[str, None] = {}
        for nme in names:
            if nme:
                seen.setdefault(nme, None)
        return list(seen)
