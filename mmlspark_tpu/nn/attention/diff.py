"""The differential core (arXiv 2410.05258): heads 2j and 2j + 1 are a PAIR,
a pair's two softmaxes read one value twice a head wide, and the second is
subtracted under a scalar:

    o_j = (softmax(q1_j k1_m^T / sqrt(D)) - lam softmax(q2_j k2_m^T / sqrt(D))) v_m

with q1_j, q2_j query heads 2j and 2j + 1, k1_m, k2_m key heads 2m and 2m + 1,
v_m = [v_2m | v_2m+1] (2 D wide) and m = j // group: a query pair reads the
key/value pair its group shares. Both softmaxes under the layer's mask:
causal, or a band of `window` keys that ends with the query's own.

It is TWO calls of the forwards the package has (`flash.causal_attention`'s
tiers: `fold._flash_fold` on the "flash" tier, `xla`'s chunked and dense ones
elsewhere), one over (q1, k1, v_m) and one over (q2, k2, v_m), scores D wide
and values 2 D; no fold of its own. What is its own is the LAYOUT: keys and
values are laid out once, pair-major, (B x pairs, S, 1, width), by
`key_pairs`, and that is what a layer keeps for the layers that read its
keys and values: a forward over ONE key/value head a row reads such an
array as it lies (its head-major copy is the array itself), so a layer that
reads another's keys and values copies nothing of them. The queries follow
them, (B x pairs, T, group, D): the copy a head narrower than a lane block
pays anyway.

The Pallas calls are named `diff_attn_<i>` (plain causal: by the innermost
`jax.named_scope`, the module's) and `diff_swa_w<window>` (banded: the
call's own name, lowered once a shape), so a device trace tells them from
`gqa_attn_*` and `swa_attn_*`."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import flash, layout


def lambda_init(depth: int) -> float:
    """The constant of a layer's lambda, by the layer's index from 0."""
    return 0.8 - 0.6 * math.exp(-0.3 * depth)


def key_pairs(k, v, heads: int):
    """k, v (B, S, heads x D) as the projections wrote them -> (k1, k2
    (B x pairs, S, 1, D), v (B x pairs, S, 1, 2 D)): pair-major, what both
    softmaxes of every layer that reads these keys take as it lies."""
    b, s, wide = k.shape
    d, pairs = wide // heads, heads // 2
    if heads % 2:
        raise ValueError(f"{heads} key heads are no pairs")

    def major(x):
        return jnp.moveaxis(x, 2, 1).reshape(b * pairs, s, 1, x.shape[-1])

    split = k.reshape(b, s, pairs, 2, d)
    return (major(split[:, :, :, 0]), major(split[:, :, :, 1]),
            major(v.reshape(b, s, pairs, 2 * d)))


# The banded Pallas forward under the differential layers' own name, jitted
# by itself as `flash._banded_flash` is: both softmaxes of every banded layer
# are one traced shape
@functools.partial(jax.jit, static_argnames=("window", "block_q", "block_k",
                                             "interpret"))
def _banded(q, k, v, *, window, block_q, block_k, interpret=False):
    return flash._flash_fwd_lse(q, k, v, True, block_q, block_k, interpret,
                                window=window,
                                name=f"diff_swa_w{window}")[0]


def _attend(q, k, v, impl, window, name, options):
    """One softmax-weighted sum of a layer, on the tier `impl`."""
    t = q.shape[1]
    if impl == "flash" and window is not None and t > window:
        rule_q, rule_k = flash.band_tiles(t, window, q.dtype)
        return _banded(
            q, k, v, window=window,
            block_q=min(options.get("block_q") or rule_q, t),
            block_k=min(options.get("block_k") or rule_k, t),
            interpret=bool(options.get("interpret", False)))
    with jax.named_scope(name):
        return flash.causal_attention(q, k, v, impl, window=window,
                                      **options)


def differential_attention(q, keys, lam, impl: str = "flash",
                           window: int | None = None,
                           name: str = "diff_attn", **flash_options):
    """q (B, T, H x D) as its projection wrote it; `keys` what `key_pairs`
    made of the keys and values it reads (its own layer's, or another's);
    `lam` the layer's scalar, float32 -> (P1 - lam P2) v, (B, T, H / 2,
    2 D) float32: each softmax-weighted sum leaves its forward in q's type,
    the subtraction is float32. `impl` is a LITERAL tier (`layout.tier`'s
    answer, or "flash" with `interpret=True` among `flash_options` in a CPU
    test); `window`: a query reads the `window` keys that end with its own
    (None: every key at or before it)."""
    layout._known(impl)
    f32 = jnp.float32
    k1, k2, v = keys
    b, t, wide = q.shape
    d = k1.shape[-1]
    pairs = k1.shape[0] // b
    group = wide // (2 * d * pairs)
    if group * 2 * d * pairs != wide:
        raise ValueError(
            f"queries {wide} wide are no pairs of heads of {d} over {pairs} "
            "key/value pairs")
    # the queries follow the keys: (B x pairs, T, group, softmax, D)
    q = jnp.moveaxis(q.reshape(b, t, pairs, group, 2, d), 2, 1).reshape(
        b * pairs, t, group, 2, d)
    first, second = (
        _attend(q[:, :, :, s], k, v, impl, window, name, flash_options)
        for s, k in enumerate((k1, k2)))
    o = first.astype(f32) - lam * second.astype(f32)
    return jnp.moveaxis(o.reshape(b, pairs, t, group, 2 * d), 1, 2).reshape(
        b, t, pairs * group, 2 * d)
