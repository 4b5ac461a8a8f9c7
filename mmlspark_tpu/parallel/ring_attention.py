"""Ring attention + Ulysses sequence parallelism for long contexts.

The reference has NO sequence models and no sequence parallelism
(SURVEY.md §5.7 — its only long-input handling is PageSplitter chunking);
these are first-class here so the framework handles modern long-context
workloads the reference's architecture never could.

Design (the "How to Scale Your Model" recipe):
  - **Ring attention**: the sequence is sharded over a mesh axis; each
    device keeps its Q shard resident and the K/V shards ROTATE one
    neighbor-hop per step via `lax.ppermute` (ICI torus neighbor exchange),
    overlapping compute with transfer. Softmax is accumulated online
    (flash-attention style running max/denominator), so the full (T, T)
    score matrix never materializes — memory is O(T_local²) per step.
  - **Ulysses**: `all_to_all` reshards (seq-sharded → head-sharded), runs
    exact attention on full sequences for the local heads, and reshards
    back. Cheaper for moderate T with many heads; ring wins at very long T.

Both are numerically equivalent to full softmax attention (tested against
the dense reference implementation).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .collectives import axis_size

__all__ = [
    "dense_attention",
    "ring_attention",
    "ulysses_attention",
    "make_ring_attention",
    "make_ulysses_attention",
]


def key_head_group(q, k, v) -> int:
    """How many query heads read one key/value head: q (B, Tq, H, D) over
    k, v (B, Tk, H / group, .); query head j reads head j // group."""
    h, hk = q.shape[2], k.shape[2]
    if v.shape[2] != hk or hk < 1 or h % hk:
        raise ValueError(
            f"{h} query heads cannot share {hk} key and {v.shape[2]} value "
            "heads: the key/value heads have to divide the query heads")
    return h // hk


def over_key_heads(attend, q, k, v):
    """`attend(q, k, v)` for grouped-query heads: the members of a group
    meet their one key/value head as it lies, a member at a time (a
    `vmap` over the members with K and V unbatched: nothing is repeated).
    Equal head counts call `attend` as it is."""
    group = key_head_group(q, k, v)
    if group == 1:
        return attend(q, k, v)
    b, tq, h, d = q.shape
    out = jax.vmap(lambda member: attend(member, k, v), in_axes=3,
                   out_axes=3)(q.reshape(b, tq, h // group, group, d))
    return out.reshape(b, tq, h, out.shape[-1])


def dense_attention(q, k, v, causal: bool = False,
                    q_offset: int = 0, k_offset: int = 0):
    """Reference implementation: full softmax attention.
    q: (B, Tq, H, D); k, v: (B, Tk, H, D) -> (B, Tq, H, D). With fewer
    key/value heads (a divisor of H), query head j reads head j // group."""
    if q.shape[2] != k.shape[2]:
        return over_key_heads(
            lambda q, k, v: dense_attention(q, k, v, causal, q_offset,
                                            k_offset), q, k, v)
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        qpos = jnp.arange(q.shape[1]) + q_offset
        kpos = jnp.arange(k.shape[1]) + k_offset
        mask = qpos[:, None] >= kpos[None, :]
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    # fully-masked rows (causal with all keys in the future) -> zeros
    p = jnp.where(jnp.isfinite(s).any(-1, keepdims=True), p, 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _online_update(o, l, m, q, k_blk, v_blk, qpos, kpos, causal, scale):
    """One online-softmax accumulation of a K/V block into (o, l, m).
    Shared by the per-hop update and the within-hop chunk scan."""
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k_blk,
        preferred_element_type=jnp.float32) * scale
    if causal:
        mask = qpos[:, None] >= kpos[None, :]
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    blk_max = scores.max(axis=-1)                           # (B, H, Tq)
    m_new = jnp.maximum(m, blk_max)
    # guard: fully-masked block keeps m_new=-inf; exp(-inf - -inf) trap
    safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    corr = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
    p = jnp.exp(jnp.where(jnp.isfinite(scores),
                          scores - safe_m[..., None], -jnp.inf))
    p = jnp.where(jnp.isfinite(scores), p, 0.0)
    l_new = l * corr + p.sum(axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v_blk,
                    preferred_element_type=jnp.float32)
    o_new = o * corr.transpose(0, 2, 1)[..., None] + pv
    return o_new, l_new, m_new


def _ring_attention_sharded(q, k, v, axis_name: str, causal: bool,
                            local_chunk: "int | None" = None):
    """Per-shard body. q/k/v: (B, T_local, H, D), sharded on T.

    local_chunk bounds the materialized score tile: each hop's K/V block
    is folded in (T_local/local_chunk) chunks under the SAME online-
    softmax state, so per-hop scores shrink from (B, H, T_local, T_local)
    to (B, H, T_local, local_chunk) — the single-device chunked tier
    (nn/attention.py) composed inside the ring hop. None keeps the
    one-block-per-hop update."""
    b, t_local, h, d = q.shape
    scale = d ** -0.5
    n_dev = axis_size(axis_name)
    my = lax.axis_index(axis_name)
    q_off = my * t_local
    qpos = jnp.arange(t_local) + q_off

    if local_chunk is not None and local_chunk < 1:
        raise ValueError(f"local_chunk={local_chunk} must be >= 1")
    if local_chunk and local_chunk < t_local:
        if t_local % local_chunk:
            raise ValueError(
                f"local_chunk={local_chunk} must divide the per-device "
                f"sequence length {t_local}")
        n_chunks = t_local // local_chunk
    else:
        n_chunks = 1

    # online-softmax state; derived from q (+0*…) so the scan carry gets
    # the same varying-over-seq-axis type as the rotating kv blocks
    zvar = 0.0 * q.astype(jnp.float32)
    o = zvar                                               # (B, T, H, D)
    l = zvar[..., 0].transpose(0, 2, 1)                    # (B, H, Tq)
    m = l - jnp.inf                                        # running max

    def step(carry, s):
        o, l, m, k_blk, v_blk = carry
        src = (my - s) % n_dev          # origin device of the current block
        k_off = src * t_local
        if n_chunks == 1:
            kpos = jnp.arange(t_local) + k_off
            o, l, m = _online_update(o, l, m, q, k_blk, v_blk, qpos, kpos,
                                     causal, scale)
        else:
            c = local_chunk
            kc = jnp.moveaxis(
                k_blk.reshape(b, n_chunks, c, h, d), 1, 0)
            vc = jnp.moveaxis(
                v_blk.reshape(b, n_chunks, c, h, d), 1, 0)

            def chunk_body(carry2, xs):
                o2, l2, m2 = carry2
                k_c, v_c, ci = xs
                kpos = k_off + ci * c + jnp.arange(c)
                return _online_update(o2, l2, m2, q, k_c, v_c, qpos, kpos,
                                      causal, scale), None

            (o, l, m), _ = lax.scan(
                chunk_body, (o, l, m), (kc, vc, jnp.arange(n_chunks)))
        # rotate kv one hop for the next step (overlaps with next compute)
        perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
        k_next = lax.ppermute(k_blk, axis_name, perm)
        v_next = lax.ppermute(v_blk, axis_name, perm)
        return (o, l, m, k_next, v_next), None

    (o, l, m, _, _), _ = lax.scan(
        step, (o, l, m, k.astype(jnp.float32), v.astype(jnp.float32)),
        jnp.arange(n_dev),
    )
    denom = jnp.where(l > 0, l, 1.0).transpose(0, 2, 1)[..., None]
    return (o / denom).astype(q.dtype)


def make_ring_attention(mesh: Mesh, seq_axis: str, causal: bool = False,
                        local_chunk: "int | None" = None):
    """Jitted ring attention over `seq_axis` of `mesh`.
    Inputs (B, T, H, D) with T sharded over seq_axis. `local_chunk`
    bounds the per-hop score tile (see _ring_attention_sharded) for
    long-context training where T/n_dev is itself large."""
    fn = shard_map(
        functools.partial(_ring_attention_sharded, axis_name=seq_axis,
                          causal=causal, local_chunk=local_chunk),
        mesh=mesh,
        in_specs=(P(None, seq_axis), P(None, seq_axis), P(None, seq_axis)),
        out_specs=P(None, seq_axis),
    )
    return jax.jit(fn)


def ring_attention(q, k, v, mesh: Mesh, seq_axis: str, causal: bool = False,
                   local_chunk: "int | None" = None):
    return make_ring_attention(mesh, seq_axis, causal, local_chunk)(q, k, v)


def _ulysses_sharded(q, k, v, axis_name: str, causal: bool,
                     local_chunk: "int | None" = None):
    """Per-shard body: (B, T_local, H, D) seq-sharded -> exact attention via
    two all_to_alls (seq shards <-> head shards).

    After the first all_to_all every device holds the FULL sequence for
    H/n heads, so the attention core is a single-device problem:
    `local_chunk=None` runs the dense reference math ((T, T) scores —
    fine for moderate T), and `local_chunk=c` runs the chunked
    online-softmax core instead (identical result, score tiles bounded
    at (c, c) — the long-context setting where a (T, T) materialization
    is exactly what Ulysses users are trying to avoid)."""
    if local_chunk is not None and local_chunk < 1:
        raise ValueError(f"local_chunk={local_chunk} must be >= 1")

    def to_heads(x):
        # (B, T_local, H, D) -> (B, T_global, H/n, D)
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)
    if local_chunk:
        # runtime import: nn.attention imports this module's dense tier,
        # so the dependency must stay one-way at import time
        from ..nn.attention import chunked_attention

        out = chunked_attention(qh, kh, vh, causal=causal,
                                q_chunk=local_chunk, k_chunk=local_chunk)
    else:
        out = dense_attention(qh, kh, vh, causal=causal)
    return to_seq(out)


def make_ulysses_attention(mesh: Mesh, seq_axis: str, causal: bool = False,
                           local_chunk: "int | None" = None):
    """Jitted Ulysses (all-to-all) attention over `seq_axis`. Requires the
    head count to be divisible by the axis size. `local_chunk` bounds the
    post-all_to_all score tile (see _ulysses_sharded)."""
    fn = shard_map(
        functools.partial(_ulysses_sharded, axis_name=seq_axis,
                          causal=causal, local_chunk=local_chunk),
        mesh=mesh,
        in_specs=(P(None, seq_axis), P(None, seq_axis), P(None, seq_axis)),
        out_specs=P(None, seq_axis),
    )
    return jax.jit(fn)


def ulysses_attention(q, k, v, mesh: Mesh, seq_axis: str, causal: bool = False,
                      local_chunk: "int | None" = None):
    return make_ulysses_attention(mesh, seq_axis, causal, local_chunk)(q, k, v)
