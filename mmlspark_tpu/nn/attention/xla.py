"""The tiers that run on every backend, in XLA: the chunked ones (online
softmax or a block of queries at a time, never a (T, T) array; the CPU's
path for every core, and the backward of the sliding window) and the dense
ones the tests hold the others to (`dense_attention` itself lives in
`parallel.ring_attention`)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ...parallel.ring_attention import key_head_group, over_key_heads
from . import layout
from .layout import _NEG_INF


def chunked_attention(q, k, v, causal: bool = False,
                      q_chunk: int = 128, k_chunk: int = 128):
    """Online-softmax attention over k/v chunks; O(T) memory.

    q: (B, Tq, H, D); k: (B, Tk, H, D); v: (B, Tk, H, Dv) -> (B, Tq, H, Dv),
    matching `dense_attention` (tested bit-close against it). The values
    may be narrower or wider than the scores' channels (latent attention:
    192 for scores, 128 for values). Differentiable — XLA transposes the
    scan for the backward pass; pair with `jax.checkpoint` on the caller
    for long sequences. Fewer key/value heads than query heads: query
    head j reads head j // group.
    """
    if q.shape[2] != k.shape[2]:
        return over_key_heads(
            lambda q, k, v: chunked_attention(q, k, v, causal, q_chunk,
                                              k_chunk), q, k, v)
    orig_dtype = q.dtype
    b, tq_orig, h, d = q.shape
    dv = v.shape[-1]
    tk_orig = k.shape[1]
    q_chunk = min(q_chunk, max(tq_orig, 1))
    k_chunk = min(k_chunk, max(tk_orig, 1))
    q, tq = layout._pad_seq(q, q_chunk)
    k, tk = layout._pad_seq(k, k_chunk)
    v, _ = layout._pad_seq(v, k_chunk)
    nq, nk = q.shape[1] // q_chunk, k.shape[1] // k_chunk
    scale = d ** -0.5

    # (nq, B, qc, H, D) so scan carries one q-chunk at a time
    qr = jnp.moveaxis(q.reshape(b, nq, q_chunk, h, d), 1, 0)
    kr = jnp.moveaxis(k.reshape(b, nk, k_chunk, h, d), 1, 0)
    vr = jnp.moveaxis(v.reshape(b, nk, k_chunk, h, dv), 1, 0)

    kpos = jnp.arange(nk * k_chunk).reshape(nk, k_chunk)
    k_valid = kpos < tk                                       # pad mask

    def one_q_chunk(qi, qb):
        qpos = qi * q_chunk + jnp.arange(q_chunk)

        def body(carry, xs):
            m, l, acc = carry
            kb, vb, kp, kv_ok = xs
            s = jnp.einsum("bqhd,bkhd->bhqk", qb, kb,
                           preferred_element_type=jnp.float32) * scale
            ok = kv_ok[None, :]
            if causal:
                ok = ok & (qpos[:, None] >= kp[None, :])
            s = jnp.where(ok[None, None], s, _NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            # masked entries contribute 0 even when the whole row is
            # masked (then m_new == _NEG_INF and exp(s - m_new) == 1)
            p = jnp.where(ok[None, None], p, 0.0)
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p, vb.astype(jnp.float32),
                preferred_element_type=jnp.float32)
            return (m_new, l, acc), None

        # + 0*qb: the carry inherits qb's type — under shard_map (the
        # Ulysses local core) that includes the varying-over-seq-axis
        # tag, which a plain zeros/full init would lack
        zvar = 0.0 * qb.astype(jnp.float32).transpose(0, 2, 1, 3)
        m0 = zvar[..., 0] + _NEG_INF                      # (B, H, qc)
        l0 = zvar[..., 0]
        # (B, H, qc, Dv): as wide as the values
        a0 = zvar if dv == d else jnp.broadcast_to(
            zvar[..., :1], zvar.shape[:-1] + (dv,))
        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                      (kr, vr, kpos, k_valid))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        # rows with no visible key (all masked) -> zeros, as dense does
        out = jnp.where((l > 0)[..., None], out, 0.0)
        return jnp.moveaxis(out, 1, 2)                        # (B, qc, H, D)

    outs = jax.lax.map(lambda xs: one_q_chunk(*xs),
                       (jnp.arange(nq), qr))                  # (nq,B,qc,H,D)
    out = jnp.moveaxis(outs, 0, 1).reshape(b, nq * q_chunk, h, dv)
    return out[:, :tq].astype(orig_dtype)


def _banded_dense(q, k, v, window: int):
    """One masked softmax over all keys (u <= t and t - u < window), in the
    queries' type like `dense_attention`: tests, short rows."""
    if q.shape[2] != k.shape[2]:
        return over_key_heads(lambda q, k, v: _banded_dense(q, k, v, window),
                              q, k, v)
    pos = jnp.arange(q.shape[1])
    behind = pos[:, None] - pos[None, :]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    p = jax.nn.softmax(
        jnp.where((behind >= 0) & (behind < window), s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _banded_chunked(q, k, v, window: int, q_chunk: int = 128):
    """XLA, a block of queries against the keys of its band only (the
    `window + q_chunk - 1` that end with the block's last query), float32
    scores, never a (T, T) array. Runs on every backend (the CPU's path)
    and is differentiable: the sliding window's backward is this tier's."""
    b, t, h, d = q.shape
    hk, group, f32 = k.shape[2], key_head_group(q, k, v), jnp.float32
    q_chunk = min(q_chunk, t)
    q, _ = layout._pad_seq(q, q_chunk)
    k, _ = layout._pad_seq(k, q_chunk)
    v, _ = layout._pad_seq(v, q_chunk)
    padded = q.shape[1]
    span = min(window - 1 + q_chunk, padded)
    # query head j reads key/value head j // group: (.., hk, group, d)
    q = q.reshape(b, padded, hk, group, d)

    def some_queries(first):
        start = jnp.clip(first + q_chunk - span, 0, padded - span)
        qpos = (first + jnp.arange(q_chunk))[:, None]
        kpos = (start + jnp.arange(span))[None, :]
        ok = (qpos >= kpos) & (qpos - kpos < window)
        kb, vb = (jax.lax.dynamic_slice_in_dim(x, start, span, 1)
                  for x in (k, v))
        s = jnp.einsum(
            "bqhgd,bkhd->bhgqk",
            jax.lax.dynamic_slice_in_dim(q, first, q_chunk, 1), kb,
            preferred_element_type=f32) * d ** -0.5
        # every query sees itself, so no row is empty
        p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhgqk,bkhd->bqhgd", p, vb.astype(f32),
                          preferred_element_type=f32)

    out = jax.lax.map(some_queries, jnp.arange(0, padded, q_chunk))
    return jnp.moveaxis(out, 0, 1).reshape(b, padded, h, v.shape[-1])[
        :, :t].astype(q.dtype)


def _eva_masked(q, kbar, k, ok_remote, ok_local, vbar, v):
    """ONE softmax over [summaries; keys]: float32 scores (B, H, q, .) of
    the queries against both, what of them counts, and the weighted values.
    Every query sees itself, so no row is empty."""
    f32, c = jnp.float32, kbar.shape[1]
    scale = q.shape[-1] ** -0.5
    s = jnp.concatenate([
        jnp.where(ok_remote, jnp.einsum(
            "bqhd,bchd->bhqc", q, kbar, preferred_element_type=f32) * scale,
            -jnp.inf),
        jnp.where(ok_local, jnp.einsum(
            "bqhd,bkhd->bhqk", q, k, preferred_element_type=f32) * scale,
            -jnp.inf)], -1)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqc,bchd->bqhd", p[..., :c], vbar.astype(f32),
                     preferred_element_type=f32)
    out = out + jnp.einsum("bhqk,bkhd->bqhd", p[..., c:], v.astype(f32),
                           preferred_element_type=f32)
    return out.astype(q.dtype)


def _eva_dense(q, k, v, kbar, vbar, window, chunk):
    """One masked score matrix over [summaries; keys]: tests, short rows."""
    pos = jnp.arange(q.shape[1])
    own = pos // window
    ok_local = (pos[:, None] >= pos[None, :]) & (own[:, None] == own[None, :])
    ok_remote = ((jnp.arange(kbar.shape[1]) + 1) * chunk
                 <= (own * window)[:, None])
    return _eva_masked(q, kbar, k, ok_remote, ok_local, vbar, v)


def _eva_chunked(q, k, v, kbar, vbar, window, chunk, q_chunk: int = 128):
    """XLA, a window of keys at a time: a block of queries against the keys
    of its own window and the summaries, never a (T, T) matrix. Runs on
    every backend (the CPU's path, where Mosaic cannot lower)."""
    b, t, h, _d = q.shape
    q_chunk = max(n for n in range(1, min(q_chunk, window) + 1)
                  if window % n == 0)
    q, _ = layout._pad_seq(q, window)
    k, _ = layout._pad_seq(k, window)
    v, _ = layout._pad_seq(v, window)
    chunk_end = (jnp.arange(kbar.shape[1]) + 1) * chunk

    def some_queries(first):
        start = (first // window) * window
        qpos = first + jnp.arange(q_chunk)
        return _eva_masked(
            jax.lax.dynamic_slice_in_dim(q, first, q_chunk, 1), kbar,
            jax.lax.dynamic_slice_in_dim(k, start, window, 1),
            (chunk_end <= start)[None, :],
            qpos[:, None] >= (start + jnp.arange(window))[None, :],
            vbar, jax.lax.dynamic_slice_in_dim(v, start, window, 1))

    out = jax.lax.map(some_queries, jnp.arange(0, q.shape[1], q_chunk))
    return jnp.moveaxis(out, 0, 1).reshape(b, -1, h, v.shape[-1])[:, :t]
