"""Single-device attention implementations: dense, chunked, Pallas flash.

The reference has no sequence-model family at all (SURVEY.md §5.7); this
module is the single-device half of the beyond-reference attention stack —
the cross-device half (ring / Ulysses sequence parallelism over the mesh)
lives in `parallel.ring_attention` and implements identical math.

Three tiers, one contract (q, k (B, T, H, D), v (B, T, H, Dv), output
(B, T, H, Dv); the chunked and flash tiers take Dv != D, which latent
attention needs). Every tier takes k and v with FEWER heads than q (a
divisor: grouped-query attention, query head j reads key/value head
j // group), and none repeats K or V to do it:

- ``dense_attention`` (re-exported from parallel.ring_attention): full
  (T, T) score matrix. The reference implementation every other tier is
  tested against; O(T^2) HBM, fine for short sequences.
- ``chunked_attention``: online-softmax over key/value chunks via
  `lax.scan` (the Rabe-Staats memory-efficient formulation). O(T) memory,
  differentiable (XLA derives the backward through the scan), works on
  every backend — the long-sequence TRAINING path on one device.
- ``flash_attention``: a Pallas TPU kernel for the forward hot path —
  the (block_q, block_k) score tile lives only in VMEM, never HBM, with
  the online-softmax running max / denominator / accumulator carried in
  VMEM scratch across the sequential key-block grid dimension. The tile
  is chosen here, by `flash_tiles`, from the lengths and the dtype.
  DIFFERENTIABLE via `jax.custom_vjp`: the kernel also emits the per-row
  logsumexp, and the backward is the standard flash recomputation as a
  pure-XLA k-block scan (compiles on every backend; O(T) score memory).

A fourth core, ``eva_attention``, reads TWO sets of keys in one softmax
(EVA, arXiv 2302.04542): the keys of the query's own window, exactly and
causally, and one pooled key and value (``eva_summaries``) for every chunk
of the windows before it; the same three tiers behind one switch.

The chunked and flash tiers compute scores and the softmax accumulator in
float32 whatever the input dtype (bf16 inputs stay bf16 through the
projections; the numerically sensitive reduction is f32 — the standard
TPU recipe). The dense tier is the unmodified reference math from
`parallel.ring_attention` and follows the INPUT dtype throughout — with
bf16 inputs it is the least accurate tier, not the most; prefer chunked
or flash for bf16 serving.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..observability.metrics import get_registry
from ..parallel.ring_attention import (dense_attention, key_head_group,
                                       over_key_heads)

__all__ = ["dense_attention", "chunked_attention", "flash_attention",
           "flash_tiles", "causal_attention", "eva_summaries",
           "eva_attention", "SelfAttention"]

_NEG_INF = -1e30  # large-negative instead of -inf: keeps exp/max NaN-free


def _pad_seq(x, mult):
    t = x.shape[1]
    pad = (-t) % mult
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return x, t


# --------------------------------------------------------------------- #
# chunked (memory-efficient, differentiable)                            #
# --------------------------------------------------------------------- #

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
    q, tq = _pad_seq(q, q_chunk)
    k, tk = _pad_seq(k, k_chunk)
    v, _ = _pad_seq(v, k_chunk)
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


# --------------------------------------------------------------------- #
# Pallas flash forward                                                  #
# --------------------------------------------------------------------- #

def flash_tiles(tq: int, tk: int, dtype,
                window: int | None = None) -> tuple[int, int]:
    """The (block_q, block_k) the flash forward works on, from what it can
    see. Read on a v5e (PERF.md, PRs 27 and 30): the kernel pays about
    0.6 us a grid step whatever is in it, so the largest tile wins: alone
    it gives 11 / 27 / 48 / 65 TFLOP/s of the causal triangle at tiles of
    128 / 256 / 512 / 1024 over 4096 tokens, and 14 / 30 / 97 of the
    square at 128 / 256 / 512 over 512. The cap is 1024 for inputs of 2
    bytes and 512 for float32, where a 1024 x 1024 tile passes the default
    16 MB of scoped VMEM (what a kernel asks beyond the default is taken
    from the whole program). A tile is a multiple of 128, or the whole of
    a sequence shorter than that, and is never bought with padding: the
    padded length stays within one eighth of the length rounded up to 128
    (512 -> 512, 514 -> 640, 1100 -> two of 640, 4096 -> 1024). The head's
    width plays no part: at 64 channels and 16384 tokens (32 query heads
    over 8 key/value heads, bfloat16, causal; PERF.md, PR 31) 1024 x 1024
    gives 36.8 ms a call, 512 x 2048 43.7, 512 x 1024 41.3, 2048 x 512
    59.0, 1024 x 512 69.9, and 2048 x 1024 does not fit the 16 MB: the
    score tile, not the head, fills VMEM.

    Told a `window` (`eva_attention`: a query reads the keys of its own
    window of that many positions), both tiles are the largest under the
    cap that DIVIDE the window, so that a block of queries lies in one
    window and a window is whole blocks of keys: 1024 x 1024 of 2048
    (PERF.md, PR 33). Without one the answers are what they were."""
    cap = 1024 if jnp.dtype(dtype).itemsize <= 2 else 512
    if window is not None:
        if window <= 128:
            return window, window
        fits = [b for b in range(128, cap + 1, 128) if window % b == 0]
        if not fits:
            raise ValueError(
                f"no tile of the flash forward divides a window of {window} "
                "positions: a multiple of 128 does, or one of at most 128")
        return max(fits), max(fits)

    def padded(t, b):
        return -(-t // b) * b

    def tile(t):
        if t <= 128:
            return max(t, 1)
        most = padded(t, 128) + padded(t, 128) // 8
        return max(b for b in range(128, cap + 1, 128)
                   if padded(t, b) <= most)

    return tile(tq), tile(tk)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch,
                  block_q, block_k, num_kv, causal, tk_valid, scale):
    import jax.experimental.pallas as pl

    qi = pl.program_id(1)
    kv = pl.program_id(2)
    # only a padded sequence needs the key mask: decided here, in Python
    padded = tk_valid < num_kv * block_k

    def scores(mask_keys: bool, mask_causal: bool):
        """This step's (bq, bk) score tile, and which of it counts (None:
        all of it). `mask_keys`: keys at or past `tk_valid` are padding;
        `mask_causal`: a query sees the keys at or before it."""
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # (bq, bk)
        ok = None
        if mask_keys or mask_causal:
            kpos = kv * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
        if mask_keys:
            ok = kpos < tk_valid
        if mask_causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            ok = (qpos >= kpos) if ok is None else ok & (qpos >= kpos)
        if ok is not None:
            s = jnp.where(ok, s, _NEG_INF)
        return s, ok

    def weigh(s, ok, m):
        """exp(s - m): its row sums (bq, 1) and its product with the
        values (bq, Dv)."""
        p = jnp.exp(s - m)                                    # (bq, bk)
        if ok is not None:
            # masked entries must contribute 0 even when the whole row is
            # masked (then m == _NEG_INF and exp(s - m) == 1, not 0)
            p = jnp.where(ok, p, 0.0)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return p.sum(-1, keepdims=True), pv

    def write(m, l, acc):
        out = acc / jnp.maximum(l, 1e-30)
        out = jnp.where(l > 0, out, 0.0)
        o_ref[0] = out.astype(o_ref.dtype)
        # per-row logsumexp, the backward pass's softmax residual;
        # +inf on fully-masked rows makes exp(s - lse) vanish there
        lse_ref[0] = jnp.where(
            l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), jnp.inf)

    if num_kv == 1:
        # the softmax is whole in this tile: no running maximum, no
        # correction, no accumulator through scratch (the same numbers,
        # bit for bit, as one step of the path below)
        s, ok = scores(padded, causal)
        m = s.max(-1, keepdims=True)
        write(m, *weigh(s, ok, m))
        return

    m_sc, l_sc, acc_sc = scratch

    @pl.when(kv == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def step(mask_keys: bool, mask_causal: bool):
        """One key block folded into the running max / denominator /
        accumulator."""
        s, ok = scores(mask_keys, mask_causal)
        m_prev = m_sc[...]                                    # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        l, pv = weigh(s, ok, m_new)
        corr = jnp.exp(m_prev - m_new)                        # (bq, 1)
        l_sc[...] = l_sc[...] * corr + l
        acc_sc[...] = acc_sc[...] * corr + pv
        m_sc[...] = m_new

    if not causal:
        step(padded, False)
    else:
        # key blocks wholly above the diagonal are skipped, not masked
        # (their index map re-names the last block needed, so nothing is
        # fetched for them either); blocks wholly below it need no causal
        # mask
        needed = kv * block_k <= qi * block_q + block_q - 1
        crosses = (kv + 1) * block_k - 1 > qi * block_q

        @pl.when(needed & crosses)
        def _diagonal():
            step(padded, True)

        @pl.when(needed & jnp.logical_not(crosses))
        def _below():
            step(padded, False)

    @pl.when(kv == num_kv - 1)
    def _finalize():
        write(m_sc[...], l_sc[...], acc_sc[...])


def _flash_fwd_lse(q, k, v, causal, block_q, block_k, interpret):
    """Pallas forward at the given tile (a multiple of what Mosaic tiles,
    or the whole length); returns (out (B,Tq,H,Dv), lse (B,H,Tq) f32). The
    values may have a width of their own (latent attention scores over
    192 channels and weighs values of 128), and keys and values fewer
    heads than the queries: the key block of query head j is head
    j // group's, named by the index map, so K and V stay as they lie."""
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    orig_dtype = q.dtype
    b, _, h, d = q.shape
    dv = v.shape[-1]
    group = key_head_group(q, k, v)
    q, tq = _pad_seq(q, block_q)
    k, tk = _pad_seq(k, block_k)
    v, _ = _pad_seq(v, block_k)

    # (B*H, T, D): one grid row per (batch, query head); keys and values
    # (B*H/group, T, .), a row per (batch, key/value head)
    def bh(x):
        return jnp.moveaxis(x, 2, 1).reshape(
            b * x.shape[2], x.shape[1], x.shape[-1])

    qf, kf, vf = bh(q), bh(k), bh(v)
    nq, nk = qf.shape[1] // block_q, kf.shape[1] // block_k

    # row b * H + j of the queries reads row b * H/group + j // group
    def key_row(bh_):
        return bh_ if group == 1 else bh_ // group

    if causal:
        # a key block above the diagonal is never computed on: name the
        # last block this query block needs instead, which is already in
        # VMEM, so that no copy is issued for the skipped steps
        def key_block(bh_, qi, kv):
            last = (qi * block_q + block_q - 1) // block_k
            return (key_row(bh_), jnp.minimum(kv, last), 0)
    else:
        def key_block(bh_, qi, kv):
            return (key_row(bh_), kv, 0)

    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, num_kv=nk,
        causal=causal, tk_valid=tk, scale=d ** -0.5)
    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh_, qi, kv: (bh_, qi, 0)),
            pl.BlockSpec((1, block_k, d), key_block),
            pl.BlockSpec((1, block_k, dv), key_block),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda bh_, qi, kv: (bh_, qi, 0)),
            # lse keeps the scratch's (block_q, 1) column layout: a
            # trailing dim equal to the array's satisfies Mosaic's block
            # rule, and no sublane->lane relayout happens in the kernel
            pl.BlockSpec((1, block_q, 1), lambda bh_, qi, kv: (bh_, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(qf.shape[:2] + (dv,), orig_dtype),
            jax.ShapeDtypeStruct(qf.shape[:2] + (1,), jnp.float32),
        ],
        # one key block carries nothing from step to step
        scratch_shapes=[] if nk == 1 else [
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)

    out = out.reshape(b, h, out.shape[1], dv)  # already orig_dtype via
    out = jnp.moveaxis(out, 1, 2)[:, :tq]      # pallas out_shape
    lse = lse.reshape(b, h, -1)[:, :, :tq]     # (B, H, Tq)
    return out, lse


def _flash_bwd_xla(q, k, v, out, lse, do, causal, k_chunk):
    """Flash-attention backward as a pure-XLA scan over k blocks (the
    standard dV/dK/dQ recomputation driven by the saved logsumexp).
    Pure XLA by design: it compiles on every backend and avoids the
    interpret-vs-Mosaic gap the histogram kernels hit on real v5e, while
    keeping O(T) score memory like the forward."""
    f32 = jnp.float32
    b, tq, h, d = q.shape
    tk = k.shape[1]
    scale = d ** -0.5
    qf = jnp.moveaxis(q, 2, 1).astype(f32)            # (B, H, Tq, D)
    dof = jnp.moveaxis(do, 2, 1).astype(f32)
    of = jnp.moveaxis(out, 2, 1).astype(f32)
    delta = (dof * of).sum(-1)                        # (B, H, Tq)

    k_chunk = min(k_chunk, max(tk, 1))
    kp_, _ = _pad_seq(k, k_chunk)
    vp_, _ = _pad_seq(v, k_chunk)
    kf = jnp.moveaxis(kp_, 2, 1).astype(f32)          # (B, H, Tk+, D)
    vf = jnp.moveaxis(vp_, 2, 1).astype(f32)
    nk = kf.shape[2] // k_chunk
    kr = jnp.moveaxis(kf.reshape(b, h, nk, k_chunk, d), 2, 0)
    vr = jnp.moveaxis(vf.reshape(b, h, nk, k_chunk, v.shape[-1]), 2, 0)
    kpos = jnp.arange(nk * k_chunk).reshape(nk, k_chunk)
    qpos = jnp.arange(tq)

    def body(dq_acc, xs):
        kb, vb, kp = xs                               # (B,H,kc,D), (kc,)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kb,
                       preferred_element_type=f32) * scale
        ok = (kp < tk)[None, None, None, :]
        if causal:
            ok = ok & (qpos[:, None] >= kp[None, :])[None, None]
        # lse is +inf on fully-masked rows -> p = 0 there
        p = jnp.where(ok, jnp.exp(s - lse[..., None]), 0.0)
        dv_b = jnp.einsum("bhqk,bhqd->bhkd", p, dof,
                          preferred_element_type=f32)
        dp = jnp.einsum("bhqd,bhkd->bhqk", dof, vb,
                        preferred_element_type=f32)
        ds = p * (dp - delta[..., None])
        dq_acc = dq_acc + jnp.einsum(
            "bhqk,bhkd->bhqd", ds, kb, preferred_element_type=f32) * scale
        dk_b = jnp.einsum("bhqk,bhqd->bhkd", ds, qf,
                          preferred_element_type=f32) * scale
        return dq_acc, (dk_b, dv_b)

    dq, (dks, dvs) = jax.lax.scan(
        body, jnp.zeros_like(qf), (kr, vr, kpos))
    dk = jnp.moveaxis(dks, 0, 2).reshape(b, h, nk * k_chunk, d)[:, :, :tk]
    dv = jnp.moveaxis(dvs, 0, 2).reshape(
        b, h, nk * k_chunk, v.shape[-1])[:, :, :tk]
    return (jnp.moveaxis(dq, 1, 2).astype(q.dtype),
            jnp.moveaxis(dk, 1, 2).astype(k.dtype),
            jnp.moveaxis(dv, 1, 2).astype(v.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_diff(q, k, v, causal, block_q, block_k, bwd_chunk, interpret):
    out, _ = _flash_fwd_lse(q, k, v, causal, block_q, block_k, interpret)
    return out


def _flash_diff_fwd(q, k, v, causal, block_q, block_k, bwd_chunk, interpret):
    out, lse = _flash_fwd_lse(q, k, v, causal, block_q, block_k, interpret)
    return out, (q, k, v, out, lse)


def _flash_diff_bwd(causal, block_q, block_k, bwd_chunk, interpret, res, do):
    q, k, v, out, lse = res
    group = key_head_group(q, k, v)
    if group == 1:
        return _flash_bwd_xla(q, k, v, out, lse, do, causal, bwd_chunk)
    # grouped-query heads: a member of every group at a time against the
    # one K and V; a key/value head's gradient is the sum over its members
    b, tq, h, _d = q.shape

    def members(x):
        return x.reshape(b, tq, h // group, group, x.shape[-1])

    dq, dk, dv = jax.vmap(
        lambda q1, out1, lse1, do1: _flash_bwd_xla(
            q1, k, v, out1, lse1, do1, causal, bwd_chunk),
        in_axes=(3, 3, 2, 3), out_axes=(3, 0, 0))(
            members(q), members(out), lse.reshape(b, h // group, group, tq),
            members(do))
    return (dq.reshape(q.shape), dk.sum(0).astype(k.dtype),
            dv.sum(0).astype(v.dtype))


_flash_diff.defvjp(_flash_diff_fwd, _flash_diff_bwd)


def flash_attention(q, k, v, causal: bool = False,
                    block_q: int | None = None, block_k: int | None = None,
                    bwd_chunk: int | None = 128, interpret: bool = False):
    """Pallas TPU flash attention, DIFFERENTIABLE: the forward is the
    Pallas online-softmax kernel (score tile only in VMEM) and the
    backward is the standard flash recomputation as a pure-XLA k-block
    scan driven by the kernel's saved logsumexp. Same contract as
    `dense_attention`, grouped-query heads included (k and v with a
    divisor of q's heads).

    The forward's tile is `flash_tiles`' unless a test names one.
    `bwd_chunk` is the backward scan's key chunk and no tile: the scan
    materialises a (B, H, Tq, chunk) float32 score slab in HBM, so it
    does not follow the forward to 512 or 1024 (None: the forward's key
    tile). `interpret=True` runs the forward kernel on CPU for tests."""
    tq, tk = q.shape[1], k.shape[1]
    rule_q, rule_k = flash_tiles(tq, tk, q.dtype)
    block_q = rule_q if block_q is None else min(block_q, max(tq, 1))
    block_k = rule_k if block_k is None else min(block_k, max(tk, 1))
    # counted where the call is traced: once a compiled shape
    get_registry().counter(
        "mmlspark_tpu_flash_calls_total",
        "flash-attention forward calls traced, by the tile they run at",
        labels=("tile", "causal")).labels(
            tile=f"{block_q}x{block_k}", causal=str(causal).lower()).inc()
    group = key_head_group(q, k, v)
    if group > 1:
        get_registry().counter(
            "mmlspark_tpu_flash_grouped_calls_total",
            "flash-attention forward calls traced whose query heads share "
            "key/value heads, by the heads a key/value head serves",
            labels=("group", "tile")).labels(
                group=str(group), tile=f"{block_q}x{block_k}").inc()
    return _flash_diff(q, k, v, causal, block_q, block_k,
                       block_k if bwd_chunk is None else bwd_chunk, interpret)


def causal_attention(q, k, v, impl: str = "flash", **flash_options):
    """Plain causal attention by the tier's name: "flash" (None: the
    backward scans the keys a forward tile at a time; `flash_options` are
    that tier's, for tests), "chunked" or "dense" (in the queries' type)."""
    if impl == "flash":
        return flash_attention(q, k, v, causal=True, bwd_chunk=None,
                               **flash_options)
    if impl == "chunked":
        return chunked_attention(q, k, v, causal=True)
    if impl == "dense":
        return dense_attention(q, k, v, causal=True).astype(q.dtype)
    raise ValueError(f"unknown attention impl {impl!r}; have 'flash', "
                     "'chunked', 'dense'")


# --------------------------------------------------------------------- #
# a window read exactly, the windows before it as chunk summaries (EVA)  #
# --------------------------------------------------------------------- #

def eva_summaries(k, v, phi, mu, chunk: int, upto: int | None = None):
    """One pooled key and value for every chunk of `chunk` positions (EVA's
    control-variate estimate of a chunk, arXiv 2302.04542 section 4, with
    a learned vector a head in place of a sampled one). k, v: (B, T, H,
    D); phi, mu: (H, D) float32. For chunk c, over its positions m:
    a_m = softmax_m(k_m . phi / sqrt(D)); kbar_c = sum_m a_m k_m + mu;
    vbar_c = sum_m a_m v_m. -> kbar, vbar (B, C, H, D) in k's and v's
    types for the C whole chunks of the first `upto` positions (all T by
    default). Float32 throughout and rounded once. This is the dense and
    chunked tiers' pooling, in XLA, which keeps float32 copies of k and v
    (8.4 ms at 2 x 32768 x 32 x 128 on a v5e); the flash tier pools in a
    kernel of its own (`_eva_pool_kernel`, 1.5 ms; PERF.md, PR 33)."""
    b, t, h, d = k.shape
    c = (t if upto is None else min(upto, t)) // chunk
    f32 = jnp.float32

    def chunks(x):
        return x[:, :c * chunk].reshape(b, c, chunk, h, x.shape[-1]).astype(
            f32)

    kc, vc = chunks(k), chunks(v)
    scores = (kc * phi.astype(f32)).sum(-1) * d ** -0.5       # (B, C, m, H)
    a = jax.nn.softmax(scores, axis=2)[..., None]
    kbar = (a * kc).sum(2) + mu.astype(f32)
    vbar = (a * vc).sum(2)
    return kbar.astype(k.dtype), vbar.astype(v.dtype)


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
    q, _ = _pad_seq(q, window)
    k, _ = _pad_seq(k, window)
    v, _ = _pad_seq(v, window)
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


def _eva_kernel(q_ref, k_ref, v_ref, kb_ref, vb_ref, o_ref, m_sc, l_sc,
                acc_sc, *, block_q, block_k, block_s, n_local, n_remote,
                window, per_window, scale):
    """A block of queries, which lies in ONE window, over the grid's last
    axis: first the `n_local` key blocks of its window (those above the
    diagonal skipped), then the `n_remote` blocks of summaries (those past
    the `per_window` x window index that lie before it skipped), all into
    one running maximum, denominator and accumulator."""
    import jax.experimental.pallas as pl

    qi, j = pl.program_id(1), pl.program_id(2)
    first = qi * block_q
    own = first // window                       # this block's window

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def fold(keys_ref, values_ref, counts):
        """One block of keys or summaries folded in; `counts(shape)` is
        what of the (bq, bk) tile counts, or None for all of it."""
        s = jax.lax.dot_general(
            q_ref[0], keys_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        ok = None if counts is None else counts(s.shape)
        if ok is not None:
            s = jnp.where(ok, s, _NEG_INF)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if ok is not None:
            # a row with nothing yet has m_new == _NEG_INF: exp(0), not 0
            p = jnp.where(ok, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = l_sc[...] * corr + p.sum(-1, keepdims=True)
        acc_sc[...] = acc_sc[...] * corr + jax.lax.dot_general(
            p.astype(values_ref.dtype), values_ref[0],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    # the window's own keys, causally
    kfirst = (own * (window // block_k) + j) * block_k
    needed = (j < n_local) & (kfirst <= first + block_q - 1)
    crosses = kfirst + block_k - 1 > first

    def causal(shape):
        return (first + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
                >= kfirst + jax.lax.broadcasted_iota(jnp.int32, shape, 1))

    @pl.when(needed & crosses)
    def _diagonal():
        fold(k_ref, v_ref, causal)

    @pl.when(needed & jnp.logical_not(crosses))
    def _below():
        fold(k_ref, v_ref, None)

    # the summaries of the windows before it
    sfirst = (j - n_local) * block_s
    seen = own * per_window
    reads = (j >= n_local) & (sfirst < seen)
    if per_window % block_s:
        # a block of summaries may end past the windows before this one
        partly = sfirst + block_s > seen

        @pl.when(reads & partly)
        def _edge():
            fold(kb_ref, vb_ref, lambda shape: sfirst
                 + jax.lax.broadcasted_iota(jnp.int32, shape, 1) < seen)

        reads = reads & jnp.logical_not(partly)

    @pl.when(reads)
    def _before():
        fold(kb_ref, vb_ref, None)

    @pl.when(j == n_local + n_remote - 1)
    def _finalize():
        o_ref[0] = (acc_sc[...] / l_sc[...]).astype(o_ref.dtype)


def _eva_pool_kernel(k_ref, v_ref, phi_ref, mu_ref, kb_ref, vb_ref, *,
                     chunk, pooled_blocks, scale):
    """`eva_summaries` for one block of positions of one head, read where
    the attention kernel reads them: (positions, D) in, (positions / chunk,
    D) out, float32 inside. A block past the positions that are pooled
    (the padding of the summaries to whole tiles) is zeros: a masked
    summary still meets the values' product, where 0 x NaN is NaN."""
    import jax.experimental.pallas as pl

    f32 = jnp.float32

    @pl.when(pl.program_id(1) >= pooled_blocks)
    def _padding():
        kb_ref[0] = jnp.zeros_like(kb_ref[0])
        vb_ref[0] = jnp.zeros_like(vb_ref[0])

    @pl.when(pl.program_id(1) < pooled_blocks)
    def _pool():
        def chunks(ref):
            x = ref[0].astype(f32)
            return x.reshape(x.shape[0] // chunk, chunk, x.shape[1])

        kc = chunks(k_ref)
        scores = (kc * phi_ref[0].astype(f32)).sum(-1, keepdims=True) * scale
        e = jnp.exp(scores - scores.max(1, keepdims=True))
        a = e / e.sum(1, keepdims=True)                  # (chunks, m, 1)
        kb_ref[0] = ((a * kc).sum(1) + mu_ref[0].astype(f32)).astype(
            kb_ref.dtype)
        vb_ref[0] = (a * chunks(v_ref)).sum(1).astype(vb_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "window", "chunk", "block_q", "block_k", "block_s", "interpret"))
def _eva_flash(q, k, v, phi, mu, kbar=None, vbar=None, *, window, chunk,
               block_q, block_k, block_s, interpret=False):
    """The Pallas forward, jitted by itself: traced and lowered once a
    shape, not once a layer. Two calls: `eva_pool_*` pools the windows
    before the last a window a step (unless a test hands the summaries
    in), `eva_attn_*` attends. Nothing of size T x T or T x T / chunk is
    ever whole in HBM; a key block outside the query's window and a
    summary block at or past it are neither fetched (their index maps
    name a block that is already in VMEM) nor computed on."""
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    b, t, h, d = q.shape
    dv = v.shape[-1]
    per_window = window // chunk
    windows_before = -(-t // window) - 1
    q, _ = _pad_seq(q, max(block_q, block_k))
    k, _ = _pad_seq(k, max(block_q, block_k))
    v, _ = _pad_seq(v, max(block_q, block_k))

    def bh(x):
        return jnp.moveaxis(x, 2, 1).reshape(b * h, x.shape[1], x.shape[-1])

    qf, kf, vf = bh(q), bh(k), bh(v)
    n_local = window // block_k
    n_remote = -(-windows_before * per_window // block_s)
    tag = f"w{window}c{chunk}"      # a device trace's readers select by name

    if kbar is None:
        # as many rows as whole tiles of summaries, a window's a step
        steps = -(-n_remote * block_s // per_window)
        with jax.named_scope("eva.summarise"):
            kbf, vbf = pl.pallas_call(
                functools.partial(
                    _eva_pool_kernel, chunk=chunk,
                    pooled_blocks=windows_before, scale=d ** -0.5),
                grid=(b * h, steps),
                in_specs=[
                    pl.BlockSpec((1, window, d), lambda bh_, i: (
                        bh_, jnp.minimum(i, windows_before - 1), 0)),
                    pl.BlockSpec((1, window, dv), lambda bh_, i: (
                        bh_, jnp.minimum(i, windows_before - 1), 0)),
                    pl.BlockSpec((1, 1, d), lambda bh_, i: (bh_ % h, 0, 0)),
                    pl.BlockSpec((1, 1, d), lambda bh_, i: (bh_ % h, 0, 0))],
                out_specs=[
                    pl.BlockSpec((1, per_window, d),
                                 lambda bh_, i: (bh_, i, 0)),
                    pl.BlockSpec((1, per_window, dv),
                                 lambda bh_, i: (bh_, i, 0))],
                out_shape=[
                    jax.ShapeDtypeStruct((b * h, steps * per_window, d),
                                         k.dtype),
                    jax.ShapeDtypeStruct((b * h, steps * per_window, dv),
                                         v.dtype)],
                interpret=interpret, name=f"eva_pool_{tag}",
            )(kf, vf, phi[:, None], mu[:, None])
    else:
        kbf, vbf = (bh(_pad_seq(x[:, :windows_before * per_window],
                                block_s)[0]) for x in (kbar, vbar))

    def key_block(bh_, qi, j):
        own = (qi * block_q) // window
        last = (qi * block_q + block_q - 1) // block_k     # the diagonal's
        return (bh_, jnp.minimum(own * n_local + jnp.minimum(j, n_local - 1),
                                 last), 0)

    def summary_block(bh_, qi, j):
        seen = ((qi * block_q) // window) * per_window
        last = jnp.maximum(-(-seen // block_s) - 1, 0)
        return (bh_, jnp.clip(j - n_local, 0, last), 0)

    def query_block(bh_, qi, j):
        return (bh_, qi, 0)

    with jax.named_scope("eva.attend"):
        out = pl.pallas_call(
            functools.partial(
                _eva_kernel, block_q=block_q, block_k=block_k,
                block_s=block_s, n_local=n_local, n_remote=n_remote,
                window=window, per_window=per_window, scale=d ** -0.5),
            grid=(b * h, qf.shape[1] // block_q, n_local + n_remote),
            in_specs=[pl.BlockSpec((1, block_q, d), query_block),
                      pl.BlockSpec((1, block_k, d), key_block),
                      pl.BlockSpec((1, block_k, dv), key_block),
                      pl.BlockSpec((1, block_s, d), summary_block),
                      pl.BlockSpec((1, block_s, dv), summary_block)],
            out_specs=pl.BlockSpec((1, block_q, dv), query_block),
            out_shape=jax.ShapeDtypeStruct(qf.shape[:2] + (dv,), q.dtype),
            scratch_shapes=[pltpu.VMEM((block_q, 1), jnp.float32),
                            pltpu.VMEM((block_q, 1), jnp.float32),
                            pltpu.VMEM((block_q, dv), jnp.float32)],
            interpret=interpret, name=f"eva_attn_{tag}",
        )(qf, kf, vf, kbf, vbf)
    return jnp.moveaxis(out.reshape(b, h, -1, dv), 1, 2)[:, :t]


def eva_attention(q, k, v, phi, mu, window: int, chunk: int,
                  impl: str = "flash", summaries=None,
                  block_q: int | None = None, block_k: int | None = None,
                  block_s: int | None = None, interpret: bool = False):
    """Causal attention in which query t, in window w = t // window, reads
    in ONE softmax the keys of its own window at or before it and the
    summary of every chunk of the windows before it (none of its own):
    Z = sum_L exp(s q.k_m) + sum_R exp(s q.kbar_c), out = (sum_L exp(..)
    v_m + sum_R exp(..) vbar_c) / Z, s = 1 / sqrt(D). q, k, v: (B, T, H,
    D); phi, mu: (H, D), the learned vectors the summaries are pooled
    with (`eva_summaries`; the last window's chunks are read by nobody
    and are not pooled). `summaries` (kbar, vbar), each (B, C, H, D) with
    C at least the chunks of every window but the last, takes their
    place (tests). `window` is a multiple of `chunk`. A row of at most
    one window is plain causal attention and takes that tier of it.
    `impl`: "dense" (one masked score matrix over [summaries; keys]),
    "chunked" (XLA, a window of keys at a time) or "flash" (Pallas: the
    pooling and the attention a kernel each, forward only; tiles by
    `flash_tiles` unless a test names them)."""
    if window % chunk:
        raise ValueError(f"a window of {window} positions is not whole "
                         f"chunks of {chunk}")
    t = q.shape[1]
    if t <= window:
        return causal_attention(q, k, v, impl,
                                **({"interpret": True} if interpret else {}))
    before = (-(-t // window) - 1) * window      # positions that are pooled
    if summaries is not None and summaries[0].shape[1] < before // chunk:
        raise ValueError(
            f"a row of {t} positions reads {before // chunk} summaries "
            f"(windows of {window}, chunks of {chunk}); got "
            f"{summaries[0].shape[1]}")
    if impl not in ("dense", "chunked", "flash"):
        raise ValueError(f"unknown attention impl {impl!r}; have 'flash', "
                         "'chunked', 'dense'")
    if impl != "flash":
        if summaries is None:
            with jax.named_scope("eva.summarise"):
                summaries = eva_summaries(k, v, phi, mu, chunk, upto=before)
        kbar, vbar = (x[:, :before // chunk] for x in summaries)
        with jax.named_scope("eva.attend"):
            return (_eva_dense if impl == "dense" else _eva_chunked)(
                q, k, v, kbar, vbar, window, chunk)
    rule_q, rule_k = flash_tiles(t, t, q.dtype, window=window)
    block_q, block_k = block_q or rule_q, block_k or rule_k
    block_s = block_s or flash_tiles(t, before // chunk, q.dtype)[1]
    if window % block_q or window % block_k:
        raise ValueError(f"tiles of {block_q} x {block_k} do not divide a "
                         f"window of {window}")
    # counted where the call is traced (the kernels are traced once a
    # shape, this once a layer)
    get_registry().counter(
        "mmlspark_tpu_eva_calls_total",
        "windowed-and-summarised attention forward calls traced, by the "
        "window, the chunk and the tile (queries x keys x summaries)",
        labels=("window", "chunk", "tile")).labels(
            window=str(window), chunk=str(chunk),
            tile=f"{block_q}x{block_k}x{block_s}").inc()
    return _eva_flash(q, k, v, phi, mu, *(summaries or ()), window=window,
                      chunk=chunk, block_q=block_q, block_k=block_k,
                      block_s=block_s, interpret=interpret)


# --------------------------------------------------------------------- #
# param-compatible self-attention module                                #
# --------------------------------------------------------------------- #

class SelfAttention(nn.Module):
    """Multi-head self-attention with a selectable attention core.

    Parameter tree is IDENTICAL to flax's nn.MultiHeadDotProductAttention
    (submodules query/key/value/out with the same DenseGeneral layouts) so
    checkpoints, the serialize registry, and the HF import spec
    (import_weights.TRANSFORMER_SPEC -> params/attn_i/query/kernel ...)
    are impl-agnostic.

    impl: "dense" (reference math), "chunked" (O(T) scan, differentiable),
    "flash" (Pallas TPU kernel, differentiable via custom_vjp). On the CPU
    backend, where Mosaic cannot lower, "flash" runs the chunked tier so
    CPU tests can load the same model file; on any other backend the
    kernel is used and a failure to compile it propagates.
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
        proj = functools.partial(
            nn.DenseGeneral, features=(self.num_heads, head_dim),
            dtype=self.dtype)
        q = proj(name="query")(x)
        k = proj(name="key")(x)
        v = proj(name="value")(x)

        impl = self.impl
        if impl == "flash" and jax.default_backend() == "cpu":
            impl = "chunked"
        if impl == "dense":
            out = dense_attention(q, k, v, causal=self.causal)
        elif impl == "chunked":
            out = chunked_attention(q, k, v, causal=self.causal)
        elif impl == "flash":
            out = flash_attention(q, k, v, causal=self.causal)
        else:
            raise ValueError(f"unknown attention impl {self.impl!r}")
        return nn.DenseGeneral(features=d_model, axis=(-2, -1),
                               dtype=self.dtype, name="out")(out)
