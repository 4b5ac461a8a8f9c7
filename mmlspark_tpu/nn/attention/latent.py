"""Latent attention: a score of two parts, read where they lie. The kernel
is `fold._flash_fold` over the sum of two products; the forward reads what
the projections wrote and lays nothing out again in HBM; the backward is the
plain flash one over the concatenated operands."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import flash, fold, layout


def _latent_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref, lse_ref,
                   *scratch, **static):
    """The latent score as what it is, a sum of two products: a head's own
    channels against its own keys, and its rotary channels against the ONE
    rotary key. `qr_ref` holds the rotary channels of the heads that share
    a lane block, `kr_ref` the rotary key in this head's lanes of it and
    zeros in the others. The two parts are set side by side in VMEM, lane
    blocks both, so that the MXU sums them in ONE product's float32
    accumulator: added as two (bq, bk) tiles they cost the VPU a pass over
    the score tile (PERF.md, PR 34)."""
    def products(rows=None, keys=None):
        return jax.lax.dot_general(
            jnp.concatenate([fold._block(qn_ref, rows),
                             fold._block(qr_ref, rows)], -1),
            jnp.concatenate([fold._block(kn_ref, keys),
                             fold._block(kr_ref, keys)], -1),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    fold._flash_fold(products, v_ref, o_ref, lse_ref, scratch, **static)


def _latent_concatenated(q_nope, q_rope, kv, k_rope):
    """The operands as one product of nope + rope channels takes them:
    q, k (B, T, H, nope + rope), the rotary key broadcast to every head,
    and v (B, T, H, Dv) sliced off the keys' projection."""
    nope = q_nope.shape[-1]
    b, t, h, _ = kv.shape
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(k_rope[:, :, None], (b, t, h, k_rope.shape[-1]))],
        -1)
    return q, k, kv[..., nope:]


def _latent_in_place(q_nope, q_rope, kv) -> bool:
    """The rule, by shape: a head's own key channels and its values are a
    lane block each of the keys' projection (equal widths, multiples of
    128), and the rotary channels of whole heads fill a lane block."""
    h, nope, rope = q_nope.shape[2], q_nope.shape[-1], q_rope.shape[-1]
    return (nope % 128 == 0 and kv.shape[-1] == 2 * nope
            and 128 % rope == 0 and (h * rope) % 128 == 0)


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _latent_fwd_lse(q_nope, q_rope, kv, k_rope, block_q, block_k, interpret):
    """The causal Pallas forward of latent attention with NOTHING laid out
    again in HBM (shapes by `_latent_in_place`). q_nope (B, T, H, nope)
    and q_rope (B, T, H, rope) are read as the q fusion wrote them; the
    head's keys and values are lane blocks 2j and 2j + 1 of `kv` (B, T, H,
    nope + Dv), the keys' projection itself; the ONE rotary key k_rope (B,
    T, rope) is read by every head and never broadcast: the heads that
    share a lane block of q_rope read it against the key placed in their
    own lanes of 128, zeros in the others (a copy of the one key a head of
    the block: (B, T, 128 / rope x 128)). -> (out (B, T, H, Dv), lse (B,
    H, T)): the score is summed over nope + 128 channels, the zeros among
    them, where the plain kernel sums nope + rope. Jitted by itself, as
    `eva._eva_flash` is: lowered once a shape, not once a layer, so the call
    is named by its widths (`mla_attn_n128r64`; a device trace's readers
    select `mla_attn_*`) and not by the layer's scope."""
    b, _, h, nope = q_nope.shape
    rope = q_rope.shape[-1]
    share = 128 // rope                    # heads to a lane block of q_rope
    placed = jnp.concatenate(
        [jnp.pad(k_rope, ((0, 0), (0, 0), (i * rope, 128 - (i + 1) * rope)))
         for i in range(share)], -1)                    # (B, T, share x 128)
    qn, tq = layout._pad_seq(layout._rows(q_nope, True), block_q)
    qr, _ = layout._pad_seq(layout._rows(q_rope, True), block_q)
    kvf, tk = layout._pad_seq(layout._rows(kv, True), block_k)
    placed, _ = layout._pad_seq(placed, block_k)
    out, lse = fold._flash_call(
        _latent_kernel,
        [(qn, nope, lambda b_, j, i: (b_, i, j)),
         (qr, 128, lambda b_, j, i: (b_, i, j // share))],
        [(kvf, nope, lambda b_, j, i: (b_, i, 2 * j)),
         (placed, 128, lambda b_, j, i: (b_, i, j % share))],
        (kvf, nope, lambda b_, j, i: (b_, i, 2 * j + 1)),
        lambda b_, j, i: (b_, i, j),
        jax.ShapeDtypeStruct(qn.shape, qn.dtype),
        b=b, h=h, tk=tk, causal=True, scale=(nope + rope) ** -0.5,
        block_q=block_q, block_k=block_k, interpret=interpret,
        name=f"mla_attn_n{nope}r{rope}")
    return (layout._heads(out[:, :tq], b, h, True),
            lse.reshape(b, h, -1)[:, :, :tq])


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _latent_diff(q_nope, q_rope, kv, k_rope, block_q, block_k, interpret):
    return _latent_fwd_lse(q_nope, q_rope, kv, k_rope, block_q, block_k,
                           interpret)[0]


def _latent_diff_fwd(q_nope, q_rope, kv, k_rope, block_q, block_k, interpret):
    out, lse = _latent_fwd_lse(q_nope, q_rope, kv, k_rope, block_q, block_k,
                               interpret)
    return out, (q_nope, q_rope, kv, k_rope, out, lse)


def _latent_diff_bwd(block_q, block_k, interpret, res, do):
    """The plain flash backward over the concatenated operands, as the
    forward used to build them; the rotary key's gradient is the sum over
    the heads that read it."""
    q_nope, q_rope, kv, k_rope, out, lse = res
    nope = q_nope.shape[-1]
    dq, dk, dv = flash._flash_bwd_xla(
        *_latent_concatenated(q_nope, q_rope, kv, k_rope), out, lse, do,
        True, block_k)
    return (dq[..., :nope], dq[..., nope:],
            jnp.concatenate([dk[..., :nope], dv], -1),
            dk[..., nope:].sum(2).astype(k_rope.dtype))


_latent_diff.defvjp(_latent_diff_fwd, _latent_diff_bwd)


def latent_attention(q_nope, q_rope, kv, k_rope, impl: str = "flash",
                     block_q: int | None = None, block_k: int | None = None,
                     interpret: bool = False):
    """Causal multi-head latent attention over the operands as the
    projections leave them: q_nope (B, T, H, nope), q_rope (B, T, H, rope)
    (rotary applied), kv (B, T, H, nope + Dv) (a head's own key channels,
    then its values) and the ONE rotary key k_rope (B, T, rope) that every
    head reads. -> (B, T, H, Dv); scores over sqrt(nope + rope).

    "flash" at widths of whole lanes (`_latent_in_place`: nope = Dv a
    multiple of 128, the rotary channels of whole heads filling 128) is a
    path of its own for the score's assembly (`_latent_fwd_lse`: nothing
    sliced, concatenated, broadcast or transposed in HBM), differentiable
    with the plain flash backward. Every other tier and shape builds q and
    k of nope + rope channels and v, and is `causal_attention`."""
    if impl != "flash" or not _latent_in_place(q_nope, q_rope, kv):
        if impl == "flash":
            fold._count_operands("mla", False)
        return flash.causal_attention(
            *_latent_concatenated(q_nope, q_rope, kv, k_rope), impl,
            **({"interpret": True} if interpret else {}))
    t = q_nope.shape[1]
    # under the plain forward's counter too: its fold, at its tile
    block_q, block_k = flash._call_tiles(t, t, q_nope.dtype, block_q,
                                         block_k, True, "mla")
    fold._count_heads_a_step("mla", 1, 1)     # each head its own keys
    fold._count_operands("mla", True)
    return _latent_diff(q_nope, q_rope, kv, k_rope, block_q, block_k,
                        interpret)
