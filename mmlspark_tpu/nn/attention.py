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
  VMEM scratch across the sequential key-block grid dimension (the
  maximum and the denominator lane-dense, 128 lanes a row: `_fold_tile`,
  the one step every fold here takes). The tile is chosen here, by
  `flash_tiles`, from the lengths and the dtype.
  DIFFERENTIABLE via `jax.custom_vjp`: the kernel also emits the per-row
  logsumexp, and the backward is the standard flash recomputation as a
  pure-XLA k-block scan (compiles on every backend; O(T) score memory).

A fourth core, ``eva_attention``, reads TWO sets of keys in one softmax
(EVA, arXiv 2302.04542): the keys of the query's own window, exactly and
causally, and one pooled key and value (``eva_summaries``) for every chunk
of the windows before it; the same three tiers behind one switch (its
kernel, `_eva_kernel`, folds its edge tiles over what their masks leave
too: the diagonal's in `_edge_parts` parts, a block of summaries that ends
past the ones seen over a key prefix, `_edge_prefixes`). A fifth,
``causal_attention(window=...)``, is a window that SLIDES with the query
(query t reads keys t - window + 1 .. t): the plain flash fold over the key
blocks a query block's band touches, both edges masked in the kernel (an
edge tile in parts, what the mask would erase whole not computed:
`_edge_parts`); the same three tiers, the chunked one its backward.

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
import math
import operator
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..observability.metrics import get_registry
from ..parallel.ring_attention import (dense_attention, key_head_group,
                                       over_key_heads)

__all__ = ["dense_attention", "chunked_attention", "flash_attention",
           "flash_tiles", "causal_attention", "band_tiles", "band_tile_pairs",
           "latent_attention",
           "eva_summaries", "eva_attention", "eva_tile_pairs",
           "rotary_in_lanes",
           "rotary_lanes_whole", "HeadsDense", "HeadsOut", "SelfAttention"]

_NEG_INF = -1e30  # large-negative instead of -inf: keeps exp/max NaN-free


def _pad_seq(x, mult):
    t = x.shape[1]
    pad = (-t) % mult
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
    return x, t


# --------------------------------------------------------------------- #
# the layout the Pallas kernels speak                                    #
# --------------------------------------------------------------------- #

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


def rotary_cos_sin(t: int, half: int, theta: float):
    """cos and sin of positions 0 .. t-1 times the `half` rotary
    frequencies theta ** (-i / half): (t, half) float32 each."""
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq
    return jnp.cos(angle), jnp.sin(angle)


def _rotary_tables(t: int, width: int, theta: float):
    """cos and sin of rotary positions 0 .. t-1 over 128 lanes, rotate-half
    layout a head of `width` channels (channel i pairs with i + width/2):
    [cos, cos] and [-sin, sin] a head, 128 / width heads a lane block.
    (t, 128) float32 each."""
    cos, sin = rotary_cos_sin(t, width // 2, theta)
    return (jnp.tile(jnp.concatenate([cos, cos], -1), (1, 128 // width)),
            jnp.tile(jnp.concatenate([-sin, sin], -1), (1, 128 // width)))


def _rotary_kernel(x_ref, cos_ref, sin_ref, o_ref, *, width):
    """x cos + partner(x) [-sin, sin] on ONE lane block of a block of
    positions (the grid walks the lane blocks): a channel's partner is
    `width / 2` lanes away inside its head, which is a lane rotation of
    every vector register and nothing in HBM."""
    import jax.experimental.pallas.tpu as pltpu

    half = width // 2
    x = x_ref[0].astype(jnp.float32)                          # (rows, 128)
    if width == 128:
        partner = pltpu.roll(x, half, 1)
    else:
        lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        partner = jnp.where(lane % width < half,
                            pltpu.roll(x, 128 - half, 1),     # x[l + half]
                            pltpu.roll(x, half, 1))           # x[l - half]
    o_ref[0] = (x * cos_ref[...] + partner * sin_ref[...]).astype(o_ref.dtype)


# positions a grid step at inputs of 2 bytes (half as many at 4): blocks of
# 1 MB in and out and 2 MB of each table, double-buffered, inside the
# default 16 MB of scoped VMEM. The largest wins, as for `flash_tiles`
# (PERF.md, PR 35)
_ROTARY_ROWS = 4096


@functools.partial(jax.jit, static_argnames=("width", "theta", "rows",
                                             "interpret"))
def _rotary_flat(flat, *, width, theta, rows, interpret=False):
    """`rotary_in_lanes` on (B, T, heads x width) as it lies, `rows`
    positions a grid step. Jitted by itself, `theta` static, as
    `_eva_flash` is: traced and lowered once a shape, not once a tensor
    and layer (a model's q and k share one body), and the lane blocks of
    a row are the grid's last axis, not a Python loop in the body
    (PERF.md, PR 35: PR 34's form cost a warm start 4 s)."""
    import jax.experimental.pallas as pl

    b, t, lanes = flat.shape
    flat, _ = _pad_seq(flat, rows)
    cos, sin = _rotary_tables(flat.shape[1], width, theta)

    # lane blocks last: a block of positions keeps its tables across them
    def block(b_, i, j):
        return (b_, i, j)

    def table(b_, i, j):
        return (i, 0)

    out = pl.pallas_call(
        functools.partial(_rotary_kernel, width=width),
        grid=(b, flat.shape[1] // rows, lanes // 128),
        in_specs=[pl.BlockSpec((1, rows, 128), block),
                  pl.BlockSpec((rows, 128), table),
                  pl.BlockSpec((rows, 128), table)],
        out_specs=pl.BlockSpec((1, rows, 128), block),
        out_shape=jax.ShapeDtypeStruct(flat.shape, flat.dtype),
        interpret=interpret, name=f"rotary_c{width}",
    )(flat, cos, sin)
    return out[:, :t]


def rotary_in_lanes(x, theta: float, interpret: bool = False):
    """Rotary positions 0 .. T-1 on the channels of x (B, T, heads, c),
    rotate-half layout, float32 inside: `nn/models.py` `_rotary`'s numbers
    (a cos - b sin as a cos + b (-sin): the same bits), computed by a
    Pallas call on x IN PLACE as (B, T, heads x c), for heads of 128
    channels or pairs of heads of 64 (`rotary_lanes_whole`). XLA's own
    form slices half a head's channels, which the TPU's compiler does with
    positions in lanes: between a projection and a kernel that reads
    channels in lanes that costs a pass for the halves, one for their
    concatenation and a layout copy (PERF.md, PR 34); this is one pass.
    The reshapes stay out here, beside the projection's and the kernel's
    own, where they cancel: handed four dimensions, the jitted call gets
    them positions-minor and a copy (PERF.md, PR 35)."""
    b, t, h, c = x.shape
    # the fewest steps under the cap, of equal heights (multiples of 16):
    # a length just over the cap is not padded to twice it
    steps = -(-t // (_ROTARY_ROWS * 2 // max(x.dtype.itemsize, 2)))
    rows = t if steps == 1 else -(-t // (16 * steps)) * 16
    return _rotary_flat(x.reshape(b, t, h * c), width=c, theta=float(theta),
                        rows=rows, interpret=interpret).reshape(b, t, h, c)


def rotary_lanes_whole(heads: int, width: int) -> bool:
    """Whether `rotary_in_lanes` takes heads of this width: whole heads
    fill whole lane blocks."""
    return width in (64, 128) and (heads * width) % 128 == 0


def _count_operands(kernel: str, in_place: bool) -> None:
    """Counted where a forward is traced: which path a shape took."""
    get_registry().counter(
        "mmlspark_tpu_attention_operands_total",
        "attention forward calls traced, by the kernel and by how it reads "
        "its operands: in place where the projections wrote them, or from "
        "a head-major copy",
        labels=("kernel", "layout")).labels(
            kernel=kernel,
            layout="in_place" if in_place else "head_major").inc()


def _count_edge_parts(block_q: int, block_k: int, steps: int,
                      window: int | None = None) -> None:
    """Counted where a causal forward is traced: where the split of the
    edge tiles engaged (`_edge_parts`)."""
    get_registry().counter(
        "mmlspark_tpu_attention_edge_parts_total",
        "causal flash-attention forward calls traced (plain, latent, "
        "banded, and windowed-and-summarised), by the tile and by the "
        "parts an edge tile is folded in (1: whole, masked)",
        labels=("tile", "parts")).labels(
            tile=f"{block_q}x{block_k}",
            parts=str(_edge_parts(block_q, block_k, steps, window))).inc()


def _count_fold_rows(kernel: str, block_q: int, steps: int) -> None:
    """Counted where a flash forward is traced: in what row parts its
    folds take a tile that nothing masks (`_row_parts`; one tile a row
    holds no running statistics and stays whole)."""
    parts = _row_parts(block_q) if steps > 1 else 1
    get_registry().counter(
        "mmlspark_tpu_attention_fold_rows_total",
        "flash-attention forward calls traced (plain, latent, banded, and "
        "windowed-and-summarised), by the kernel and by the rows x parts in "
        "which a fold takes an unmasked tile (1024x1: whole; 512x2: two "
        "halves)",
        labels=("kernel", "rows")).labels(
            kernel=kernel, rows=f"{block_q // parts}x{parts}").inc()


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
    score tile, not the head, fills VMEM. What a causal mask's EDGE costs
    at that tile is cut inside the step, not by a smaller tile: a tile of
    1024 on the diagonal (or on a band's trailing edge) is folded in two
    parts of 512 queries and computes 3/4 of itself, about 3.9 us (4.3 on
    the trailing edge) where the whole tile masked takes 5.0 and an
    unmasked one 4.1 (`_edge_parts`; PERF.md, PR 41), in every fold that
    has such a tile: `_flash_fold` (plain, latent, banded) and, since PR
    43, `_eva_kernel`. Those are a call's time over its tiles. Since PR 44
    (`_fold_tile`: the running maximum and sum lane-dense, the scale in
    the exponent, an unmasked tile in two row halves) the triangle over
    16384 tokens takes 3.5 us a tile where it took 4.1; by leaving kinds
    of tile out of the kernel, an unmasked tile costs 2.4 us (3.0 before),
    the diagonal's in two parts 2.2 (2.9), and a grid step with nothing
    folded 0.5, the copies (PERF.md, PR 44).

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


def _band_first(qi, block_q: int, block_k: int, window: int):
    """The first key block that query block `qi` of a band reads: the one
    holding the key `window - 1` behind the block's first query, or 0."""
    return jnp.maximum(qi * block_q - (window - 1), 0) // block_k


def _edge_parts(block_q: int, block_k: int, steps: int,
                window: int | None = None) -> int:
    """In how many parts along the queries the causal fold takes an EDGE
    tile (the diagonal's, and a band's trailing one), from what it can see;
    1 is the whole tile, masked. Where the tiles are equal the diagonal's
    block holds the mask's edge corner to corner (and so does the trailing
    block of a window that is whole tiles), so part r of n needs only the
    keys up to (from) its own square on the edge: (n + 1) / 2n of the
    tile's products and exponentials, `edge_tile_share`. Two parts where a
    step is one of several (one tile a row keeps the single-step path) and
    a part is `_PART_ROWS` rows or more: tiles of 1024. Read on a v5e
    (PERF.md, PR 41), the kernels alone: two parts of 512 rows take 8.6%
    off the banded forward at 2 x 16384, 3.2% off the triangle there,
    15.4% off it at 2 x 2048 and 12.4% off the latent forward at 8 x 4096;
    four of 256 are no faster than two and cost a start three times the
    equations; two parts of 256 rows (tiles of 512) or of 128 LOSE 0.2 and
    0.8% (read with the mask by absolute positions: two parts then gave
    6.1, 2.4, 11.6 and 8.3%)."""
    aligned = block_q == block_k and (window is None or window % block_k == 0)
    # two parts, each whole lane blocks
    whole = block_q % 256 == 0 and block_q // 2 >= _PART_ROWS
    return 2 if steps > 1 and aligned and whole else 1


# a part of fewer rows than this stays in its tile
_PART_ROWS = 512


def _row_parts(block_q: int) -> int:
    """In how many parts along the queries a fold takes a tile that
    NOTHING masks, from what it can see; 1 is the whole tile. Two halves,
    each half's products, maximum, exponential and value product
    independent of the other's, so that the compiler overlaps one half's
    softmax with the other's products; by `_edge_parts`' rule, a part of
    `_PART_ROWS` rows or more and whole lane blocks: tiles of 1024 (inputs
    of 2 bytes). A row sums the same keys in the same order either way.
    Read on a v5e (PERF.md, PR 44), the kernels alone: two halves take
    4.6% off the triangle at 2 x 16384 (28 over 4 heads of 128), 3.4% off
    the band of 4096 there, 4.5% off heads of 64, 2.5% off
    `eva_attention` at 2 x 32768 and 1.6% off the latent forward at 8 x
    4096."""
    return 2 if block_q % 256 == 0 and block_q // 2 >= _PART_ROWS else 1


def _row_halves(block_q: int) -> tuple:
    """The rows (first, how many; None: all) of each of `_row_parts`' parts
    of a tile that nothing masks. A Python loop over them writes the fold
    once and applies it to each, as the edge tiles' parts are: ONE traced
    body unrolled by the lowering (`lax.fori_loop`) schedules the same
    bundles but costs a start more, 23 ms a plain kernel traced and lowered
    where two bodies written out cost 3 to 9, and the latent kernel's
    lowering 315 ms where 86 (PERF.md, PR 44)."""
    parts = _row_parts(block_q)
    if parts == 1:
        return (None,)
    size = block_q // parts
    return tuple((r * size, size) for r in range(parts))


def edge_tile_share(parts: int) -> float:
    """What of an edge tile the fold computes when it takes it in `parts`
    parts: part r of n is (r + 1) / n of the keys for 1 / n of the
    queries."""
    return (parts + 1) / (2 * parts)


def _block(ref, at=None):
    """A ref's (positions, channels) block, or the positions `at` (first,
    how many) of it."""
    import jax.experimental.pallas as pl

    return ref[0] if at is None else ref[0, pl.ds(*at), :]


# the lanes of a vector register: the running statistics are kept that wide
_LANES = 128
_LOG2_E = math.log2(math.e)


def _stat_lanes(*widths: int) -> int:
    """How many lanes wide the running maximum and sum are kept: 128, a
    vector register's, where the score tiles' columns are whole blocks of
    that many (every tile `flash_tiles` chooses past 128 keys); the widest
    block that divides them at a test's small tile."""
    return math.gcd(_LANES, *widths)


def _over(x, width: int):
    """A per-row statistic held replicated across its lanes, (rows, lanes),
    laid over `width` columns: whole registers repeated, never a (rows, 1)
    column permuted back over the lanes. A column broadcasts by itself."""
    lanes = x.shape[1]
    if lanes == 1 or width == lanes:
        return x
    if width < lanes:
        return x[:, :width]
    if width % lanes:
        return x[:, :1]
    return jnp.tile(x, (1, width // lanes))


def _lane_sums(p, lanes: int):
    """p's columns added up in blocks of `lanes`: (rows, lanes) partial
    sums a row, elementwise (no reduction across lanes), one block after
    the other (added by halves, six equations where eight, the compiler
    schedules a step 2% worse: PERF.md, PR 44); one lane is the row sum
    itself."""
    if lanes == 1:
        return p.sum(-1, keepdims=True)
    return functools.reduce(operator.add, jnp.split(p, p.shape[1] // lanes, 1))


def _weigh(s, ok, m, v_ref, exponent, lanes, keys=None):
    """exp((s - m) x scale) of RAW products s and their raw maximum m (a
    (rows, 1) column, or (rows, lanes) replicated), as ONE multiply an
    element: exp2 of (s - m) x `exponent`, the scale times log2 e folded in
    Python (scale > 0, so the maximum commutes with it). -> its row sums as
    `lanes` per-lane partial sums (rows, lanes), and its product with the
    `keys` of the value block (rows, Dv)."""
    p = jnp.exp2((s - _over(m, s.shape[1])) * exponent)
    if ok is not None:
        # masked entries must contribute 0 even when the whole row is
        # masked (then m == _NEG_INF and exp(s - m) == 1, not 0)
        p = jnp.where(ok, p, 0.0)
    pv = jax.lax.dot_general(
        p.astype(v_ref.dtype), _block(v_ref, keys),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    return _lane_sums(p, lanes), pv


def _fold_tile(s, ok, v_ref, scratch, exponent, rows=None, keys=None):
    """The online-softmax step every fold of this module takes (the plain,
    latent and banded forwards' `_flash_fold` and `_eva_kernel`): a float32
    tile `s` of RAW products (unscaled: `exponent` is the scale times
    log2 e, `_weigh`), masked already, of the `rows` of the query block
    against the `keys` of a source block (first, how many; None: all),
    folded into those rows of the running maximum, denominator and
    accumulator (`scratch`). `ok` is what of the tile counts where a row
    may have seen nothing yet; None where every row holds a real score, in
    the tile or from a step before it (exp(_NEG_INF - m) is 0 by itself).

    The statistics are LANE-DENSE, (block_q, `_stat_lanes`) float32: the
    running maximum (of the raw products) replicated across the lanes, the
    running sum as per-lane partial sums that only the finalisation adds
    up across lanes. As (block_q, 1) columns they cost a step a lane
    permute and a cross-lane sum a row block and most of its vector
    stores, which paced it (PERF.md, PR 44)."""
    import jax.experimental.pallas as pl

    m_sc, l_sc, acc_sc = scratch
    mine = ... if rows is None else (pl.ds(*rows), slice(None))
    m_prev = m_sc[mine]                                   # (rows, lanes)
    m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
    l, pv = _weigh(s, ok, m_new, v_ref, exponent, m_prev.shape[1], keys)
    corr = jnp.exp2((m_prev - m_new) * exponent)          # (rows, lanes)
    l_sc[mine] = l_sc[mine] * corr + l
    acc_sc[mine] = acc_sc[mine] * _over(corr, pv.shape[1]) + pv
    m_sc[mine] = m_new


def _flash_fold(products, v_ref, o_ref, lse_ref, scratch, *, block_q,
                block_k, num_kv, causal, tk_valid, scale, window=None,
                key_blocks=None):
    """What every flash forward does with a score tile, over a grid of
    (row, head, query block, key block): `products(rows, keys)` is this
    step's raw float32 products of queries and keys (over a head's
    channels, or over the latent score's two parts), of the whole (bq, bk)
    tile or of the `rows` and `keys` (first, how many) of it; the masks,
    the online softmax, the block skips and the finalisation are here.
    Told a `window` (a causal band: a query reads the `window` keys that
    end with its own), the grid's last axis is the `num_kv` blocks a query
    block's band can touch, counted from `_band_first` (of `key_blocks` in
    all), and the block that the band's trailing edge crosses is masked
    like the diagonal's.

    An EDGE tile, where `_edge_parts` says so, is folded in parts along
    the queries: a part's rows against the keys its mask leaves and no
    others, so the corner of the tile that the mask would erase whole is
    neither multiplied nor exponentiated (an erased entry gave exp(-inf) =
    0: every row still sums over exactly the keys it saw, in another
    order). The running maximum, sum and accumulator are a row's own, so
    the parts touch disjoint rows of the scratch and carry nothing new."""
    import jax.experimental.pallas as pl

    qi = pl.program_id(2)
    at = kv = pl.program_id(3)                  # the step, and its key block
    if window is not None:
        kv = _band_first(qi, block_q, block_k, window) + at
    # only a padded sequence needs the key mask: decided here, in Python
    padded = tk_valid < (num_kv if window is None else key_blocks) * block_k

    def scores(mask_keys: bool, mask_causal: bool, mask_trailing=False,
               rows=None, keys=None):
        """This step's tile of RAW products (the scale is in the exponent:
        `_weigh`; `_NEG_INF` masks whatever the scale), (bq, bk) or the
        `rows` and `keys` of it, and which of it counts (None: all of it).
        `mask_keys`: keys at or past `tk_valid` are padding; `mask_causal`:
        a query sees the keys at or before it; `mask_trailing`: and none
        `window` or more behind it."""
        s = products(rows, keys)
        ok = None
        if rows is not None:
            # a part of an edge tile (`fold`): the tile's corner lies on
            # the edge, so what counts is told by the part's own place in
            # the tile, whatever the block
            keys_ahead = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                          - jax.lax.broadcasted_iota(jnp.int32, s.shape, 0))
            ok = (keys_ahead <= rows[0] - keys[0] if mask_causal
                  else keys_ahead > rows[0] - keys[0])
            if mask_keys:
                ok = ok & (kv * block_k + keys[0] + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 1) < tk_valid)
            return jnp.where(ok, s, _NEG_INF), ok
        if mask_keys or mask_causal or mask_trailing:
            kpos = kv * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
        if mask_keys:
            ok = kpos < tk_valid
        if mask_causal or mask_trailing:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
        if mask_causal:
            ok = (qpos >= kpos) if ok is None else ok & (qpos >= kpos)
        if mask_trailing:
            near = qpos - kpos < window
            ok = near if ok is None else ok & near
        if ok is not None:
            s = jnp.where(ok, s, _NEG_INF)
        return s, ok

    exponent = scale * _LOG2_E

    def write(m, l, acc):
        """A row's raw maximum and its sum, (bq, 1) columns, and the
        accumulator, written out."""
        out = acc / jnp.maximum(l, 1e-30)
        out = jnp.where(l > 0, out, 0.0)
        o_ref[0] = out.astype(o_ref.dtype)
        # per-row logsumexp of the scaled scores, the backward pass's
        # softmax residual; +inf on fully-masked rows makes exp(s - lse)
        # vanish there
        lse_ref[0] = jnp.where(
            l > 0, m * scale + jnp.log(jnp.maximum(l, 1e-30)), jnp.inf)

    if num_kv == 1:
        # the softmax is whole in this tile: no running maximum, no
        # correction, no accumulator through scratch (the step of the
        # path below, its exponent and its order of sums)
        s, ok = scores(padded, causal, window is not None)
        m = s.max(-1, keepdims=True)
        write(m, *_weigh(s, ok, m, v_ref, exponent, 1))
        return

    m_sc, l_sc, acc_sc = scratch

    @pl.when(at == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def step(mask_keys: bool, mask_causal: bool, mask_trailing=False,
             rows=None, keys=None):
        """One key block, or the `keys` of it for the `rows` of the query
        block, folded into the running max / denominator / accumulator of
        those rows; a whole tile that nothing masks in `_row_parts`
        parts."""
        if rows is None and not (mask_keys or mask_causal or mask_trailing):
            for part in _row_halves(block_q):
                _fold_tile(products(part, None), None, v_ref, scratch,
                           exponent, part)
            return
        s, ok = scores(mask_keys, mask_causal, mask_trailing, rows, keys)
        if rows is not None and mask_causal and not mask_keys:
            # every row of a part on the diagonal sees its own key: the
            # maximum is a score, and exp(_NEG_INF - m) is 0 by itself
            ok = None
        _fold_tile(s, ok, v_ref, scratch, exponent, rows, keys)

    if not causal:
        step(padded, False)
    else:
        # key blocks wholly above the diagonal are skipped, not masked
        # (their index map re-names the last block needed, so nothing is
        # fetched for them either); blocks wholly below it need no causal
        # mask
        needed = kv * block_k <= qi * block_q + block_q - 1
        crosses = (kv + 1) * block_k - 1 > qi * block_q
        parts = _edge_parts(block_q, block_k, num_kv, window)

        def fold(diagonal: bool, trailing: bool = False):
            """The step of a block by the edges that cross it. An edge
            tile in parts: the diagonal's valid half is its lower-left
            triangle, part r reads the keys up to its own square; the
            trailing edge's is the upper-right one, part r reads them
            from its own square on."""
            if parts == 1 or diagonal == trailing:
                return step(padded, diagonal, trailing)
            size = block_q // parts
            for r in range(parts):
                step(padded, diagonal, trailing, (r * size, size),
                     (0, (r + 1) * size) if diagonal
                     else (r * size, block_k - r * size))

        if window is None:
            pl.when(needed & crosses)(functools.partial(fold, True))
            pl.when(needed & jnp.logical_not(crosses))(
                functools.partial(fold, False))
        else:
            # the band's other edge: some query of the block lies `window`
            # or more past some key of this one (blocks wholly behind the
            # band are never reached: the axis starts at `_band_first`)
            trails = qi * block_q + block_q - 1 - kv * block_k >= window
            needed = needed & (kv < key_blocks)
            for diagonal in (True, False):
                for trailing in (True, False):
                    if parts > 1 and diagonal and trailing:
                        # equal tiles that divide the window: the edges
                        # are `window // block_k` blocks apart
                        continue
                    pl.when(needed
                            & (crosses if diagonal
                               else jnp.logical_not(crosses))
                            & (trails if trailing
                               else jnp.logical_not(trails)))(
                        functools.partial(fold, diagonal, trailing))

    @pl.when(at == num_kv - 1)
    def _finalize():
        # the ONE sum across lanes a row
        write(m_sc[:, :1], l_sc[...].sum(-1, keepdims=True), acc_sc[...])


def _fold_scratch(block_q: int, dv: int, *key_widths: int) -> list:
    """`_fold_tile`'s scratch: the running maximum and sum, lane-dense, and
    the accumulator. As (block_q, 1) columns the first two were padded to
    128 lanes already: the same VMEM."""
    import jax.experimental.pallas.tpu as pltpu

    lanes = _stat_lanes(*key_widths)
    return [pltpu.VMEM((block_q, lanes), jnp.float32),
            pltpu.VMEM((block_q, lanes), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32)]


def _qk(q_ref, k_ref, rows=None, keys=None):
    """(bq, D) x (bk, D) -> (bq, bk), float32 sums; of the `rows` and
    `keys` where told."""
    return jax.lax.dot_general(
        _block(q_ref, rows), _block(k_ref, keys), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch, **static):
    _flash_fold(functools.partial(_qk, q_ref, k_ref), v_ref, o_ref, lse_ref,
                scratch, **static)


def _latent_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref, lse_ref,
                   *scratch, **static):
    """The latent score as what it is, a sum of two products: a head's own
    channels against its own keys, and its rotary channels against the ONE
    rotary key. `qr_ref` holds the rotary channels of the heads that share
    a lane block, `kr_ref` the rotary key in this head's lanes of it and
    zeros in the others. The two parts are set side by side in VMEM, lane
    blocks both, so that the MXU sums them in ONE product's float32
    accumulator: added as two (bq, bk) tiles they cost the VPU a pass over
    the score tile, 6% of the kernel (PERF.md, PR 34)."""
    def products(rows=None, keys=None):
        return jax.lax.dot_general(
            jnp.concatenate([_block(qn_ref, rows), _block(qr_ref, rows)], -1),
            jnp.concatenate([_block(kn_ref, keys), _block(kr_ref, keys)], -1),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    _flash_fold(products, v_ref, o_ref, lse_ref, scratch, **static)


def _band_steps(tq: int, block_q: int, block_k: int, window: int) -> int:
    """The key blocks the widest band of a query block touches: the extent
    of a banded forward's last grid axis (`window // block_k + 1` where the
    tiles are equal and divide the window)."""
    return max((qi * block_q + block_q - 1) // block_k
               - max(qi * block_q - (window - 1), 0) // block_k + 1
               for qi in range(-(-tq // block_q)))


def _flash_call(kernel, queries, keys, value, out_at, out_shape, *, b, h,
                tk, causal, scale, block_q, block_k, interpret, name=None,
                window=None):
    """ONE Pallas forward over a grid of (row, head, query block, key
    block). `queries`, `keys` and `value` are (array, block width, at):
    `at(row, head, block along the sequence)` names the (1, positions,
    width) block of that head in the array, wherever it lies; the value
    block is the last input. The output's blocks are named by `out_at` in
    an array of `out_shape` (as wide a block as the value's). -> (out in
    that shape, lse (B x H, Tq, 1) float32); `tk` is the keys' length
    before padding. `name` is the call's own in a device trace; without
    one the innermost `jax.named_scope` around it names it. With a
    `window` (causal) the last axis is a query block's band, `_band_steps`
    key blocks from `_band_first` on: a block wholly behind the band is
    never named, one above the diagonal re-names the diagonal's."""
    import jax.experimental.pallas as pl

    keys = [*keys, value]
    dv = value[1]
    nq = queries[0][0].shape[1] // block_q
    nk = steps = keys[0][0].shape[1] // block_k
    band = {}
    if window is not None:
        steps = _band_steps(nq * block_q, block_q, block_k, window)
        band = {"window": window, "key_blocks": nk}

    def query_spec(width, at):
        return pl.BlockSpec((1, block_q, width),
                            lambda b_, j, qi, kv: at(b_, j, qi))

    def key_spec(width, at):
        if window is not None:
            def index(b_, j, qi, kv):
                last = (qi * block_q + block_q - 1) // block_k
                return at(b_, j, jnp.minimum(
                    _band_first(qi, block_q, block_k, window) + kv,
                    jnp.minimum(last, nk - 1)))
        elif causal:
            # a key block above the diagonal is never computed on: name
            # the last block this query block needs instead, which is
            # already in VMEM, so that no copy is issued for the skipped
            # steps
            def index(b_, j, qi, kv):
                last = (qi * block_q + block_q - 1) // block_k
                return at(b_, j, jnp.minimum(kv, last))
        else:
            def index(b_, j, qi, kv):
                return at(b_, j, kv)
        return pl.BlockSpec((1, block_k, width), index)

    return pl.pallas_call(
        functools.partial(
            kernel, block_q=block_q, block_k=block_k, num_kv=steps,
            causal=causal, tk_valid=tk, scale=scale, **band),
        grid=(b, h, nq, steps),
        in_specs=[query_spec(w, at) for _x, w, at in queries]
        + [key_spec(w, at) for _x, w, at in keys],
        out_specs=[
            query_spec(dv, out_at),
            # lse is a (block_q, 1) column, a row's statistic as the
            # finalisation's sum across lanes leaves it: a trailing dim
            # equal to the array's satisfies Mosaic's block rule, and no
            # sublane->lane relayout happens in the kernel
            pl.BlockSpec((1, block_q, 1),
                         lambda b_, j, qi, kv: (b_ * h + j, qi, 0)),
        ],
        out_shape=[
            out_shape,
            jax.ShapeDtypeStruct((b * h, nq * block_q, 1), jnp.float32),
        ],
        # one key block carries nothing from step to step
        scratch_shapes=[] if steps == 1 else _fold_scratch(
            block_q, dv, block_k),
        interpret=interpret, name=name,
    )(*(x for x, _w, _at in [*queries, *keys]))


def _flash_fwd_lse(q, k, v, causal, block_q, block_k, interpret,
                   window=None, name=None):
    """Pallas forward at the given tile (a multiple of what Mosaic tiles,
    or the whole length); q, k (B, T, H, D), v (B, T, H, Dv) in, returns
    (out (B,Tq,H,Dv), lse (B,H,Tq) f32). The values may have a width of
    their own, and keys and values fewer heads than the queries: the key
    block of query head j is head j // group's, named by the index map,
    so K and V stay as they lie.

    The layout, by shape (`_lanes_whole`): where D and Dv are multiples of
    128 the kernel reads q, k and v IN PLACE, a head's channels one lane
    block of the (B, T, H x D) array the projection wrote, and writes the
    output where the output projection reads it, (B, T, H x Dv): the
    reshapes around the call move nothing. At any other width (64, 192, a
    test's 8) q, k and v are copied head-major to (B x H, T, D) first and
    the output is copied back. A block holds the same values in the same
    order either way. `window`, `name`: `_flash_call`'s."""
    b, _, h, d = q.shape
    hk, dv = k.shape[2], v.shape[-1]
    group = key_head_group(q, k, v)
    in_place = _lanes_whole(d, dv)
    # laid out first, padded there: in place, a pad of the array as it lies
    qf, tq = _pad_seq(_rows(q, in_place), block_q)
    kf, tk = _pad_seq(_rows(k, in_place), block_k)
    vf, _ = _pad_seq(_rows(v, in_place), block_k)
    at, key_at = _block_at(in_place, h), _block_at(in_place, hk)

    # query head j reads key/value head j // group
    def key_head_at(b_, j, i):
        return key_at(b_, j if group == 1 else j // group, i)

    out, lse = _flash_call(
        _flash_kernel, [(qf, d, at)], [(kf, d, key_head_at)],
        (vf, dv, key_head_at), at,
        jax.ShapeDtypeStruct(qf.shape[:-1] + (qf.shape[-1] // d * dv,),
                             q.dtype),
        b=b, h=h, tk=tk, causal=causal, scale=d ** -0.5, block_q=block_q,
        block_k=block_k, interpret=interpret, name=name, window=window)
    out = _heads(out[:, :tq], b, h, in_place)
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


def _call_tiles(tq: int, tk: int, dtype, block_q, block_k, causal: bool,
                kernel: str = "flash"):
    """A forward's (block_q, block_k): `flash_tiles`' unless a test names
    one; counted where the call is traced, once a compiled shape."""
    rule_q, rule_k = flash_tiles(tq, tk, dtype)
    block_q = rule_q if block_q is None else min(block_q, max(tq, 1))
    block_k = rule_k if block_k is None else min(block_k, max(tk, 1))
    get_registry().counter(
        "mmlspark_tpu_flash_calls_total",
        "flash-attention forward calls traced, by the tile they run at",
        labels=("tile", "causal")).labels(
            tile=f"{block_q}x{block_k}", causal=str(causal).lower()).inc()
    if causal:
        _count_edge_parts(block_q, block_k, -(-tk // block_k))
    _count_fold_rows(kernel, block_q, -(-tk // block_k))
    return block_q, block_k


def flash_attention(q, k, v, causal: bool = False,
                    block_q: int | None = None, block_k: int | None = None,
                    bwd_chunk: int | None = 128, interpret: bool = False):
    """Pallas TPU flash attention, DIFFERENTIABLE: the forward is the
    Pallas online-softmax kernel (score tile only in VMEM) and the
    backward is the standard flash recomputation as a pure-XLA k-block
    scan driven by the kernel's saved logsumexp. Same contract as
    `dense_attention`, grouped-query heads included (k and v with a
    divisor of q's heads): q, k (B, T, H, D), v (B, T, H, Dv) in, (B, T,
    H, Dv) out. Heads whose D and Dv are multiples of 128 are read and
    written in place, as blocks of the (B, T, H x D) arrays around the
    call; any other width pays a head-major copy of q, k and v in and of
    the output back (`_flash_fwd_lse`; the registry's
    `mmlspark_tpu_attention_operands_total` says which, by `kernel` and
    `layout`).

    The forward's tile is `flash_tiles`' unless a test names one.
    `bwd_chunk` is the backward scan's key chunk and no tile: the scan
    materialises a (B, H, Tq, chunk) float32 score slab in HBM, so it
    does not follow the forward to 512 or 1024 (None: the forward's key
    tile). `interpret=True` runs the forward kernel on CPU for tests."""
    block_q, block_k = _call_tiles(q.shape[1], k.shape[1], q.dtype, block_q,
                                   block_k, causal)
    group = key_head_group(q, k, v)
    if group > 1:
        get_registry().counter(
            "mmlspark_tpu_flash_grouped_calls_total",
            "flash-attention forward calls traced whose query heads share "
            "key/value heads, by the heads a key/value head serves",
            labels=("group", "tile")).labels(
                group=str(group), tile=f"{block_q}x{block_k}").inc()
    _count_operands("flash", _lanes_whole(q.shape[-1], v.shape[-1]))
    return _flash_diff(q, k, v, causal, block_q, block_k,
                       block_k if bwd_chunk is None else bwd_chunk, interpret)


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
    q, _ = _pad_seq(q, q_chunk)
    k, _ = _pad_seq(k, q_chunk)
    v, _ = _pad_seq(v, q_chunk)
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


# The banded Pallas forward, jitted by itself as `_eva_flash` is: traced and
# lowered once a shape, not once a layer, so the call is named by its window
# (`swa_attn_w4096`; a device trace's readers select `swa_attn_*`)
@functools.partial(jax.jit, static_argnames=("window", "block_q", "block_k",
                                             "interpret"))
def _banded_flash(q, k, v, *, window, block_q, block_k, interpret=False):
    return _flash_fwd_lse(q, k, v, True, block_q, block_k, interpret,
                          window=window, name=f"swa_attn_w{window}")[0]


def band_tile_pairs(t: int, window: int, block_q: int, block_k: int):
    """-> (computed, needed) for one head of one row of `t` positions: the
    (query block, key block) tiles the banded forward COMPUTES at that
    tile, in tiles and fractions of one (a block on the diagonal or on
    the band's trailing edge counts `edge_tile_share` of a tile where
    `_edge_parts` folds it in parts, a whole one where it is masked
    whole), and the band's own (query, key) pairs in tiles of that size.
    Their ratio is what the tiles' edges cost."""
    steps = _band_steps(t, block_q, block_k, window)
    share = edge_tile_share(_edge_parts(block_q, block_k, steps, window))
    computed = 0.0
    for q0 in range(0, t, block_q):
        last = min((q0 + block_q - 1) // block_k, (t - 1) // block_k)
        first = max(q0 - (window - 1), 0) // block_k
        # the blocks ONE edge crosses (`_flash_fold`'s `crosses`, `trails`)
        edges = sum(
            ((kv + 1) * block_k - 1 > q0)
            != (q0 + block_q - 1 - kv * block_k >= window)
            for kv in range(first, last + 1))
        computed += last - first + 1 - edges * (1 - share)
    inside = min(window, t)
    needed = inside * (inside + 1) / 2 + (t - inside) * window
    return computed, needed / (block_q * block_k)


def band_tiles(t: int, window: int, dtype):
    """The banded forward's (block_q, block_k): `flash_tiles` told the
    window (the largest equal tiles that divide it: 1024 x 1024 of 4096,
    so a band is `window // block_k + 1` key blocks a query block); a
    window no multiple of 128 divides takes the lengths' own tiles, the
    kernel masks both edges wherever they fall."""
    if window % 128 == 0:
        return flash_tiles(t, t, dtype, window=window)
    return flash_tiles(t, t, dtype)


def causal_attention(q, k, v, impl: str = "flash", window: int | None = None,
                     **flash_options):
    """Causal attention by the tier's name: "flash" (None: the backward
    scans the keys a forward tile at a time; `flash_options` are that
    tier's, for tests), "chunked" or "dense" (in the queries' type).

    With a `window` the band SLIDES with the query: query t reads keys
    t - window + 1 .. t (its own position counts). "dense": one masked
    softmax; "chunked": a block of queries against the keys of its band
    (XLA, the CPU's tier, and the only one with a backward: differentiate
    through it); "flash": the plain forward's fold over the key blocks a
    query block's band touches (`_flash_fold`: a block wholly outside the
    band is neither fetched nor computed; the diagonal's and the trailing
    edge's blocks are masked in the kernel and, where the tiles are equal
    and divide the window, folded in parts that leave out what the mask
    would erase whole, `_edge_parts`: `band_tile_pairs` counts what is
    computed; grouped key heads by index map and heads of whole lanes in
    place as there), FORWARD ONLY, named `swa_attn_w<window>`. A row no
    longer than the window is plain causal attention and takes that tier
    of it. Without a window every call is what it was."""
    if window is not None and q.shape[1] > window:
        if impl == "dense":
            return _banded_dense(q, k, v, window).astype(q.dtype)
        if impl == "chunked":
            return _banded_chunked(q, k, v, window)
        if impl == "flash":
            t = q.shape[1]
            rule_q, rule_k = band_tiles(t, window, q.dtype)
            block_q = min(flash_options.get("block_q") or rule_q, t)
            block_k = min(flash_options.get("block_k") or rule_k, t)
            # counted where the call is traced: the kernel is traced once
            # a shape, this once a layer
            get_registry().counter(
                "mmlspark_tpu_attention_window_calls_total",
                "sliding-window attention forward calls traced, by the "
                "window and the tile (queries x keys)",
                labels=("window", "tile")).labels(
                    window=str(window), tile=f"{block_q}x{block_k}").inc()
            steps = _band_steps(t, block_q, block_k, window)
            _count_edge_parts(block_q, block_k, steps, window)
            _count_fold_rows("swa", block_q, steps)
            _count_operands("swa", _lanes_whole(q.shape[-1], v.shape[-1]))
            return _banded_flash(
                q, k, v, window=window, block_q=block_q, block_k=block_k,
                interpret=bool(flash_options.get("interpret", False)))
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
# latent attention: a score of two parts, read where they lie            #
# --------------------------------------------------------------------- #

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
    `_eva_flash` is: lowered once a shape, not once a layer, so the call
    is named by its widths (`mla_attn_n128r64`; a device trace's readers
    select `mla_attn_*`) and not by the layer's scope."""
    b, _, h, nope = q_nope.shape
    rope = q_rope.shape[-1]
    share = 128 // rope                    # heads to a lane block of q_rope
    placed = jnp.concatenate(
        [jnp.pad(k_rope, ((0, 0), (0, 0), (i * rope, 128 - (i + 1) * rope)))
         for i in range(share)], -1)                       # (B, T, share x 128)
    qn, tq = _pad_seq(_rows(q_nope, True), block_q)
    qr, _ = _pad_seq(_rows(q_rope, True), block_q)
    kvf, tk = _pad_seq(_rows(kv, True), block_k)
    placed, _ = _pad_seq(placed, block_k)
    out, lse = _flash_call(
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
    return (_heads(out[:, :tq], b, h, True),
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
    dq, dk, dv = _flash_bwd_xla(
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
            _count_operands("mla", False)
        return causal_attention(
            *_latent_concatenated(q_nope, q_rope, kv, k_rope), impl,
            **({"interpret": True} if interpret else {}))
    t = q_nope.shape[1]
    # under the plain forward's counter too: its fold, at its tile
    block_q, block_k = _call_tiles(t, t, q_nope.dtype, block_q, block_k, True,
                                   "mla")
    _count_operands("mla", True)
    return _latent_diff(q_nope, q_rope, kv, k_rope, block_q, block_k,
                        interpret)


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


def _edge_prefixes(block_s: int, per_window: int) -> tuple[int, ...]:
    """The key prefixes among which `_eva_kernel` folds a block of summaries
    that ends past the ones its queries see, from what it can see; () is
    the whole block, masked by column. A query block sees `per_window`
    summaries a window before its own, so where a block is whole windows'
    shares the valid part of such a block is one of its prefixes of
    `per_window`, 2 x `per_window`, .. columns. A prefix has to be a static
    slice, so the kernel chooses among them by `pl.when` and folds that
    prefix alone, UNMASKED: the block is fetched whole, the products, the
    exponentials and the values' product shrink and the mask goes. Shares
    of whole lane blocks only: a block of 1024 with 128 summaries a window
    has 7 (`evabyte_6_5b.score_byte_docs`' rows of 32768 bytes); a block
    that ends with a window's share has no edge, and the tests' small
    windows keep the whole masked block. Read on a v5e (PERF.md, PR 43),
    2 x 32768 x 32 heads: the seven prefixes take 0.51 to 0.62 ms off a
    call of 25.2, which is the MASK's cost (0.3 us a tile); fewer, wider
    classes that keep the mask (halves, quarters) gained nothing then, a
    fold's cost being mostly its rows' while the running maximum and sum
    were (rows, 1) columns; lane-dense since PR 44, a prefix of 128
    columns schedules a third of what it did."""
    if per_window % 128 or block_s % per_window:
        return ()
    return tuple(range(per_window, block_s, per_window))


def _eva_steps(t: int, window: int, chunk: int, block_k: int,
               block_s: int) -> tuple[int, int]:
    """(key blocks of a window, blocks of the summaries a row of `t`
    positions reads): the two runs of `_eva_kernel`'s last grid axis."""
    summaries = (-(-t // window) - 1) * (window // chunk)
    return window // block_k, -(-summaries // block_s)


def _eva_kernel(q_ref, k_ref, v_ref, kb_ref, vb_ref, o_ref, m_sc, l_sc,
                acc_sc, *, block_q, block_k, block_s, n_local, n_remote,
                window, per_window, scale):
    """A grid of (row, head, query block, source block). A block of
    queries, which lies in ONE window, over the grid's last axis: first
    the `n_remote` blocks of summaries, the last first (those past the
    `per_window` x window index that lie before it skipped), then the
    `n_local` key blocks of its window, the last first (those above the
    diagonal skipped), all into one running maximum, denominator and
    accumulator (`_fold_tile`, the step `_flash_fold` takes). In that
    order the steps that compute nothing come before those that do and a
    query block's LAST step is a whole tile of its window's keys: the next
    query block's operands are fetched under it (under a skipped step, or
    a short one, the copy was waited for: PERF.md, PR 43).

    An EDGE tile is folded only over what its mask leaves: the diagonal's
    in parts along the queries where `_edge_parts` says so, as
    `_flash_fold` does, and a block of summaries that ends past the ones
    seen over a key prefix (`_edge_prefixes`). Every row still sums over
    exactly the keys and summaries it saw."""
    import jax.experimental.pallas as pl

    qi, j = pl.program_id(2), pl.program_id(3)
    first = qi * block_q
    own = first // window                       # this block's window
    scratch = (m_sc, l_sc, acc_sc)
    exponent = scale * _LOG2_E                  # on raw products: `_weigh`

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def fold(keys_ref, values_ref, counts=None, rows=None, keys=None):
        """One block of keys or summaries, or the `keys` of it for the
        `rows` of the query block, folded in; `counts(shape)` is what of
        that tile counts (None: all of it). Every masked tile holds a
        score that counts in each of its rows (a summary seen by one query
        of the block is seen by all; a query's own key), so a row's
        maximum is a real score from its first tile on and a masked
        entry's exp(_NEG_INF - m) is 0 by itself."""
        if counts is None and rows is None:
            # a whole tile, or a key prefix of one, that nothing masks
            for part in _row_halves(block_q):
                _fold_tile(_qk(q_ref, keys_ref, part, keys), None,
                           values_ref, scratch, exponent, part, keys)
            return
        s = _qk(q_ref, keys_ref, rows, keys)
        if counts is not None:
            s = jnp.where(counts(s.shape), s, _NEG_INF)
        _fold_tile(s, None, values_ref, scratch, exponent, rows, keys)

    # the summaries of the windows before it
    sfirst = (n_remote - 1 - j) * block_s
    seen = own * per_window
    reads = (j < n_remote) & (sfirst < seen)
    if per_window % block_s:
        # a block of summaries may end past the windows before this one
        visible = seen - sfirst
        partly = reads & (visible < block_s)
        prefixes = _edge_prefixes(block_s, per_window)
        if not prefixes:
            pl.when(partly)(functools.partial(
                fold, kb_ref, vb_ref, lambda shape: jax.lax.broadcasted_iota(
                    jnp.int32, shape, 1) < visible))
        for prefix in prefixes:
            pl.when(partly & (visible == prefix))(functools.partial(
                fold, kb_ref, vb_ref, keys=(0, prefix)))
        reads = reads & jnp.logical_not(partly)

    @pl.when(reads)
    def _before():
        fold(kb_ref, vb_ref)

    # the window's own keys, causally
    kfirst = (own * n_local + n_local + n_remote - 1 - j) * block_k
    needed = (j >= n_remote) & (kfirst <= first + block_q - 1)
    crosses = kfirst + block_k - 1 > first
    parts = _edge_parts(block_q, block_k, n_local + n_remote, window)

    @pl.when(needed & crosses)
    def _diagonal():
        if parts == 1:
            return fold(k_ref, v_ref, lambda shape: (
                first + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
                >= kfirst + jax.lax.broadcasted_iota(jnp.int32, shape, 1)))
        # equal tiles: the tile's corner lies on the diagonal, and part r
        # reads the keys up to its own square
        size = block_q // parts
        for r in range(parts):
            fold(k_ref, v_ref, lambda shape, r=r: (
                jax.lax.broadcasted_iota(jnp.int32, shape, 1)
                - jax.lax.broadcasted_iota(jnp.int32, shape, 0) <= r * size),
                (r * size, size), (0, (r + 1) * size))

    @pl.when(needed & jnp.logical_not(crosses))
    def _below():
        fold(k_ref, v_ref)

    @pl.when(j == n_local + n_remote - 1)
    def _finalize():
        o_ref[0] = (acc_sc[...] / l_sc[...].sum(-1, keepdims=True)).astype(
            o_ref.dtype)


def _eva_pool_kernel(k_ref, v_ref, phi_ref, mu_ref, kb_ref, vb_ref, *,
                     chunk, pooled_blocks, scale):
    """`eva_summaries` for one block of positions of one head (a grid of
    (row, head, window)), read where the attention kernel reads them:
    (positions, D) in, (positions / chunk, D) out, float32 inside. A block
    past the positions that are pooled (the padding of the summaries to
    whole tiles) is zeros: a masked summary still meets the values'
    product, where 0 x NaN is NaN."""
    import jax.experimental.pallas as pl

    f32 = jnp.float32

    @pl.when(pl.program_id(2) >= pooled_blocks)
    def _padding():
        kb_ref[0] = jnp.zeros_like(kb_ref[0])
        vb_ref[0] = jnp.zeros_like(vb_ref[0])

    @pl.when(pl.program_id(2) < pooled_blocks)
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
    name the block the query block's next computing step reads) nor
    computed on.

    q, k, v (B, T, H, D) in, (B, T, H, D) out. The layout is
    `_flash_fwd_lse`'s, by shape: heads of whole lane blocks (multiples of
    128 channels) are read IN PLACE from the (B, T, H x D) arrays the
    projections wrote, the summaries are written and read the same way,
    and the output is written where the output projection reads it; at
    any other width (a test's 8) every operand is copied head-major to
    (B x H, T, D) first and the output copied back."""
    import jax.experimental.pallas as pl

    b, t, h, d = q.shape
    dv = v.shape[-1]
    in_place = _lanes_whole(d, dv)
    at = _block_at(in_place, h)
    per_window = window // chunk
    windows_before = -(-t // window) - 1
    qf, kf, vf = (_pad_seq(_rows(x, in_place), max(block_q, block_k))[0]
                  for x in (q, k, v))
    n_local, n_remote = _eva_steps(t, window, chunk, block_k, block_s)
    tag = f"w{window}c{chunk}"      # a device trace's readers select by name

    def like(x, positions, width):
        """An array of `positions` in x's layout, heads of `width`."""
        return jax.ShapeDtypeStruct(
            (x.shape[0], positions, x.shape[2] // d * width), x.dtype)

    if kbar is None:
        # as many rows as whole tiles of summaries, a window's a step
        steps = -(-n_remote * block_s // per_window)

        def pooled_window(b_, j, i):
            return at(b_, j, jnp.minimum(i, windows_before - 1))

        def vector(b_, j, i):
            return (j, 0, 0)

        with jax.named_scope("eva.summarise"):
            kbf, vbf = pl.pallas_call(
                functools.partial(
                    _eva_pool_kernel, chunk=chunk,
                    pooled_blocks=windows_before, scale=d ** -0.5),
                grid=(b, h, steps),
                in_specs=[pl.BlockSpec((1, window, d), pooled_window),
                          pl.BlockSpec((1, window, dv), pooled_window),
                          pl.BlockSpec((1, 1, d), vector),
                          pl.BlockSpec((1, 1, d), vector)],
                out_specs=[pl.BlockSpec((1, per_window, d), at),
                           pl.BlockSpec((1, per_window, dv), at)],
                out_shape=[like(kf, steps * per_window, d),
                           like(vf, steps * per_window, dv)],
                interpret=interpret, name=f"eva_pool_{tag}",
            )(kf, vf, phi[:, None], mu[:, None])
    else:
        kbf, vbf = (_pad_seq(_rows(x[:, :windows_before * per_window],
                                   in_place), block_s)[0]
                    for x in (kbar, vbar))

    # a step that computes nothing names the block the next one that does
    # will read, so that it is fetched under the last step before them

    def key_block(b_, j, qi, s):
        own = (qi * block_q) // window
        last = (qi * block_q + block_q - 1) // block_k     # the diagonal's
        return at(b_, j, jnp.minimum(own * n_local + jnp.clip(
            n_local + n_remote - 1 - s, 0, n_local - 1), last))

    def summary_block(b_, j, qi, s):
        seen = ((qi * block_q) // window) * per_window
        last = jnp.maximum(-(-seen // block_s) - 1, 0)
        return at(b_, j, jnp.clip(n_remote - 1 - s, 0, last))

    def query_block(b_, j, qi, s):
        return at(b_, j, qi)

    with jax.named_scope("eva.attend"):
        out = pl.pallas_call(
            functools.partial(
                _eva_kernel, block_q=block_q, block_k=block_k,
                block_s=block_s, n_local=n_local, n_remote=n_remote,
                window=window, per_window=per_window, scale=d ** -0.5),
            grid=(b, h, qf.shape[1] // block_q, n_local + n_remote),
            in_specs=[pl.BlockSpec((1, block_q, d), query_block),
                      pl.BlockSpec((1, block_k, d), key_block),
                      pl.BlockSpec((1, block_k, dv), key_block),
                      pl.BlockSpec((1, block_s, d), summary_block),
                      pl.BlockSpec((1, block_s, dv), summary_block)],
            out_specs=pl.BlockSpec((1, block_q, dv), query_block),
            out_shape=like(qf, qf.shape[1], dv),
            scratch_shapes=_fold_scratch(block_q, dv, block_k, block_s),
            interpret=interpret, name=f"eva_attn_{tag}",
        )(qf, kf, vf, kbf, vbf)
    return _heads(out[:, :t], b, h, in_place)


def eva_tile_pairs(t: int, window: int, chunk: int, block_q: int,
                   block_k: int, block_s: int):
    """-> (computed, needed) for one head of one row of `t` positions, in
    tiles of block_q x block_k: what the flash tier's attention kernel
    COMPUTES at those tiles, by the kernel's own rules (a key block at or
    below the diagonal, the diagonal's `edge_tile_share` of one where
    `_edge_parts` folds it in parts; a block of summaries that holds one
    its queries see, the key prefix of `_edge_prefixes` where it ends past
    them; a block of summaries counts block_s / block_k of a tile), and
    the (query, key) and (query, summary) pairs the masks leave. Their
    ratio is what the tiles' edges cost (`band_tile_pairs`' count, for
    this kernel)."""
    per_window = window // chunk
    share = edge_tile_share(_edge_parts(
        block_q, block_k, sum(_eva_steps(t, window, chunk, block_k, block_s)),
        window))
    prefixes = _edge_prefixes(block_s, per_window)
    tile = max(block_q, block_k)
    computed = 0.0
    for first in range(0, -(-t // tile) * tile, block_q):
        own = first // window
        for kfirst in range(own * window, first + block_q, block_k):
            computed += share if kfirst + block_k - 1 > first else 1.0
        seen = own * per_window
        for sfirst in range(0, seen, block_s):
            columns = next((p for p in prefixes if seen - sfirst <= p),
                           block_s)
            computed += columns / block_k
    whole, rest = divmod(t, window)
    needed = (whole * window * (window + 1) / 2 + rest * (rest + 1) / 2
              + per_window * (window * whole * (whole - 1) / 2
                              + rest * whole))
    return computed, needed / (block_q * block_k)


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
        "window, the chunk, the tile (queries x keys x summaries) and the "
        "key prefixes among which a block of summaries that ends past the "
        "ones seen is folded (0: the whole block, masked, or no such "
        "block)",
        labels=("window", "chunk", "tile", "prefixes")).labels(
            window=str(window), chunk=str(chunk),
            tile=f"{block_q}x{block_k}x{block_s}",
            prefixes=str(len(_edge_prefixes(block_s, window // chunk)))
        ).inc()
    steps = sum(_eva_steps(t, window, chunk, block_k, block_s))
    _count_edge_parts(block_q, block_k, steps, window)
    _count_fold_rows("eva", block_q, steps)
    _count_operands("eva", _lanes_whole(q.shape[-1], v.shape[-1]))
    return _eva_flash(q, k, v, phi, mu, *(summaries or ()), window=window,
                      chunk=chunk, block_q=block_q, block_k=block_k,
                      block_s=block_s, interpret=interpret)


# --------------------------------------------------------------------- #
# param-compatible self-attention module                                #
# --------------------------------------------------------------------- #

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
    test_chipless_compile.py` holds absent (what the copy takes on the
    chip: PERF.md section 5, PR 40)."""

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
        proj = functools.partial(HeadsDense, self.num_heads, head_dim,
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
