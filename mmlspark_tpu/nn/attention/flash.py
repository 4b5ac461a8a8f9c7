"""The plain and the banded core: `fold._flash_fold` over a head's own
products. `flash_attention` (any mask-free or causal row, differentiable: the
backward is an XLA scan over key blocks), the banded forward behind
`causal_attention(window=)` (a window that slides with the query, forward
only), and `causal_attention`, a decoder's causal core by the tier's
name."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...observability.metrics import get_registry
from ...parallel.ring_attention import dense_attention, key_head_group
from . import fold, layout, xla


def _qk(q_ref, k_ref, rows=None, keys=None):
    """(bq, D) x (bk, D) -> (bq, bk), float32 sums; of the `rows` and
    `keys` where told."""
    return jax.lax.dot_general(
        fold._block(q_ref, rows), fold._block(k_ref, keys),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


def _heads_a_step(q, k, v, block_q: int, block_k: int) -> int:
    """`fold.heads_a_step` of a call's operands (B, T, H, D) at its tile."""
    return fold.heads_a_step(
        key_head_group(q, k, v), -(-k.shape[1] // block_k), block_q, block_k,
        q.shape[-1], v.shape[-1], q.dtype.itemsize)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch, **static):
    fold._flash_fold(functools.partial(_qk, q_ref, k_ref), v_ref, o_ref,
                     lse_ref, scratch, **static)


def _flash_fwd_lse(q, k, v, causal, block_q, block_k, interpret,
                   window=None, name=None):
    """Pallas forward at the given tile (a multiple of what Mosaic tiles,
    or the whole length); q, k (B, T, H, D), v (B, T, H, Dv) in, returns
    (out (B,Tq,H,Dv), lse (B,H,Tq) f32). The values may have a width of
    their own, and keys and values fewer heads than the queries: the key
    block of query head j is head j // group's, named by the index map,
    so K and V stay as they lie.

    The layout, by shape (`layout._lanes_whole`): where D and Dv are multiples
    of 128 the kernel reads q, k and v IN PLACE, a head's channels one lane
    block of the (B, T, H x D) array the projection wrote, and writes the
    output where the output projection reads it, (B, T, H x Dv): the
    reshapes around the call move nothing. At any other width (64, 192, a
    test's 8) q, k and v are copied head-major to (B x H, T, D) first and
    the output is copied back. A block holds the same values in the same
    order either way. `window`, `name`: `fold._flash_call`'s.

    A grid step is a (query block, key block) pair of `heads` query heads
    of ONE key/value head (`fold.heads_a_step`, from the shapes: 1 where a
    row is one key block or heads share nothing): their q, output and lse
    blocks are `heads` consecutive rows of the head-major arrays' axis 0,
    or `heads` consecutive lane blocks in place, and the key and value
    block is fetched once for them."""
    b, _, h, d = q.shape
    hk, dv = k.shape[2], v.shape[-1]
    group = key_head_group(q, k, v)
    in_place = layout._lanes_whole(d, dv)
    # laid out first, padded there: in place, a pad of the array as it lies
    qf, tq = layout._pad_seq(layout._rows(q, in_place), block_q)
    kf, tk = layout._pad_seq(layout._rows(k, in_place), block_k)
    vf, _ = layout._pad_seq(layout._rows(v, in_place), block_k)
    # a grid step folds `heads` query heads of one key/value head
    heads = _heads_a_step(q, k, v, block_q, block_k)
    at = layout._block_at(in_place, h // heads)
    key_at = layout._block_at(in_place, hk)

    # the `heads` query heads of step j read key/value head j // steps, of
    # the `steps` a group takes (one: the step is the key head's own)
    steps = group // heads

    def key_head_at(b_, j, i):
        return key_at(b_, j if steps == 1 else j // steps, i)

    out, lse = fold._flash_call(
        _flash_kernel, [(qf, d, at)], [(kf, d, key_head_at)],
        (vf, dv, key_head_at), at,
        jax.ShapeDtypeStruct(qf.shape[:-1] + (qf.shape[-1] // d * dv,),
                             q.dtype),
        b=b, h=h, tk=tk, causal=causal, scale=d ** -0.5, block_q=block_q,
        block_k=block_k, interpret=interpret, name=name, window=window,
        heads=heads, in_place=in_place)
    out = layout._heads(out[:, :tq], b, h, in_place)
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
    kp_, _ = layout._pad_seq(k, k_chunk)
    vp_, _ = layout._pad_seq(v, k_chunk)
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
    """A forward's (block_q, block_k): `fold.flash_tiles`' unless a test names
    one; counted where the call is traced, once a compiled shape."""
    rule_q, rule_k = fold.flash_tiles(tq, tk, dtype)
    block_q = rule_q if block_q is None else min(block_q, max(tq, 1))
    block_k = rule_k if block_k is None else min(block_k, max(tk, 1))
    get_registry().counter(
        "mmlspark_tpu_flash_calls_total",
        "flash-attention forward calls traced, by the tile they run at",
        labels=("tile", "causal")).labels(
            tile=f"{block_q}x{block_k}", causal=str(causal).lower()).inc()
    if causal:
        fold._count_edge_parts(block_q, block_k, -(-tk // block_k))
    fold._count_fold_rows(kernel, block_q, -(-tk // block_k))
    # the plain forward by its mask: a decoder's `gqa_attn_<i>`, an
    # encoder's `attn_<i>`
    fold._count_grid_steps(
        kernel if kernel != "flash" else "gqa" if causal else "attn",
        tq, tk, block_q, block_k, causal)
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
    H, Dv) out; where a row has several key blocks a grid step folds
    several query heads of a key/value head against the key block fetched
    once (`fold.heads_a_step`; `mmlspark_tpu_flash_heads_a_step_total` says
    how many). Heads whose D and Dv are multiples of 128 are read and
    written in place, as blocks of the (B, T, H x D) arrays around the
    call; any other width pays a head-major copy of q, k and v in and of
    the output back (`_flash_fwd_lse`; the registry's
    `mmlspark_tpu_attention_operands_total` says which, by `kernel` and
    `layout`).

    The forward's tile is `fold.flash_tiles`' unless a test names one.
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
    fold._count_heads_a_step(
        "gqa" if causal else "attn", group,
        _heads_a_step(q, k, v, block_q, block_k))
    fold._count_operands(
        "flash", layout._lanes_whole(q.shape[-1], v.shape[-1]))
    return _flash_diff(q, k, v, causal, block_q, block_k,
                       block_k if bwd_chunk is None else bwd_chunk, interpret)


# The banded Pallas forward, jitted by itself as `eva._eva_flash` is: traced
# and lowered once a shape, not once a layer, so the call is named by its
# window (`swa_attn_w4096`; a device trace's readers select `swa_attn_*`)
@functools.partial(jax.jit, static_argnames=("window", "block_q", "block_k",
                                             "interpret"))
def _banded_flash(q, k, v, *, window, block_q, block_k, interpret=False):
    return _flash_fwd_lse(q, k, v, True, block_q, block_k, interpret,
                          window=window, name=f"swa_attn_w{window}")[0]


def band_tile_pairs(t: int, window: int, block_q: int, block_k: int):
    """-> (computed, needed) for one head of one row of `t` positions: the
    (query block, key block) tiles the banded forward COMPUTES at that
    tile, in tiles and fractions of one (a block on the diagonal or on
    the band's trailing edge counts `fold.edge_tile_share` of a tile where
    `fold._edge_parts` folds it in parts, a whole one where it is masked
    whole), and the band's own (query, key) pairs in tiles of that size.
    Their ratio is what the tiles' edges cost."""
    steps = fold._band_steps(t, block_q, block_k, window)
    share = fold.edge_tile_share(
        fold._edge_parts(block_q, block_k, steps, window))
    computed = 0.0
    for q0 in range(0, t, block_q):
        last = min((q0 + block_q - 1) // block_k, (t - 1) // block_k)
        first = max(q0 - (window - 1), 0) // block_k
        # the blocks ONE edge crosses (`fold._flash_fold`'s `crosses`,
        # `trails`)
        edges = sum(
            ((kv + 1) * block_k - 1 > q0)
            != (q0 + block_q - 1 - kv * block_k >= window)
            for kv in range(first, last + 1))
        computed += last - first + 1 - edges * (1 - share)
    inside = min(window, t)
    needed = inside * (inside + 1) / 2 + (t - inside) * window
    return computed, needed / (block_q * block_k)


def band_tiles(t: int, window: int, dtype):
    """The banded forward's (block_q, block_k): `fold.flash_tiles` told the
    window (the largest equal tiles that divide it: 1024 x 1024 of 4096,
    so a band is `window // block_k + 1` key blocks a query block); a
    window no multiple of 128 divides takes the lengths' own tiles, the
    kernel masks both edges wherever they fall."""
    if window % 128 == 0:
        return fold.flash_tiles(t, t, dtype, window=window)
    return fold.flash_tiles(t, t, dtype)


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
    query block's band touches (`fold._flash_fold`: a block wholly outside the
    band is neither fetched nor computed; the diagonal's and the trailing
    edge's blocks are masked in the kernel and, where the tiles are equal
    and divide the window, folded in parts that leave out what the mask
    would erase whole, `fold._edge_parts`: `band_tile_pairs` counts what is
    computed; grouped key heads by index map and heads of whole lanes in
    place as there), FORWARD ONLY, named `swa_attn_w<window>`. A row no
    longer than the window is plain causal attention and takes that tier
    of it. Without a window every call is what it was."""
    layout._known(impl)
    if window is not None and q.shape[1] > window:
        if impl == "dense":
            return xla._banded_dense(q, k, v, window).astype(q.dtype)
        if impl == "chunked":
            return xla._banded_chunked(q, k, v, window)
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
            steps = fold._band_steps(t, block_q, block_k, window)
            fold._count_edge_parts(block_q, block_k, steps, window)
            fold._count_fold_rows("swa", block_q, steps)
            fold._count_grid_steps("swa", t, t, block_q, block_k, True,
                                   window)
            fold._count_heads_a_step(
                "swa", key_head_group(q, k, v),
                _heads_a_step(q, k, v, block_q, block_k))
            fold._count_operands(
                "swa", layout._lanes_whole(q.shape[-1], v.shape[-1]))
            return _banded_flash(
                q, k, v, window=window, block_q=block_q, block_k=block_k,
                interpret=bool(flash_options.get("interpret", False)))
    if impl == "flash":
        return flash_attention(q, k, v, causal=True, bwd_chunk=None,
                               **flash_options)
    if impl == "chunked":
        return xla.chunked_attention(q, k, v, causal=True)
    return dense_attention(q, k, v, causal=True).astype(q.dtype)
