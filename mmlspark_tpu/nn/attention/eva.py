"""EVA (arXiv 2302.04542): a query reads its own window exactly and the
windows before it as one pooled key and value a chunk, in ONE softmax. The
pooling (`eva_summaries` in XLA, `_eva_pool_kernel` on the "flash" tier),
the attention kernel (`fold._fold_tile` over two runs of blocks: summaries,
then the window's keys), what it computes (`eva_tile_pairs`) and
`eva_attention`, the core by the tier's name."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...observability.metrics import get_registry
from . import flash, fold, layout, xla
from .layout import _NEG_INF


def eva_summaries(k, v, phi, mu, chunk: int, upto: int | None = None):
    """One pooled key and value for every chunk of `chunk` positions (EVA's
    control-variate estimate of a chunk, arXiv 2302.04542 section 4, with
    a learned vector a head in place of a sampled one). k, v: (B, T, H,
    D); phi, mu: (H, D) float32. For chunk c, over its positions m:
    a_m = softmax_m(k_m . phi / sqrt(D)); kbar_c = sum_m a_m k_m + mu;
    vbar_c = sum_m a_m v_m. -> kbar, vbar (B, C, H, D) in k's and v's
    types for the C whole chunks of the first `upto` positions (all T by
    default). Float32 throughout and rounded once. This is the dense and
    chunked tiers' pooling, in XLA, which keeps float32 copies of k and v;
    the flash tier pools in a kernel of its own (`_eva_pool_kernel`:
    PERF.md, PR 33)."""
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
    windows keep the whole masked block. What the prefixes take off a call
    is the MASK's cost; fewer, wider classes that keep the mask gained
    nothing (PERF.md, PRs 43 and 44)."""
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
    accumulator (`fold._fold_tile`, the step `fold._flash_fold` takes). In that
    order the steps that compute nothing come before those that do and a
    query block's LAST step is a whole tile of its window's keys: the next
    query block's operands are fetched under it (under a skipped step, or
    a short one, the copy was waited for: PERF.md, PR 43).

    An EDGE tile is folded only over what its mask leaves: the diagonal's
    in parts along the queries where `fold._edge_parts` says so, as
    `fold._flash_fold` does, and a block of summaries that ends past the ones
    seen over a key prefix (`_edge_prefixes`). Every row still sums over
    exactly the keys and summaries it saw."""
    import jax.experimental.pallas as pl

    qi, j = pl.program_id(2), pl.program_id(3)
    first = qi * block_q
    own = first // window                       # this block's window
    scratch = (m_sc, l_sc, acc_sc)
    exponent = scale * fold._LOG2_E        # on raw products: `fold._weigh`

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def fold_in(keys_ref, values_ref, counts=None, rows=None, keys=None):
        """One block of keys or summaries, or the `keys` of it for the
        `rows` of the query block, folded in; `counts(shape)` is what of
        that tile counts (None: all of it). Every masked tile holds a
        score that counts in each of its rows (a summary seen by one query
        of the block is seen by all; a query's own key), so a row's
        maximum is a real score from its first tile on and a masked
        entry's exp(_NEG_INF - m) is 0 by itself."""
        if counts is None and rows is None:
            # a whole tile, or a key prefix of one, that nothing masks
            for part in fold._row_halves(block_q):
                fold._fold_tile(flash._qk(q_ref, keys_ref, part, keys), None,
                           values_ref, scratch, exponent, part, keys)
            return
        s = flash._qk(q_ref, keys_ref, rows, keys)
        if counts is not None:
            s = jnp.where(counts(s.shape), s, _NEG_INF)
        fold._fold_tile(s, None, values_ref, scratch, exponent, rows, keys)

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
                fold_in, kb_ref, vb_ref,
                lambda shape: jax.lax.broadcasted_iota(
                    jnp.int32, shape, 1) < visible))
        for prefix in prefixes:
            pl.when(partly & (visible == prefix))(functools.partial(
                fold_in, kb_ref, vb_ref, keys=(0, prefix)))
        reads = reads & jnp.logical_not(partly)

    @pl.when(reads)
    def _before():
        fold_in(kb_ref, vb_ref)

    # the window's own keys, causally
    kfirst = (own * n_local + n_local + n_remote - 1 - j) * block_k
    needed = (j >= n_remote) & (kfirst <= first + block_q - 1)
    crosses = kfirst + block_k - 1 > first
    parts = fold._edge_parts(block_q, block_k, n_local + n_remote, window)

    @pl.when(needed & crosses)
    def _diagonal():
        if parts == 1:
            return fold_in(k_ref, v_ref, lambda shape: (
                first + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
                >= kfirst + jax.lax.broadcasted_iota(jnp.int32, shape, 1)))
        # equal tiles: the tile's corner lies on the diagonal, and part r
        # reads the keys up to its own square
        size = block_q // parts
        for r in range(parts):
            fold_in(k_ref, v_ref, lambda shape, r=r: (
                jax.lax.broadcasted_iota(jnp.int32, shape, 1)
                - jax.lax.broadcasted_iota(jnp.int32, shape, 0) <= r * size),
                (r * size, size), (0, (r + 1) * size))

    @pl.when(needed & jnp.logical_not(crosses))
    def _below():
        fold_in(k_ref, v_ref)

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
    `flash._flash_fwd_lse`'s, by shape: heads of whole lane blocks (multiples
    of 128 channels) are read IN PLACE from the (B, T, H x D) arrays the
    projections wrote, the summaries are written and read the same way,
    and the output is written where the output projection reads it; at
    any other width (a test's 8) every operand is copied head-major to
    (B x H, T, D) first and the output copied back."""
    import jax.experimental.pallas as pl

    b, t, h, d = q.shape
    dv = v.shape[-1]
    in_place = layout._lanes_whole(d, dv)
    at = layout._block_at(in_place, h)
    per_window = window // chunk
    windows_before = -(-t // window) - 1
    qf, kf, vf = (
        layout._pad_seq(layout._rows(x, in_place), max(block_q, block_k))[0]
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
        kbf, vbf = (
            layout._pad_seq(layout._rows(
                x[:, :windows_before * per_window], in_place), block_s)[0]
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
            scratch_shapes=fold._fold_scratch(block_q, dv, block_k, block_s),
            interpret=interpret, name=f"eva_attn_{tag}",
        )(qf, kf, vf, kbf, vbf)
    return layout._heads(out[:, :t], b, h, in_place)


def eva_tile_pairs(t: int, window: int, chunk: int, block_q: int,
                   block_k: int, block_s: int):
    """-> (computed, needed) for one head of one row of `t` positions, in
    tiles of block_q x block_k: what the flash tier's attention kernel
    COMPUTES at those tiles, by the kernel's own rules (a key block at or
    below the diagonal, the diagonal's `fold.edge_tile_share` of one where
    `fold._edge_parts` folds it in parts; a block of summaries that holds one
    its queries see, the key prefix of `_edge_prefixes` where it ends past
    them; a block of summaries counts block_s / block_k of a tile), and
    the (query, key) and (query, summary) pairs the masks leave. Their
    ratio is what the tiles' edges cost (`flash.band_tile_pairs`' count, for
    this kernel)."""
    per_window = window // chunk
    share = fold.edge_tile_share(fold._edge_parts(
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
    `fold.flash_tiles` unless a test names them)."""
    if window % chunk:
        raise ValueError(f"a window of {window} positions is not whole "
                         f"chunks of {chunk}")
    t = q.shape[1]
    if t <= window:
        return flash.causal_attention(q, k, v, impl,
                                **({"interpret": True} if interpret else {}))
    before = (-(-t // window) - 1) * window      # positions that are pooled
    if summaries is not None and summaries[0].shape[1] < before // chunk:
        raise ValueError(
            f"a row of {t} positions reads {before // chunk} summaries "
            f"(windows of {window}, chunks of {chunk}); got "
            f"{summaries[0].shape[1]}")
    if layout._known(impl) != "flash":
        if summaries is None:
            with jax.named_scope("eva.summarise"):
                summaries = eva_summaries(k, v, phi, mu, chunk, upto=before)
        kbar, vbar = (x[:, :before // chunk] for x in summaries)
        with jax.named_scope("eva.attend"):
            return (xla._eva_dense if impl == "dense" else xla._eva_chunked)(
                q, k, v, kbar, vbar, window, chunk)
    rule_q, rule_k = fold.flash_tiles(t, t, q.dtype, window=window)
    block_q, block_k = block_q or rule_q, block_k or rule_k
    block_s = block_s or fold.flash_tiles(t, before // chunk, q.dtype)[1]
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
    fold._count_edge_parts(block_q, block_k, steps, window)
    fold._count_fold_rows("eva", block_q, steps)
    fold._count_operands("eva", layout._lanes_whole(q.shape[-1], v.shape[-1]))
    return _eva_flash(q, k, v, phi, mu, *(summaries or ()), window=window,
                      chunk=chunk, block_q=block_q, block_k=block_k,
                      block_s=block_s, interpret=interpret)
