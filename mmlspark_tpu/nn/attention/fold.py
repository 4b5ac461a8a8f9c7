"""The fold every Pallas forward here takes, in ONE place: the tile rule
(`flash_tiles`), the rule for the query heads a grid step folds
(`heads_a_step`), the rules for the tiles a mask's edge crosses
(`_edge_parts`, `_row_parts`), the online-softmax step (`_fold_tile`), the
kernel body of a (query block, key block) pair of a row and head
(`_flash_fold`), the list of the pairs that fold something (`_fold_steps`),
the call whose grid walks it, a step a pair of one head or of several that
share their keys (`_flash_call`), and the registry's counters of which path a
traced shape took. The plain, banded and latent cores (`flash.py`,
`latent.py`) are `_flash_fold` with their own products; `eva.py`'s kernel
takes `_fold_tile` directly. What each rule measured on a v5e is in PERF.md
(section 6: PRs 27, 30, 31, 41, 43, 44, 47, 48)."""

from __future__ import annotations

import collections
import functools
import math

import jax
import jax.numpy as jnp

from ...observability.metrics import get_registry
from ..lanes import LANES, STEP_VMEM, lane_sums, over
from .layout import _NEG_INF


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


def flash_tiles(tq: int, tk: int, dtype,
                window: int | None = None) -> tuple[int, int]:
    """The (block_q, block_k) the flash forward works on, from what it can
    see. The kernel pays a fixed cost a grid step whatever is in it, so the
    largest tile wins; the cap is 1024 for inputs of 2 bytes and 512 for
    float32, where a 1024 x 1024 tile passes the default 16 MB of scoped
    VMEM (what a kernel asks beyond the default is taken from the whole
    program). A tile is a multiple of 128, or the whole of a sequence
    shorter than that, and is never bought with padding: the padded length
    stays within one eighth of the length rounded up to 128 (512 -> 512,
    514 -> 640, 1100 -> two of 640, 4096 -> 1024). The head's width plays
    no part: the score tile, not the head, fills VMEM. What a causal
    mask's EDGE costs at that tile is cut inside the step, not by a
    smaller tile (`_edge_parts`). The readings: PERF.md, PRs 27, 30, 31.

    Told a `window` (`eva_attention`: a query reads the keys of its own
    window of that many positions), both tiles are the largest under the
    cap that DIVIDE the window, so that a block of queries lies in one
    window and a window is whole blocks of keys: 1024 x 1024 of 2048.
    Without one the answers are what they were."""
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


def _step_bytes(heads: int, block_q: int, block_k: int, d: int, dv: int,
                itemsize: int) -> int:
    """VMEM a grid step of `_flash_call` holds at `heads` query heads a
    step, counted as `parallel.moe._grouped_bytes` counts: every block in
    two slots (the pipeline fetches the next step's while this one folds),
    an array's last dimension padded to the 128 lanes it occupies ((block_q,
    64) and lse's (block_q, 1) are stored 128 wide). A head: its q and
    output blocks, its lse column, its running maximum, sum and accumulator
    (`_fold_scratch`): 3.5 MB at tiles of 1024. The step, once: the key and
    value blocks, and three float32 tiles of the `_row_parts` part in flight
    (the scores, which the compiler spills, their exponentials, and a
    mask's positions where a padded tail or an edge needs one): 7 MB. The
    chip's compiler counts 5.95, 9.45 and 16.45 MB at 1, 2 and 4 heads of
    64 and 6.45 and 27.45 at 1 and 7 of 128, 2.6 MB more where the keys'
    tail is padded, and at float32 heads of 256 in tiles of 512 0.2 MB over
    this count less a tile (compiled for a described v5e, PR 48): this
    count is 1.5 to 4.5 MB over it, never under."""
    def lanes(width):
        return -(-width // LANES) * LANES

    a_head = (2 * block_q * (lanes(d) + lanes(dv)) * itemsize
              + 2 * block_q * LANES * 4
              + block_q * (2 * LANES + lanes(dv)) * 4)
    shared = (2 * block_k * (lanes(d) + lanes(dv)) * itemsize
              + 3 * block_q // _row_parts(block_q) * block_k * 4)
    return heads * a_head + shared


def heads_a_step(group: int, key_blocks: int, block_q: int, block_k: int,
                 d: int, dv: int, itemsize: int) -> int:
    """How many query heads of a key/value head ONE grid step of the flash
    forward folds, from what the call's shapes show: the largest divisor of
    the `group` (query heads a key/value head) whose blocks and statistics
    fit `STEP_VMEM` (`_step_bytes`): the whole group in the cells that have
    one (4 heads of 64, 5 and 7 of 128). The heads of a step share the fetch
    of the key and value blocks, which a kernel that reads them in place, in
    strided pieces, does not hide under its folds (6% of it), and the step's
    own cost (PERF.md, PR 48). 1, the program of one head a step, where
    heads share nothing (`group` 1) and where a row is ONE key block, which
    carries nothing from step to step."""
    if group == 1 or key_blocks == 1:
        return 1
    return max(g for g in range(1, group + 1) if group % g == 0 and (
        g == 1 or _step_bytes(g, block_q, block_k, d, dv,
                              itemsize) <= STEP_VMEM))


def _band_first(qi: int, block_q: int, block_k: int, window: int) -> int:
    """The first key block that query block `qi` of a band reads: the one
    holding the key `window - 1` behind the block's first query, or 0."""
    return max(qi * block_q - (window - 1), 0) // block_k


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
    a part is `_PART_ROWS` rows or more: tiles of 1024. More parts are no
    faster and cost a start their equations; parts of fewer rows lose
    (PERF.md, PR 41)."""
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
    of 2 bytes). A row sums the same keys in the same order either way
    (PERF.md, PR 44)."""
    return 2 if block_q % 256 == 0 and block_q // 2 >= _PART_ROWS else 1


def _row_halves(block_q: int) -> tuple:
    """The rows (first, how many; None: all) of each of `_row_parts`' parts
    of a tile that nothing masks. A Python loop over them writes the fold
    once and applies it to each, as the edge tiles' parts are: ONE traced
    body unrolled by the lowering (`lax.fori_loop`) schedules the same
    bundles but costs a start several times the tracing and lowering
    (PERF.md, PR 44)."""
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


_LOG2_E = math.log2(math.e)


def _stat_lanes(*widths: int) -> int:
    """How many lanes wide the running maximum and sum are kept: 128, a
    vector register's, where the score tiles' columns are whole blocks of
    that many (every tile `flash_tiles` chooses past 128 keys); the widest
    block that divides them at a test's small tile."""
    return math.gcd(LANES, *widths)


def _weigh(s, ok, m, v_ref, exponent, lanes, keys=None):
    """exp((s - m) x scale) of RAW products s and their raw maximum m (a
    (rows, 1) column, or (rows, lanes) replicated), as ONE multiply an
    element: exp2 of (s - m) x `exponent`, the scale times log2 e folded in
    Python (scale > 0, so the maximum commutes with it). -> its row sums as
    `lanes` per-lane partial sums (rows, lanes), and its product with the
    `keys` of the value block (rows, Dv)."""
    p = jnp.exp2((s - over(m, s.shape[1])) * exponent)
    if ok is not None:
        # masked entries must contribute 0 even when the whole row is
        # masked (then m == _NEG_INF and exp(s - m) == 1, not 0)
        p = jnp.where(ok, p, 0.0)
    pv = jax.lax.dot_general(
        p.astype(v_ref.dtype), _block(v_ref, keys),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    return lane_sums(p, lanes), pv


def _fold_tile(s, ok, v_ref, scratch, exponent, rows=None, keys=None):
    """The online-softmax step every fold of this package takes (the plain,
    latent and banded forwards' `_flash_fold` and `eva._eva_kernel`): a
    float32 tile `s` of RAW products (unscaled: `exponent` is the scale
    times log2 e, `_weigh`), masked already, of the `rows` of the query block
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
    acc_sc[mine] = acc_sc[mine] * over(corr, pv.shape[1]) + pv
    m_sc[mine] = m_new


def _flash_fold(products, v_ref, o_ref, lse_ref, scratch, *, block_q,
                block_k, num_kv, key_blocks, causal, tk_valid, scale,
                window=None, walk=None, step=None):
    """What every flash forward does with a score tile, a grid step a
    (query block, key block) pair that folds something (`_fold_steps`):
    `products(rows, keys)` is this step's raw float32 products of queries
    and keys (over a head's channels, or over the latent score's two
    parts), of the whole (bq, bk) tile or of the `rows` and `keys` (first,
    how many) of it; the masks, the online softmax and the finalisation
    are here. `walk` is the scalar-prefetch ref of the steps the grid's
    last axis takes (`_flash_call`): -1, the steps' query blocks, -1, their
    key blocks; `step` is this one's place in it (the grid's last axis); a
    step is its query block's first where the entry before its own differs,
    and its last where the one after does. A call of ONE key block has
    none: the grid's last two axes are the query block and that block.
    `num_kv` is the most key blocks a query block reads. Told a
    `window` (a causal band: a query reads the `window` keys that end with
    its own, of `key_blocks` blocks in all), the block that the band's
    trailing edge crosses is masked like the diagonal's.

    An EDGE tile, where `_edge_parts` says so, is folded in parts along
    the queries: a part's rows against the keys its mask leaves and no
    others, so the corner of the tile that the mask would erase whole is
    neither multiplied nor exponentiated (an erased entry gave exp(-inf) =
    0: every row still sums over exactly the keys it saw, in another
    order). The running maximum, sum and accumulator are a row's own, so
    the parts touch disjoint rows of the scratch and carry nothing new."""
    import jax.experimental.pallas as pl

    if walk is None:
        qi, kv = pl.program_id(2), pl.program_id(3)
    else:
        at, n = step, walk.shape[0] // 2 - 1
        qi, kv = walk[1 + at], walk[n + 2 + at]
    # only a padded sequence needs the key mask: decided here, in Python
    padded = tk_valid < key_blocks * block_k

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

    @pl.when(walk[at] != qi)                  # its query block's first step
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
        # a key block wholly above the diagonal, or wholly behind a band,
        # is no step (`_fold_steps`); one wholly inside needs no mask
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
            pl.when(crosses)(functools.partial(fold, True))
            pl.when(jnp.logical_not(crosses))(functools.partial(fold, False))
        else:
            # the band's other edge: some query of the block lies `window`
            # or more past some key of this one
            trails = qi * block_q + block_q - 1 - kv * block_k >= window
            for diagonal in (True, False):
                for trailing in (True, False):
                    if parts > 1 and diagonal and trailing:
                        # equal tiles that divide the window: the edges
                        # are `window // block_k` blocks apart
                        continue
                    pl.when((crosses if diagonal
                             else jnp.logical_not(crosses))
                            & (trails if trailing
                               else jnp.logical_not(trails)))(
                        functools.partial(fold, diagonal, trailing))

    @pl.when(walk[at + 2] != qi)              # its query block's last step
    def _finalize():
        # the ONE sum across lanes a row
        write(m_sc[:, :1], l_sc[...].sum(-1, keepdims=True), acc_sc[...])


def _fold_scratch(block_q: int, dv: int, *key_widths: int,
                  heads: int = 1) -> list:
    """`_fold_tile`'s scratch: the running maximum and sum, lane-dense, and
    the accumulator. As (block_q, 1) columns the first two were padded to
    128 lanes already: the same VMEM. A step of several `heads`
    (`heads_a_step`) holds a set a head, along a leading axis."""
    import jax.experimental.pallas.tpu as pltpu

    lanes = _stat_lanes(*key_widths)
    lead = () if heads == 1 else (heads,)
    return [pltpu.VMEM(lead + (block_q, lanes), jnp.float32),
            pltpu.VMEM(lead + (block_q, lanes), jnp.float32),
            pltpu.VMEM(lead + (block_q, dv), jnp.float32)]


class _HeadOf:
    """Head `head` of a grid step's block of several heads (`_flash_call`),
    read and written as the bodies read and write one head's: the (1,
    positions, width) block that is row `head` of the step's axis 0 or, told
    `lanes`, those lanes of its last axis; not a `block`, a head's
    statistics, (positions, width) at `head` of axis 0. Indexed THROUGH, no
    view of the ref (`ref.at[...]`): Mosaic slices a memref in whole tiles,
    and a head of 64 channels, or lse's one column, is less than a tile's
    128 lanes."""

    def __init__(self, ref, head, lanes=None, block=True):
        self.ref, self.dtype = ref, ref.dtype
        self.head, self.lanes, self.block = head, lanes, block

    @property
    def shape(self):
        return self.ref.shape[1:]              # of a head's statistics

    def _at(self, idx):
        idx = () if idx is ... else idx if isinstance(idx, tuple) else (idx,)
        # a block's own leading index, 0 of 1, becomes the head's
        idx = (self.head, *idx[self.block:])
        if self.lanes is not None:
            idx = (idx[0], idx[1] if len(idx) > 1 else slice(None), self.lanes)
        return idx

    def __getitem__(self, idx):
        return self.ref[self._at(idx)]

    def __setitem__(self, idx, value):
        self.ref[self._at(idx)] = value


def _band_steps(tq: int, block_q: int, block_k: int, window: int) -> int:
    """The key blocks the widest band of a query block touches
    (`window // block_k + 1` where the tiles are equal and divide the
    window): the longest run of one query block in `_fold_steps`' list,
    what `_edge_parts` and `band_tile_pairs` take for a banded forward's
    steps a query block."""
    return max(collections.Counter(
        qi for qi, _kv in _fold_steps(tq, tq, block_q, block_k, True,
                                      window)).values())


@functools.lru_cache(maxsize=None)
def _fold_steps(tq: int, tk: int, block_q: int, block_k: int, causal: bool,
                window: int | None = None) -> tuple:
    """The (query block, key block) pairs of a forward that FOLD something,
    in the order the grid walks them: query blocks ascending, and of a
    query block its key blocks ascending from the first its mask leaves (0;
    with a `window`, `_band_first`) to the last (every one; causal, the
    diagonal's). Without a mask that is the whole rectangle; a causal row
    of 16384 in tiles of 1024 has 136 of its square's 256, its band of
    4096 has 70."""
    nk = -(-tk // block_k)
    pairs = []
    for qi in range(-(-tq // block_q)):
        first = 0 if window is None else _band_first(
            qi, block_q, block_k, window)
        last = min((qi * block_q + block_q - 1) // block_k,
                   nk - 1) if causal else nk - 1
        if first > last:
            raise ValueError(
                f"query block {qi} of {tq} positions reads none of {tk} keys "
                f"behind a window of {window}")
        pairs += [(qi, kv) for kv in range(first, last + 1)]
    return tuple(pairs)


def _count_grid_steps(kernel: str, tq: int, tk: int, block_q: int,
                      block_k: int, causal: bool,
                      window: int | None = None) -> None:
    """Counted where a flash forward is traced: the grid steps a head of a
    row takes (`_fold_steps`) beside those of the whole rectangle of (query
    block, key block) pairs, the square or a band's `_band_steps` a query
    block. Equal where nothing is masked."""
    nq = -(-tq // block_q)
    steps = -(-tk // block_k) if window is None else _band_steps(
        tq, block_q, block_k, window)
    counter = get_registry().counter(
        "mmlspark_tpu_flash_grid_steps_total",
        "grid steps a head of a row takes in the flash-attention forward "
        "calls traced, by the kernel (gqa: plain causal; attn: plain, no "
        "mask; swa: banded; mla: latent) and by kind: visited (the steps "
        "that fold something, which the grid walks) and square (every "
        "query block against every key block, or against its band's most)",
        labels=("kernel", "kind"))
    counter.labels(kernel=kernel, kind="visited").inc(
        len(_fold_steps(tq, tk, block_q, block_k, causal, window)))
    counter.labels(kernel=kernel, kind="square").inc(nq * steps)


def _count_heads_a_step(kernel: str, group: int, heads: int) -> None:
    """Counted where a flash forward is traced, beside its grid steps: how
    many query heads ONE grid step folds (`heads_a_step`)."""
    get_registry().counter(
        "mmlspark_tpu_flash_heads_a_step_total",
        "flash-attention forward calls traced, by the kernel (gqa, attn, "
        "swa, mla), by the query heads a key/value head serves and by the "
        "heads of it ONE grid step folds against a key block fetched once "
        "(1: a head a step)",
        labels=("kernel", "group", "heads")).labels(
            kernel=kernel, group=str(group), heads=str(heads)).inc()


def _flash_call(kernel, queries, keys, value, out_at, out_shape, *, b, h,
                tk, causal, scale, block_q, block_k, interpret, name=None,
                window=None, heads=1, in_place=True):
    """ONE Pallas forward over a grid of (row, head, step), a step a
    (query block, key block) pair that folds something (`_fold_steps`).
    `queries`, `keys` and `value` are (array, block width, at): `at(row,
    head, block along the sequence)` names the (1, positions, width) block
    of that head in the array, wherever it lies; the value block is the
    last input. The output's blocks are named by `out_at` in an array of
    `out_shape` (as wide a block as the value's). -> (out in that shape,
    lse (B x H, Tq, 1) float32); `tk` is the keys' length before padding.
    `name` is the call's own in a device trace; without one the innermost
    `jax.named_scope` around it names it.

    The grid's last axis walks the list, read from ONE scalar-prefetch
    operand (`_flash_fold`'s `walk`), so a block above the diagonal or
    behind a band is no step at all, and a query block's last step, the
    one under which the next block's copies are issued, is one that
    computes. A call of ONE key block holds no list: its grid is (row,
    head, query block, 1), with no operand added.

    `heads` (`heads_a_step`: query heads that share ONE key/value head) makes
    the grid (row, h / heads, step): a step folds its pair for each of
    `heads` consecutive query heads, one after the other, against the key
    and value blocks fetched ONCE. `at` and `out_at` are then told the
    step's place among the h / heads and name the block of ALL its heads:
    `heads` rows of axis 0 where the array is head-major, `heads` lane
    blocks of the last (`in_place`). A head's own q, output, lse and
    statistics are views of the step's; the body is the one head's."""
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu
    import numpy as np

    keys = [*keys, value]
    dv = value[1]
    nq = queries[0][0].shape[1] // block_q
    nk = steps = keys[0][0].shape[1] // block_k
    if window is not None:
        steps = _band_steps(nq * block_q, block_q, block_k, window)
    static = dict(block_q=block_q, block_k=block_k, num_kv=steps,
                  key_blocks=nk, causal=causal, tk_valid=tk, scale=scale,
                  window=window)
    if nk > 1:
        qs, ks = np.asarray(_fold_steps(
            nq * block_q, nk * block_k, block_q, block_k, causal, window)).T
        n = len(qs)
        # ONE operand, both tables end to end: as several operands they
        # moved the latent call's arrays to positions where the compiler
        # no longer wrote the projections in the call's layout and copied
        # q_nope and kv before every call (PERF.md, PR 47)
        walk = (np.concatenate([[-1], qs, [-1], ks]).astype(np.int32),)
        grid = (b, h // heads, n)

        def query_block(at, walk):
            return walk[1 + at]

        def key_block(at, walk):
            return walk[n + 2 + at]

        def fold_head(step, walk, *refs):
            kernel(*refs, walk=walk, step=step, **static)

        def body(walk, *refs):
            fold_head(pl.program_id(2), walk, *refs)
    else:
        walk, grid = (), (b, h, nq, 1)
        body = functools.partial(kernel, **static)

        def query_block(qi, _kv):
            return qi

        def key_block(_qi, kv):
            return kv

    if heads > 1:
        widths = [w for _x, w, _at in queries]

        def block_of(a, ref, width):
            """Head `a`'s (1, positions, width) block of the step's."""
            if in_place:
                return _HeadOf(ref, 0, pl.ds(pl.multiple_of(a * width, width),
                                             width))
            return _HeadOf(ref, a)

        def body(walk, *refs):
            # ONE traced body under a loop over the step's heads: written
            # out a head it schedules the same bundles a head, costs a
            # start 3 to 9 times the compile (5.8 to 17 s a kernel) and ran
            # the banded kernel of 7 heads a step 50% SLOWER (PERF.md, PR
            # 48). The step is read here: interpreted, a loop's body knows
            # no grid
            step = pl.program_id(2)
            qs, rest = refs[:len(queries)], refs[len(queries):]
            shared, (o, lse, *scratch) = rest[:len(keys)], rest[len(keys):]

            def one_head(a, carry):
                fold_head(
                    step, walk,
                    *map(functools.partial(block_of, a), qs, widths),
                    *shared, block_of(a, o, dv), _HeadOf(lse, a),
                    *(_HeadOf(ref, a, block=False) for ref in scratch))
                return carry

            jax.lax.fori_loop(0, heads, one_head, 0)

    stated = {} if heads == 1 else dict(
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=STEP_VMEM))
    # a step's block of `heads` heads: rows of axis 0, or lane blocks
    lead, wide = (1, heads) if in_place else (heads, 1)

    def query_spec(width, at, lead=lead, wide=wide):
        return pl.BlockSpec(
            (lead, block_q, wide * width),
            lambda b_, j, *step: at(b_, j, query_block(*step)))

    def key_spec(width, at):
        return pl.BlockSpec(
            (1, block_k, width),
            lambda b_, j, *step: at(b_, j, key_block(*step)))

    def lse_at(b_, j, qi):
        return b_ * (h // heads) + j, qi, 0

    return pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(walk), grid=grid,
            in_specs=[query_spec(w, at) for _x, w, at in queries]
            + [key_spec(w, at) for _x, w, at in keys],
            out_specs=[
                query_spec(dv, out_at),
                # lse is a (block_q, 1) column, a row's statistic as the
                # finalisation's sum across lanes leaves it: a trailing dim
                # equal to the array's satisfies Mosaic's block rule, and
                # no sublane->lane relayout happens in the kernel
                query_spec(1, lse_at, heads, 1),
            ],
            # one key block carries nothing from step to step
            scratch_shapes=[] if steps == 1 else _fold_scratch(
                block_q, dv, block_k, heads=heads)),
        out_shape=[
            out_shape,
            jax.ShapeDtypeStruct((b * h, nq * block_q, 1), jnp.float32),
        ],
        interpret=interpret, name=name, **stated,
    )(*walk, *(x for x, _w, _at in [*queries, *keys]))
