"""The decoders' log-likelihood head: log_softmax(h @ head)[next token] a
token, in ONE place. `chunked_logprobs` is XLA's path (a chunk of tokens at
a time: the product to HBM, the log-sum-exp over it, the target's pick);
`loglik_head` is the Pallas call that folds the product over the
vocabulary, the log-sum-exp and the pick, so that no (tokens, vocabulary)
array exists in HBM in any type, and that reads a tied embedding (V, d)
where the parameters keep it. `head_tiles` is the rule that says which runs
and at what tile, from the call's shapes alone; `token_logprobs` is what
the decoders call. What the rule's constants measured on a v5e is in
PERF.md (section 6, PR 50)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..observability.metrics import get_registry
from .lanes import LANES, NEG_INF, STEP_VMEM, lane_sums, over

# What a custom call gets as its scoped VMEM without asking. A `loglik_head`
# whose step does not fit that STATES `lanes.STEP_VMEM`, twice it, the one
# constant of that kind (what a call states beyond the default is taken
# from the whole program: 32 MB cost the neighbours 0.04 to 0.2% of a call,
# 64 MB cost 8%). `head_tiles` takes the tile that asks for nothing
# wherever one of 256 columns or more fits.
_DEFAULT_VMEM = 16 * 1024 * 1024
# The most bytes of ONE block of the head: blocks of 5.2 MB (1024 columns
# of d = 2560, 512 of 5120) ran the call a third SLOWER than XLA's chunk,
# whatever else the step held; 3.9 and 4.2 MB (768 of 2560, 384 of 5120,
# 1024 of 2048) ran it at the best tile's pace (PERF.md, PR 50).
_HEAD_BLOCK = 4 * 1024 * 1024
# the fewest vocabulary blocks a call folds: below that the running
# statistics carry nothing worth a kernel (EvaByte's 320 bytes a head)
_HEAD_BLOCKS = 4


def _head_bytes(tm: int, tn: int, d: int, itemsize: int) -> int:
    """VMEM a grid step of `loglik_head` holds, counted as
    `fold._step_bytes` counts: the head's block in two slots (the pipeline
    fetches the next step's while this one folds), the hidden states' tile
    in ONE (it changes once in V / tn steps), the targets' and the output's
    blocks in two, 128 lanes wide each, the three running statistics, and
    the float32 tiles of the row parts in flight (the products, which the
    compiler spills, their exponentials and the target's select: three of
    a whole tile, four of a half), and what the product keeps of a part's
    rows past 2048 of d. The chip's compiler counts 12.24, 17.45 and 26.08
    MB at (512, 512), (1024, 512) and (1024, 1024) of d = 2560, 15.48 at
    (1024, 512) of 2048 and 16.20 and 23.56 at (512, 256) and (1024, 256)
    of 5120 (compiled for a described v5e under a limit lowered in steps of
    a quarter MB, PR 50): this count is 0.02 to 0.9 MB over it, at the
    last of them level with it."""
    parts = _row_parts(tm)
    rows = tm // parts
    return (tm * d * itemsize + 2 * tn * d * itemsize
            + (2 * 2 + 3) * tm * LANES * 4
            + (3 if parts == 1 else 4) * rows * tn * 4
            + rows * max(d - 2048, 0) * itemsize)


def _row_parts(tm: int) -> int:
    """In how many parts along the tokens a step takes its tile: parts of
    512 rows, `fold._row_parts`' size. The parts are no faster than the
    whole tile here (191.7 and 191.9 TFLOP/s at 1024 x 512 of phi4's head,
    PR 50): the float32 tiles in flight are a part's, so a tile of 1024
    tokens keeps 128 more columns inside the same VMEM."""
    return tm // 512 if tm % 512 == 0 else 1


def head_tiles(tokens: int, d: int, vocab: int, itemsize: int,
               chunk: int = 1024):
    """-> (tm, tn): the tokens and the vocabulary's columns a grid step of
    `loglik_head` takes of a head of (d, vocab) (or a tied one's (vocab,
    d)) over `tokens` hidden states; None where the kernel does not take
    the head (float32 operands, a width that is no multiple of 128, a
    `chunk` that is no multiple of the 16 rows a register of 2-byte
    operands holds, a vocabulary of fewer than `_HEAD_BLOCKS` blocks, a
    width whose tile passes `STEP_VMEM`) and `chunked_logprobs` runs it,
    which takes any chunk. ONE rule for every family, from what the call
    shows.

    Tokens: `chunk` (the decoders' `head_chunk`), or the tokens where they
    are fewer: a step streams the head's block against the tile's rows, so
    at 1024 rows its product pays the block's fetch four times over, and
    the whole head is read once a TILE (512 rows read 1.5% slower, 2048 no
    faster). Columns: the most, in multiples of 128 and a block of
    `_HEAD_BLOCK` bytes at most, that fit the VMEM a call gets without
    asking if 256 columns do, else what it may state. d is taken whole.
    That gives 1024 x 384 at d = 2560 (V = 200064, 37984), 1024 x 512 at
    2048 (65536, 40960) and 1024 x 384 under a stated 32 MB at 5120
    (32640): in each the last block hangs over by 32 columns at most."""
    if (itemsize != 2 or d % LANES or chunk % 16
            or vocab < _HEAD_BLOCKS * LANES):
        return None
    tm = min(chunk, -(-tokens // 16) * 16)
    for budget in (_DEFAULT_VMEM, STEP_VMEM):
        fits = [tn for tn in range(LANES, vocab // _HEAD_BLOCKS + 1, LANES)
                if tn * d * itemsize <= _HEAD_BLOCK
                and _head_bytes(tm, tn, d, itemsize) <= budget]
        if fits and (max(fits) >= 2 * LANES or budget == STEP_VMEM):
            return tm, max(fits)
    return None


def chunked_logprobs(flat, target, head, *, multiplier: float = 1.0,
                     chunk: int = 1024):
    """XLA's path: log_softmax(flat @ head)[target] a token, `chunk` tokens
    at a time. flat (n, d); target (n,) int32; head (d, V). -> (n,)
    float32. Products take the operands' type and accumulate in float32;
    `multiplier` is the family's scalar on the logits."""
    n, d = flat.shape
    chunk = min(chunk, n)
    pad = (-n) % chunk
    if pad:
        flat = jnp.pad(flat, ((0, pad), (0, 0)))
        target = jnp.pad(target, (0, pad))

    def one(xs):
        hc, tc = xs
        logits = jnp.dot(hc, head, preferred_element_type=jnp.float32)
        if multiplier != 1.0:
            logits = logits * multiplier
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, tc[:, None], -1)[:, 0]
        return picked - lse

    out = jax.lax.map(one, (flat.reshape(-1, chunk, d),
                            target.reshape(-1, chunk)))
    return out.reshape(-1)[:n]


def _head_kernel(target_ref, h_ref, w_ref, out_ref, m_sc, l_sc, p_sc, *,
                 tied: bool, multiplier: float, tn: int, vocab: int,
                 blocks: int, parts: tuple):
    """A grid step of `loglik_head`: the tile's hidden states (tm, d)
    against ONE block of the head ((tn, d) of a tied one, (d, tn)), folded
    into the running maximum, sum and picked logit of the tile's tokens.

    The statistics are LANE-DENSE, (tm, 128) float32, and a lane's own:
    lane c keeps the maximum and the sum of exp(logit - that maximum) over
    the columns c, c + 128, ... seen so far, so a step takes its tile with
    elementwise operations alone; the ONE maximum and sum across lanes a
    token are the last step's. The target's logit is added where the
    column's index meets the token's target, in the one block that holds
    it. Columns at or past `vocab` (the last block's tail) are masked, in
    the last step only."""
    import jax.experimental.pallas as pl

    j = pl.program_id(1)
    contract = (((1,), (1 if tied else 0,)), ((), ()))

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        p_sc[...] = jnp.zeros_like(p_sc)

    def fold(ragged: bool):
        w = w_ref[...]
        for first, size in parts:
            mine = (pl.ds(first, size), slice(None))
            s = jax.lax.dot_general(h_ref[mine], w, contract,
                                    preferred_element_type=jnp.float32)
            if multiplier != 1.0:
                s = s * multiplier
            column = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            hit = column == over(target_ref[mine] - j * tn, tn)
            p_sc[mine] += lane_sums(jnp.where(hit, s, 0.0), LANES)
            if ragged:
                ok = column < vocab - (blocks - 1) * tn
                s = jnp.where(ok, s, NEG_INF)
            m_prev = m_sc[mine]
            m_new = jnp.maximum(m_prev, functools.reduce(
                jnp.maximum, jnp.split(s, tn // LANES, 1)))
            p = jnp.exp(s - over(m_new, tn))
            if ragged:
                # a lane whose every column so far is masked holds
                # m == NEG_INF, and exp(s - m) is 1 there, not 0
                p = jnp.where(ok, p, 0.0)
            l_sc[mine] = (l_sc[mine] * jnp.exp(m_prev - m_new)
                          + lane_sums(p, LANES))
            m_sc[mine] = m_new

    if vocab == blocks * tn:
        fold(False)
    else:
        # only the last block has a tail: decided here, in Python
        pl.when(j < blocks - 1)(functools.partial(fold, False))
        pl.when(j == blocks - 1)(functools.partial(fold, True))

    @pl.when(j == blocks - 1)
    def _write():
        m = m_sc[...]
        top = m.max(-1, keepdims=True)
        total = (l_sc[...] * jnp.exp(m - top)).sum(-1, keepdims=True)
        out_ref[...] = (p_sc[...].sum(-1, keepdims=True)
                        - (top + jnp.log(total)))


def _loglik_pallas(flat, target, head, *, tied: bool, multiplier: float,
                   tiles: tuple, interpret: bool):
    """The Pallas call over a grid of (token tiles, vocabulary blocks), the
    vocabulary last and in order. flat (n, d), target (n,), head (V, d) if
    `tied` else (d, V). -> (n,) float32."""
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    n, d = flat.shape
    vocab = head.shape[0 if tied else 1]
    tm, tn = tiles
    pad = (-n) % tm
    if pad:
        flat = jnp.pad(flat, ((0, pad), (0, 0)))
        target = jnp.pad(target, (0, pad))
    blocks = -(-vocab // tn)
    size = tm // _row_parts(tm)
    needs = _head_bytes(tm, tn, d, flat.dtype.itemsize)
    out = pl.pallas_call(
        functools.partial(
            _head_kernel, tied=tied, multiplier=float(multiplier), tn=tn,
            vocab=vocab, blocks=blocks,
            parts=tuple((r * size, size) for r in range(tm // size))),
        grid=((n + pad) // tm, blocks),
        in_specs=[
            # a token's target across its lanes, as the statistics lie
            pl.BlockSpec((tm, LANES), lambda i, j: (i, 0)),
            # the tile changes once in `blocks` steps: ONE slot
            pl.BlockSpec((tm, d), lambda i, j: (i, 0),
                         pipeline_mode=pl.Buffered(1)),
            pl.BlockSpec((tn, d), lambda i, j: (j, 0)) if tied
            else pl.BlockSpec((d, tn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((tm, 1), lambda i, j: (i, 0)),
        scratch_shapes=[pltpu.VMEM((tm, LANES), jnp.float32)] * 3,
        out_shape=jax.ShapeDtypeStruct((n + pad, 1), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=STEP_VMEM if needs > _DEFAULT_VMEM else None),
        interpret=interpret,
        name="loglik_head",
    )(jnp.broadcast_to(target.astype(jnp.int32)[:, None], (n + pad, LANES)),
      flat, head)
    return out[:n, 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _loglik(flat, target, head, tied, multiplier, tiles, interpret):
    return _loglik_pallas(flat, target, head, tied=tied,
                          multiplier=multiplier, tiles=tiles,
                          interpret=interpret)


def _loglik_fwd(flat, target, head, tied, multiplier, tiles, interpret):
    return (_loglik(flat, target, head, tied, multiplier, tiles, interpret),
            (flat, target, head))


def _loglik_bwd(tied, multiplier, tiles, _interpret, kept, g):
    """The gradient is XLA's path's, recomputed a chunk at a time."""
    flat, target, head = kept
    _out, pull = jax.vjp(
        lambda f, w: chunked_logprobs(f, target, w.T if tied else w,
                                      multiplier=multiplier, chunk=tiles[0]),
        flat, head)
    d_flat, d_head = pull(g)
    return d_flat, None, d_head


_loglik.defvjp(_loglik_fwd, _loglik_bwd)


# jitted by itself, like the experts' products: one trace and lowering a shape
@functools.partial(jax.jit, static_argnames=("tied", "multiplier", "tiles",
                                             "interpret"))
def loglik_head(flat, target, head, *, tied: bool, tiles: tuple,
                multiplier: float = 1.0, interpret: bool = False):
    """log_softmax((flat @ head) x multiplier)[target] a token as ONE
    Pallas call at `tiles` (`head_tiles`). flat (n, d); target (n,) int32;
    head (V, d) where `tied` (the embedding, as the parameters keep it),
    else (d, V). -> (n,) float32. Operands of 2 bytes, float32 products and
    sums; a gradient is `chunked_logprobs`'."""
    return _loglik(flat, target, head, tied, multiplier, tiles, interpret)


def token_logprobs(flat, target, head, *, embedding=None,
                   multiplier: float = 1.0, chunk: int = 1024):
    """What the decoders' head calls. head: (d, V); `embedding`: (V, d),
    where the head is the embedding transposed (the module says so, not a
    name), as the parameters keep it. On a TPU, where `head_tiles` takes
    the shapes, `loglik_head`, which reads the embedding where it is given
    one, so that nothing forms `head`; everywhere else `chunked_logprobs`
    over `head`. Counted where it is traced."""
    n, d = flat.shape
    tied = embedding is not None
    tiles = None
    if jax.default_backend() != "cpu":
        tiles = head_tiles(n, d, head.shape[1], flat.dtype.itemsize, chunk)
    get_registry().counter(
        "mmlspark_tpu_loglik_head_calls_total",
        "log-likelihood heads traced, by what runs them (pallas: the ONE "
        "call `loglik_head`; xla: a chunk's logits to HBM and back) and by "
        "whether the head is the embedding",
        labels=("kernel", "head")).labels(
            kernel="pallas" if tiles else "xla",
            head="tied" if tied else "untied").inc()
    if tiles:
        return loglik_head(flat, target, embedding if tied else head,
                           tied=tied, tiles=tiles, multiplier=multiplier)
    return chunked_logprobs(flat, target, head, multiplier=multiplier,
                            chunk=chunk)
