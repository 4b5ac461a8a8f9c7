"""The state-space scan core (Mamba-2's state-space duality, arXiv
2405.21060), forward of a selective scan with a scalar decay a head:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        S: (P, N), zero before
    y_t = S_t C_t + D x_t                             a row's first token

x (B, T, H, P): H heads of P channels; B, C (B, T, G, N): G groups of N
state channels, head j reads group j // (H / G); dt (B, T, H) float32, after
its softplus; A (H,) negative; D (H,). One contract, two tiers behind one
rule (`tier`), as `attention/layout.py` has it for attention:

- "plain": the chunked form in `jax.numpy`, every backend, differentiable.
  With a_t the running sum of dt A inside a chunk of Q tokens and L_ts =
  exp(a_t - a_s) for s <= t, else 0:
      Y      = ((C B^T) * L)(dt * X) + (exp(a) * C) S_prev
      S_next = exp(a_Q) S_prev + sum_t exp(a_Q - a_t) dt_t x_t B_t^T
  a `lax.scan` over the chunks carrying S.
- "kernel": ONE Pallas call over a grid of (row, group, chunk), the chunk
  axis sequential, every head's state in VMEM scratch from chunk to chunk:
  no state goes to HBM. A grid step takes a GROUP: its B and C are fetched
  and C B^T computed once, then its heads one after the other, three
  products each (a head a step, the first form, re-read B and C and
  re-computed C B^T sixteen times a group at the published widths and ran
  the long rows 3.1 times slower on a v5e: PERF.md, PR 46). x, B and C are
  read IN PLACE from the (B, T, channels) array the convolution wrote (head
  j's P channels are lane block j of it, a group's N channels a block
  further on), so heads and states of whole lanes only
  (`attention.layout._lanes_whole`). Its backward is the plain tier's
  (`jax.custom_vjp`): the chunks recomputed in `jax.numpy`, no kernel.

Precision: the four products of a chunk take the inputs' type and add up in
float32; decays, their running sums, every exp and the state are float32.

A SECOND form, Mamba-1's (arXiv 2312.00752): the decay is a (channel, state)
pair, the step a channel's own, B and C shared by ALL channels:

    S_t[c, n] = exp(dt_t[c] A[c, n]) S_{t-1}[c, n] + dt_t[c] B_t[n] x_t[c]
    y_t[c]    = sum_n C_t[n] S_t[c, n] + D[c] x_t[c]

x (B, T, C); dt (B, T, C) float32, after its softplus; A (C, N) negative; B,
C (B, T, N); D (C,). exp(a_t - a_s) does not factor out of C B^T here, so
there is no (Q, Q) product form: the work is elementwise, the vector unit's.
The same two tiers behind the same rule (`channel_scan`):

- "plain" (`sel_plain`): a `lax.scan` over chunks of Q tokens that carries S
  (B, C, N), the chunk's tokens one after the other inside it; every
  backend, differentiable (a chunk is recomputed in the backward, so what
  is saved is S a chunk), never a (T, C, N) array.
- "kernel" (`sel_kernel`): ONE Pallas call over a grid of (row, channel
  block, chunk), the chunk axis sequential, S of the block's channels in
  VMEM scratch from chunk to chunk, channels on lanes and the N states on
  sublanes; x, dt, B, C read and y written once. Channels in whole lane
  blocks and states in whole sublanes only. Its backward is the plain
  tier's.

Everything of the second form is float32 but x as it is read and y as it is
written.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .attention import layout

CHUNK = 128     # tokens of a chunk: the published `mamba_chunk_size`


def tier(*widths: int) -> str:
    """The tier that runs, by what can be observed and no option: "kernel",
    the Pallas call, wherever attention's "flash" is its Pallas kernels
    (`attention.layout.tier`, the one function that asks the backend: the
    plain tier on the CPU, where Mosaic cannot lower) AND the `widths` (a
    head's channels, a group's state) are whole lane blocks; "plain" at any
    other width."""
    if layout.tier("flash") == "flash" and layout._lanes_whole(*widths):
        return "kernel"
    return "plain"


def running_decay(dt, a, chunk: int = CHUNK):
    """dt (B, T, H) float32, T a multiple of `chunk`; a (H,) -> the running
    sum of dt A inside each chunk, (B, T, H) float32 (inclusive: position
    t's own term is in a_t)."""
    b, t, h = dt.shape
    steps = (dt * a.astype(jnp.float32)).reshape(b, t // chunk, chunk, h)
    return jnp.cumsum(steps, axis=2).reshape(b, t, h)


def _pad_chunks(x, chunk: int):
    return layout._pad_seq(x, chunk)[0]


def ssd_plain(x, bm, cm, dt, a, d, chunk: int = CHUNK):
    """The plain tier -> y (B, T, H, P) in x's type."""
    f32 = jnp.float32
    b, t, h, p = x.shape
    g, n = bm.shape[2:]
    per = h // g
    xq, bq, cq, dq = (_pad_chunks(v, chunk) for v in (x, bm, cm,
                                                     dt.astype(f32)))
    nc = xq.shape[1] // chunk
    acc = running_decay(dq, a, chunk).reshape(b, nc, chunk, g, per)
    dq = dq.reshape(b, nc, chunk, g, per)
    xq = xq.reshape(b, nc, chunk, g, per, p)
    bq = bq.reshape(b, nc, chunk, g, n)
    cq = cq.reshape(b, nc, chunk, g, n)
    seen = jnp.tril(jnp.ones((chunk, chunk), bool))

    def one(state, xs):
        """state (B, G, per, P, N) float32; one chunk of every row."""
        xc, bc, cc, dc, ac = xs
        # (C B^T): once a group, every head of it reads the same
        cb = jnp.einsum("btgn,bsgn->bgts", cc, bc,
                        preferred_element_type=f32)
        # L_ts = exp(a_t - a_s), s <= t: (B, G, per, t, s)
        gap = (jnp.moveaxis(ac, 1, -1)[..., :, None]
               - jnp.moveaxis(ac, 1, -1)[..., None, :])
        decay = jnp.exp(jnp.where(seen, gap, -jnp.inf))
        dtx = (dc[..., None] * xc.astype(f32)).astype(x.dtype)
        y = jnp.einsum("bgkts,bsgkp->btgkp",
                       (cb[:, :, None] * decay).astype(x.dtype), dtx,
                       preferred_element_type=f32)
        y = y + jnp.exp(ac)[..., None] * jnp.einsum(
            "btgn,bgkpn->btgkp", cc, state.astype(x.dtype),
            preferred_element_type=f32)
        last = ac[:, -1]                                   # (B, G, per)
        keep = (jnp.exp(last[:, None] - ac)[..., None]
                * dtx.astype(f32)).astype(x.dtype)
        state = jnp.exp(last)[..., None, None] * state + jnp.einsum(
            "bsgkp,bsgn->bgkpn", keep, bc, preferred_element_type=f32)
        return state, y

    chunks = tuple(jnp.moveaxis(v, 1, 0) for v in (xq, bq, cq, dq, acc))
    _, y = jax.lax.scan(one, jnp.zeros((b, g, per, p, n), f32), chunks)
    y = jnp.moveaxis(y, 0, 1).reshape(b, nc * chunk, h, p)[:, :t]
    skip = d.astype(f32)[:, None] * x.astype(f32)
    return (y + skip).astype(x.dtype)


def _plain_flat(xbc, dt, a, d, *, heads, width, groups, state):
    """The plain tier on the convolution's output as it lies: `xbc` (B, T,
    heads x width + 2 x groups x state), the heads' channels, then B's
    groups, then C's -> y (B, T, heads x width)."""
    b, t, _ = xbc.shape
    inner, bc = heads * width, groups * state
    x = xbc[..., :inner].reshape(b, t, heads, width)
    bm = xbc[..., inner:inner + bc].reshape(b, t, groups, state)
    cm = xbc[..., inner + bc:].reshape(b, t, groups, state)
    return ssd_plain(x, bm, cm, dt, a, d).reshape(b, t, inner)


def _column(block, j):
    """Column `j` (traced) of a (rows, H) block, (rows, 1): a masked sum
    across lanes, no slice at a lane only known when the step runs."""
    lane = jax.lax.broadcasted_iota(jnp.int32, block.shape, 1)
    return jnp.sum(jnp.where(lane == j, block, 0.0), axis=1, keepdims=True)


def _ssd_kernel(x_ref, b_ref, c_ref, dt_ref, acc_ref, row_ref, d_ref, y_ref,
                state_ref, *, chunk, per, width):
    """One chunk of one GROUP of one row: the group's B and C (Q, N) and C
    B^T once, then head after head of the group, `per` of them: x (Q, P) a
    lane block of the group's (Q, per x P) block; dt and the running decay
    a as columns of their (Q, H) blocks and a again as a row (1, Q) of its
    (H, Q) copy; every head's state (N, P) float32 carried in scratch along
    the grid's last axis."""
    import jax.experimental.pallas as pl

    f32 = jnp.float32
    g, c = pl.program_id(1), pl.program_id(2)

    @pl.when(c == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    bm, cm = b_ref[0], c_ref[0]
    cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=f32)     # (Q, Q)
    t_at = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_at = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    seen = s_at <= t_at
    steps, decays = dt_ref[0], acc_ref[0]
    for k in range(per):
        j = g * per + k
        lanes = slice(k * width, (k + 1) * width)
        x = x_ref[0, :, lanes]
        dt = _column(steps, j)                               # (Q, 1)
        at = _column(decays, j)                              # (Q, 1)
        a_row = row_ref[0, 0, pl.ds(j, 1), :]                # (1, Q)
        last = a_row[:, chunk - 1:]                          # (1, 1)
        decay = jnp.exp(jnp.where(seen, at - a_row, -1e30))
        xf = x.astype(f32)
        dtx = (dt * xf).astype(x.dtype)
        y = jnp.dot((cb * decay).astype(x.dtype), dtx,
                    preferred_element_type=f32)
        state = state_ref[k]
        y = y + jnp.exp(at) * jnp.dot(cm, state.astype(x.dtype),
                                      preferred_element_type=f32)
        y_ref[0, :, lanes] = (y + d_ref[k] * xf).astype(y_ref.dtype)
        keep = (jnp.exp(last - at) * dtx.astype(f32)).astype(x.dtype)
        # the chunk's whole decay across lanes first, then down the
        # sublanes: Mosaic broadcasts one way at a time
        whole = jnp.exp(jnp.broadcast_to(last, (1, width)))
        state_ref[k] = whole * state + jax.lax.dot_general(
            bm, keep, (((0,), (0,)), ((), ())), preferred_element_type=f32)


# Jitted by itself, as `attention.flash._banded_flash` is: the call is traced
# and lowered apart from the model's program, under its own name in a device
# trace (`ssd_scan_<i>`; the readers select the prefix)
@functools.partial(jax.jit, static_argnames=("heads", "width", "groups",
                                             "state", "name", "interpret"))
def _ssd_flat(xbc, dt, a, d, heads, width, groups, state, name,
              interpret=False):
    """`xbc` (B, T, heads x width + 2 x groups x state) as the convolution
    wrote it: the heads' channels, then B's groups, then C's. -> y (B, T,
    heads x width)."""
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    f32 = jnp.float32
    b, t, _ = xbc.shape
    per = heads // groups
    xbc = _pad_chunks(xbc, CHUNK)
    dt = _pad_chunks(dt.astype(f32), CHUNK)    # 0 past the row: no decay
    acc = running_decay(dt, a, CHUNK)
    tp = xbc.shape[1]
    nc = tp // CHUNK
    rows = jnp.moveaxis(acc.reshape(b, nc, CHUNK, heads), 3, 2)
    skip = jnp.broadcast_to(d.astype(f32)[:, None, None], (heads, 1, width))
    # B's first block, then C's, in blocks of `state` lanes
    b_first = heads * width // state
    c_first = b_first + groups

    y = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=CHUNK, per=per, width=width),
        grid=(b, groups, nc),
        in_specs=[
            pl.BlockSpec((1, CHUNK, per * width), lambda r, g, c: (r, c, g)),
            pl.BlockSpec((1, CHUNK, state),
                         lambda r, g, c: (r, c, b_first + g)),
            pl.BlockSpec((1, CHUNK, state),
                         lambda r, g, c: (r, c, c_first + g)),
            pl.BlockSpec((1, CHUNK, heads), lambda r, g, c: (r, c, 0)),
            pl.BlockSpec((1, CHUNK, heads), lambda r, g, c: (r, c, 0)),
            pl.BlockSpec((1, 1, heads, CHUNK), lambda r, g, c: (r, c, 0, 0)),
            pl.BlockSpec((per, 1, width), lambda r, g, c: (g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, CHUNK, per * width),
                               lambda r, g, c: (r, c, g)),
        out_shape=jax.ShapeDtypeStruct((b, tp, heads * width), xbc.dtype),
        scratch_shapes=[pltpu.VMEM((per, state, width), f32)],
        interpret=interpret, name=name,
    )(xbc, xbc, xbc, dt, acc, rows, skip)
    return y[:, :t]


# The kernel is forward only; differentiated, the call runs the plain tier's
# backward on what the forward was given (nothing of the kernel is saved:
# its state never left VMEM)
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _ssd_diff(xbc, dt, a, d, heads, width, groups, state, name, interpret):
    return _ssd_flat(xbc, dt, a, d, heads, width, groups, state, name,
                     interpret)


def _ssd_diff_fwd(xbc, dt, a, d, *static):
    return _ssd_flat(xbc, dt, a, d, *static), (xbc, dt, a, d)


def _ssd_diff_bwd(heads, width, groups, state, _name, _interpret, given, dy):
    _y, back = jax.vjp(functools.partial(
        _plain_flat, heads=heads, width=width, groups=groups, state=state),
        *given)
    return back(dy)


_ssd_diff.defvjp(_ssd_diff_fwd, _ssd_diff_bwd)


def ssd_kernel(xbc, dt, a, d, *, heads: int, width: int, groups: int,
               state: int, name: str = "ssd_scan", interpret: bool = False):
    """The kernel tier on the convolution's output as it lies (`_ssd_flat`,
    differentiable through the plain tier: `_ssd_diff`); `interpret=True`
    runs it on the CPU for tests."""
    if (heads * width) % state or heads % groups or not layout._lanes_whole(
            width, state):
        raise ValueError(
            f"the scan kernel reads heads of {width} channels and states of "
            f"{state} in place: whole lane blocks, the states' blocks "
            f"dividing the heads' {heads * width} channels")
    return _ssd_diff(xbc, dt, a, d, heads, width, groups, state, name,
                     interpret)


def scan_steps(rows: int, length: int, heads: int, chunk: int = CHUNK) -> int:
    """Chunks a scan of `rows` rows of `length` tokens steps through, over
    all heads: a step is one chunk of one head (the kernel takes a group's
    heads a grid step; the plain tier all of them a chunk)."""
    return rows * heads * -(-length // chunk)


def selective_scan(xbc, dt, a, d, *, heads: int, width: int, groups: int,
                   state: int, name: str = "ssd_scan"):
    """The one entry a model's state-space mixer calls, on the tier `tier`
    picks: `xbc` (B, T, heads x width + 2 x groups x state) as the
    convolution wrote it -> y (B, T, heads x width)."""
    if tier(width, state) == "kernel":
        return ssd_kernel(xbc, dt, a, d, heads=heads, width=width,
                          groups=groups, state=state, name=name)
    return _plain_flat(xbc, dt, a, d, heads=heads, width=width,
                       groups=groups, state=state)


# ---------------------------------------------------------------------------
# The second form: a decay a (channel, state) pair (Mamba-1)

SEL_BLOCK = 512     # channels of a grid step of the kernel tier, at most
SEL_UNROLL = 8      # tokens written out a pass of the kernel's loop


def sel_block(channels: int) -> int:
    """Channels a grid step of the kernel tier takes: the most whole lane
    blocks, at most `SEL_BLOCK`, that divide `channels` (512 of 5120)."""
    return max(n for n in range(128, min(SEL_BLOCK, channels) + 1, 128)
               if channels % n == 0)


def sel_tier(channels: int, state: int) -> str:
    """`tier`'s answer for the second form: the kernel where the channels
    are whole lane blocks and the states whole sublanes."""
    return "kernel" if tier(channels) == "kernel" and state % 8 == 0 \
        else "plain"


def sel_plain(x, dt, a, bm, cm, d, chunk: int = CHUNK):
    """The plain tier of the second form -> y (B, T, C) in x's type."""
    f32 = jnp.float32
    b, t, c = x.shape
    n = a.shape[1]
    a32 = a.astype(f32)
    padded = [_pad_chunks(v, chunk) for v in (x, dt.astype(f32), bm, cm)]
    nc = padded[0].shape[1] // chunk
    # (chunks, tokens of a chunk, B, .)
    chunks = tuple(jnp.moveaxis(
        v.reshape(b, nc, chunk, v.shape[-1]), 0, 2) for v in padded)

    def token(state, now):
        x_t, dt_t, b_t, c_t = now                  # (B,C) (B,C) (B,N) (B,N)
        fed = (dt_t * x_t.astype(f32))[..., None] * b_t.astype(f32)[:, None]
        state = jnp.exp(dt_t[..., None] * a32) * state + fed
        return state, (state * c_t.astype(f32)[:, None]).sum(-1)

    # a chunk is recomputed in the backward: what is saved is S a chunk
    @jax.checkpoint
    def one(state, xs):
        return jax.lax.scan(token, state, xs)

    _, y = jax.lax.scan(one, jnp.zeros((b, c, n), f32), chunks)
    y = jnp.moveaxis(y.reshape(nc * chunk, b, c), 0, 1)[:, :t]
    return (y + d.astype(f32) * x.astype(f32)).astype(x.dtype)


def _sel_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, state_ref,
                x32_ref, y32_ref, *, chunk, unroll):
    """One chunk of one block of channels of one row: the tokens one after
    the other, S (N, channels) float32 carried in the loop and, from chunk
    to chunk, in scratch. x and y go through float32 copies of the block, so
    that a token's row is read and written as 32-bit sublanes; B and C come
    as (N, Q) tiles, states on sublanes, and token t's column is a masked
    sum across lanes (`_column`)."""
    import jax.experimental.pallas as pl

    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    a, skip = a_ref[...], d_ref[...]                 # (N, C), (1, C)
    bt, ct = b_ref[0, 0], c_ref[0, 0]                # (N, Q)
    x32_ref[...] = x_ref[0].astype(f32)

    def token(t, state):
        """S after token `t` of the chunk, whose y is written."""
        row = pl.ds(t, 1)
        dt, x = dt_ref[0, row, :], x32_ref[row, :]   # (1, C)
        keep = jnp.exp(jnp.broadcast_to(dt, a.shape) * a)
        fed = jnp.broadcast_to(dt * x, a.shape) * jnp.broadcast_to(
            _column(bt, t), a.shape)
        state = keep * state + fed
        y = jnp.sum(state * jnp.broadcast_to(_column(ct, t), a.shape),
                    axis=0, keepdims=True)
        y32_ref[row, :] = y + skip * x
        return state

    def some(g, state):
        # `unroll` tokens written out a pass of the loop (the lowering
        # unrolls a loop whole or not at all)
        for j in range(unroll):
            state = token(g * unroll + j, state)
        return state

    state_ref[...] = jax.lax.fori_loop(0, chunk // unroll, some,
                                       state_ref[...])
    y_ref[0] = y32_ref[...].astype(y_ref.dtype)


# Jitted by itself, as `_ssd_flat` is: under its own name in a device trace
# (`sel_scan_<i>`; the readers select the prefix)
@functools.partial(jax.jit, static_argnames=("name", "interpret"))
def _sel_flat(x, dt, a, bm, cm, d, name, interpret=False):
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    f32 = jnp.float32
    b, t, c = x.shape
    n = a.shape[1]
    block = sel_block(c)
    x = _pad_chunks(x, CHUNK)
    dt = _pad_chunks(dt.astype(f32), CHUNK)    # 0 past the row: S stays
    tp = x.shape[1]
    nc = tp // CHUNK

    def tiles(m):
        """(B, T, N) -> a chunk's (N, Q) tile: (B, chunks, N, Q)."""
        return jnp.swapaxes(_pad_chunks(m.astype(f32), CHUNK).reshape(
            b, nc, CHUNK, n), 2, 3)

    def tokens(r, j, k):
        return r, k, j

    def channels(r, j, k):
        return 0, j

    def states(r, j, k):
        return r, k, 0, 0

    y = pl.pallas_call(
        functools.partial(_sel_kernel, chunk=CHUNK, unroll=SEL_UNROLL),
        grid=(b, c // block, nc),
        in_specs=[
            pl.BlockSpec((1, CHUNK, block), tokens),
            pl.BlockSpec((1, CHUNK, block), tokens),
            pl.BlockSpec((n, block), channels),
            pl.BlockSpec((1, 1, n, CHUNK), states),
            pl.BlockSpec((1, 1, n, CHUNK), states),
            pl.BlockSpec((1, block), channels),
        ],
        out_specs=pl.BlockSpec((1, CHUNK, block), tokens),
        out_shape=jax.ShapeDtypeStruct((b, tp, c), x.dtype),
        scratch_shapes=[pltpu.VMEM((n, block), f32),
                        pltpu.VMEM((CHUNK, block), f32),
                        pltpu.VMEM((CHUNK, block), f32)],
        interpret=interpret, name=name,
    )(x, dt, a.astype(f32).T, tiles(bm), tiles(cm), d.astype(f32)[None])
    return y[:, :t]


# forward only, as `_ssd_diff`: differentiated, the plain tier's backward
@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _sel_diff(x, dt, a, bm, cm, d, name, interpret):
    return _sel_flat(x, dt, a, bm, cm, d, name, interpret)


def _sel_diff_fwd(x, dt, a, bm, cm, d, name, interpret):
    return (_sel_flat(x, dt, a, bm, cm, d, name, interpret),
            (x, dt, a, bm, cm, d))


def _sel_diff_bwd(_name, _interpret, given, dy):
    _y, back = jax.vjp(sel_plain, *given)
    return back(dy)


_sel_diff.defvjp(_sel_diff_fwd, _sel_diff_bwd)


def sel_kernel(x, dt, a, bm, cm, d, *, name: str = "sel_scan",
               interpret: bool = False):
    """The kernel tier of the second form (`_sel_flat`, differentiable
    through the plain tier); `interpret=True` runs it on the CPU for
    tests."""
    if x.shape[-1] % 128 or a.shape[1] % 8:
        raise ValueError(
            f"the scan kernel takes channels in whole lane blocks and states "
            f"in whole sublanes, not {x.shape[-1]} and {a.shape[1]}")
    return _sel_diff(x, dt, a, bm, cm, d, name, interpret)


def sel_scan_steps(rows: int, length: int, channels: int) -> int:
    """Grid steps the kernel tier of the second form takes for `rows` rows
    of `length` tokens: rows x channel blocks x chunks."""
    return rows * (channels // sel_block(channels)) * -(-length // CHUNK)


def channel_scan(x, dt, a, bm, cm, d, *, name: str = "sel_scan"):
    """The one entry a Mamba-1 mixer calls, on the tier `sel_tier` picks:
    x (B, T, C), dt (B, T, C) float32, a (C, N), bm, cm (B, T, N), d (C,)
    -> y (B, T, C) in x's type."""
    if sel_tier(x.shape[-1], a.shape[1]) == "kernel":
        return sel_kernel(x, dt, a, bm, cm, d, name=name)
    return sel_plain(x, dt, a, bm, cm, d)
