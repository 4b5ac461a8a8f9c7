"""Rotary positions, rotate-half layout (channel i pairs with channel i +
c/2; a checkpoint with the interleaved layout is permuted at import), float32
inside: XLA's form, which runs everywhere, and a Pallas call that rotates
heads where they lie in lanes. `rotary_positions` chooses between them."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import layout


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
# default 16 MB of scoped VMEM. The largest wins, as for `fold.flash_tiles`
# (PERF.md, PR 35)
_ROTARY_ROWS = 4096


@functools.partial(jax.jit, static_argnames=("width", "theta", "rows",
                                             "interpret"))
def _rotary_flat(flat, *, width, theta, rows, interpret=False):
    """`rotary_in_lanes` on (B, T, heads x width) as it lies, `rows`
    positions a grid step. Jitted by itself, `theta` static, as
    `eva._eva_flash` is: traced and lowered once a shape, not once a
    tensor and layer (a model's q and k share one body), and the lane
    blocks of a row are the grid's last axis, not a Python loop in the
    body: either costs a start seconds (PERF.md, PR 35)."""
    import jax.experimental.pallas as pl

    b, t, lanes = flat.shape
    flat, _ = layout._pad_seq(flat, rows)
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
    """Rotary positions 0 .. T-1 on the channels of x (B, T, heads, c):
    `rotary_xla`'s numbers (a cos - b sin as a cos + b (-sin): the same
    bits), computed by a Pallas call on x IN PLACE as (B, T, heads x c),
    for heads of 128 channels or pairs of heads of 64
    (`rotary_lanes_whole`). One pass where XLA's form, which slices half a
    head's channels, costs a kernel that reads channels in lanes two
    passes and a layout copy. The reshapes stay out here, beside the
    projection's and the kernel's own, where they cancel: handed four
    dimensions, the jitted call gets them positions-minor and a copy
    (PERF.md, PRs 34 and 35)."""
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


def rotary_xla(x, theta: float):
    """Rotary positions 0 .. T-1 on the channels of x (B, T, heads, c), in
    XLA: every backend, every width."""
    half = x.shape[-1] // 2
    cos, sin = (table[:, None, :]
                for table in rotary_cos_sin(x.shape[1], half, theta))
    a = x[..., :half].astype(jnp.float32)
    b = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           -1).astype(x.dtype)


def rotary_positions(x, theta: float, impl: str):
    """Rotary positions on x (B, T, heads, c) as a `HeadsDense` wrote it,
    for a model that states the tier `impl`: where the "flash" tier runs
    and whole heads fill lane blocks, rotated where they lie
    (`rotary_in_lanes`: the same numbers, no relayout before the kernel);
    XLA's form everywhere else."""
    if layout.tier(impl) == "flash" and rotary_lanes_whole(*x.shape[2:]):
        return rotary_in_lanes(x, theta)
    return rotary_xla(x, theta)


def rotary_heads(x, theta: float, impl: str):
    """`rotary_positions` on heads a `layout.head_projection` wrote: heads
    that are not whole lane blocks came from `nn.DenseGeneral`, positions-
    minor, and the kernel reads a head-major copy of them, so nothing lies
    in lanes to rotate there and XLA's form runs on every tier."""
    if not layout._lanes_whole(x.shape[-1]):
        return rotary_xla(x, theta)
    return rotary_positions(x, theta, impl)
