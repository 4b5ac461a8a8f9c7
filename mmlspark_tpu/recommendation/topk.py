"""The selection of a row's k largest scores: `top_k_rows`, the ONE
selection of the recommendation package (`sar.py`'s two block programs and
`resident.py`'s serving lane call it).

`jax.lax.top_k`'s contract to the bit: values descending, among equal
values the lowest column first, `-inf` entries returned like any other,
float32 values and int32 columns out. On a TPU, for float32 blocks of at
least a tile's rows whose columns fit VMEM, a Pallas kernel does it
(`sar_topk_k<k>`); everything else (the CPU, fewer rows than a tile,
rows wider than VMEM takes a tile of, other dtypes, k over 128) is
`lax.top_k` through the same function. The rule is by shape
(`kernel_takes`), never a knob.

Pallas is imported where the kernel is first TRACED, not with this module:
`import jax.experimental.pallas` is 1.0 s (JAX 0.9.0 imports `mosaic_gpu`'s
interpreter with it, 0.64 s), which the CPU, the `lax` side of the rule
and every import of `recommendation` never pay (PERF.md, PR 39).

The kernel reads the scores AS THE CHIP KEEPS THEM. A (4096, 10677)
float32 array lies columns-major on a TPU (the dimension that is a
multiple of 128 goes to the lanes), which is how the product writes it and
how `lax.top_k`'s own call read it: so the call takes `scores.T`, a
relabelling, and a tile is 128 ROWS of `scores`, one a lane, with every
column of theirs down the sublanes. A lane's selection then never looks
at another lane: no reduction across lanes, no mask laid out again.

A tile's columns are cut into `SETS` runs of consecutive registers (8
columns x 128 rows each); a run's sublane is a CLASS, 128 classes a row.
ONE pass over the tile keeps, a class, the `DEPTH` best entries seen with
their columns, by insertion with a strict `>` (the earlier column wins a
tie), and the VALUE of the next best as a bound on what the class holds
behind its list. The k rounds then read those lists alone: the row's
maximum over its classes' heads, among equal maxima the lowest column, and
that class's list moves up. A class whose list is spent shows its bound,
before every real entry of equal value: a row is exact until a bound is
what a round would take, and there its rounds end. A tile with rows left
short is scanned again for the entries AFTER each class's last given one
(value, then column) and goes through the rounds again. With a row's best
spread evenly over its classes one row in 10,000 needs that at k = 10;
rows sorted against the kernel (the k best all in one class) cost
k / DEPTH scans, and stay exact.

NaN scores are not ordered (as a comparison is not): a row that holds one
returns something, in bounded time. -0.0 and 0.0 are equal, a tie.

Inside the kernel everything is `lax`, nothing `jnp`: a `jnp` function is
jitted, and a start pays a trace for each one a kernel is the first to
use, and its lowering for every operation in a kernel's jaxpr, once a
shape: so every loop body below is written once (PERF.md, PR 38: 61
traces where 15 do, 1.43 s of lowering where 0.43 does)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..observability.metrics import get_registry

__all__ = ["top_k_rows", "kernel_takes"]

# rows of `scores` a grid step takes: one a lane
TILE = 128
# runs of consecutive registers a tile's columns are cut into: with a
# register's 8 sublanes, 128 classes a row
SETS = 16
# runs a pass works on at once (four registers a value: four chains of
# insertions fill the bundles where one waits on itself)
BATCH = 4
# entries a class keeps in the pass, beside the value of the next: a row
# is scanned again only when one class holds more than DEPTH of its k best
DEPTH = 3
# registers of a run a trip of the pass's loop takes, written out: at 2
# the kernel is 4% slower than at 4 and a start lowers half the operations
UNROLL = 2
# a column no row has: an empty place in a class's list
_EMPTY = np.int32(1 << 30)
# the column a bound shows: before every real one
_BOUND = np.int32(-1)
# a tile with all its columns, twice (the pipeline's two buffers), has to
# stay inside the default scoped VMEM with room for the lists
_TILE_BYTES = 6 << 20


def _run_length(width: int) -> int:
    """Registers a run holds: SETS runs cover the width, each with more
    entries than a list keeps."""
    return max(-(-width // (8 * SETS)), DEPTH + 1)


def kernel_takes(rows: int, width: int, k: int, dtype) -> bool:
    """Whether the Pallas kernel does the selection. `lax.top_k` does it
    off a TPU, for fewer rows than a tile has lanes (a serving rung), and
    for rows so wide that a tile of them with ALL its columns passes the
    VMEM a step may hold (a catalogue over 12,288 items: the columns are
    not tiled); and for what the kernel is not written for: anything but
    float32, k over 128 or over the width."""
    return (jax.default_backend() == "tpu" and dtype == jnp.float32
            and 1 <= k <= min(128, width) and rows >= TILE
            and SETS * _run_length(width) * 8 * TILE * 4 <= _TILE_BYTES)


def top_k_rows(scores, k: int, *, interpret: bool = False):
    """(values, columns) of the k largest of each row of `scores`
    (rows, width), as `jax.lax.top_k(scores, k)` gives them, bit for bit.
    `interpret` is a test's way to the kernel on the CPU."""
    rows, width = scores.shape
    kernel = interpret or kernel_takes(rows, width, k, scores.dtype)
    # counted where the call is traced: once a compiled shape
    get_registry().counter(
        "mmlspark_tpu_sar_topk_calls_total",
        "selections of a row's k best scores traced, by what runs them",
        labels=("kernel", "k")).labels(
            kernel="pallas" if kernel else "lax", k=str(k)).inc()
    if not kernel:
        return lax.top_k(scores, k)
    # the transposes relabel what the chip keeps columns-major (see the
    # module's text) and stay OUTSIDE the jitted call (ROADMAP S5); where
    # it keeps rows-major (a width that is a multiple of 128), XLA copies
    values, columns = _top_k_pallas(lax.transpose(scores, (1, 0)), k,
                                    interpret)
    return lax.transpose(values, (1, 0)), lax.transpose(columns, (1, 0))


def _insert(vals, cols, x, col, *, empty=None, fresh=None):
    """A register's entries `x`, of columns `col`, into each class's
    descending list: DEPTH values with their columns, and one value more,
    the bound. A strict `>` keeps the earlier column before an equal later
    one; given `empty` (the mark of an empty place) an entry also fills
    one, so that a `-inf` entry is an entry; `fresh` says which entries
    take part at all."""
    kept, bound = vals[:-1], vals[-1]
    above = [lax.gt(x, m) for m in kept]
    if empty is not None:
        above = [lax.bitwise_or(a, lax.eq(c, empty))
                 for a, c in zip(above, cols)]
    shown = x
    if fresh is not None:
        above = [lax.bitwise_and(a, fresh) for a in above]
        shown = lax.select(fresh, x, lax.full_like(x, -np.inf))
    bound = lax.max(bound, lax.min(kept[-1], shown))
    new_vals = [lax.select(above[0], x, kept[0])]
    new_cols = [lax.select(above[0], col, cols[0])]
    for t in range(1, len(kept)):
        new_vals.append(lax.select(above[t - 1], kept[t - 1],
                                   lax.select(above[t], x, kept[t])))
        new_cols.append(lax.select(above[t - 1], cols[t - 1],
                                   lax.select(above[t], col, cols[t])))
    return new_vals + [bound], new_cols


def _topk_kernel(x_ref, v_ref, i_ref, vals_ref, cols_ref, more_ref,
                 last_v_ref, last_c_ref, *, k: int, width: int, n_rows: int,
                 run: int):
    """x_ref: (SETS * run * 8, TILE), the columns x rows of one tile;
    v_ref, i_ref: (k, TILE). Scratch, 128 classes x TILE rows each: the
    lists (vals_ref (DEPTH + 1, ..) with the bound last, cols_ref
    (DEPTH, ..)), more_ref (entries a class holds behind its list),
    last_v_ref and last_c_ref (the last entry a class gave).

    Every loop body is written ONCE (a start pays for each operation's
    lowering: PERF.md, PR 38): the first pass and a second scan are one
    code, told apart by loop bounds."""
    from jax.experimental import pallas as pl

    i32, f32 = jnp.int32, jnp.float32
    group = (BATCH * 8, TILE)           # a batch of runs, a register each
    classes = (SETS * 8, TILE)
    ranks = (-(-k // 8) * 8, TILE)
    one_row = (1, TILE)
    neg = lax.full(group, -np.inf, f32)
    empty = lax.full(group, _EMPTY, i32)
    past_width = lax.full(group, width, i32)
    # an entry's column: (run x the run's length + register) x 8 + sublane
    place = lax.broadcasted_iota(i32, group, 0)
    in_group = lax.add(
        lax.mul(lax.shift_right_logical(place, lax.full(group, 3, i32)),
                lax.full(group, run * 8, i32)),
        lax.bitwise_and(place, lax.full(group, 7, i32)))
    # the first pass takes `singly` registers of a run one by one, each
    # against the lists' empty places, then the rest UNROLL a trip with the
    # plain insertion; a second scan takes them all one by one
    singly = DEPTH + (run - DEPTH) % UNROLL
    trips = (run - singly) // UNROLL

    def scan(first):
        """Every class's DEPTH best entries, the next one's value and how
        many it holds behind them, into the lists. Only what FOLLOWS the
        class's last given entry (value, then column) takes part: on the
        `first` pass that is everything."""
        def batch(b, _):
            rows = pl.ds(pl.multiple_of(lax.mul(b, np.int32(BATCH * 8)),
                                        BATCH * 8), BATCH * 8)
            base = lax.mul(b, np.int32(BATCH * run * 8))
            column_0 = lax.add(in_group, lax.broadcast(base, group))
            last_v, last_c = last_v_ref[rows, :], last_c_ref[rows, :]

            def registers(at):
                """The batch's registers `at` rows into their runs, and
                their entries' columns."""
                x = lax.concatenate([
                    x_ref[pl.ds(pl.multiple_of(lax.add(
                        lax.add(base, at), np.int32(r * run * 8)), 8), 8), :]
                    for r in range(BATCH)], 0)
                col = lax.add(column_0, lax.broadcast(at, group))
                # past the width: -inf entries of columns that follow
                # every real one (k is at most the width: never returned)
                return lax.select(lax.lt(col, past_width), x, neg), col

            def one(j, carry):
                vals, cols, more = carry
                x, col = registers(lax.mul(j, np.int32(8)))
                fresh = lax.bitwise_or(
                    lax.lt(x, last_v), lax.bitwise_and(
                        lax.eq(x, last_v), lax.gt(col, last_c)))
                vals, cols = _insert(vals, cols, x, col, fresh=fresh,
                                     empty=empty)
                return vals, cols, lax.add(
                    more, lax.convert_element_type(fresh, i32))

            def trip(i, carry):
                vals, cols, more = carry
                for u in range(UNROLL):
                    x, col = registers(lax.mul(lax.add(
                        lax.mul(i, np.int32(UNROLL)), np.int32(singly + u)),
                        np.int32(8)))
                    vals, cols = _insert(vals, cols, x, col)
                return vals, cols, more

            carry = ([neg] * (DEPTH + 1), [empty] * DEPTH,
                     lax.full(group, 0, i32))
            carry = lax.fori_loop(
                0, lax.select(first, np.int32(singly), np.int32(run)), one,
                carry)
            vals, cols, more = lax.fori_loop(
                0, lax.select(first, np.int32(trips), np.int32(0)), trip,
                carry)
            for t in range(DEPTH + 1):
                vals_ref[t, rows, :] = vals[t]
            for t in range(DEPTH):
                cols_ref[t, rows, :] = cols[t]
            more_ref[rows, :] = lax.add(more, lax.broadcast(lax.select(
                first, np.int32(trips * UNROLL - DEPTH), np.int32(-DEPTH)),
                group))

        lax.fori_loop(0, SETS // BATCH, batch, None)

    def rounds(out_v, out_i, taken):
        """k rounds over the classes' lists; a row's rounds count until
        one would take a class's bound. The lists stay as the pass left
        them: `given` counts what a class has given, and its head is read
        by that."""
        def at(ref, n, past):
            found = ref[0]
            for t in range(1, DEPTH):
                found = lax.select(lax.ge(n, lax.full(classes, t, i32)),
                                   ref[t], found)
            return lax.select(lax.ge(n, lax.full(classes, DEPTH, i32)),
                              past, found)

        def down(x, shape=classes):     # a row's value to all its classes
            return lax.broadcast_in_dim(x, shape, (0, 1))

        def over_classes(reduce, x):
            return lax.expand_dims(reduce(x, (0,)), (0,))

        # behind a spent list: the bound, under a column that sorts before
        # every real one; or nothing
        hidden = lax.gt(more_ref[...], lax.full(classes, 0, i32))
        past_v = lax.select(hidden, vals_ref[DEPTH],
                            lax.full(classes, -np.inf, f32))
        past_c = lax.select(hidden, lax.full(classes, _BOUND, i32),
                            lax.full(classes, _EMPTY, i32))
        rank = lax.broadcasted_iota(i32, ranks, 0)

        def one(_, carry):
            given, out_v, out_i, taken, alive = carry
            live = lax.gt(alive, lax.full(one_row, 0, i32))
            head_v = at(vals_ref, given, past_v)
            best = over_classes(lax.reduce_max, head_v)
            head_c = lax.select(lax.eq(head_v, down(best)),
                                at(cols_ref, given, past_c),
                                lax.full(classes, _EMPTY, i32))
            first = over_classes(lax.reduce_min, head_c)
            # a bound: the row stops
            live = lax.bitwise_and(live, lax.ge(
                first, lax.full(one_row, 0, i32)))
            here = lax.bitwise_and(lax.eq(head_c, down(first)), down(live))
            put = lax.bitwise_and(lax.eq(rank, down(taken, ranks)),
                                  down(live, ranks))
            out_v = lax.select(put, down(best, ranks), out_v)
            out_i = lax.select(put, down(first, ranks), out_i)
            taken = lax.add(taken, lax.convert_element_type(live, i32))
            live = lax.bitwise_and(live, lax.lt(
                taken, lax.full(one_row, k, i32)))
            return (lax.add(given, lax.convert_element_type(here, i32)),
                    out_v, out_i, taken,
                    lax.convert_element_type(live, i32))   # no booleans

        given, out_v, out_i, taken, _ = lax.fori_loop(
            0, k, one, (lax.full(classes, 0, i32), out_v, out_i, taken,
                        lax.convert_element_type(lax.lt(
                            taken, lax.full(one_row, k, i32)), i32)))
        # the last entry a class gave: what a second scan starts after
        gave = lax.gt(given, lax.full(classes, 0, i32))
        before = lax.sub(given, lax.full(classes, 1, i32))
        last_v_ref[...] = lax.select(gave, at(vals_ref, before, past_v),
                                     last_v_ref[...])
        last_c_ref[...] = lax.select(gave, at(cols_ref, before, past_c),
                                     last_c_ref[...])
        return out_v, out_i, taken

    last_v_ref[...] = lax.full(classes, np.inf, f32)
    last_c_ref[...] = lax.full(classes, -1, i32)
    # rows past the array's end (the last tile of a block) take no part:
    # they hold whatever the buffer held
    row = lax.add(lax.broadcasted_iota(i32, one_row, 1),
                  lax.mul(pl.program_id(0), np.int32(TILE)))
    taken = lax.select(lax.lt(row, lax.full(one_row, n_rows, i32)),
                       lax.full(one_row, 0, i32), lax.full(one_row, k, i32))

    def short(carry):
        scans, _, _, taken = carry
        return lax.bitwise_or(lax.eq(scans, np.int32(0)), lax.bitwise_and(
            lax.le(scans, np.int32(k)),
            lax.lt(lax.reduce_min(taken, (0, 1)), np.int32(k))))

    def again(carry):
        scans, out_v, out_i, taken = carry
        scan(lax.eq(scans, np.int32(0)))
        return (lax.add(scans, np.int32(1)), *rounds(out_v, out_i, taken))

    _, out_v, out_i, _ = lax.while_loop(
        short, again, (np.int32(0), lax.full(ranks, -np.inf, f32),
                       lax.full(ranks, 0, i32), taken))
    v_ref[...] = lax.slice(out_v, (0, 0), (k, TILE))
    i_ref[...] = lax.slice(out_i, (0, 0), (k, TILE))


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def _top_k_pallas(columns_major, k: int, interpret: bool = False):
    """`columns_major`: (width, rows), the scores transposed; -> values
    and columns (k, rows). Jitted by itself with k static: a pass's blocks
    share one lowered body a shape, whatever program calls it."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    width, rows = columns_major.shape
    run = _run_length(width)
    lists = (SETS * 8, TILE)
    kernel = functools.partial(_topk_kernel, k=k, width=width, n_rows=rows,
                               run=run)
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(rows, TILE),),
        in_specs=[pl.BlockSpec((SETS * run * 8, TILE), lambda i: (0, i))],
        out_specs=[pl.BlockSpec((k, TILE), lambda i: (0, i)),
                   pl.BlockSpec((k, TILE), lambda i: (0, i))],
        out_shape=[jax.ShapeDtypeStruct((k, rows), jnp.float32),
                   jax.ShapeDtypeStruct((k, rows), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((DEPTH + 1, *lists), jnp.float32),
                        pltpu.VMEM((DEPTH, *lists), jnp.int32),
                        pltpu.VMEM(lists, jnp.int32),
                        pltpu.VMEM(lists, jnp.float32),
                        pltpu.VMEM(lists, jnp.int32)],
        name=f"sar_topk_k{k}",
        interpret=interpret,
    )(columns_major)
