"""Expert layers: mixtures of feed-forward experts.

Like pipeline parallelism, MoE is beyond the reference's capability set
(SURVEY.md §2.2 lists EP as absent there) — it is part of the TPU build's
first-class distributed story. Two layers live here.

**The layer a model calls: `moe_ffn_dropless`** (`nn/models.py`'s
`ExpertLayer`, in three decoder families). Top-k routing over ALL
`n_routed_experts` with sigmoid scores and a selection bias (DeepSeek-V3's
`noaux_tc`: the bias moves the picks, never the weights) or a softmax over
the picked logits (`route_top_k`), from the layer's own input or from picks
made earlier (`routed`: a router that reads the attention's input), and NO
capacity:
every pick is computed, whatever the skew. The layer is TOLD which experts
it holds (`experts_held`: first index and count, separately from
`n_routed_experts`) and returns the part of the result its own experts
give: picks are sorted by expert, the rows of the experts held come first,
and two grouped products of ours (`_grouped_pallas`: the custom calls
`ragged-dot-gated` and `ragged-dot-down`) run over them with
`experts_gate`, `experts_up` and `experts_down` read where the parameters
keep them. The first multiplies a tile of rows by one expert's gate AND up
blocks, applies act(a) * b (silu, or relu where the family says so) to the
two float32 sums and writes (rows, w) once; the second multiplies by the expert's down block and weighs each row
by its pick's weight before the one rounding. Their grid is as long as the
tiles that the picks here touch, and the tiles come from the shapes
(`grouped_tiles`). On the CPU, for float32 operands and for extents that
are no multiple of 128, the products are `jax.lax.ragged_dot`, gate and up
apart: no path concatenates the weights. A Pallas call (`moe_combine`)
adds each token's weighted rows up from where they lie.
The parts of the chips of an expert-parallel host add up to the whole
layer; on one chip the layer runs without its exchange, and nothing stands
in for the absent chips. The shared experts are a plain gated feed-forward
(`nn.models.GatedFFN`) that the model adds once.

**The capacity-dropping top-1 pair: `moe_ffn_local` / `moe_ffn_sharded`**
(Switch-Transformer-style, no model calls it). Tokens are sharded over the
SAME axis that shards experts, routing builds a fixed-capacity (tokens,
experts, capacity) dispatch tensor (static shapes — XLA-friendly; overflow
tokens drop, the standard capacity_factor trade), and two `lax.all_to_all`
collectives move token slabs to their experts' devices and back over ICI.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ..observability.metrics import get_registry
from .collectives import axis_size

__all__ = ["moe_ffn_dropless", "route_top_k", "dropless_buffer_rows",
           "MoEParams", "init_moe", "moe_ffn_local", "moe_ffn_sharded"]

EXPERT_AXIS = "expert"


class MoEParams(NamedTuple):
    w_gate: jnp.ndarray   # (d, E)
    w1: jnp.ndarray       # (E, d, h)
    b1: jnp.ndarray       # (E, h)
    w2: jnp.ndarray       # (E, h, d)
    b2: jnp.ndarray       # (E, d)


def init_moe(rng, d: int, h: int, n_experts: int, dtype=jnp.float32) -> MoEParams:
    k1, k2, k3 = jax.random.split(rng, 3)
    s1, s2 = (2.0 / d) ** 0.5, (2.0 / h) ** 0.5
    return MoEParams(
        w_gate=jax.random.normal(k1, (d, n_experts), dtype) * s1,
        w1=jax.random.normal(k2, (n_experts, d, h), dtype) * s1,
        b1=jnp.zeros((n_experts, h), dtype),
        w2=jax.random.normal(k3, (n_experts, h, d), dtype) * s2,
        b2=jnp.zeros((n_experts, d), dtype),
    )


def _route(x, w_gate, n_experts: int, capacity: int):
    """Top-1 routing -> (dispatch (T,E,C) 0/1, combine (T,E,C) gate-weighted).

    Position of a token within its expert's capacity is its rank among
    same-expert tokens (cumsum of the one-hot); ranks >= capacity drop.
    """
    scores = jax.nn.softmax(x @ w_gate, axis=-1)            # (T, E)
    expert = jnp.argmax(scores, axis=-1)                    # (T,)
    # ranks in int32, NOT x.dtype: a bf16 cumsum cannot represent counts
    # past 256, which would silently merge two tokens into one capacity slot
    onehot_i = jax.nn.one_hot(expert, n_experts, dtype=jnp.int32)   # (T, E)
    pos = jnp.cumsum(onehot_i, axis=0) * onehot_i - 1       # rank within expert
    keep = (pos >= 0) & (pos < capacity)
    onehot = onehot_i.astype(x.dtype)
    pos_oh = jax.nn.one_hot(pos, capacity, dtype=x.dtype)
    dispatch = onehot[:, :, None] * pos_oh * keep.astype(x.dtype)[:, :, None]
    gate = jnp.sum(scores * onehot, axis=-1)                # (T,) top-1 prob
    combine = dispatch * gate[:, None, None]
    return dispatch, combine


def _expert_ffn(params: MoEParams, slabs):
    """slabs: (E_local, C, d) -> (E_local, C, d); params hold LOCAL experts."""
    hid = jax.nn.gelu(
        jnp.einsum("ecd,edh->ech", slabs, params.w1) + params.b1[:, None, :]
    )
    return jnp.einsum("ech,ehd->ecd", hid, params.w2) + params.b2[:, None, :]


def moe_ffn_local(params: MoEParams, x, capacity_factor: float = 1.25):
    """Single-device reference: full expert set, no collectives."""
    t, _ = x.shape
    e = params.w_gate.shape[1]
    cap = max(int(capacity_factor * t / e), 1)
    dispatch, combine = _route(x, params.w_gate, e, cap)
    slabs = jnp.einsum("tec,td->ecd", dispatch, x)          # (E, C, d)
    out = _expert_ffn(params, slabs)
    return jnp.einsum("tec,ecd->td", combine, out)


def moe_ffn_sharded(params: MoEParams, x, axis_name: str = EXPERT_AXIS,
                    capacity_factor: float = 1.25):
    """SPMD body (call inside shard_map over `axis_name`).

    x: (T_local, d) — this shard's tokens. params: LOCAL slice — w1/b1/w2/b2
    leading dim E_local = E / axis_size; w_gate REPLICATED (scores need all
    experts). Routing is computed on local tokens against all E experts;
    `all_to_all` #1 regroups the (E, C, d) dispatch slabs so each device
    holds its E_local experts' tokens from EVERY shard; `all_to_all` #2
    sends expert outputs back to the owning token shards.
    """
    n_shards = axis_size(axis_name)
    t_local, d = x.shape
    e_local = params.w1.shape[0]
    e = e_local * n_shards
    cap = max(int(capacity_factor * t_local / e), 1)

    dispatch, combine = _route(x, params.w_gate, e, cap)    # (T_l, E, C)
    slabs = jnp.einsum("tec,td->ecd", dispatch, x)          # (E, C, d)
    # regroup: split the E dim across shards, concat the shard dim -> each
    # device ends with (E_local * n_shards slabs) = its experts' tokens from
    # every shard, stacked on the capacity-ish axis
    slabs = slabs.reshape(n_shards, e_local, cap, d)
    inbound = lax.all_to_all(slabs, axis_name, split_axis=0, concat_axis=0,
                             tiled=False)                   # (S, E_l, C, d)
    inbound = inbound.transpose(1, 0, 2, 3).reshape(e_local, n_shards * cap, d)
    out = _expert_ffn(params, inbound)                      # (E_l, S*C, d)
    out = out.reshape(e_local, n_shards, cap, d).transpose(1, 0, 2, 3)
    outbound = lax.all_to_all(out, axis_name, split_axis=0, concat_axis=0,
                              tiled=False)                  # (S, E_l, C, d)
    outbound = outbound.reshape(e, cap, d)
    return jnp.einsum("tec,ecd->td", combine, outbound)


# --------------------------------------------------------------------- #
# dropless top-k layer over the experts held (the layer a model calls)   #
# --------------------------------------------------------------------- #

def route_top_k(x, router, bias, top_k: int, scaling: float = 1.0,
                normalise: bool = True, epsilon: float = 1e-20,
                scoring: str = "sigmoid"):
    """-> (picked (T, k) int32 expert ids, weights (T, k) float32).

    `scoring="sigmoid"`: scores are sigmoid(x @ router) in float32. The
    `top_k` experts of a token are the best of scores + bias; their weights
    are the SCORES at those experts (the bias selects, it does not weigh),
    over their sum (+ `epsilon`, which a checkpoint's family states) if
    `normalise`, times `scaling`.

    `scoring="softmax"`: the scores are the logits x @ router themselves;
    the picks are the best of logits + bias (None: no selection bias), and
    the weights a softmax over the PICKED logits (which is the softmax
    over all experts, the picks kept, over their sum; no epsilon enters),
    times `scaling`. No model here weighs by the softmax over all experts
    left as it is, so `normalise=False` is refused."""
    if scoring not in ("sigmoid", "softmax"):
        raise ValueError(f"unknown router scoring {scoring!r}; have "
                         "'sigmoid', 'softmax'")
    if scoring == "softmax" and not normalise:
        raise ValueError("softmax routing weighs its picks by a softmax "
                         "over their logits: normalise=False is not built")
    with jax.named_scope("moe.route"):
        scores = jnp.dot(x, router.astype(x.dtype),
                         preferred_element_type=jnp.float32)
        if scoring == "sigmoid":
            scores = jax.nn.sigmoid(scores)
        _best, picked = lax.top_k(
            scores if bias is None else scores + bias.astype(jnp.float32),
            top_k)
        # the scores at the picks, as a sum with zeros over the experts'
        # axis (the same bits as a gather, which XLA runs a scalar at a
        # time: 2.1 against 0.2 ms for 8 x 4096 tokens on a v5e)
        weights = jnp.where(
            picked[..., None] == jnp.arange(scores.shape[-1]),
            scores[:, None, :], 0.0).sum(-1)
        if scoring == "softmax":
            weights = jax.nn.softmax(weights, axis=-1)
        elif normalise:
            weights = weights / (weights.sum(-1, keepdims=True) + epsilon)
        return picked.astype(jnp.int32), weights * scaling


def _round_up(n: int, to: int) -> int:
    return -(-n // to) * to


def dropless_buffer_rows(tokens: int, top_k: int, held: int,
                         n_routed: int) -> int:
    """Rows of `moe_ffn_dropless`'s dispatch buffer for a batch of `tokens`
    tokens when `held` of `n_routed` experts lie here: half again the even
    share of the T x k picks (in 512s), or all of them where that is no
    less. A batch whose picks here reach it runs the whole T x k."""
    whole = tokens * top_k
    even = whole * held // n_routed
    return min(_round_up(even + even // 2 + 1, 512), whole)


# What the combine's kernel may keep in VMEM: it stays inside XLA's own
# allowance for a custom call (16 MB). Asking for more takes it from the
# whole program: at 64 MB the compiler no longer kept the grouped products'
# rows and weights in VMEM, and they ran 8% longer (PERF.md, PR 28).
_COMBINE_VMEM = 15 * 1024 * 1024


def _combine_shape(tokens: int, top_k: int, n_routed: int, held: int,
                   d: int, itemsize: int, vmem: int = _COMBINE_VMEM):
    """-> (tile, chunk, group): the tokens a grid step adds up, the rows
    it reads of an expert at a time (what even picks give a tile, plus the
    15 rows an aligned start may lie before the first, in 16s) and the
    experts it reads together: all that are held where the kernel's VMEM
    takes them, in the larger tile if that does."""
    for tile in (256, 128):
        chunk = min(_round_up(tile * top_k // n_routed + 16, 16), tile + 16)
        # an expert's rows and their tokens (two slots), its part of the
        # 0/1 matrix and of that matrix transposed; then the output's two
        # blocks and three float32 sums (the compiler's own count for 16
        # experts in tiles of 256 is 16.18 MB; this says 16.25)
        expert = 2 * chunk * (d * itemsize + 512 + tile * itemsize)
        fits = (vmem - tile * d * (2 * itemsize + 12)) // expert
        if tile == 128 or (tokens > 128 and fits >= held):
            groups = -(-held // max(fits, 1))
            return tile, chunk, -(-held // groups)


# jitted by itself: a model calls it once a layer and a branch, and the
# kernel is traced and lowered once a shape, not once a call (0.4 s each)
@functools.partial(jax.jit, static_argnames=(
    "tokens", "top_k", "n_routed", "interpret", "vmem"))
def _combine_pallas(weighed, token_of_row, key, picks, *, tokens: int,
                    top_k: int, n_routed: int, interpret: bool = False,
                    vmem: int = _COMBINE_VMEM):
    """The combine as a Pallas call (`moe_combine`): see `_combine`.

    The rows of one expert are in token order (the sort is stable), so the
    rows that a tile of tokens gets from one expert are CONSECUTIVE in
    `weighed`: [seg[e, i], seg[e, i + 1]), counted from the picks. A grid
    step copies `chunk` rows of each expert of a group from an aligned
    start (one DMA an expert, the next step's in flight while this one is
    added up), marks which row belongs to which of its tokens ((rows,
    tile) of 0 and 1; rows outside the segment belong to none) and adds
    the rows up as ONE product on the MXU, 1 x row in float32: the
    products are exact, so this is the float32 sum of a token's rows. A
    tile in which an expert has more rows than `chunk` takes further
    rounds, as many as the crowded expert needs: no row is ever left out.
    Experts beyond what VMEM takes at once are further groups, added into
    the same float32 sum."""
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    rows, d = weighed.shape
    dtype = weighed.dtype
    tile, chunk, group = _combine_shape(
        tokens, top_k, n_routed, picks.shape[0], d, dtype.itemsize, vmem)
    tiles, groups = -(-tokens // tile), -(-picks.shape[0] // group)
    held = groups * group                  # with experts nobody picks
    padded = max(_round_up(rows, 16), chunk)
    if padded != rows:                     # sizes no model has; tests do
        weighed = jnp.pad(weighed, ((0, padded - rows), (0, 0)))
        token_of_row = jnp.pad(token_of_row, (0, padded - rows))
    # a row's token along the lanes, as the kernel compares it
    tok = jnp.broadcast_to(token_of_row[:, None], (padded, 128))
    # seg[e, i]: the first row of expert e's picks by tokens of tile i on
    key = jnp.pad(key, (0, tiles * tile * top_k - key.shape[0]),
                  constant_values=held)
    per_tile = (key.reshape(tiles, tile * top_k, 1)
                == jnp.arange(held, dtype=jnp.int32)).sum(1, dtype=jnp.int32)
    before = jnp.concatenate(
        [jnp.zeros((1, held), jnp.int32), jnp.cumsum(per_tile, 0)])
    picks = jnp.pad(picks, (0, held - picks.shape[0]))
    seg = (before + (jnp.cumsum(picks) - picks)[None, :]).T
    # rounds a step needs: its most crowded expert's rows from the aligned
    # start, in chunks
    span = seg[:, 1:] - seg[:, :-1] // 16 * 16
    rounds = jnp.maximum(
        (-(-span // chunk)).reshape(groups, group, tiles).max(1), 1)
    width, stride, steps = group * chunk, tiles + 1, tiles * groups
    exact = lax.Precision.HIGHEST if dtype == jnp.float32 else None

    def kernel(seg_ref, rounds_ref, w_hbm, tok_hbm, out_ref, buf, tbuf, hot,
               total, sem):
        i, g = pl.program_id(0), pl.program_id(1)
        step = i * groups + g
        slot = step % 2

        def window(e, t, r):
            """Round r of expert e for tile t -> (first row copied, first
            and one past the last row that counts)."""
            lo = seg_ref[e * stride + t]
            hi = seg_ref[e * stride + t + 1]
            begin = lo // 16 * 16 + r * chunk
            first = pl.multiple_of(jnp.minimum(begin, padded - chunk), 16)
            return (first, jnp.maximum(lo, begin),
                    jnp.minimum(hi, begin + chunk))

        def rows_of(e):
            return pl.ds(pl.multiple_of(e * chunk, chunk), chunk)

        def copies(src, dst, slot):
            return (pltpu.make_async_copy(w_hbm.at[src, :],
                                          buf.at[slot, dst, :], sem.at[slot]),
                    pltpu.make_async_copy(tok_hbm.at[src, :],
                                          tbuf.at[slot, dst, :], sem.at[slot]))

        # loops over the group's experts, not unrolled: a model has a
        # kernel a layer and a branch, and each is traced, lowered,
        # compiled and loaded by itself
        def start(t, g, r, slot):
            def one(e, _):
                first, _lo, _hi = window(g * group + e, t, r)
                for c in copies(pl.ds(first, chunk), rows_of(e), slot):
                    c.start()
                return 0
            lax.fori_loop(0, group, one, 0)

        def wait(slot):
            def one(_e, _):
                for c in copies(pl.ds(0, chunk), pl.ds(0, chunk), slot):
                    c.wait()                # by a copy's size, not its place
                return 0
            lax.fori_loop(0, group, one, 0)

        def added(r):
            row = lax.broadcasted_iota(jnp.int32, (chunk, 128), 0)
            lane = lax.broadcasted_iota(jnp.int32, (chunk, 128), 1)

            def one(e, _):
                first, lo, hi = window(g * group + e, i, r)
                dst = rows_of(e)
                counts = (row >= lo - first) & (row < hi - first)
                local = tbuf[slot, dst, :] - i * tile
                for j in range(tile // 128):
                    hot[dst, j * 128:(j + 1) * 128] = jnp.where(
                        counts & (local == lane + j * 128), 1.0, 0.0
                    ).astype(dtype)
                # a neighbour's row is weighed 0, and 0 x inf is nan
                buf[slot, dst, :] = jnp.where(
                    counts[:, :1], buf[slot, dst, :], jnp.zeros((), dtype))
                return 0
            lax.fori_loop(0, group, one, 0)
            return lax.dot_general(
                hot[...], buf[slot], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=exact)

        @pl.when(step == 0)
        def _first():
            start(0, 0, 0, 0)

        @pl.when(step + 1 < steps)
        def _ahead():
            nxt = step + 1
            start(nxt // groups, nxt % groups, 0, 1 - slot)

        wait(slot)

        def further(r, acc):
            start(i, g, r, slot)
            wait(slot)
            return acc + added(r)

        part = lax.fori_loop(1, rounds_ref[g * tiles + i], further, added(0))
        if groups == 1:
            out_ref[...] = part.astype(dtype)
        else:
            @pl.when(g == 0)
            def _opens():
                total[...] = part

            @pl.when(g > 0)
            def _adds():
                total[...] += part

            @pl.when(g == groups - 1)
            def _closes():
                out_ref[...] = total[...].astype(dtype)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(tiles, groups),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tile, d), lambda i, g, *_: (i, 0)),
            scratch_shapes=[pltpu.VMEM((2, width, d), dtype),
                            pltpu.VMEM((2, width, 128), jnp.int32),
                            pltpu.VMEM((width, tile), dtype),
                            # the sum over groups; one group needs none
                            pltpu.VMEM((tile, d) if groups > 1 else (8, 128),
                                       jnp.float32),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((tiles * tile, d), dtype),
        # the next step's copies are started a step ahead: steps in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="moe_combine",
    )(seg.reshape(-1), rounds.reshape(-1).astype(jnp.int32), weighed, tok)
    return out[:tokens]


def _combine_in_kernel(d: int) -> bool:
    """Whether `_combine` is the Pallas call: on a TPU, for rows of whole
    lanes. That call reads the picks' rows only; XLA's adds every row."""
    return jax.default_backend() != "cpu" and d % 128 == 0


def _combine(weighed, token_of_row, key, picks, *, tokens: int, top_k: int,
             n_routed: int):
    """Each token's weighted rows added up in float32 and rounded once.

    weighed: (rows, d), the buffer's rows in expert order; those past the
    picks that are here are zero where XLA adds the rows up and may hold
    anything where the kernel does (`_combine_in_kernel`), which reads the
    picks' rows only. token_of_row: (rows,) int32. key: (T x k,)
    the flat picks' expert here, or `held` for one that lies elsewhere;
    picks: (held,) their counts. -> (tokens, d) in `weighed`'s dtype.

    What moves is bounded by the buffer's rows, never by T x k: on a TPU
    the Pallas call reads each expert's rows of a tile of tokens where
    they lie; elsewhere (the CPU's tests) XLA adds the buffer's rows into
    their tokens, a row at a time."""
    d = weighed.shape[1]
    if not _combine_in_kernel(d):
        return jnp.zeros((tokens, d), jnp.float32).at[token_of_row].add(
            weighed.astype(jnp.float32)).astype(weighed.dtype)
    return _combine_pallas(weighed, token_of_row, key, picks, tokens=tokens,
                           top_k=top_k, n_routed=n_routed)


# What a grouped product's kernel may keep in VMEM, by `_grouped_bytes`'
# count: inside the 16 MB a custom call gets, like the combine's.
_GROUPED_VMEM = 15 * 1024 * 1024
# The rows a grid step of a grouped product multiplies (measured on a v5e
# at the two decoder cells' five buffers, 256 to 6,200 rows an expert: 128
# and 512 are within 3% either way, 1024 loses a third; PERF.md, PR 32).
_GROUPED_ROWS = 256
# What a grid step costs besides its product, in multiply-adds: the step's
# own 0.3 us and the rows' tile fetched again for every column block. Read
# from the same runs: 25 columns of 256 rows by two operands of 2048.
_GROUPED_STEP = 25 * 256 * 2048 * 2


def _grouped_bytes(tm: int, k: int, tn: int, itemsize: int,
                   operands: int) -> int:
    """VMEM a grid step of `_grouped_pallas` holds: the rows' tile, each
    operand's block and the result's, two slots each (the pipeline fetches
    the next step's while this one is multiplied), and in float32 a sum an
    operand and the activation (the compiler's own count for two operands
    at 512 x 512 is 16.15 MB, this says 16.0; at 256 x 768 16.53, 17.0)."""
    blocks = tm * k + operands * k * tn + tm * tn
    return 2 * blocks * itemsize + (2 * operands - 1) * tm * tn * 4


def grouped_tiles(rows: int, k: int, n: int, itemsize: int, operands: int):
    """-> (tm, tn): the rows and the columns a grid step of `_grouped_pallas`
    takes of a product of (rows, k) by `operands` x (held, k, n); None where
    the kernel does not take the product (float32 operands, an extent that
    is no multiple of 128) and `lax.ragged_dot` runs it.

    Rows: `_GROUPED_ROWS`, or the buffer where it is smaller. Columns: the
    multiple of 128 that fits VMEM and makes the call cheapest, counting the
    columns computed (the last block hangs over where tn does not divide n:
    1408 = 11 x 128 is computed as 3 x 512) and a step's own cost beside
    them. That gives 512 for Moonlight's experts (2048 -> 1408), 256 for
    LFM2's (2048 -> 1792 = 7 x 256) and 1024 for both second products
    (-> 2048), at every buffer: each within 2% of the best tile timed."""
    if itemsize != 2 or k % 128 or n % 128:
        return None
    tm = min(_GROUPED_ROWS, _round_up(rows, 16))
    step = _GROUPED_STEP / (tm * k * operands)      # in columns

    def cost(tn):
        return _round_up(n, tn) * (1.0 + step / tn)

    fits = [tn for tn in range(128, n + 1, 128) if _grouped_bytes(
        tm, k, tn, itemsize, operands) <= _GROUPED_VMEM]
    return (tm, min(fits, key=cost)) if fits else None


def _grouped_plan(picks, rows: int, tm: int):
    """The visits of a grouped product over row tiles of `tm`: a visit is
    a tile and one expert with picks inside it, in the rows' order.
    -> (group (V,), tile (V,), offsets (held + 1,), visits ()), V the most
    there can be; only the first `visits` are real, and no tile wholly past
    the picks that are here is among them."""
    held = picks.shape[0]
    ends = jnp.cumsum(picks)
    starts = ends - picks
    tiles = jnp.where(picks > 0, -(-ends // tm) - starts // tm, 0)
    last = jnp.cumsum(tiles)
    v = jnp.arange(-(-rows // tm) + held - 1, dtype=jnp.int32)
    group = jnp.minimum((last[None, :] <= v[:, None]).sum(1, dtype=jnp.int32),
                        held - 1)
    tile = starts[group] // tm + v - (last - tiles)[group]
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return group, jnp.clip(tile, 0, -(-rows // tm) - 1), offsets, last[-1]


# the gate's activation, by the name a family states
_ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def _grouped_pallas(xs, weights, plan, *, tm: int, tn: int, name: str,
                    scale=None, activation: str = "silu",
                    interpret: bool = False):
    """One grouped product as a Pallas call: `xs` (rows, k) in expert order
    against each expert's own (k, n) matrix of every operand in `weights`
    ((held, k, n) each, read where the parameters keep them). One operand:
    the product, rounded once. Two: act(xs @ first) * (xs @ second), both
    sums and the activation (`activation`: "silu" or "relu") in float32,
    rounded once. `scale` (rows, 1) float32 multiplies each row before the
    rounding.

    The grid is (n // tn, visits): a step multiplies one tile of `tm` rows
    by one expert's (k, tn) blocks, the whole of k at once (no sum carried
    between steps), and writes the rows of the tile that are that expert's;
    a tile that several experts share is visited once by each, and stays in
    VMEM between them. Column blocks are the outer axis, so an expert's
    block is fetched ONCE a call, when the visits reach the expert; the
    rows' tile is fetched once a column block. The number of visits is the
    batch's own (a dynamic grid extent): tiles past the picks that are here
    are not visited, and their rows of the result hold nothing defined, as
    do the rows of the last tile that are no expert's."""
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    group, tile, offsets, visits = plan
    rows, k = xs.shape
    n, dtype = weights[0].shape[2], xs.dtype
    exact = lax.Precision.HIGHEST if dtype == jnp.float32 else None
    act = _ACTIVATIONS[activation]

    def kernel(group_ref, tile_ref, offsets_ref, xs_ref, *refs):
        operands, out_ref = refs[:len(weights)], refs[-1]
        v = pl.program_id(1)
        g = group_ref[v]
        x = xs_ref[...]
        out = jnp.dot(x, operands[0][...], precision=exact,
                      preferred_element_type=jnp.float32)
        if len(operands) == 2:
            out = act(out) * jnp.dot(
                x, operands[1][...], precision=exact,
                preferred_element_type=jnp.float32)
        if scale is not None:
            out = out * refs[-2][...]
        row = tile_ref[v] * tm + lax.broadcasted_iota(jnp.int32, (tm, tn), 0)
        mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
        out_ref[...] = jnp.where(mine, out.astype(dtype), out_ref[...])

    def block(n_i, v, group_ref, tile_ref, offsets_ref):
        return group_ref[v], 0, n_i

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(-(-n // tn), visits),
            in_specs=[pl.BlockSpec((tm, k), lambda n_i, v, g, t, o: (t[v], 0))]
            + [pl.BlockSpec((None, k, tn), block) for _ in weights]
            + ([] if scale is None else [pl.BlockSpec(
                (tm, 1), lambda n_i, v, g, t, o: (t[v], 0))]),
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda n_i, v, g, t, o: (t[v], n_i))),
        out_shape=jax.ShapeDtypeStruct((rows, n), dtype),
        # a tile shared by experts is revisited: visits in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(group, tile, offsets, xs, *weights,
      *(() if scale is None else (scale,)))


# jitted by itself, like the combine: one trace and lowering a shape
@functools.partial(jax.jit, static_argnames=("first", "second", "activation",
                                             "interpret"))
def _grouped_ffn(xs, gate, up, down, picks, weight_of_row, *, first, second,
                 activation: str = "silu", interpret: bool = False):
    """weight_of_row x (act(xs @ gate[e]) * (xs @ up[e])) @ down[e] over
    the rows of each expert e, act the gate's `activation` ("silu" or
    "relu"). A stage at a tile is the Pallas call, at None `lax.ragged_dot`
    over the parameters as they lie, rounded where PR 27 rounded (each sum,
    the activation, the product, the weighing)."""
    rows, dtype = xs.shape[0], xs.dtype
    plans = {tile[0]: _grouped_plan(picks, rows, tile[0])
             for tile in (first, second) if tile}
    if first:
        act = _grouped_pallas(xs, (gate, up), plans[first[0]], tm=first[0],
                              tn=first[1], name="ragged-dot-gated",
                              activation=activation, interpret=interpret)
    else:
        a, b = (lax.ragged_dot(xs, m, picks, preferred_element_type=dtype)
                for m in (gate, up))
        act = (_ACTIVATIONS[activation](a.astype(jnp.float32))
               * b).astype(dtype)
    if second:
        return _grouped_pallas(act, (down,), plans[second[0]], tm=second[0],
                               tn=second[1], name="ragged-dot-down",
                               scale=weight_of_row[:, None],
                               interpret=interpret)
    ys = lax.ragged_dot(act, down, picks, preferred_element_type=dtype)
    return (ys.astype(jnp.float32) * weight_of_row[:, None]).astype(dtype)


def _experts(xs, gate, up, down, picks, weight_of_row, *, tiles=None,
             activation: str = "silu", interpret: bool = False):
    """The gated feed-forward of each held expert over its rows of the
    buffer, weighed: xs (rows, d) in expert order, picks (held,) the rows
    of each, weight_of_row (rows,) float32. -> (rows, d); the rows past
    the picks hold nothing defined.

    On a TPU each product runs at the tile `grouped_tiles` gives it; on the
    CPU (the tests) and where it gives none, `lax.ragged_dot` does
    (`tiles` and `interpret` are a test's way to the kernel)."""
    if activation not in _ACTIVATIONS:
        raise ValueError(f"unknown gate activation {activation!r}; have "
                         f"{sorted(_ACTIVATIONS)}")
    (rows, d), w = xs.shape, gate.shape[2]
    if tiles is None:
        size = xs.dtype.itemsize
        tiles = (None, None) if jax.default_backend() == "cpu" else (
            grouped_tiles(rows, d, w, size, 2),
            grouped_tiles(rows, w, d, size, 1))
    # counted where the call is traced: once a layer and compiled shape
    calls = get_registry().counter(
        "mmlspark_tpu_moe_grouped_calls_total",
        "grouped products of the experts held traced, by what runs them",
        labels=("kernel", "stage", "tile"))
    for stage, tile in zip(("gated", "down"), tiles):
        calls.labels(kernel="pallas" if tile else "ragged_dot", stage=stage,
                     tile="x".join(map(str, tile)) if tile else "none").inc()
    get_registry().counter(
        "mmlspark_tpu_moe_activation_calls_total",
        "gated feed-forwards of the experts held traced, by the gate's "
        "activation", labels=("activation",)).labels(
            activation=activation).inc()
    return _grouped_ffn(xs, gate, up, down, picks, weight_of_row,
                        first=tiles[0], second=tiles[1],
                        activation=activation, interpret=interpret)


def moe_ffn_dropless(x, router, bias, gate, up, down, *,
                     n_routed_experts: int, experts_held: tuple,
                     top_k: int, scaling: float = 1.0,
                     normalise: bool = True, epsilon: float = 1e-20,
                     dtype=jnp.float32, scoring: str = "sigmoid",
                     activation: str = "silu", routed=None):
    """The routed part of an expert layer that the experts held here give.

    x: (T, d). router: (d, n_routed_experts); bias: (n_routed_experts,)
    selection bias. gate, up: (held, d, w); down: (held, w, d): the gated
    feed-forwards (`activation` on the gate's branch: "silu" or "relu") of
    experts `experts_held[0]` .. `+ experts_held[1]`. Routing
    (`route_top_k`, scores by `scoring`) is over all `n_routed_experts`; a
    pick of an expert that lies elsewhere contributes nothing here (its
    chip adds it). `routed` (picked (T, k), weights (T, k)): the picks of a
    router that read ANOTHER input than the experts do, computed where the
    model computes them (a router before the attention reads the
    attention's input); `router` and `bias` are then not read.

    -> (out (T, d) `dtype`, picks (held,) int32: how many picks each held
    expert received). Dropless: there is no capacity, so a batch whose
    tokens all pick one expert is computed like any other.

    Static shapes: the picks are sorted by expert with those of absent
    experts last, and the first `rows` of that order are gathered and go
    through the grouped products. `rows` is the whole T x k only when more
    picks than half again the even share land here (`lax.cond`,
    `dropless_buffer_rows`): the common case gathers and activates a
    buffer a quarter the size at 16 of 64 experts held, and the combine
    (`_combine`) moves that buffer's rows, not one row a pick.

    The products (`_experts`): on a TPU, for 2-byte operands with d and w
    multiples of 128, two Pallas calls that read gate, up and down where
    they lie, in tiles of 256 rows by the columns `grouped_tiles` counts
    cheapest (512 of w = 1408, 256 of w = 1792, 1024 of d = 2048); the
    hidden values are rounded once, after the activation, and the rows are
    weighed in the second call's epilogue. Everywhere else three
    `lax.ragged_dot` (gate, up, down) with the activation and the weighing
    in XLA, rounded after each. Neither makes a (held, d, 2w) or a
    (rows, 2w) array."""
    t, d = x.shape
    first, held = (int(v) for v in experts_held)
    if gate.shape[0] != held:
        raise ValueError(f"experts_held says {held} experts, the weights "
                         f"hold {gate.shape[0]}")
    picked, weights = routed if routed is not None else route_top_k(
        x, router, bias, top_k, scaling, normalise, epsilon, scoring)

    with jax.named_scope("moe.dispatch"):
        local = picked.reshape(-1) - first                    # (T*k,)
        here = (local >= 0) & (local < held)
        key = jnp.where(here, local, held)
        # the picks' weights ride through the sort beside their places
        _key, order, weight_of_row = lax.sort(
            (key, jnp.arange(t * top_k, dtype=jnp.int32),
             weights.reshape(-1)), num_keys=1, is_stable=True)
        picks = (key[:, None] == jnp.arange(held, dtype=jnp.int32)).sum(
            0, dtype=jnp.int32)
        n_here = picks.sum()
    gate, up, down = (m.astype(dtype) for m in (gate, up, down))

    def routed(rows: int):
        with jax.named_scope("moe.dispatch"):
            token_of_row = order[:rows] // top_k
            xs = x.astype(dtype)[token_of_row]                 # (rows, d)
        with jax.named_scope("moe.experts"):
            weighed = _experts(xs, gate, up, down, picks,
                               weight_of_row[:rows],
                               activation=activation)          # (rows, d)
        with jax.named_scope("moe.combine"):
            # rows past the picks that are here hold nothing defined: the
            # Pallas combine counts only the picks' rows, XLA's adds every
            # row into a token, so there they become zero
            if not _combine_in_kernel(d):
                here_rows = jnp.arange(rows, dtype=jnp.int32) < n_here
                weighed = jnp.where(here_rows[:, None], weighed,
                                    jnp.zeros((), dtype))
            return _combine(weighed, token_of_row, key, picks, tokens=t,
                            top_k=top_k, n_routed=n_routed_experts)

    whole = t * top_k
    small = dropless_buffer_rows(t, top_k, held, n_routed_experts)
    if small >= whole:
        return routed(whole), picks
    out = lax.cond(n_here < small, lambda: routed(small),
                   lambda: routed(whole))
    return out, picks
