"""Expert layers: mixtures of feed-forward experts.

Like pipeline parallelism, MoE is beyond the reference's capability set
(SURVEY.md §2.2 lists EP as absent there) — it is part of the TPU build's
first-class distributed story. Two layers live here.

**The layer a model calls: `moe_ffn_dropless`** (`nn/models.py`'s
`mla_moe_decoder` family). Top-k routing over ALL `n_routed_experts` with
sigmoid scores and a selection bias (DeepSeek-V3's `noaux_tc`: the bias
moves the picks, never the weights), and NO capacity: every pick is
computed, whatever the skew. The layer is TOLD which experts it holds
(`experts_held`: first index and count, separately from
`n_routed_experts`) and returns the part of the result its own experts
give: picks are sorted by expert, the rows of the experts held come first,
and one grouped product a projection (`jax.lax.ragged_dot`, which the TPU
compiler lowers to its own grouped-matmul kernel with a grid as long as the
rows that are really there) runs over them. The parts of the chips of an
expert-parallel host add up to the whole layer; on one chip the layer runs
without its exchange, and nothing stands in for the absent chips. The
shared experts are a plain gated feed-forward (`nn.models.GatedFFN`) that
the model adds once.

**The capacity-dropping top-1 pair: `moe_ffn_local` / `moe_ffn_sharded`**
(Switch-Transformer-style, no model calls it). Tokens are sharded over the
SAME axis that shards experts, routing builds a fixed-capacity (tokens,
experts, capacity) dispatch tensor (static shapes — XLA-friendly; overflow
tokens drop, the standard capacity_factor trade), and two `lax.all_to_all`
collectives move token slabs to their experts' devices and back over ICI.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from .collectives import axis_size

__all__ = ["moe_ffn_dropless", "route_top_k",
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
                normalise: bool = True):
    """-> (picked (T, k) int32 expert ids, weights (T, k) float32).

    Scores are sigmoid(x @ router) in float32. The `top_k` experts of a
    token are the best of scores + bias; their weights are the SCORES at
    those experts (the bias selects, it does not weigh), over their sum
    (+1e-20) if `normalise`, times `scaling`."""
    with jax.named_scope("moe.route"):
        scores = jax.nn.sigmoid(jnp.dot(
            x, router.astype(x.dtype), preferred_element_type=jnp.float32))
        _best, picked = lax.top_k(scores + bias.astype(jnp.float32), top_k)
        weights = jnp.take_along_axis(scores, picked, axis=-1)
        if normalise:
            weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
        return picked.astype(jnp.int32), weights * scaling


def _round_up(n: int, to: int) -> int:
    return -(-n // to) * to


def moe_ffn_dropless(x, router, bias, gate, up, down, *,
                     n_routed_experts: int, experts_held: tuple,
                     top_k: int, scaling: float = 1.0,
                     normalise: bool = True, dtype=jnp.float32):
    """The routed part of an expert layer that the experts held here give.

    x: (T, d). router: (d, n_routed_experts); bias: (n_routed_experts,)
    selection bias. gate, up: (held, d, w); down: (held, w, d): the gated
    feed-forwards of experts `experts_held[0]` .. `+ experts_held[1]`.
    Routing is over all `n_routed_experts`; a pick of an expert that lies
    elsewhere contributes nothing here (its chip adds it).

    -> (out (T, d) `dtype`, picks (held,) int32: how many picks each held
    expert received). Dropless: there is no capacity, so a batch whose
    tokens all pick one expert is computed like any other.

    Static shapes: the picks are sorted by expert with those of absent
    experts last, and the first `rows` of that order are gathered and go
    through the grouped products. `rows` is the whole T x k only when more
    picks than half again the even share land here (`lax.cond`): the
    common case gathers and activates a buffer a quarter the size at
    16 of 64 experts held."""
    t, d = x.shape
    first, held = (int(v) for v in experts_held)
    if gate.shape[0] != held:
        raise ValueError(f"experts_held says {held} experts, the weights "
                         f"hold {gate.shape[0]}")
    picked, weights = route_top_k(x, router, bias, top_k, scaling, normalise)

    with jax.named_scope("moe.dispatch"):
        local = picked.reshape(-1) - first                    # (T*k,)
        here = (local >= 0) & (local < held)
        key = jnp.where(here, local, held)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        picks = (key[:, None] == jnp.arange(held, dtype=jnp.int32)).sum(
            0, dtype=jnp.int32)
        n_here = picks.sum()
        # where each pick sits in the sorted order
        place = jnp.argsort(order).astype(jnp.int32)
    gate_up = jnp.concatenate([gate, up], axis=-1).astype(dtype)
    down = down.astype(dtype)

    def routed(rows: int):
        with jax.named_scope("moe.dispatch"):
            first_rows = order[:rows]
            xs = x.astype(dtype)[first_rows // top_k]          # (rows, d)
        with jax.named_scope("moe.experts"):
            hidden = lax.ragged_dot(xs, gate_up, picks,
                                    preferred_element_type=dtype)
            w = hidden.shape[-1] // 2
            act = (jax.nn.silu(hidden[:, :w].astype(jnp.float32))
                   * hidden[:, w:]).astype(dtype)
            ys = lax.ragged_dot(act, down, picks,
                                preferred_element_type=dtype)  # (rows, d)
        with jax.named_scope("moe.combine"):
            # weigh in the sorted order; rows past the picks that are here
            # hold nothing defined and become zero. Whenever a pick lies
            # elsewhere the last row is such a row (`rows` exceeds the
            # picks here, or is all of them), so those picks read it
            here_rows = jnp.arange(rows, dtype=jnp.int32) < n_here
            weighed = jnp.where(
                here_rows[:, None],
                ys.astype(jnp.float32)
                * weights.reshape(-1)[first_rows][:, None], 0.0).astype(dtype)
            # (k, T): a token's picks lie T rows apart, so the gathered
            # rows add up as k slabs of (T, d) with no relayout between
            at = jnp.where(place < n_here, place, rows - 1).reshape(
                t, top_k).T
            return weighed[at].astype(jnp.float32).sum(0).astype(dtype)

    whole = t * top_k
    even = whole * held // n_routed_experts
    small = _round_up(even + even // 2 + 1, 512)
    if small >= whole:
        return routed(whole), picks
    out = lax.cond(n_here < small, lambda: routed(small),
                   lambda: routed(whole))
    return out, picks
