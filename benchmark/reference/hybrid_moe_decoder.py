"""The plain reference of the program's `hybrid_moe_decoder` family, as a
neural cell's configuration brings one (README, "Adding a neural cell"): a
causal decoder over token ids whose layers are told apart by a list (the
LFM2 block; HF `modeling_lfm2_moe`). x is a row's (T, d) states, every
product without bias:

- block: h = x + Op(RMSNorm_op(x)), out = h + FF(RMSNorm_ff(h)); Op is
  attention where `layer_types[i] == "full_attention"`, else the
  convolution; FF is a gated feed-forward, down(silu(gate y) * up y), of
  width `d_ff_dense` in the first `num_dense_layers` layers, else the
  experts. RMSNorm: x / sqrt(mean(x^2) + eps) * w;
- gated short convolution: [B, C, u] = split3(y W_in); z = B * u;
  c[t] = w[:, 0] z[t-2] + w[:, 1] z[t-1] + w[:, 2] z[t] per channel, z zero
  before a row's first token (a depthwise causal convolution, torch's
  Conv1d layout: the LAST tap meets the newest token); Op(y) = (C * c) W_out.
  No activation, no state across rows;
- attention: `num_heads` query heads and `num_kv_heads` key/value heads of
  d / num_heads channels; q and k each through an RMSNorm over a head's
  channels (ONE scale vector for all query heads, one for all key heads);
  rotary positions over the whole head, rotate-half layout; query head j
  attends causally to key/value head j // (num_heads / num_kv_heads) with
  scores over sqrt(head width);
- experts: s = sigmoid(y W_r) over ALL `n_routed_experts` in float32; the
  `top_k` picks are the best of s + b (b the selection bias); weights s at
  the picks (without b) over their sum + 1e-6, times
  `routed_scaling_factor`. No shared expert;
- ends: h0 = Embed[ids]; after the last layer RMSNorm_final; logits
  h Embed^T (the head is the embedding, `tie_embeddings`); the fetched
  output is each next token's log-probability, `token_logprobs[r, t]` =
  log_softmax(logits[r, t])[ids[r, t + 1]], t = 0 .. length - 2.

A chip may hold a SHARE of the model: `experts_held` (first index, count)
of the routed experts. Routing is always over all `n_routed_experts`; the
reference adds up the experts held, so a pick that lies on another chip
contributes nothing here (its chip adds it).

Nothing here imports the program or takes anything the program has made.
Plain `jax.numpy` in float32 at the highest matmul precision. Every held
expert is computed for every token and weighted by its gate (zero where it
was not picked): no sort, no grouping, no kernel. Attention is a plain
masked softmax over ALL keys, a block of queries at a time, so that a row
of 16384 tokens fits; the feed-forwards and the head go in blocks of
tokens, and the layers one compiled program each."""

from __future__ import annotations

import functools

import numpy as np

BLOCK_TOKENS = 1 << 14          # tokens of a block of rows (one row at 16384)
FF_BLOCK = 1 << 12              # tokens of a block of a feed-forward
HEAD_BLOCK = 1 << 10            # tokens of a block of the head's logits
SCORE_BYTES = 1 << 28           # attention scores of a block of queries:
#                                 128 queries x 32 heads x 16384 keys
FETCHES = ("token_logprobs", "logits", "hidden")
# The selection bias is drawn at a hundredth of a score: it changes the last
# pick of a share of the tokens (scores near the cut lie about 0.01 apart),
# so a bias left out shows, and it leaves the load even. Drawn at 0.1 it
# UNBALANCES seeded experts (read on the chip for the sibling family,
# PERF.md, PR 27)
BIAS_STD = 0.01
ROUTE_EPSILON = 1e-6            # HF Lfm2MoeSparseMoeBlock: sum + 1e-6


def sizes(config: dict) -> dict:
    """The family's sizes from a configuration's `model` group, with the
    layer counts derived."""
    m = config["model"]
    s = {k: int(m[k]) for k in (
        "d_model", "num_heads", "num_kv_heads", "d_ff_dense",
        "num_dense_layers", "n_routed_experts", "num_experts_per_tok",
        "d_ff_expert", "vocab_size")}
    s["layer_types"] = tuple(m["layer_types"])
    s["conv_taps"] = int(m.get("conv_taps", 3))
    s["first_expert"], s["experts_held"] = (int(v) for v in m["experts_held"])
    s["routed_scaling_factor"] = float(m.get("routed_scaling_factor", 1.0))
    s["rms_norm_eps"] = float(m.get("rms_norm_eps", 1e-5))
    s["rope_theta"] = float(m.get("rope_theta", 1e6))
    s["tie_embeddings"] = bool(m.get("tie_embeddings", True))
    s["num_layers"] = len(s["layer_types"])
    unknown = set(s["layer_types"]) - {"conv", "full_attention"}
    if unknown:
        raise ValueError(f"unknown layer types {sorted(unknown)}")
    s["attn_layers"] = s["layer_types"].count("full_attention")
    s["conv_layers"] = s["num_layers"] - s["attn_layers"]
    s["dense_layers"] = min(s["num_dense_layers"], s["num_layers"])
    s["expert_layers"] = s["num_layers"] - s["dense_layers"]
    s["head_dim"] = s["d_model"] // s["num_heads"]
    return s


def _slot(s: dict, i: int) -> int:
    """Layer i's place among the layers of its own kind."""
    return s["layer_types"][:i].count(s["layer_types"][i])


def weights(key, config: dict) -> dict:
    """Float32 weights on the device, one jitted call from the key: an
    array for the embedding and the final norm, and for every other name
    a LIST with one array a layer of its kind (a stacked array would be
    cut a layer at a time inside the forward, and the compiler keeps every
    cut alive at once: a second copy of the tree). Kernels are normal at
    1/sqrt(fan in), the taps at 1/sqrt(taps); RMSNorm scales 1 + 0.1 n; the
    selection bias is drawn at `BIAS_STD`. A tied embedding is drawn at
    1/sqrt(d), so that the logits have a spread near 1 (the final RMSNorm
    leaves states of unit size)."""
    import jax
    import jax.numpy as jnp

    s = sizes(config)
    d, heads, kv, hd = (s["d_model"], s["num_heads"], s["num_kv_heads"],
                        s["head_dim"])
    layers, nc, na = s["num_layers"], s["conv_layers"], s["attn_layers"]
    nd, ne, held = s["dense_layers"], s["expert_layers"], s["experts_held"]
    w, ff, taps = s["d_ff_expert"], s["d_ff_dense"], s["conv_taps"]
    tied = s["tie_embeddings"]
    # name -> (layers of that kind, or None for a single array; shape; std)
    shapes = {
        "embed": (None, (s["vocab_size"], d), d ** -0.5 if tied else 1.0),
        "ln_op_scale": (layers, (d,), 0.1),
        "conv_in": (nc, (d, 3 * d), d ** -0.5),
        "conv_taps": (nc, (d, taps), taps ** -0.5),
        "conv_out": (nc, (d, d), d ** -0.5),
        "wq": (na, (d, heads, hd), d ** -0.5),
        "wk": (na, (d, kv, hd), d ** -0.5),
        "wv": (na, (d, kv, hd), d ** -0.5),
        "q_norm_scale": (na, (hd,), 0.1),
        "k_norm_scale": (na, (hd,), 0.1),
        "wo": (na, (heads, hd, d), d ** -0.5),
        "ln_mlp_scale": (layers, (d,), 0.1),
        "dense_gate": (nd, (d, ff), d ** -0.5),
        "dense_up": (nd, (d, ff), d ** -0.5),
        "dense_down": (nd, (ff, d), ff ** -0.5),
        "router": (ne, (d, s["n_routed_experts"]), d ** -0.5),
        "router_bias": (ne, (s["n_routed_experts"],), BIAS_STD),
        "expert_gate": (ne, (held, d, w), d ** -0.5),
        "expert_up": (ne, (held, d, w), d ** -0.5),
        "expert_down": (ne, (held, w, d), w ** -0.5),
        "ln_final_scale": (None, (d,), 0.1),
    }
    if not tied:
        shapes["head"] = (None, (d, s["vocab_size"]), d ** -0.5)

    @jax.jit
    def make(key):
        out = {}
        for i, (name, (count, shape, std)) in enumerate(
                sorted(shapes.items())):
            def draw(at):
                drawn = std * jax.random.normal(at, shape, jnp.float32)
                return 1.0 + drawn if name.endswith("_scale") else drawn

            at = jax.random.fold_in(key, i)
            out[name] = draw(at) if count is None else [
                draw(jax.random.fold_in(at, layer)) for layer in range(count)]
        return out

    return make(key)


def variables(w: dict, config: dict) -> dict:
    """The weights under the names the program's module takes (the
    parameter tree of `HybridMoEDecoder`)."""
    s = sizes(config)
    params = {"embed": {"embedding": w["embed"]},
              "ln_final": {"scale": w["ln_final_scale"]}}
    if not s["tie_embeddings"]:
        params["head_kernel"] = w["head"]
    for i, kind in enumerate(s["layer_types"]):
        j = _slot(s, i)
        params[f"ln_op_{i}"] = {"scale": w["ln_op_scale"][i]}
        if kind == "conv":
            params[f"conv_{i}"] = {
                "in_proj": {"kernel": w["conv_in"][j]},
                "conv_kernel": w["conv_taps"][j],
                "out_proj": {"kernel": w["conv_out"][j]}}
        else:
            params[f"gqa_attn_{i}"] = {
                "q_proj": {"kernel": w["wq"][j]},
                "k_proj": {"kernel": w["wk"][j]},
                "v_proj": {"kernel": w["wv"][j]},
                "q_norm": {"scale": w["q_norm_scale"][j]},
                "k_norm": {"scale": w["k_norm_scale"][j]},
                "out": {"kernel": w["wo"][j]}}
        params[f"ln_mlp_{i}"] = {"scale": w["ln_mlp_scale"][i]}
        if i < s["dense_layers"]:
            params[f"mlp_{i}"] = {"gate": {"kernel": w["dense_gate"][i]},
                                  "up": {"kernel": w["dense_up"][i]},
                                  "down": {"kernel": w["dense_down"][i]}}
        else:
            e = i - s["dense_layers"]
            params[f"moe_{i}"] = {
                "router_kernel": w["router"][e],
                "router_bias": w["router_bias"][e],
                "experts_gate": w["expert_gate"][e],
                "experts_up": w["expert_up"][e],
                "experts_down": w["expert_down"][e]}
    return {"params": params}


def rms_norm(x, scale, eps: float):
    import jax

    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def rotary(x, theta: float):
    """Rotary positions 0 .. T-1 on the last axis of x (..., T, heads, c),
    rotate-half layout: channel i pairs with channel i + c/2."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[-3], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def gated_ffn(y, gate, up, down):
    import jax

    return (jax.nn.silu(y @ gate) * (y @ up)) @ down


def short_conv(y, w_in, taps, w_out):
    """The gated short convolution. y: (B, T, d); taps: (d, n), tap n - 1
    on the newest token."""
    import jax.numpy as jnp

    t, n = y.shape[1], taps.shape[1]
    gate_in, gate_out, u = jnp.split(y @ w_in, 3, axis=-1)
    z = jnp.pad(gate_in * u, ((0, 0), (n - 1, 0), (0, 0)))
    c = sum(taps[:, j] * z[:, j:j + t] for j in range(n))
    return (gate_out * c) @ w_out


def routing(y, router, bias, top_k: int, scaling: float,
            epsilon: float = ROUTE_EPSILON):
    """-> (T, n_routed_experts) float32 gates: a token's weight for each of
    its `top_k` experts, zero elsewhere."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(y @ router)
    _best, picked = jax.lax.top_k(s + bias, top_k)
    chosen = jnp.take_along_axis(s, picked, axis=-1)
    chosen = chosen / (chosen.sum(-1, keepdims=True) + epsilon) * scaling
    rows = jnp.arange(y.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, picked].set(chosen)


def expert_layer(y, w: dict, s: dict):
    """The routed experts held here, each computed for every token and
    weighted by its gate. y: (T, d); w: one layer's weights."""
    import jax

    gates = routing(y, w["router"], w["router_bias"],
                    s["num_experts_per_tok"], s["routed_scaling_factor"])
    lo = s["first_expert"]
    held = gates[:, lo:lo + s["experts_held"]]               # (T, held)

    # one expert at a time, its matrices cut from the layer's inside the
    # loop: nothing the size of a layer's experts is copied
    def one(n, acc):
        cut = lambda name: jax.lax.dynamic_index_in_dim(      # noqa: E731
            w[name], n, keepdims=False)
        g = jax.lax.dynamic_slice_in_dim(held, n, 1, axis=1)
        return acc + g * gated_ffn(y, cut("expert_gate"), cut("expert_up"),
                                   cut("expert_down"))

    return jax.lax.fori_loop(0, s["experts_held"], one, 0.0 * y)


def attention(y, w: dict, s: dict):
    """Grouped-query attention. y: (B, T, d) -> (B, T, d); w: one layer's
    weights. A block of queries at a time against every key."""
    import jax
    import jax.numpy as jnp

    b, t, _d = y.shape
    heads, kv, eps = s["num_heads"], s["num_kv_heads"], s["rms_norm_eps"]
    group = heads // kv
    q = jnp.einsum("btd,dhc->bthc", y, w["wq"])
    k = jnp.einsum("btd,dhc->bthc", y, w["wk"])
    v = jnp.einsum("btd,dhc->bthc", y, w["wv"])
    q = rotary(rms_norm(q, w["q_norm_scale"], eps), s["rope_theta"])
    k = rotary(rms_norm(k, w["k_norm_scale"], eps), s["rope_theta"])
    scale = q.shape[-1] ** -0.5
    block = max(1, min(t, SCORE_BYTES // (4 * b * heads * t)))
    while t % block:
        block -= 1
    # query head j reads key/value head j // group: (.., kv, group, c)
    q = q.reshape(b, t // block, block, kv, group, -1)
    kpos = jnp.arange(t)

    def some_queries(xs):
        first, qb = xs                                # (B, block, kv, g, c)
        scores = jnp.einsum("bqhgc,bthc->bhgqt", qb, k) * scale
        seen = (first + jnp.arange(block))[:, None] >= kpos[None, :]
        p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return jnp.einsum("bhgqt,bthc->bqhgc", p, v)

    o = jax.lax.map(some_queries, (jnp.arange(0, t, block),
                                   jnp.moveaxis(q, 1, 0)))
    o = jnp.moveaxis(o, 0, 1).reshape(b, t, heads, -1)
    return jnp.einsum("bthc,hcd->btd", o, w["wo"])


def _in_blocks(fn, flat, block: int):
    """fn over (tokens, d) a block of tokens at a time."""
    import jax

    n, d = flat.shape
    block = min(block, n)
    while n % block:
        block -= 1
    return jax.lax.map(fn, flat.reshape(n // block, block, d)).reshape(n, -1)


def layer_weights(w: dict, s: dict, i: int) -> dict:
    """Layer i's own arrays, under the names of `weights` (a list's entry
    for this layer's place among its kind)."""
    kind = s["layer_types"][i]
    names = {"ln_op_scale": i, "ln_mlp_scale": i}
    names.update(dict.fromkeys(
        ("conv_in", "conv_taps", "conv_out") if kind == "conv" else
        ("wq", "wk", "wv", "q_norm_scale", "k_norm_scale", "wo"),
        _slot(s, i)))
    if i < s["dense_layers"]:
        names.update(dict.fromkeys(("dense_gate", "dense_up", "dense_down"),
                                   i))
    else:
        names.update(dict.fromkeys(
            ("router", "router_bias", "expert_gate", "expert_up",
             "expert_down"), i - s["dense_layers"]))
    return {name: w[name][at] for name, at in names.items()}


def _embed(embed, ids):
    return embed[ids]


def _layer(h, w: dict, frozen_sizes: tuple):
    """One block: h + Op(norm(h)), then + FF(norm(.)). The layer's kind is
    told by the weights it is given."""
    s = dict(frozen_sizes)
    y = rms_norm(h, w["ln_op_scale"], s["rms_norm_eps"])
    if "conv_in" in w:
        h = h + short_conv(y, w["conv_in"], w["conv_taps"], w["conv_out"])
    else:
        h = h + attention(y, w, s)
    y = rms_norm(h, w["ln_mlp_scale"], s["rms_norm_eps"])
    if "dense_gate" in w:
        ff = functools.partial(gated_ffn, gate=w["dense_gate"],
                               up=w["dense_up"], down=w["dense_down"])
    else:
        ff = functools.partial(expert_layer, w=w, s=s)
    return h + _in_blocks(ff, y.reshape(-1, y.shape[-1]),
                          FF_BLOCK).reshape(y.shape)


def _head(h, scale, head, ids, eps: float, tied: bool, fetch: str):
    """The final norm and the fetched output. `head`: the embedding where
    the head is tied to it, else the (d, vocabulary) matrix."""
    import jax
    import jax.numpy as jnp

    h = rms_norm(h, scale, eps)
    if fetch == "hidden":
        return h
    head = head.T if tied else head
    b, t, d = h.shape
    flat = h.reshape(b * t, d)
    if fetch == "logits":
        return (flat @ head).reshape(b, t, -1)
    # the next token of every position but a row's last; the last scores a
    # target that is cut off below
    target = jnp.concatenate([ids[:, 1:], ids[:, :1]], 1).reshape(b * t)
    block = min(HEAD_BLOCK, b * t)
    while (b * t) % block:
        block -= 1

    def one(xs):
        hb, tb = xs
        logp = jax.nn.log_softmax(hb @ head, -1)
        return jnp.take_along_axis(logp, tb[:, None], -1)[:, 0]

    out = jax.lax.map(one, (flat.reshape(-1, block, d),
                            target.reshape(-1, block)))
    return out.reshape(b, t)[:, :t - 1]


_STATIC = {"_embed": (), "_layer": (2,), "_head": (4, 5, 6)}


@functools.lru_cache(maxsize=None)
def _compiled(name: str):
    """One jitted function a name and a process, so that a second call at
    the same shapes (the next layer of that kind, another block of rows,
    the control, the next seed) traces nothing."""
    import jax

    return jax.jit(globals()[name], static_argnums=_STATIC[name])


def _forward(w: dict, ids, frozen_sizes: tuple, fetch: str):
    """The forward a LAYER at a time, each a compiled program of its own
    that is handed that layer's weights and nothing else. One program over
    all layers carries every weight it closes over through its loops as a
    copy: a second float32 tree, which does not fit beside the first and
    the served model (the chip's compiler planned 9.29 GB of temporaries
    for a row of 16384 tokens; PERF.md, PR 31)."""
    s = dict(frozen_sizes)
    h = _compiled("_embed")(w["embed"], ids)
    for i in range(s["num_layers"]):
        h = _compiled("_layer")(h, layer_weights(w, s, i), frozen_sizes)
    tied = s["tie_embeddings"]
    return _compiled("_head")(h, w["ln_final_scale"],
                              w["embed"] if tied else w["head"], ids,
                              s["rms_norm_eps"], tied, fetch)


def outputs(w: dict, config: dict, rows, fetch: str) -> np.ndarray:
    """The value of the fetched output `fetch` for `rows` ((n, length)
    token ids, one length), float64 on the host. Rows go through in blocks
    of at most `BLOCK_TOKENS` tokens (one row at 16384), as equal as the
    count allows; a row's value depends on no other row."""
    import jax
    import jax.numpy as jnp

    if fetch not in FETCHES:
        raise ValueError(f"the reference knows the fetches {FETCHES}, not "
                         f"{fetch!r}")
    frozen = tuple(sorted(sizes(config).items()))
    rows = np.asarray(rows)
    most = max(1, BLOCK_TOKENS // rows.shape[1])
    # blocks of equal size where the rows divide so (22 rows of 1024: two
    # of 11, not 16 and 6): every block shape is a set of compiled programs
    block = -(-len(rows) // -(-len(rows) // most))
    out = []
    with jax.default_matmul_precision("highest"):
        for lo in range(0, len(rows), block):
            ids = jnp.asarray(rows[lo:lo + block], jnp.int32)
            out.append(np.asarray(_forward(w, ids, frozen, fetch),
                                  np.float64))
    return np.concatenate(out)


def operations(config: dict, lengths) -> dict:
    """What scoring rows of the given lengths needs on THIS chip, from
    shapes alone: `lengths` is [(length, rows), ...]. One multiply and one
    add per weight a token meets; per (query, key, channel) triple of the
    causal TRIANGLE (a query and the keys at or before it) in the scores
    and in the weighted values, over all query heads; the convolution's
    two gates and its taps per channel and token; the head for the
    length - 1 positions that are scored. Routed experts count the picks
    expected here when picks are even: `num_experts_per_tok` x held /
    routed a token (`per_pick` lets a reader use counted picks instead).
    Bytes are the weights read once at two bytes each, the ids read and
    the log-probabilities written; the convolution's are the (T, 3 d)
    projection read and the (T, d) result written a layer, the attention's
    the queries, the key and the value heads and the output once a layer.
    `parts` splits both by layer kind, so that roofline readers divide by
    the same counts."""
    s = sizes(config)
    d, heads, kv, hd = (s["d_model"], s["num_heads"], s["num_kv_heads"],
                        s["head_dim"])
    nc, na, nd, ne = (s["conv_layers"], s["attn_layers"], s["dense_layers"],
                      s["expert_layers"])
    taps = s["conv_taps"]
    conv_w = d * 3 * d + d * d
    attn_w = d * heads * hd + 2 * d * kv * hd + heads * hd * d
    router_w = d * s["n_routed_experts"]
    dense_w = 3 * d * s["d_ff_dense"]
    expert_w = 3 * d * s["d_ff_expert"]
    picks_per_token = (s["num_experts_per_tok"] * s["experts_held"]
                       / s["n_routed_experts"])
    head_w = d * s["vocab_size"]
    tokens = sum(length * n for length, n in lengths)
    scored = sum((length - 1) * n for length, n in lengths)
    triangle = sum(n * length * (length + 1) / 2 for length, n in lengths)
    ids_bytes, out_bytes = 4.0 * tokens, 4.0 * scored
    projected = nc * conv_w + na * attn_w + ne * router_w + nd * dense_w
    parts = {
        "projections": {"ops": 2.0 * tokens * projected,
                        "bytes": 2.0 * projected},
        # B * u, `taps` products and taps - 1 sums, C * c
        "conv": {"ops": float(nc) * tokens * d * (2 * taps + 1),
                 "bytes": 2.0 * nc * tokens * (3 * d + d)},
        "attention": {
            "ops": 2.0 * na * triangle * heads * (hd + hd),
            "bytes": 2.0 * na * tokens * hd * (2 * heads + 2 * kv)},
        "routed_experts": {
            "ops": 2.0 * tokens * ne * picks_per_token * expert_w,
            "bytes": 2.0 * ne * s["experts_held"] * expert_w,
            "per_pick": {"ops": 2.0 * expert_w,
                         "bytes": 2.0 * 2 * (d + s["d_ff_expert"])}},
        # tied: the one matrix is the embedding and the head
        "head": {"ops": 2.0 * scored * head_w,
                 "bytes": 2.0 * head_w * (1 if s["tie_embeddings"] else 2)
                          + ids_bytes + out_bytes},
    }
    return {"ops": sum(p["ops"] for p in parts.values()),
            "bytes": sum(p["bytes"] for p in parts.values()),
            "parts": parts}
