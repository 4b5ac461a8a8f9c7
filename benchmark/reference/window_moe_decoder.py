"""The plain reference of the program's `window_moe_decoder` family, as a
neural cell's configuration brings one (README, "Adding a neural cell"): a
causal decoder over token ids whose attention layers are told apart by a
list (the SmallThinker block, arXiv 2507.20984; HF `modeling_smallthinker`).
x is a row's (T, d) states, every product without bias. Layer i:

    a      = RMSNorm_in(x)
    r      = a W_router                 # (n_routed_experts,): the router
                                        # reads the ATTENTION's input
    q,k,v  = a W_q (heads x c), a W_k (kv x c), a W_v (kv x c)
    q,k    = rotary(q), rotary(k)       if layer_types[i] == "sliding"
                                        (whole head, rotate-half), else as
                                        they are: no positions at all
    s[t,u] = q_t . k_u / sqrt(c)        for u <= t, and t - u < window if
                                        layer_types[i] == "sliding" (query
                                        head j reads key/value head
                                        j // (heads / kv))
    h      = x + concat_heads(softmax(s) v) W_o
    y      = RMSNorm_post(h)
    E      = the top_k largest of r;  w = softmax(r[E])
    out    = h + sum_{e in E} w_e W_down_e (relu(W_gate_e y) * (W_up_e y))

then RMSNorm_final and an untied head; the fetched output is each next
token's log-probability, `token_logprobs[r, t]` = log_softmax(logits[r,
t])[ids[r, t + 1]], t = 0 .. length - 2. RMSNorm: x / sqrt(mean(x^2) + eps)
* w. The experts read y, the router read a: they differ.

What the catalog's row does not give and is assumed here (the
configuration's `assumed` lists the same): no bias anywhere; no norm on
query or key heads; the rotate-half layout with no scaling; a window of W
counts the query's own position (keys t - W + 1 .. t); ReLU on the gate
branch only; the weights are a softmax over the PICKED logits (which is the
softmax over all experts, the picks kept, divided by their sum:
`moe_primary_router_apply_softmax` with `norm_topk_prob`); no selection
bias; the "secondary experts" of the family's paper have no key in the
checkpoint's config and none is built.

A chip may hold a SHARE of the model: `experts_held` (first index, count)
of the routed experts. Routing is always over all `n_routed_experts`; the
reference adds up the experts held, so a pick that lies on another chip
contributes nothing here (its chip adds it).

Nothing here imports the program or takes anything the program has made.
Plain `jax.numpy` in float32 at the highest matmul precision. Every held
expert is computed for every token and weighted by its gate (zero where it
was not picked): no sort, no grouping, no kernel. Attention is a plain
masked softmax over ALL keys (the band is a mask, nothing is skipped), a
block of queries at a time, so that a row of 16384 tokens fits; the experts
and the head go in blocks of tokens, and the layers one compiled program
each."""

from __future__ import annotations

import functools

import numpy as np

BLOCK_TOKENS = 1 << 14          # tokens of a block of rows (one row at 16384)
FF_BLOCK = 1 << 12              # tokens of a block of the experts
HEAD_BLOCK = 1 << 10            # tokens of a block of the head's logits
SCORE_BYTES = 1 << 28           # attention scores of a block of queries:
#                                 128 queries x 28 heads x 16384 keys
FETCHES = ("token_logprobs", "logits", "hidden")
LAYER_KINDS = ("global", "sliding")


def sizes(config: dict) -> dict:
    """The family's sizes from a configuration's `model` group, with the
    layer counts derived."""
    m = config["model"]
    s = {k: int(m[k]) for k in (
        "d_model", "num_heads", "num_kv_heads", "head_dim", "window_size",
        "n_routed_experts", "num_experts_per_tok", "d_ff_expert",
        "vocab_size")}
    s["layer_types"] = tuple(m["layer_types"])
    s["first_expert"], s["experts_held"] = (int(v) for v in m["experts_held"])
    s["rms_norm_eps"] = float(m.get("rms_norm_eps", 1e-6))
    s["rope_theta"] = float(m.get("rope_theta", 1.5e6))
    s["num_layers"] = len(s["layer_types"])
    unknown = set(s["layer_types"]) - set(LAYER_KINDS)
    if unknown:
        raise ValueError(f"unknown layer types {sorted(unknown)}")
    s["sliding_layers"] = s["layer_types"].count("sliding")
    s["global_layers"] = s["num_layers"] - s["sliding_layers"]
    return s


def weights(key, config: dict) -> dict:
    """Float32 weights on the device, one jitted call from the key: an
    array for the embedding, the head and the final norm, and for every
    other name a LIST with one array a layer (a stacked array would be cut
    a layer at a time inside the forward, and the compiler keeps every cut
    alive at once: a second copy of the tree). Kernels are normal at
    1/sqrt(fan in), the embedding at 1; RMSNorm scales 1 + 0.1 n. The
    attention's output projection is drawn at 1/sqrt(fan in) / sqrt(2 x
    layers), the depth-scaled draw of a residual projection that public
    decoders train from. At 1/sqrt(fan in) seeded attention, an average
    over thousands of keys, keeps what all tokens share and drops what
    tells them apart: the shared part of the states grows by half a layer
    (read here at hidden 256: from 0.02 of a state to 0.7 over 18 layers),
    every token's router then reads much the same input, and by the
    tenth layer most tokens pick the same experts (the busiest held expert
    at 8.2 times the mean here; 3.22 on the chip over the cell's 18
    layers; PERF.md, PR 40). Depth-scaled it stays at 0.03 and the held
    experts' load at 1.1 to 1.4 of even, what a trained router's
    balancing gives a deployment."""
    import jax
    import jax.numpy as jnp

    s = sizes(config)
    d, heads, kv, hd = (s["d_model"], s["num_heads"], s["num_kv_heads"],
                        s["head_dim"])
    layers, held, w = s["num_layers"], s["experts_held"], s["d_ff_expert"]
    # name -> (layers, or None for a single array; shape; std)
    shapes = {
        "embed": (None, (s["vocab_size"], d), 1.0),
        "ln_attn_scale": (layers, (d,), 0.1),
        "router": (layers, (d, s["n_routed_experts"]), d ** -0.5),
        "wq": (layers, (d, heads, hd), d ** -0.5),
        "wk": (layers, (d, kv, hd), d ** -0.5),
        "wv": (layers, (d, kv, hd), d ** -0.5),
        "wo": (layers, (heads, hd, d),
               (heads * hd) ** -0.5 * (2 * layers) ** -0.5),
        "ln_mlp_scale": (layers, (d,), 0.1),
        "expert_gate": (layers, (held, d, w), d ** -0.5),
        "expert_up": (layers, (held, d, w), d ** -0.5),
        "expert_down": (layers, (held, w, d), w ** -0.5),
        "ln_final_scale": (None, (d,), 0.1),
        "head": (None, (d, s["vocab_size"]), d ** -0.5),
    }

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
    parameter tree of `WindowMoEDecoder`: a global layer's attention is
    `gqa_attn_<i>`, a sliding layer's `swa_attn_<i>`, the router apart from
    its experts as `router_<i>`)."""
    s = sizes(config)
    params = {"embed": {"embedding": w["embed"]},
              "ln_final": {"scale": w["ln_final_scale"]},
              "head_kernel": w["head"]}
    for i, kind in enumerate(s["layer_types"]):
        attn = f"swa_attn_{i}" if kind == "sliding" else f"gqa_attn_{i}"
        params[f"ln_attn_{i}"] = {"scale": w["ln_attn_scale"][i]}
        params[f"router_{i}"] = {"kernel": w["router"][i]}
        params[attn] = {"q_proj": {"kernel": w["wq"][i]},
                        "k_proj": {"kernel": w["wk"][i]},
                        "v_proj": {"kernel": w["wv"][i]},
                        "out": {"kernel": w["wo"][i]}}
        params[f"ln_mlp_{i}"] = {"scale": w["ln_mlp_scale"][i]}
        params[f"moe_{i}"] = {"experts_gate": w["expert_gate"][i],
                              "experts_up": w["expert_up"][i],
                              "experts_down": w["expert_down"][i]}
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


def routing(a, router, top_k: int):
    """-> (T, n_routed_experts) float32 gates from the ATTENTION's normed
    input `a`: a token's weight for each of its `top_k` experts (a softmax
    over the picked logits), zero elsewhere."""
    import jax
    import jax.numpy as jnp

    logits = a @ router
    best, picked = jax.lax.top_k(logits, top_k)
    chosen = jax.nn.softmax(best, axis=-1)
    rows = jnp.arange(a.shape[0])[:, None]
    return jnp.zeros_like(logits).at[rows, picked].set(chosen)


def expert_layer(y, a, w: dict, s: dict):
    """The routed experts held here, each computed for every token and
    weighted by its gate. y: (T, d), what the experts read; a: (T, d), what
    the router read; w: one layer's weights."""
    import jax

    gates = routing(a, w["router"], s["num_experts_per_tok"])
    lo = s["first_expert"]
    held = gates[:, lo:lo + s["experts_held"]]               # (T, held)

    # one expert at a time, its matrices cut from the layer's inside the
    # loop: nothing the size of a layer's experts is copied
    def one(n, acc):
        cut = lambda name: jax.lax.dynamic_index_in_dim(      # noqa: E731
            w[name], n, keepdims=False)
        g = jax.lax.dynamic_slice_in_dim(held, n, 1, axis=1)
        hidden = jax.nn.relu(y @ cut("expert_gate")) * (y @ cut("expert_up"))
        return acc + g * (hidden @ cut("expert_down"))

    return jax.lax.fori_loop(0, s["experts_held"], one, 0.0 * y)


def attention(a, w: dict, s: dict, sliding: bool):
    """Grouped-query attention. a: (B, T, d) -> (B, T, d); w: one layer's
    weights. A block of queries at a time against every key; `sliding`: the
    rotary positions and the band's mask."""
    import jax
    import jax.numpy as jnp

    b, t, _d = a.shape
    heads, kv = s["num_heads"], s["num_kv_heads"]
    group = heads // kv
    q = jnp.einsum("btd,dhc->bthc", a, w["wq"])
    k = jnp.einsum("btd,dhc->bthc", a, w["wk"])
    v = jnp.einsum("btd,dhc->bthc", a, w["wv"])
    if sliding:
        q, k = rotary(q, s["rope_theta"]), rotary(k, s["rope_theta"])
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
        behind = (first + jnp.arange(block))[:, None] - kpos[None, :]
        seen = behind >= 0
        if sliding:
            seen = seen & (behind < s["window_size"])
        p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return jnp.einsum("bhgqt,bthc->bqhgc", p, v)

    o = jax.lax.map(some_queries, (jnp.arange(0, t, block),
                                   jnp.moveaxis(q, 1, 0)))
    o = jnp.moveaxis(o, 0, 1).reshape(b, t, heads, -1)
    return jnp.einsum("bthc,hcd->btd", o, w["wo"])


def _in_blocks(fn, block: int, *flat):
    """fn over (tokens, d) arrays a block of tokens at a time."""
    import jax

    n, d = flat[0].shape
    block = min(block, n)
    while n % block:
        block -= 1
    return jax.lax.map(lambda xs: fn(*xs), tuple(
        x.reshape(n // block, block, d) for x in flat)).reshape(n, -1)


LAYER_NAMES = ("ln_attn_scale", "router", "wq", "wk", "wv", "wo",
               "ln_mlp_scale", "expert_gate", "expert_up", "expert_down")


def layer_weights(w: dict, i: int) -> dict:
    """Layer i's own arrays, under the names of `weights`."""
    return {name: w[name][i] for name in LAYER_NAMES}


def _embed(embed, ids):
    return embed[ids]


def _layer(h, w: dict, frozen_sizes: tuple, sliding: bool):
    """One block: a = norm(h); h + Attention(a); then + Experts(norm(.),
    routed by a)."""
    s = dict(frozen_sizes)
    d = h.shape[-1]
    a = rms_norm(h, w["ln_attn_scale"], s["rms_norm_eps"])
    h = h + attention(a, w, s, sliding)
    y = rms_norm(h, w["ln_mlp_scale"], s["rms_norm_eps"])
    ff = functools.partial(expert_layer, w=w, s=s)
    return h + _in_blocks(ff, FF_BLOCK, y.reshape(-1, d),
                          a.reshape(-1, d)).reshape(y.shape)


def _head(h, scale, head, ids, eps: float, fetch: str):
    """The final norm and the fetched output; `head`: (d, vocabulary)."""
    import jax
    import jax.numpy as jnp

    h = rms_norm(h, scale, eps)
    if fetch == "hidden":
        return h
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


_STATIC = {"_embed": (), "_layer": (2, 3), "_head": (4, 5)}


@functools.lru_cache(maxsize=None)
def _compiled(name: str):
    """One jitted function a name and a process, so that a second call at
    the same shapes (the next layer of that kind, another block of rows,
    the control, the next seed) traces nothing."""
    import jax

    return jax.jit(globals()[name], static_argnums=_STATIC[name])


def _forward(w: dict, ids, frozen_sizes: tuple, fetch: str):
    """The forward a LAYER at a time, each a compiled program of its own
    that is handed that layer's weights and nothing else (one program over
    all layers carries every weight it closes over through its loops as a
    copy: a second float32 tree, which does not fit beside the first and
    the served model; PERF.md, PR 31)."""
    s = dict(frozen_sizes)
    h = _compiled("_embed")(w["embed"], ids)
    for i, kind in enumerate(s["layer_types"]):
        h = _compiled("_layer")(h, layer_weights(w, i), frozen_sizes,
                                kind == "sliding")
    return _compiled("_head")(h, w["ln_final_scale"], w["head"], ids,
                              s["rms_norm_eps"], fetch)


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
    # blocks of equal size where the rows divide so: every block shape is a
    # set of compiled programs
    block = -(-len(rows) // -(-len(rows) // most))
    out = []
    with jax.default_matmul_precision("highest"):
        for lo in range(0, len(rows), block):
            ids = jnp.asarray(rows[lo:lo + block], jnp.int32)
            out.append(np.asarray(_forward(w, ids, frozen, fetch),
                                  np.float64))
    return np.concatenate(out)


def band_pairs(length: int, window: int) -> int:
    """The (query, key) pairs of a row's causal band: a query and the
    `window` keys that end with its own, fewer near the row's start."""
    inside = min(window, length)
    return inside * (inside + 1) // 2 + (length - inside) * window


def operations(config: dict, lengths) -> dict:
    """What scoring rows of the given lengths needs on THIS chip, from
    shapes alone: `lengths` is [(length, rows), ...]. One multiply and one
    add per weight a token meets; per (query, key, channel) triple in the
    scores and in the weighted values, over all query heads: of the causal
    TRIANGLE in a global layer (part `attention`), of the BAND in a sliding
    layer (part `window_attention`: what a query needs and no more, the
    triangle itself for a row no longer than the window); the head for the
    length - 1 positions that are scored. Routed experts count the picks
    expected here when picks are even: `num_experts_per_tok` x held /
    routed a token (`per_pick` lets a reader use counted picks instead).
    Bytes are the weights read once at two bytes each, the ids read and
    the log-probabilities written; the attention's are the queries, the
    key and the value heads and the output once a layer. `parts` splits
    both by layer kind, so that roofline readers divide by the same
    counts."""
    s = sizes(config)
    d, heads, kv, hd = (s["d_model"], s["num_heads"], s["num_kv_heads"],
                        s["head_dim"])
    layers, ng, nw = s["num_layers"], s["global_layers"], s["sliding_layers"]
    attn_w = d * heads * hd + 2 * d * kv * hd + heads * hd * d
    router_w = d * s["n_routed_experts"]
    expert_w = 3 * d * s["d_ff_expert"]
    picks_per_token = (s["num_experts_per_tok"] * s["experts_held"]
                       / s["n_routed_experts"])
    head_w = d * s["vocab_size"]
    tokens = sum(length * n for length, n in lengths)
    scored = sum((length - 1) * n for length, n in lengths)
    triangle = sum(n * length * (length + 1) / 2 for length, n in lengths)
    band = sum(n * band_pairs(length, s["window_size"])
               for length, n in lengths)
    ids_bytes, out_bytes = 4.0 * tokens, 4.0 * scored
    projected = layers * (attn_w + router_w)
    moved = 2.0 * tokens * hd * (2 * heads + 2 * kv)       # q, k, v, out
    parts = {
        "projections": {"ops": 2.0 * tokens * projected,
                        "bytes": 2.0 * projected},
        "attention": {"ops": 2.0 * ng * triangle * heads * (hd + hd),
                      "bytes": ng * moved},
        "window_attention": {"ops": 2.0 * nw * band * heads * (hd + hd),
                             "bytes": nw * moved},
        "routed_experts": {
            "ops": 2.0 * tokens * layers * picks_per_token * expert_w,
            "bytes": 2.0 * layers * s["experts_held"] * expert_w,
            "per_pick": {"ops": 2.0 * expert_w,
                         "bytes": 2.0 * 2 * (d + s["d_ff_expert"])}},
        # untied: the embedding and the head are two matrices
        "head": {"ops": 2.0 * scored * head_w,
                 "bytes": 2.0 * head_w * 2 + ids_bytes + out_bytes},
    }
    return {"ops": sum(p["ops"] for p in parts.values()),
            "bytes": sum(p["bytes"] for p in parts.values()),
            "parts": parts}
