"""The plain reference of the program's `eva_decoder` family, as a neural
cell's configuration brings one (README, "Adding a neural cell"): a causal
decoder over bytes whose attention reads its own window exactly and
everything before it as one summary a chunk (EvaByte; the published EVA
attention, arXiv 2302.04542, with learned vectors a head in place of
sampled ones). x is a row's (T, d) states, every product without bias,
s = head width ^ -0.5:

- block: h = x + Attn(RMSNorm_a(x)), out = h + FF(RMSNorm_f(h));
  RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w (w is the scale as it
  multiplies: a checkpoint's `norm_add_unit_offset` stores w - 1);
  FF(y) = down(silu(gate y) * up y);
- projections: q, k, v = y W_q, y W_k, y W_v as `num_heads` heads; rotary
  positions 0 .. T-1 over the whole head on q and k, rotate-half layout;
- summaries, a head j and a chunk c (positions chunk c .. chunk c + chunk
  - 1), with learned phi_j, mu_j: a_m = softmax over m in c of
  (s k_m . phi_j); kbar_c = sum_m a_m k_m + mu_j; vbar_c = sum_m a_m v_m;
- attention, query t in window w = t // window: local keys
  L = {m : window w <= m <= t}, remote chunks R = {c : chunk (c + 1) <=
  window w}; ONE softmax over both: Z = sum_L exp(s q_t . k_m) + sum_R
  exp(s q_t . kbar_c); o_t = (sum_L exp(..) v_m + sum_R exp(..) vbar_c)
  / Z; Attn(y) = concat(heads) W_o. A row of at most one window is plain
  causal softmax attention;
- ends: h0 = Embed[ids]; RMSNorm_final; logits h W_head with W_head
  (d, num_pred_heads x vocabulary), prediction p in columns vocabulary p
  onwards; the fetched output is the next byte's log-probability under
  prediction 0, `token_logprobs[r, t]` = log_softmax(logits[r, t, 0])[
  ids[r, t + 1]], t = 0 .. length - 2. Predictions 1 onwards are held in
  the head and computed only for the fetch `logits`.

Nothing here imports the program or takes anything the program has made.
Plain `jax.numpy` in float32 at the highest matmul precision: the
attention is a masked softmax over [summaries; ALL keys], a block of
queries and a group of heads at a time, so that a row of 32768 bytes fits
beside the served model and the float32 tree; the feed-forward and the
head go in blocks of tokens, and the layers one compiled program each."""

from __future__ import annotations

import functools

import numpy as np

BLOCK_TOKENS = 1 << 15          # tokens of a block of rows (one row at 32768)
FF_BLOCK = 1 << 12              # tokens of a block of a feed-forward
HEAD_BLOCK = 1 << 12            # tokens of a block of the head's logits
HEAD_GROUP = 8                  # heads projected and attended together
SCORE_BYTES = 1 << 28           # attention scores of a block of queries:
#                                 60 queries x 8 heads x (32768 + 1920)
FETCHES = ("token_logprobs", "logits", "hidden")
# phi is drawn at 1: inside a chunk the 16 scores s k_m . phi then spread
# like a unit normal, so the largest weight of a chunk is some five times
# its smallest (a plain mean in phi's place shows). mu at a half: beside a
# pooled key of spread 0.4 a channel it moves every summary's score by
# about half a unit, the same for all chunks of a head (a mu left out shows)
PHI_STD = 1.0
MU_STD = 0.5


def sizes(config: dict) -> dict:
    """The family's sizes from a configuration's `model` group."""
    m = config["model"]
    s = {k: int(m[k]) for k in (
        "num_layers", "d_model", "num_heads", "window_size", "chunk_size",
        "d_ff_dense", "vocab_size", "num_pred_heads")}
    s["rms_norm_eps"] = float(m.get("rms_norm_eps", 1e-5))
    s["rope_theta"] = float(m.get("rope_theta", 1e5))
    s["head_dim"] = s["d_model"] // s["num_heads"]
    if s["window_size"] % s["chunk_size"]:
        raise ValueError("a window is whole chunks")
    return s


def weights(key, config: dict) -> dict:
    """Float32 weights on the device, one jitted call from the key: an
    array for the embedding, the head and the final norm, and for every
    other name a LIST with one array a layer. Kernels are normal at
    1/sqrt(fan in), the embedding at 1, RMSNorm scales 1 + 0.1 n, phi at
    `PHI_STD` and mu at `MU_STD`."""
    import jax
    import jax.numpy as jnp

    s = sizes(config)
    d, heads, hd, ff = (s["d_model"], s["num_heads"], s["head_dim"],
                        s["d_ff_dense"])
    layers = s["num_layers"]
    shapes = {
        "embed": (None, (s["vocab_size"], d), 1.0),
        "ln_attn_scale": (layers, (d,), 0.1),
        "wq": (layers, (d, heads, hd), d ** -0.5),
        "wk": (layers, (d, heads, hd), d ** -0.5),
        "wv": (layers, (d, heads, hd), d ** -0.5),
        "phi": (layers, (heads, hd), PHI_STD),
        "mu": (layers, (heads, hd), MU_STD),
        "wo": (layers, (heads, hd, d), d ** -0.5),
        "ln_mlp_scale": (layers, (d,), 0.1),
        "gate": (layers, (d, ff), d ** -0.5),
        "up": (layers, (d, ff), d ** -0.5),
        "down": (layers, (ff, d), ff ** -0.5),
        "ln_final_scale": (None, (d,), 0.1),
        "head": (None, (d, s["num_pred_heads"] * s["vocab_size"]),
                 d ** -0.5),
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
    parameter tree of `EvaDecoder`)."""
    s = sizes(config)
    params = {"embed": {"embedding": w["embed"]},
              "ln_final": {"scale": w["ln_final_scale"]},
              "head_kernel": w["head"]}
    for i in range(s["num_layers"]):
        params[f"ln_attn_{i}"] = {"scale": w["ln_attn_scale"][i]}
        params[f"eva_attn_{i}"] = {
            "q_proj": {"kernel": w["wq"][i]},
            "k_proj": {"kernel": w["wk"][i]},
            "v_proj": {"kernel": w["wv"][i]},
            "phi": w["phi"][i], "mu": w["mu"][i],
            "out": {"kernel": w["wo"][i]}}
        params[f"ln_mlp_{i}"] = {"scale": w["ln_mlp_scale"][i]}
        params[f"mlp_{i}"] = {"gate": {"kernel": w["gate"][i]},
                              "up": {"kernel": w["up"][i]},
                              "down": {"kernel": w["down"][i]}}
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


def summaries(k, v, phi, mu, chunk: int):
    """kbar, vbar (B, C, heads, c) for the C whole chunks of k, v (B, T,
    heads, c); phi, mu (heads, c)."""
    import jax
    import jax.numpy as jnp

    b, t, heads, c = k.shape
    n = t // chunk
    kc = k[:, :n * chunk].reshape(b, n, chunk, heads, c)
    vc = v[:, :n * chunk].reshape(b, n, chunk, heads, c)
    a = jax.nn.softmax(
        jnp.einsum("bnmhc,hc->bnmh", kc, phi) * c ** -0.5, axis=2)
    return (jnp.einsum("bnmh,bnmhc->bnhc", a, kc) + mu,
            jnp.einsum("bnmh,bnmhc->bnhc", a, vc))


def attend(q, k, v, kbar, vbar, window: int, chunk: int):
    """The masked softmax over [summaries; keys], a block of queries at a
    time. q, k, v: (B, T, heads, c); kbar, vbar: (B, C, heads, c)."""
    import jax
    import jax.numpy as jnp

    b, t, heads, c = q.shape
    n = kbar.shape[1]
    block = max(1, min(t, SCORE_BYTES // (4 * b * heads * (t + n))))
    while t % block:
        block -= 1
    keys = jnp.concatenate([kbar, k], 1)
    values = jnp.concatenate([vbar, v], 1)
    kpos = jnp.arange(t)
    chunk_end = (jnp.arange(n) + 1) * chunk

    def some_queries(xs):
        first, qb = xs                                    # (B, block, h, c)
        qpos = first + jnp.arange(block)
        start = (qpos // window) * window                 # its window's
        seen = jnp.concatenate([
            chunk_end[None, :] <= start[:, None],
            (kpos[None, :] <= qpos[:, None])
            & (kpos[None, :] >= start[:, None])], 1)
        scores = jnp.einsum("bqhc,bthc->bhqt", qb, keys) * c ** -0.5
        p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return jnp.einsum("bhqt,bthc->bqhc", p, values)

    o = jax.lax.map(some_queries, (
        jnp.arange(0, t, block),
        jnp.moveaxis(q.reshape(b, t // block, block, heads, c), 1, 0)))
    return jnp.moveaxis(o, 0, 1).reshape(b, t, heads, c)


def attention(y, w: dict, s: dict):
    """Attn(y): (B, T, d) -> (B, T, d); w: one layer's weights. A group of
    heads at a time (projected, pooled, attended, projected back and added
    up), so that the heads' queries, keys and values of a long row are
    never all alive."""
    import jax
    import jax.numpy as jnp

    heads = s["num_heads"]
    group = min(HEAD_GROUP, heads)
    while heads % group:
        group -= 1

    def some_heads(g, acc):
        def cut(name, axis):
            return jax.lax.dynamic_slice_in_dim(w[name], g * group, group,
                                                axis)

        q = rotary(jnp.einsum("btd,dhc->bthc", y, cut("wq", 1)),
                   s["rope_theta"])
        k = rotary(jnp.einsum("btd,dhc->bthc", y, cut("wk", 1)),
                   s["rope_theta"])
        v = jnp.einsum("btd,dhc->bthc", y, cut("wv", 1))
        kbar, vbar = summaries(k, v, cut("phi", 0), cut("mu", 0),
                               s["chunk_size"])
        o = attend(q, k, v, kbar, vbar, s["window_size"], s["chunk_size"])
        return acc + jnp.einsum("bthc,hcd->btd", o, cut("wo", 0))

    return jax.lax.fori_loop(0, heads // group, some_heads, 0.0 * y)


def _in_blocks(fn, flat, block: int):
    """fn over (tokens, d) a block of tokens at a time."""
    import jax

    n, d = flat.shape
    block = min(block, n)
    while n % block:
        block -= 1
    return jax.lax.map(fn, flat.reshape(n // block, block, d)).reshape(n, -1)


def layer_weights(w: dict, i: int) -> dict:
    """Layer i's own arrays, under the names of `weights`."""
    return {name: w[name][i] for name in (
        "ln_attn_scale", "wq", "wk", "wv", "phi", "mu", "wo",
        "ln_mlp_scale", "gate", "up", "down")}


def _embed(embed, ids):
    return embed[ids]


def _layer(h, w: dict, frozen_sizes: tuple):
    """One block: h + Attn(norm(h)), then + FF(norm(.))."""
    s = dict(frozen_sizes)
    h = h + attention(rms_norm(h, w["ln_attn_scale"], s["rms_norm_eps"]),
                      w, s)
    y = rms_norm(h, w["ln_mlp_scale"], s["rms_norm_eps"])
    ff = functools.partial(gated_ffn, gate=w["gate"], up=w["up"],
                           down=w["down"])
    return h + _in_blocks(ff, y.reshape(-1, y.shape[-1]),
                          FF_BLOCK).reshape(y.shape)


def _head(h, scale, head, ids, eps: float, vocab: int, fetch: str):
    """The final norm and the fetched output."""
    import jax
    import jax.numpy as jnp

    h = rms_norm(h, scale, eps)
    if fetch == "hidden":
        return h
    b, t, d = h.shape
    flat = h.reshape(b * t, d)
    if fetch == "logits":
        return (flat @ head).reshape(b, t, -1, vocab)
    first = head[:, :vocab]                 # prediction 0: the next byte
    # the next byte of every position but a row's last; the last scores a
    # target that is cut off below
    target = jnp.concatenate([ids[:, 1:], ids[:, :1]], 1).reshape(b * t)
    block = min(HEAD_BLOCK, b * t)
    while (b * t) % block:
        block -= 1

    def one(xs):
        hb, tb = xs
        logp = jax.nn.log_softmax(hb @ first, -1)
        return jnp.take_along_axis(logp, tb[:, None], -1)[:, 0]

    out = jax.lax.map(one, (flat.reshape(-1, block, d),
                            target.reshape(-1, block)))
    return out.reshape(b, t)[:, :t - 1]


_STATIC = {"_embed": (), "_layer": (2,), "_head": (4, 5, 6)}


@functools.lru_cache(maxsize=None)
def _compiled(name: str):
    """One jitted function a name and a process, so that a second call at
    the same shapes (the next layer, another block of rows, the control,
    the next seed) traces nothing."""
    import jax

    return jax.jit(globals()[name], static_argnums=_STATIC[name])


def _forward(w: dict, ids, frozen_sizes: tuple, fetch: str):
    """The forward a LAYER at a time, each a compiled program that is
    handed that layer's weights and nothing else (one program over all
    layers carries every weight it closes over through its loops as a
    copy: PERF.md, PR 31)."""
    s = dict(frozen_sizes)
    h = _compiled("_embed")(w["embed"], ids)
    for i in range(s["num_layers"]):
        h = _compiled("_layer")(h, layer_weights(w, i), frozen_sizes)
    return _compiled("_head")(h, w["ln_final_scale"], w["head"], ids,
                              s["rms_norm_eps"], s["vocab_size"], fetch)


def outputs(w: dict, config: dict, rows, fetch: str) -> np.ndarray:
    """The value of the fetched output `fetch` for `rows` ((n, length)
    byte ids, one length), float64 on the host. Rows go through in blocks
    of at most `BLOCK_TOKENS` tokens (one row at 32768), as equal as the
    count allows; a row's value depends on no other row."""
    import jax
    import jax.numpy as jnp

    if fetch not in FETCHES:
        raise ValueError(f"the reference knows the fetches {FETCHES}, not "
                         f"{fetch!r}")
    frozen = tuple(sorted(sizes(config).items()))
    rows = np.asarray(rows)
    most = max(1, BLOCK_TOKENS // rows.shape[1])
    block = -(-len(rows) // -(-len(rows) // most))
    out = []
    with jax.default_matmul_precision("highest"):
        for lo in range(0, len(rows), block):
            ids = jnp.asarray(rows[lo:lo + block], jnp.int32)
            out.append(np.asarray(_forward(w, ids, frozen, fetch),
                                  np.float64))
    return np.concatenate(out)


def attended_pairs(length: int, window: int, chunk: int) -> "tuple[int, int]":
    """(query, key) pairs and (query, summary) pairs one head of a row of
    `length` positions needs: per window the causal TRIANGLE of its own
    positions, and every position of it against the summaries of the
    windows before."""
    local = remote = 0
    for w, first in enumerate(range(0, length, window)):
        own = min(window, length - first)
        local += own * (own + 1) // 2
        remote += own * w * (window // chunk)
    return local, remote


def operations(config: dict, lengths) -> dict:
    """What scoring rows of the given lengths needs, from shapes alone:
    `lengths` is [(length, rows), ...]. One multiply and one add per weight
    a token meets; per (query, key or summary, channel) triple in the
    scores and in the weighted values, over all heads, a query meeting the
    keys of its window at or before it and the summaries of the windows
    before (`attended_pairs`); the summaries' weights, pooled keys and
    pooled values per channel of every position that is pooled (the
    chunks of every window but a row's last); the head for prediction 0's
    columns at the length - 1 positions that are scored. Bytes are the
    weights read once at two bytes each, the ids read and the
    log-probabilities written; the attention's are the queries, keys,
    values and summaries read and the output written once a layer, the
    summaries' the pooled keys and values read and the summaries written.
    `parts` splits both, so that roofline readers divide by the same
    counts."""
    s = sizes(config)
    d, heads, hd, layers = (s["d_model"], s["num_heads"], s["head_dim"],
                            s["num_layers"])
    window, chunk = s["window_size"], s["chunk_size"]
    tokens = sum(length * n for length, n in lengths)
    scored = sum((length - 1) * n for length, n in lengths)
    local = remote = pooled = 0
    for length, n in lengths:
        l, r = attended_pairs(length, window, chunk)
        local, remote = local + n * l, remote + n * r
        pooled += n * (-(-length // window) - 1) * window
    attn_w, ff_w = 4 * d * heads * hd, 3 * d * s["d_ff_dense"]
    head_w = d * s["vocab_size"]
    parts = {
        "projections": {"ops": 2.0 * tokens * layers * attn_w,
                        "bytes": 2.0 * layers * attn_w},
        # a position's score (c products, c - 1 sums), its weight, and its
        # share of the pooled key and value (2 c products, 2 c sums)
        "summaries": {
            "ops": float(layers) * pooled * heads * (6 * hd + 4),
            "bytes": 2.0 * layers * heads * hd * (2 * pooled
                                                  + 2 * pooled // chunk)},
        "attention": {
            "ops": 2.0 * layers * (local + remote) * heads * (hd + hd),
            "bytes": 2.0 * layers * heads * hd * (4 * tokens
                                                  + 2 * pooled // chunk)},
        "feed_forward": {"ops": 2.0 * tokens * layers * ff_w,
                         "bytes": 2.0 * layers * ff_w},
        # prediction 0's columns; the embedding and the whole head held
        "head": {"ops": 2.0 * scored * head_w,
                 "bytes": 2.0 * head_w * (1 + s["num_pred_heads"])
                          + 4.0 * tokens + 4.0 * scored},
    }
    return {"ops": sum(p["ops"] for p in parts.values()),
            "bytes": sum(p["bytes"] for p in parts.values()),
            "parts": parts}
