"""The plain reference of the program's `mla_moe_decoder` family, as a
neural cell's configuration brings one (README, "Adding a neural cell"): a
causal decoder over token ids whose block is
h = x + Attn(RMSNorm(x)), out = h + FFN(RMSNorm(h)), with

- latent attention (DeepSeek-V2, arXiv 2405.04434, section 2.1): queries
  of `qk_nope + qk_rope` channels a head; one down-projection of the input to
  a latent of `kv_lora_rank` channels and ONE rotary key of `qk_rope`
  channels for all heads; the latent, RMS-normed, is projected up to every
  head's `qk_nope` key channels and `v_head` value channels; rotary positions
  (rotate-half layout, no scaling) on the rope channels only; scores over
  sqrt(qk_nope + qk_rope), causal;
- a gated feed-forward, down(silu(gate y) * up y): dense in the leading
  `first_k_dense` layers, and in the others a routed expert layer
  (DeepSeek-V3, arXiv 2412.19437, section 2.1.2): scores s = sigmoid(y Wg) in
  float32 over ALL `n_routed_experts`, the `top_k` experts of s + b (b the
  selection bias), weights s at those experts (without b) over their sum
  (+1e-20) times `routed_scaling_factor`, plus one shared gated feed-forward
  every token takes;
- a final RMSNorm and an untied head; the fetched output is each next
  token's log-probability, `token_logprobs[r, t]` =
  log_softmax(logits[r, t])[ids[r, t + 1]], t = 0 .. length - 2.

A chip may hold a SHARE of the model: `experts_held` (first index, count)
of the routed experts, and `vocab_size` rows of the embedding and the head.
Routing is always over all `n_routed_experts`; the reference adds up the
experts held, so a pick that lies on another chip contributes nothing here
(its chip adds it), and the log-softmax is over the rows held.

Nothing here imports the program or takes anything the program has made.
Plain `jax.numpy` in float32 at the highest matmul precision. Every held
expert is computed for every token and weighted by its gate (zero where it
was not picked): no sort, no grouping, no kernel. Attention is a plain
masked softmax, a few heads at a time; the head goes in blocks of tokens."""

from __future__ import annotations

import functools

import numpy as np

BLOCK_TOKENS = 1 << 12          # tokens of a block of rows (one row at 4096)
HEAD_BLOCK = 1 << 10            # tokens of a block of the head's logits
SCORE_BYTES = 1 << 27           # attention scores of the heads taken at once
#                                 (two heads of a row of 4096 tokens)
FETCHES = ("token_logprobs", "logits", "hidden")
# The selection bias is drawn at a hundredth of a score: it changes the sixth
# pick of about a third of the tokens (scores near the cut lie about 0.01
# apart), so a bias left out shows, and it leaves the load even. A trained
# checkpoint's bias is what BALANCES its experts; one drawn at 0.1 does the
# opposite (read on the chip, PR 27: the busiest held expert got 4.6 times a
# layer's mean, and a call's length moved by 1.2% with the seed, because the
# experts held were popular under one seed and not under the next)
BIAS_STD = 0.01


def sizes(config: dict) -> dict:
    """The family's sizes from a configuration's `model` group, with the
    layer counts derived."""
    m = config["model"]
    s = {k: int(m[k]) for k in (
        "num_layers", "d_model", "num_heads", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "d_ff_dense",
        "first_k_dense", "n_routed_experts", "num_experts_per_tok",
        "d_ff_expert", "n_shared_experts", "vocab_size")}
    s["first_expert"], s["experts_held"] = (int(v) for v in m["experts_held"])
    s["routed_scaling_factor"] = float(m["routed_scaling_factor"])
    s["rms_norm_eps"] = float(m["rms_norm_eps"])
    s["rope_theta"] = float(m["rope_theta"])
    s["dense_layers"] = min(s["first_k_dense"], s["num_layers"])
    s["expert_layers"] = s["num_layers"] - s["dense_layers"]
    s["d_ff_shared"] = s["n_shared_experts"] * s["d_ff_expert"]
    return s


def weights(key, config: dict) -> dict:
    """Float32 weights on the device, one jitted call from the key. Kernels
    are normal at 1/sqrt(fan in); RMSNorm scales 1 + 0.1 n; the selection
    bias is drawn at `BIAS_STD`, a size that changes some picks, so that a
    term left out shows."""
    import jax
    import jax.numpy as jnp

    s = sizes(config)
    d, heads, lat = s["d_model"], s["num_heads"], s["kv_lora_rank"]
    nope, rope, vd = (s["qk_nope_head_dim"], s["qk_rope_head_dim"],
                      s["v_head_dim"])
    layers, nd, ne = s["num_layers"], s["dense_layers"], s["expert_layers"]
    held, w, ws, ff = (s["experts_held"], s["d_ff_expert"], s["d_ff_shared"],
                       s["d_ff_dense"])
    shapes = {
        "embed": ((s["vocab_size"], d), 1.0),
        "ln_attn_scale": ((layers, d), 0.1),
        "wq": ((layers, d, heads, nope + rope), d ** -0.5),
        "wkv_a": ((layers, d, lat + rope), d ** -0.5),
        "kv_norm_scale": ((layers, lat), 0.1),
        "wkv_b": ((layers, lat, heads, nope + vd), lat ** -0.5),
        "wo": ((layers, heads, vd, d), (heads * vd) ** -0.5),
        "ln_mlp_scale": ((layers, d), 0.1),
        "dense_gate": ((nd, d, ff), d ** -0.5),
        "dense_up": ((nd, d, ff), d ** -0.5),
        "dense_down": ((nd, ff, d), ff ** -0.5),
        "router": ((ne, d, s["n_routed_experts"]), d ** -0.5),
        "router_bias": ((ne, s["n_routed_experts"]), BIAS_STD),
        "expert_gate": ((ne, held, d, w), d ** -0.5),
        "expert_up": ((ne, held, d, w), d ** -0.5),
        "expert_down": ((ne, held, w, d), w ** -0.5),
        "shared_gate": ((ne, d, ws), d ** -0.5),
        "shared_up": ((ne, d, ws), d ** -0.5),
        "shared_down": ((ne, ws, d), ws ** -0.5),
        "ln_final_scale": ((d,), 0.1),
        "head": ((d, s["vocab_size"]), d ** -0.5),
    }

    @jax.jit
    def make(key):
        out = {}
        for i, (name, (shape, std)) in enumerate(sorted(shapes.items())):
            drawn = std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                            jnp.float32)
            out[name] = 1.0 + drawn if name.endswith("_scale") else drawn
        return out

    return make(key)


def variables(w: dict, config: dict) -> dict:
    """The weights under the names the program's module takes (the
    parameter tree of `MLAMoEDecoder`)."""
    s = sizes(config)
    params = {"embed": {"embedding": w["embed"]},
              "ln_final": {"scale": w["ln_final_scale"]},
              "head_kernel": w["head"]}
    for i in range(s["num_layers"]):
        params[f"ln_attn_{i}"] = {"scale": w["ln_attn_scale"][i]}
        params[f"mla_attn_{i}"] = {
            "q_proj": {"kernel": w["wq"][i]},
            "kv_a_proj": {"kernel": w["wkv_a"][i]},
            "kv_a_norm": {"scale": w["kv_norm_scale"][i]},
            "kv_b_proj": {"kernel": w["wkv_b"][i]},
            "out": {"kernel": w["wo"][i]}}
        params[f"ln_mlp_{i}"] = {"scale": w["ln_mlp_scale"][i]}
        if i < s["dense_layers"]:
            params[f"mlp_{i}"] = {"gate": {"kernel": w["dense_gate"][i]},
                                  "up": {"kernel": w["dense_up"][i]},
                                  "down": {"kernel": w["dense_down"][i]}}
        else:
            j = i - s["dense_layers"]
            params[f"moe_{i}"] = {
                "router_kernel": w["router"][j],
                "router_bias": w["router_bias"][j],
                "experts_gate": w["expert_gate"][j],
                "experts_up": w["expert_up"][j],
                "experts_down": w["expert_down"][j],
                "shared": {"gate": {"kernel": w["shared_gate"][j]},
                           "up": {"kernel": w["shared_up"][j]},
                           "down": {"kernel": w["shared_down"][j]}}}
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


def routing(y, router, bias, top_k: int, scaling: float):
    """-> (T, n_routed_experts) float32 gates: a token's weight for each of
    its `top_k` experts, zero elsewhere."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(y @ router)
    _best, picked = jax.lax.top_k(s + bias, top_k)
    chosen = jnp.take_along_axis(s, picked, axis=-1)
    chosen = chosen / (chosen.sum(-1, keepdims=True) + 1e-20) * scaling
    rows = jnp.arange(y.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, picked].set(chosen)


def expert_layer(y, w: dict, j: int, s: dict):
    """The routed experts held here, each computed for every token and
    weighted by its gate, plus the shared feed-forward. y: (T, d)."""
    import jax

    gates = routing(y, w["router"][j], w["router_bias"][j],
                    s["num_experts_per_tok"], s["routed_scaling_factor"])
    lo = s["first_expert"]
    held = gates[:, lo:lo + s["experts_held"]]               # (T, held)

    # one expert at a time, its matrices cut from the stacked weights
    # inside the loop: nothing the size of a layer's experts is copied
    def one(e, acc):
        cut = lambda name: jax.lax.dynamic_index_in_dim(      # noqa: E731
            w[name][j], e, keepdims=False)
        g = jax.lax.dynamic_slice_in_dim(held, e, 1, axis=1)
        return acc + g * gated_ffn(y, cut("expert_gate"), cut("expert_up"),
                                   cut("expert_down"))

    routed = jax.lax.fori_loop(0, s["experts_held"], one, 0.0 * y)
    return routed + gated_ffn(y, w["shared_gate"][j], w["shared_up"][j],
                              w["shared_down"][j])


def attention(y, w: dict, i: int, s: dict):
    """Latent attention of layer i. y: (B, T, d) -> (B, T, d)."""
    import jax
    import jax.numpy as jnp

    nope, lat = s["qk_nope_head_dim"], s["kv_lora_rank"]
    b, t, _d = y.shape
    heads = s["num_heads"]
    q = jnp.einsum("btd,dhc->bthc", y, w["wq"][i])
    q = jnp.concatenate([q[..., :nope],
                         rotary(q[..., nope:], s["rope_theta"])], -1)
    kv = y @ w["wkv_a"][i]
    k_pe = rotary(kv[..., None, lat:], s["rope_theta"])      # (B, T, 1, rope)
    c = rms_norm(kv[..., :lat], w["kv_norm_scale"][i], s["rms_norm_eps"])
    kvb = jnp.einsum("btl,lhc->bthc", c, w["wkv_b"][i])
    k = jnp.concatenate([kvb[..., :nope],
                         jnp.broadcast_to(k_pe, (b, t, heads, k_pe.shape[-1]))],
                        -1)
    v = kvb[..., nope:]
    scale = q.shape[-1] ** -0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    group = max(1, min(heads, SCORE_BYTES // (4 * b * t * t)))
    while heads % group:
        group -= 1

    def some_heads(xs):
        qh, kh, vh = xs                                      # (B, T, g, c)
        scores = jnp.einsum("bqhc,bthc->bhqt", qh, kh) * scale
        p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        return jnp.einsum("bhqt,bthc->bqhc", p, vh)

    def split(x):                                # (n, B, T, g, c)
        return jnp.moveaxis(x.reshape(b, t, heads // group, group, -1), 2, 0)

    o = jax.lax.map(some_heads, (split(q), split(k), split(v)))
    o = jnp.moveaxis(o, 0, 2).reshape(b, t, heads, -1)
    return jnp.einsum("bthc,hcd->btd", o, w["wo"][i])


def _hidden(w: dict, ids, s: dict):
    h = w["embed"][ids]
    for i in range(s["num_layers"]):
        h = h + attention(rms_norm(h, w["ln_attn_scale"][i],
                                   s["rms_norm_eps"]), w, i, s)
        y = rms_norm(h, w["ln_mlp_scale"][i], s["rms_norm_eps"])
        if i < s["dense_layers"]:
            h = h + gated_ffn(y, w["dense_gate"][i], w["dense_up"][i],
                              w["dense_down"][i])
        else:
            flat = y.reshape(-1, y.shape[-1])
            h = h + expert_layer(flat, w, i - s["dense_layers"],
                                 s).reshape(y.shape)
    return rms_norm(h, w["ln_final_scale"], s["rms_norm_eps"])


def _forward(w: dict, ids, frozen_sizes: tuple, fetch: str):
    import jax
    import jax.numpy as jnp

    s = dict(frozen_sizes)
    h = _hidden(w, ids, s)
    if fetch == "hidden":
        return h
    b, t, d = h.shape
    flat = h.reshape(b * t, d)
    if fetch == "logits":
        return (flat @ w["head"]).reshape(b, t, -1)
    # the next token of every position but a row's last; the last scores a
    # target that is cut off below
    target = jnp.concatenate([ids[:, 1:], ids[:, :1]], 1).reshape(b * t)
    block = min(HEAD_BLOCK, b * t)
    while (b * t) % block:
        block -= 1

    def one(xs):
        hb, tb = xs
        logp = jax.nn.log_softmax(hb @ w["head"], -1)
        return jnp.take_along_axis(logp, tb[:, None], -1)[:, 0]

    out = jax.lax.map(one, (flat.reshape(-1, block, d),
                            target.reshape(-1, block)))
    return out.reshape(b, t)[:, :t - 1]


@functools.lru_cache(maxsize=None)
def _jitted_forward():
    """One jitted forward a process, so that a second call at the same
    shapes (another length, the control, the next seed) traces nothing."""
    import jax

    return jax.jit(_forward, static_argnums=(2, 3))


def outputs(w: dict, config: dict, rows, fetch: str) -> np.ndarray:
    """The value of the fetched output `fetch` for `rows` ((n, length)
    token ids, one length), float64 on the host. Rows go through in blocks
    of at most `BLOCK_TOKENS` tokens (one row at 4096); a row's value
    depends on no other row."""
    import jax
    import jax.numpy as jnp

    if fetch not in FETCHES:
        raise ValueError(f"the reference knows the fetches {FETCHES}, not "
                         f"{fetch!r}")
    frozen = tuple(sorted(sizes(config).items()))
    rows = np.asarray(rows)
    block = max(1, BLOCK_TOKENS // rows.shape[1])
    forward = _jitted_forward()
    out = []
    with jax.default_matmul_precision("highest"):
        for lo in range(0, len(rows), block):
            ids = jnp.asarray(rows[lo:lo + block], jnp.int32)
            out.append(np.asarray(forward(w, ids, frozen, fetch),
                                  np.float64))
    return np.concatenate(out)


def operations(config: dict, lengths) -> dict:
    """What scoring rows of the given lengths needs on THIS chip, from
    shapes alone: `lengths` is [(length, rows), ...]. One multiply and one
    add per weight a token meets; per (query, key, channel) triple of the
    causal TRIANGLE (a query and the keys at or before it) in the scores
    and in the weighted values; the head for the length - 1 positions that
    are scored. Routed experts count the picks expected here when picks are
    even: `num_experts_per_tok` x held / routed a token (`per_pick` lets a
    reader use counted picks instead). Bytes are the weights read once at
    two bytes each, the ids read and the log-probabilities written.
    `parts` splits both by layer kind, so that roofline readers divide by
    the same counts."""
    s = sizes(config)
    d, heads = s["d_model"], s["num_heads"]
    qk = s["qk_nope_head_dim"] + s["qk_rope_head_dim"]
    layers, ne = s["num_layers"], s["expert_layers"]
    attn_w = (d * heads * qk + d * (s["kv_lora_rank"] + s["qk_rope_head_dim"])
              + s["kv_lora_rank"] * heads
              * (s["qk_nope_head_dim"] + s["v_head_dim"])
              + heads * s["v_head_dim"] * d)
    router_w = d * s["n_routed_experts"]
    dense_w = 3 * d * s["d_ff_dense"]
    shared_w = 3 * d * s["d_ff_shared"]
    expert_w = 3 * d * s["d_ff_expert"]
    picks_per_token = (s["num_experts_per_tok"] * s["experts_held"]
                       / s["n_routed_experts"])
    head_w = d * s["vocab_size"]
    tokens = sum(length * n for length, n in lengths)
    scored = sum((length - 1) * n for length, n in lengths)
    triangle = sum(n * length * (length + 1) / 2 for length, n in lengths)
    ids_bytes, out_bytes = 4.0 * tokens, 4.0 * scored
    parts = {
        "projections": {
            "ops": 2.0 * tokens * (layers * attn_w + ne * router_w
                                   + s["dense_layers"] * dense_w),
            "bytes": 2.0 * (layers * attn_w + ne * router_w
                            + s["dense_layers"] * dense_w)},
        "attention": {
            "ops": 2.0 * layers * triangle * heads * (qk + s["v_head_dim"]),
            # queries, keys, values read and the output written, once a layer
            "bytes": 2.0 * layers * tokens * heads
                     * (2 * qk + 2 * s["v_head_dim"])},
        "routed_experts": {
            "ops": 2.0 * tokens * ne * picks_per_token * expert_w,
            "bytes": 2.0 * ne * s["experts_held"] * expert_w,
            "per_pick": {"ops": 2.0 * expert_w,
                         "bytes": 2.0 * 2 * (d + s["d_ff_expert"])}},
        "shared_experts": {"ops": 2.0 * tokens * ne * shared_w,
                           "bytes": 2.0 * ne * shared_w},
        "head": {"ops": 2.0 * scored * head_w,
                 "bytes": 2.0 * 2 * head_w + ids_bytes + out_bytes},
    }
    return {"ops": sum(p["ops"] for p in parts.values()),
            "bytes": sum(p["bytes"] for p in parts.values()),
            "parts": parts}
