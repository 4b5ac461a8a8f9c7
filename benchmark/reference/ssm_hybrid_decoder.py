"""The plain reference of the program's `ssm_hybrid_decoder` family, as a
neural cell's configuration brings one (README, "Adding a neural cell"): a
causal decoder over token ids whose every layer runs a Mamba-2 state-space
mixer and grouped-query attention SIDE BY SIDE on the same normed input,
under the family's fixed scalars (the Falcon-H1 block, HF `falcon_h1`). N
are RMSNorms with their own scales, N(x) = x / sqrt(mean(x^2) + eps) * w;
every projection without bias; m_* are the configuration's multipliers:

    h = m_emb Embed[ids]
    for every layer:
        x = N1(h)
        # state-space branch
        p = ((m_ssm_in x) W_in) * m           W_in: d -> [z | xs B C | dt]
                                              m: ssm_multipliers repeated
                                              over z, xs, B, C, dt
        [xs B C] <- silu(conv([xs B C]))      depthwise, causal, 4 taps,
                                              a bias, zero before the row
        dt_j = softplus(dt_j + dt_bias_j);  A_j = -exp(A_log_j)
        S_t = exp(dt_t A) S_{t-1} + dt_t xs_t B_t^T     a head, S (P, N),
        y_t = S_t C_t + D_j xs_t                        zero before the row
        y <- Ng(y * silu(z))                  RMSNorm in groups of channels,
                                              one weight a channel
        ssm = m_ssm_out (y W_out)
        # attention branch
        a = m_attn_in x
        q, k, v = a W_q, m_key (a W_k), a W_v ; rotary ; causal softmax
        attn = m_attn_out (o W_o)
        h = h + ssm + attn
        u = N2(h)
        h = h + m_down W_down( silu(m_gate (u W_gate)) * (u W_up) )
    logits = m_head (N_final(h) W_head)                       # untied

Head j of the scan reads B and C of group j // (heads / groups); attention's
query head j reads key/value head j // (heads / kv heads); rotary positions
0 .. T - 1 over the whole head, rotate-half layout; scores over
sqrt(head_dim). The fetched output: `token_logprobs[r, t]` =
log_softmax(logits[r, t])[ids[r, t + 1]], t = 0 .. length - 2.

The scan here is the RECURRENCE, a token at a time (`lax.scan` over the
tokens of a block, the state carried from block to block): not the chunked
algebra the program runs, so a wrong decay matrix or a decay off by one
cannot be in both.

Where this departs from, or fills in, the text above and the catalog's row
(the configuration's `assumed` lists the same): the order of
`ssm_multipliers` over the projection's parts (z, xs, B, C, dt); the gated
norm's groups (`mamba_n_groups`) and the gate BEFORE the norm
(`mamba_norm_before_gate` false); no clamp on dt; the taps as torch's
Conv1d lays them (the last meets the newest token); `mamba_d_ssm` over
`mamba_expand`; no norm on query or key heads; no rotary scaling.

Nothing here imports the program or takes anything the program has made.
Plain `jax.numpy` in float32 at the highest matmul precision; a Python loop
over layers, each one call of ONE compiled program that is handed that
layer's weights and the residual stream, which it DONATES and rewrites in
place a block of `TOKEN_BLOCK` tokens at a time: a row of 32768 tokens at
hidden 5120 is 671 MB in float32, and beside the served model and the
float32 tree about 2 GB of the chip are left. The keys and values of the
whole row are made first (134 MB), then every block goes through both
mixers (the scan's state and the convolution's last taps carried from
block to block), the residual add and the feed-forward (in blocks of
`FF_BLOCK` tokens); attention is a masked softmax over ALL keys, a
sub-block of queries at a time; the head goes in blocks of `HEAD_BLOCK`
tokens."""

from __future__ import annotations

import functools
import math

import numpy as np

BLOCK_TOKENS = 1 << 15          # tokens of a block of rows (one row at 32768)
TOKEN_BLOCK = 1 << 10           # tokens a row's layer pass rewrites at a time
FF_BLOCK = 1 << 9               # tokens of a block of a feed-forward
HEAD_BLOCK = 1 << 9             # tokens of a block of the head's logits
SCORE_BYTES = 1 << 27           # attention scores of a sub-block of queries
FETCHES = ("token_logprobs", "logits", "hidden")
CHUNK = 128                     # the published `mamba_chunk_size`
A_RANGE = (1.0, 16.0)           # A is drawn uniform here (Mamba-2's init)
DT_RANGE = (1e-3, 1e-1)         # dt's bias log-uniform here, through the
                                # inverse softplus (Mamba-2's init)
MULTIPLIERS = {
    "embedding_multiplier": 1.0, "key_multiplier": 1.0,
    "attention_in_multiplier": 1.0, "attention_out_multiplier": 1.0,
    "ssm_in_multiplier": 1.0, "ssm_out_multiplier": 1.0,
    "lm_head_multiplier": 1.0}


def sizes(config: dict) -> dict:
    """The family's sizes and scalars from a configuration's `model`
    group."""
    m = config["model"]
    s = {k: int(m[k]) for k in (
        "num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim",
        "ssm_heads", "ssm_head_dim", "ssm_groups", "ssm_state",
        "d_ff_dense", "vocab_size")}
    s["conv_taps"] = int(m.get("conv_taps", 4))
    s["rms_norm_eps"] = float(m.get("rms_norm_eps", 1e-5))
    s["rope_theta"] = float(m.get("rope_theta", 1e11))
    for name, default in MULTIPLIERS.items():
        s[name] = float(m.get(name, default))
    s["ssm_multipliers"] = tuple(
        float(x) for x in m.get("ssm_multipliers", (1.0,) * 5))
    s["mlp_multipliers"] = tuple(
        float(x) for x in m.get("mlp_multipliers", (1.0,) * 2))
    if s["num_heads"] % s["num_kv_heads"] or s["ssm_heads"] % s["ssm_groups"]:
        raise ValueError("the key/value heads divide the query heads, and "
                         "the scan's groups its heads")
    return s


def _widths(s: dict):
    """(the scan's channels, B's and C's channels each, the convolution's
    channels, the projection's width)."""
    inner = s["ssm_heads"] * s["ssm_head_dim"]
    bc = s["ssm_groups"] * s["ssm_state"]
    return inner, bc, inner + 2 * bc, 2 * inner + 2 * bc + s["ssm_heads"]


def part_multipliers(s: dict):
    """`ssm_multipliers` repeated over the projection's columns: z, xs, B,
    C, dt, in that order -> (projection's width,) float32."""
    inner, bc, _mixed, _wide = _widths(s)
    return np.repeat(np.asarray(s["ssm_multipliers"], np.float32),
                     [inner, inner, bc, bc, s["ssm_heads"]])


def weights(key, config: dict) -> dict:
    """Float32 weights on the device, one jitted call from the key: an array
    for the embedding, the head and the final norm, and for every other name
    a LIST with one array a layer. A kernel is normal at 1/sqrt(fan in) OVER
    the fixed scalars that meet its output (the family is trained under
    them: a checkpoint's weights are large where its multiplier is small), so
    that every pre-activation spreads like a unit normal as in the other
    families' references: the key projection over key_multiplier, so the
    scores are not all alike and the softmax is no plain mean; the parts of
    the state-space projection each over its own; the embedding at 1 over
    its multiplier. RMSNorm scales 1 + 0.1 n (a norm left out or in
    another's place shows); taps normal at 1/sqrt(taps), their bias at 0.2.
    The scan's own vectors are drawn as Mamba-2 initialises them: A uniform
    in `A_RANGE` (A_log its logarithm), dt's bias the inverse softplus of a
    log-uniform draw in `DT_RANGE`, so that a head's decay over a chunk of
    128 tokens spans forgetting everything to forgetting little (with
    normals there the scan is an identity or a zero, and the comparison
    would test nothing); D normal around 1."""
    import jax
    import jax.numpy as jnp

    s = sizes(config)
    d, heads, kv, hd, ff = (s["d_model"], s["num_heads"], s["num_kv_heads"],
                            s["head_dim"], s["d_ff_dense"])
    layers, ssm_heads = s["num_layers"], s["ssm_heads"]
    inner, _bc, mixed, wide = _widths(s)
    a_in, m_gate, m_down = (s["attention_in_multiplier"],
                            *s["mlp_multipliers"])
    by_column = 1.0 / (s["ssm_in_multiplier"] * part_multipliers(s))
    # name -> (layers, or None for a single array; shape; std or a draw)
    shapes = {
        "embed": (None, (s["vocab_size"], d),
                  1.0 / s["embedding_multiplier"]),
        "ln_op_scale": (layers, (d,), 0.1),
        "w_in": (layers, (d, wide), d ** -0.5),
        "conv_w": (layers, (mixed, s["conv_taps"]), s["conv_taps"] ** -0.5),
        "conv_b": (layers, (mixed,), 0.2),
        "dt_bias": (layers, (ssm_heads,), "dt"),
        "a_log": (layers, (ssm_heads,), "a"),
        "d_skip": (layers, (ssm_heads,), 0.1),
        "gate_norm_scale": (layers, (inner,), 0.1),
        "w_out": (layers, (inner, d),
                  inner ** -0.5 / s["ssm_out_multiplier"]),
        "wq": (layers, (d, heads, hd), d ** -0.5 / a_in),
        "wk": (layers, (d, kv, hd),
               d ** -0.5 / (a_in * s["key_multiplier"])),
        "wv": (layers, (d, kv, hd), d ** -0.5 / a_in),
        "wo": (layers, (heads, hd, d),
               (heads * hd) ** -0.5 / s["attention_out_multiplier"]),
        "ln_mlp_scale": (layers, (d,), 0.1),
        "gate": (layers, (d, ff), d ** -0.5 / m_gate),
        "up": (layers, (d, ff), d ** -0.5),
        "down": (layers, (ff, d), ff ** -0.5 / m_down),
        "ln_final_scale": (None, (d,), 0.1),
        "head": (None, (d, s["vocab_size"]),
                 d ** -0.5 / s["lm_head_multiplier"]),
    }

    @jax.jit
    def make(key):
        out = {}
        for i, (name, (count, shape, std)) in enumerate(
                sorted(shapes.items())):
            def draw(at):
                if std == "a":
                    return jnp.log(jax.random.uniform(
                        at, shape, jnp.float32, *A_RANGE))
                if std == "dt":
                    step = jnp.exp(jax.random.uniform(
                        at, shape, jnp.float32, math.log(DT_RANGE[0]),
                        math.log(DT_RANGE[1])))
                    return step + jnp.log(-jnp.expm1(-step))
                drawn = std * jax.random.normal(at, shape, jnp.float32)
                if name == "w_in":
                    drawn = drawn * by_column
                centred = name.endswith("_scale") or name == "d_skip"
                return 1.0 + drawn if centred else drawn

            at = jax.random.fold_in(key, i)
            out[name] = draw(at) if count is None else [
                draw(jax.random.fold_in(at, layer)) for layer in range(count)]
        return out

    return make(key)


def variables(w: dict, config: dict) -> dict:
    """The weights under the names the program's module takes (the
    parameter tree of `SSMHybridDecoder`)."""
    s = sizes(config)
    params = {"embed": {"embedding": w["embed"]},
              "ln_final": {"scale": w["ln_final_scale"]},
              "head_kernel": w["head"]}
    for i in range(s["num_layers"]):
        params[f"ln_op_{i}"] = {"scale": w["ln_op_scale"][i]}
        params[f"ssm_{i}"] = {
            "in_proj": {"kernel": w["w_in"][i]},
            "conv_kernel": w["conv_w"][i], "conv_bias": w["conv_b"][i],
            "dt_bias": w["dt_bias"][i], "A_log": w["a_log"][i],
            "D": w["d_skip"][i], "norm_scale": w["gate_norm_scale"][i],
            "out_proj": {"kernel": w["w_out"][i]}}
        params[f"gqa_attn_{i}"] = {"q_proj": {"kernel": w["wq"][i]},
                                   "k_proj": {"kernel": w["wk"][i]},
                                   "v_proj": {"kernel": w["wv"][i]},
                                   "out": {"kernel": w["wo"][i]}}
        params[f"ln_mlp_{i}"] = {"scale": w["ln_mlp_scale"][i]}
        params[f"mlp_{i}"] = {"gate": {"kernel": w["gate"][i]},
                              "up": {"kernel": w["up"][i]},
                              "down": {"kernel": w["down"][i]}}
    return {"params": params}


def rms_norm(x, scale, eps: float):
    import jax

    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def rotary(x, theta: float, first=0):
    """Rotary positions first .. first + T - 1 on the last axis of x (...,
    T, heads, c), rotate-half layout: channel i pairs with channel i +
    c/2."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    at = (first + jnp.arange(x.shape[-3])).astype(jnp.float32)
    angle = at[:, None] * freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _in_blocks(fn, flat, block: int):
    """fn over a (tokens, d) array a block of tokens at a time."""
    import jax

    n, d = flat.shape
    block = _divisor(n, block)
    return jax.lax.map(fn, flat.reshape(n // block, block, d)).reshape(n, -1)


def _divisor(n: int, most: int) -> int:
    """The largest divisor of n that is at most `most`."""
    block = max(1, min(n, most))
    while n % block:
        block -= 1
    return block


def recurrence(xs, bm, cm, dt, a, d_skip, state):
    """The selective scan a token at a time. xs (B, T, H, P); bm, cm (B, T,
    G, N); dt (B, T, H) after its softplus; a, d_skip (H,); state (B, H, P,
    N) before the first token -> (y (B, T, H, P), the state after the
    last)."""
    import jax
    import jax.numpy as jnp

    b, _t, h, p = xs.shape
    g, n = bm.shape[2:]
    per = h // g

    def token(state, now):
        x_t, b_t, c_t, dt_t = now            # (B,H,P) (B,G,N) (B,G,N) (B,H)
        keep = jnp.exp(dt_t * a)                            # (B, H)
        grouped = state.reshape(b, g, per, p, n)
        fed = (dt_t[..., None] * x_t).reshape(b, g, per, p)
        grouped = (keep.reshape(b, g, per)[..., None, None] * grouped
                   + fed[..., None] * b_t[:, :, None, None, :])
        y_t = (grouped * c_t[:, :, None, None, :]).sum(-1).reshape(b, h, p)
        return grouped.reshape(b, h, p, n), y_t + d_skip[:, None] * x_t

    state, y = jax.lax.scan(token, state, tuple(
        jnp.moveaxis(v, 1, 0) for v in (xs, bm, cm, dt)))
    return jnp.moveaxis(y, 0, 1), state


def state_space(x, w: dict, s: dict, state, tail):
    """The state-space branch of a block of tokens, before its output
    multiplier. x (B, T, d), normed; `state` (B, H, P, N) and `tail` (B,
    taps - 1, channels: the convolution's inputs of the tokens before the
    block) carried in -> (out (B, T, d), state, tail)."""
    import jax
    import jax.numpy as jnp

    b, t, _d = x.shape
    heads, p, g, n = (s["ssm_heads"], s["ssm_head_dim"], s["ssm_groups"],
                      s["ssm_state"])
    inner, bc, mixed, _wide = _widths(s)
    proj = ((s["ssm_in_multiplier"] * x) @ w["w_in"]) * part_multipliers(s)
    z, xbc, dt = (proj[..., :inner], proj[..., inner:inner + mixed],
                  proj[..., inner + mixed:])
    taps = s["conv_taps"]
    padded = jnp.concatenate([tail, xbc], 1)
    conv = sum(w["conv_w"][:, j] * padded[:, j:j + t] for j in range(taps))
    xbc = jax.nn.silu(conv + w["conv_b"])
    y, state = recurrence(
        xbc[..., :inner].reshape(b, t, heads, p),
        xbc[..., inner:inner + bc].reshape(b, t, g, n),
        xbc[..., inner + bc:].reshape(b, t, g, n),
        jax.nn.softplus(dt + w["dt_bias"]), -jnp.exp(w["a_log"]),
        w["d_skip"], state)
    gated = (y.reshape(b, t, inner) * jax.nn.silu(z)).reshape(
        b, t, g, inner // g)
    normed = gated * jax.lax.rsqrt(
        (gated * gated).mean(-1, keepdims=True) + s["rms_norm_eps"])
    out = (normed.reshape(b, t, inner) * w["gate_norm_scale"]) @ w["w_out"]
    return out, state, padded[:, t:]


def keys_and_values(x, w: dict, s: dict, first):
    """A block's keys (rotated at its positions) and values. x (B, T, d),
    normed -> k, v (B, T, kv heads, c)."""
    import jax.numpy as jnp

    a = s["attention_in_multiplier"] * x
    k = s["key_multiplier"] * jnp.einsum("btd,dhc->bthc", a, w["wk"])
    return (rotary(k, s["rope_theta"], first),
            jnp.einsum("btd,dhc->bthc", a, w["wv"]))


def attention(x, k, v, w: dict, s: dict, first):
    """The attention branch of a block of queries at positions `first`
    onwards against the keys and values of the WHOLE row, before its output
    multiplier. x (B, T, d), normed; k, v (B, row, kv heads, c)."""
    import jax
    import jax.numpy as jnp

    b, t, _d = x.shape
    heads, kv = s["num_heads"], s["num_kv_heads"]
    group, row = heads // kv, k.shape[1]
    q = rotary(jnp.einsum("btd,dhc->bthc",
                          s["attention_in_multiplier"] * x, w["wq"]),
               s["rope_theta"], first)
    scale = q.shape[-1] ** -0.5
    block = _divisor(t, SCORE_BYTES // (4 * b * heads * row))
    # query head j reads key/value head j // group: (.., kv, group, c)
    q = q.reshape(b, t // block, block, kv, group, -1)
    kpos = jnp.arange(row)

    def some_queries(xs):
        start, qb = xs                                # (B, block, kv, g, c)
        scores = jnp.einsum("bqhgc,bthc->bhgqt", qb, k) * scale
        seen = (start + jnp.arange(block))[:, None] >= kpos[None, :]
        prob = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return jnp.einsum("bhgqt,bthc->bqhgc", prob, v)

    o = jax.lax.map(some_queries, (first + jnp.arange(0, t, block),
                                   jnp.moveaxis(q, 1, 0)))
    o = jnp.moveaxis(o, 0, 1).reshape(b, t, heads, -1)
    return jnp.einsum("bthc,hcd->btd", o, w["wo"])


def feed_forward(u, w: dict, s: dict):
    import jax

    m_gate, m_down = s["mlp_multipliers"]
    return m_down * ((jax.nn.silu(m_gate * (u @ w["gate"]))
                      * (u @ w["up"])) @ w["down"])


LAYER_NAMES = ("ln_op_scale", "w_in", "conv_w", "conv_b", "dt_bias", "a_log",
               "d_skip", "gate_norm_scale", "w_out", "wq", "wk", "wv", "wo",
               "ln_mlp_scale", "gate", "up", "down")


def layer_weights(w: dict, i: int) -> dict:
    """Layer i's own arrays, under the names of `weights`."""
    return {name: w[name][i] for name in LAYER_NAMES}


def _embed(embed, ids, multiplier: float):
    return multiplier * embed[ids]


def _layer(h, w: dict, frozen_sizes: tuple):
    """One layer over rows h (B, T, d), which is DONATED and rewritten a
    block of tokens at a time: the row's keys and values first, from the
    stream as it came in; then block after block both mixers, the residual
    add and the feed-forward, the scan's state and the convolution's tail
    carried along."""
    import jax
    import jax.numpy as jnp

    s = dict(frozen_sizes)
    b, t, d = h.shape
    eps = s["rms_norm_eps"]
    block = _divisor(t, TOKEN_BLOCK)
    _inner, _bc, mixed, _wide = _widths(s)

    def at(h, i):
        return jax.lax.dynamic_slice(h, (0, i * block, 0), (b, block, d))

    def block_keys(i):
        return keys_and_values(rms_norm(at(h, i), w["ln_op_scale"], eps),
                               w, s, i * block)

    k, v = (jnp.moveaxis(x, 0, 1).reshape(b, t, *x.shape[3:])
            for x in jax.lax.map(block_keys, jnp.arange(t // block)))

    def one(i, carry):
        h, state, tail = carry
        hb = at(h, i)
        x = rms_norm(hb, w["ln_op_scale"], eps)
        ssm, state, tail = state_space(x, w, s, state, tail)
        hb = (hb + s["ssm_out_multiplier"] * ssm
              + s["attention_out_multiplier"] * attention(
                  x, k, v, w, s, i * block))
        u = rms_norm(hb, w["ln_mlp_scale"], eps)
        ff = functools.partial(feed_forward, w=w, s=s)
        hb = hb + _in_blocks(ff, u.reshape(-1, d), FF_BLOCK).reshape(
            hb.shape)
        return (jax.lax.dynamic_update_slice(h, hb, (0, i * block, 0)),
                state, tail)

    state = jnp.zeros((b, s["ssm_heads"], s["ssm_head_dim"],
                       s["ssm_state"]), jnp.float32)
    tail = jnp.zeros((b, s["conv_taps"] - 1, mixed), jnp.float32)
    return jax.lax.fori_loop(0, t // block, one, (h, state, tail))[0]


def _head(h, scale, head, ids, eps: float, multiplier: float, fetch: str):
    """The fetched output from the stream after the last layer: the final
    norm, then `head`: (d, vocabulary), in blocks of tokens."""
    import jax
    import jax.numpy as jnp

    b, t, d = h.shape
    if fetch == "hidden":
        return rms_norm(h, scale, eps)
    flat = h.reshape(b * t, d)
    if fetch == "logits":
        return (multiplier * (rms_norm(flat, scale, eps) @ head)).reshape(
            b, t, -1)
    # the next token of every position but a row's last; the last scores a
    # target that is cut off below
    target = jnp.concatenate([ids[:, 1:], ids[:, :1]], 1).reshape(b * t)
    block = _divisor(b * t, HEAD_BLOCK)

    def one(xs):
        hb, tb = xs
        logp = jax.nn.log_softmax(
            multiplier * (rms_norm(hb, scale, eps) @ head), -1)
        return jnp.take_along_axis(logp, tb[:, None], -1)[:, 0]

    out = jax.lax.map(one, (flat.reshape(-1, block, d),
                            target.reshape(-1, block)))
    return out.reshape(b, t)[:, :t - 1]


_JIT = {"_embed": dict(static_argnums=(2,)),
        "_layer": dict(static_argnums=(2,), donate_argnums=(0,)),
        "_head": dict(static_argnums=(4, 5, 6))}


@functools.lru_cache(maxsize=None)
def _compiled(name: str):
    """One jitted function a name and a process, so that a second call at
    the same shapes (the next layer, another block of rows, the control,
    the next seed) traces nothing."""
    import jax

    return jax.jit(globals()[name], **_JIT[name])


def _forward(w: dict, ids, frozen_sizes: tuple, fetch: str):
    """The forward a LAYER at a time: one compiled layer program, handed
    that layer's weights and the stream (one program over all layers
    carries every weight it closes over through its loops as a copy: a
    second float32 tree, which does not fit; PERF.md, PR 31)."""
    s = dict(frozen_sizes)
    h = _compiled("_embed")(w["embed"], ids, s["embedding_multiplier"])
    for i in range(s["num_layers"]):
        h = _compiled("_layer")(h, layer_weights(w, i), frozen_sizes)
    return _compiled("_head")(h, w["ln_final_scale"], w["head"], ids,
                              s["rms_norm_eps"], s["lm_head_multiplier"],
                              fetch)


def outputs(w: dict, config: dict, rows, fetch: str) -> np.ndarray:
    """The value of the fetched output `fetch` for `rows` ((n, length)
    token ids, one length), float64 on the host. Rows go through in blocks
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


def operations(config: dict, lengths) -> dict:
    """What scoring rows of the given lengths needs, from shapes alone:
    `lengths` is [(length, rows), ...]. One multiply and one add per weight
    a token meets; per (query, key, channel) triple in the scores and in the
    weighted values, over all query heads, of the causal TRIANGLE; the head
    for the length - 1 positions that are scored. The scan (`ssd`) is
    counted as its chunked form, whatever implements it: a chunk of Q = 128
    tokens costs a head three products, ((C B^T) * L)(dt X) (Q x Q x P), C S
    (Q x N x P) and B^T X (Q x N x P), and a GROUP one, C B^T (Q x Q x N:
    the heads of a group read the same B and C); its bytes are xs, B, C
    read and y written once in the served type and dt read in float32. The
    convolution is a memory pass: its taps' multiply-adds, and its channels
    read and written once. Bytes of the products: the weights read once at
    two bytes each, the ids read and the log-probabilities written; the
    attention's are the queries, the key and the value heads read and the
    output written once a layer. `parts` splits both, so that roofline
    readers divide by the same counts."""
    s = sizes(config)
    d, heads, kv, hd = (s["d_model"], s["num_heads"], s["num_kv_heads"],
                        s["head_dim"])
    layers = s["num_layers"]
    ssm_heads, p, g, n = (s["ssm_heads"], s["ssm_head_dim"],
                          s["ssm_groups"], s["ssm_state"])
    inner, bc, mixed, wide = _widths(s)
    attn_w = d * heads * hd + 2 * d * kv * hd + heads * hd * d
    ssm_w = d * wide + inner * d
    ff_w = 3 * d * s["d_ff_dense"]
    head_w = d * s["vocab_size"]
    tokens = sum(length * rows for length, rows in lengths)
    scored = sum((length - 1) * rows for length, rows in lengths)
    triangle = sum(rows * length * (length + 1) / 2
                   for length, rows in lengths)
    chunks = sum(rows * -(-length // CHUNK) for length, rows in lengths)
    a_chunk = (ssm_heads * (2.0 * CHUNK * CHUNK * p + 4.0 * CHUNK * n * p)
               + g * 2.0 * CHUNK * CHUNK * n)
    parts = {
        "projections": {"ops": 2.0 * tokens * layers * (attn_w + ssm_w),
                        "bytes": 2.0 * layers * (attn_w + ssm_w)},
        "attention": {"ops": 2.0 * layers * triangle * heads * (hd + hd),
                      "bytes": layers * 2.0 * tokens * hd * (
                          2 * heads + 2 * kv)},
        "ssd": {"ops": layers * chunks * a_chunk,
                "bytes": layers * tokens * (2.0 * (2 * inner + 2 * bc)
                                            + 4.0 * ssm_heads)},
        "convolution": {"ops": 2.0 * layers * tokens * mixed
                               * s["conv_taps"],
                        "bytes": layers * tokens * 2.0 * 2 * mixed},
        "feed_forward": {"ops": 2.0 * tokens * layers * ff_w,
                         "bytes": 2.0 * layers * ff_w},
        # untied: the embedding and the head are two matrices
        "head": {"ops": 2.0 * scored * head_w,
                 "bytes": 2.0 * head_w * 2 + 4.0 * tokens + 4.0 * scored},
    }
    return {"ops": sum(part["ops"] for part in parts.values()),
            "bytes": sum(part["bytes"] for part in parts.values()),
            "parts": parts}
