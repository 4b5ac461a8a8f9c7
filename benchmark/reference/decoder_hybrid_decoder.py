"""The plain reference of the program's `decoder_hybrid_decoder` family, as a
neural cell's configuration brings one (README, "Adding a neural cell"): a
causal decoder over token ids whose SECOND half reads what two of its layers
made (the SambaY shape of arXiv 2507.06607, HF `phi4flash`). LN is LayerNorm
with a weight and a bias, LN(x) = (x - mean) / sqrt(var + eps) * w + b; N
layers, N a multiple of 4, d_in the Mamba width:

    h = Embed[ids]
    for layer i:
        a = LN_i(h)
        h = h + Op_i(a)
        h = h + W_2 (silu(g) * u),  [g | u] = LN'_i(h) W_1     # first half the gate
    logits = LN_final(h) Embed^T                                # tied

    Op_i:  i < N/2, i even        Mamba
           i < N/2, i odd         differential attention, band of `window` keys
           i = N/2                Mamba; its scan output M is kept
           i = N/2 + 1            differential attention, causal; its K, V kept
           i >= N/2 + 2, i even   GMU:   W_2g (silu(a W_1g) * M)
           i >= N/2 + 2, i odd    differential attention, causal, q from a,
                                  K and V layer N/2 + 1's

    Mamba (Mamba-1, arXiv 2312.00752):
        [x | z] = a W_in;  x <- silu(conv(x) + b_conv)          depthwise, causal,
                                                                zero before the row
        [r | B | C] = x W_x;  dt = softplus(r W_dt + b_dt);  A = -exp(A_log)
        S_t[c,n] = exp(dt_t[c] A[c,n]) S_{t-1}[c,n] + dt_t[c] B_t[n] x_t[c]
        y_t[c]   = sum_n C_t[n] S_t[c,n] + D[c] x_t[c]          S zero before the row
        out = (y * silu(z)) W_out;   M = y  (before the gate)

    Differential attention (arXiv 2410.05258), layer index i:
        [q | k | v] = a W_qkv + b  (a cross layer: q = a W_q + b)
        heads 2j, 2j+1 a pair: q1_j, q2_j; k1_m, k2_m; query pair j reads key
        pair m = j // (query pairs / key pairs)
        P1 = softmax(q1 k1^T / sqrt(D)), P2 = softmax(q2 k2^T / sqrt(D))
        o_j = [P1 v_2m | P1 v_2m+1] - lambda [P2 v_2m | P2 v_2m+1]
        lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init,
        lambda_init = 0.8 - 0.6 exp(-0.3 i)
        o_j <- RMSNorm(o_j) w (1 - lambda_init);  out = [o_0 .. ] W_o + b_o

The fetched output: `token_logprobs[r, t]` = log_softmax(logits[r, t])[ids[r,
t + 1]], t = 0 .. length - 2.

The scan here is the RECURRENCE, a token at a time; the attention FOUR
softmax-weighted sums a pair, concatenated, as the published description has
them: not the chunked scan, the two forwards over a value twice as wide, or
the pair-major layout the program runs.

Where this departs from, or fills in, the text above and the catalog's row
(the configuration's `assumed` lists the same): state 16, 4 taps with a bias,
expand 2 and a step rank of ceil(d / 16) are the config class's defaults; no
bias on the Mamba projections; the differential form and lambda_init's depth
being the layer's index from 0; the pairing of heads 2j and 2j + 1; the band
read as `window` keys WITH the query's own; biases on W_qkv and W_o; the gate
W_1's first half; M before the gate; no positional encoding; no clamp on dt.

Nothing here imports the program or takes anything the program has made.
Plain `jax.numpy` in float32 at the highest matmul precision; a Python loop
over layers, each one call of a compiled program of its KIND that is handed
that layer's weights, what earlier layers kept and the residual stream, which
it DONATES and rewrites in place a block of `TOKEN_BLOCK` tokens at a time
(a row of 32768 tokens at hidden 2560 is 336 MB in float32; M is 671 MB, K and
V 336 MB). An attention layer makes the row's keys and values first, then
takes its queries a sub-block and a key pair at a time, a masked softmax over
all keys (a banded layer: over the keys its block's band can reach); the head
goes in blocks of `HEAD_BLOCK` tokens."""

from __future__ import annotations

import functools
import math

import numpy as np

BLOCK_TOKENS = 1 << 15          # tokens of a block of rows (one row at 32768)
TOKEN_BLOCK = 1 << 10           # tokens a row's layer pass rewrites at a time
FF_BLOCK = 1 << 9               # tokens of a block of a feed-forward
HEAD_BLOCK = 1 << 9             # tokens of a block of the head's logits
SCORE_BYTES = 1 << 27           # scores of a sub-block of queries, a key pair
FETCHES = ("token_logprobs", "logits", "hidden")
CHUNK = 128                     # tokens of a grid step of the program's scan
A_RANGE = (1.0, 16.0)           # A is drawn uniform here (Mamba-1: 1 .. N)
DT_RANGE = (1e-3, 1e-1)         # dt's bias log-uniform here, through the
                                # inverse softplus (Mamba-1's init)
SCAN_OPS = 6.0                  # a state cell a token: an exp and five more


def sizes(config: dict) -> dict:
    """The family's sizes from a configuration's `model` group."""
    m = config["model"]
    s = {k: int(m[k]) for k in (
        "num_layers", "d_model", "num_heads", "num_kv_heads", "mamba_inner",
        "mamba_state", "mamba_dt_rank", "window_size", "d_ff_dense",
        "vocab_size")}
    s["conv_taps"] = int(m.get("conv_taps", 4))
    s["layer_norm_eps"] = float(m.get("layer_norm_eps", 1e-5))
    if s["num_layers"] < 4 or s["num_layers"] % 4:
        raise ValueError("a decoder-hybrid-decoder has a multiple of 4 "
                         f"layers, not {s['num_layers']}")
    if s["d_model"] % s["num_heads"] or s["num_kv_heads"] % 2 or (
            s["num_heads"] % s["num_kv_heads"]):
        raise ValueError("the heads come in pairs, the key/value heads "
                         "divide the query heads and those the width")
    return s


def kinds(layers: int) -> tuple:
    """Layer i's operator, by the published rule."""
    half = layers // 2
    return tuple(
        ("mamba" if i % 2 == 0 else "sliding") if i < half
        else "mamba_keeps" if i == half
        else "full_keeps" if i == half + 1
        else "gmu" if i % 2 == 0 else "cross" for i in range(layers))


def lambda_init(i: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * i)


# a layer's arrays by its kind: name -> (shape from the sizes, std or a draw)
def _layer_shapes(s: dict, kind: str) -> dict:
    d, ff, inner = s["d_model"], s["d_ff_dense"], s["mamba_inner"]
    state, rank, taps = s["mamba_state"], s["mamba_dt_rank"], s["conv_taps"]
    hd = d // s["num_heads"]
    kv = s["num_kv_heads"] * hd
    shapes = {
        "ln_op_scale": ((d,), 0.1), "ln_op_bias": ((d,), 0.1),
        "ln_mlp_scale": ((d,), 0.1), "ln_mlp_bias": ((d,), 0.1),
        "w_1": ((d, 2 * ff), d ** -0.5), "w_2": ((ff, d), ff ** -0.5),
    }
    if kind.startswith("mamba"):
        shapes.update({
            "w_in": ((d, 2 * inner), d ** -0.5),
            "conv_w": ((inner, taps), taps ** -0.5),
            "conv_b": ((inner,), 0.2),
            "w_x": ((inner, rank + 2 * state), inner ** -0.5),
            "w_dt": ((rank, inner), 0.5 * rank ** -0.5),
            "dt_bias": ((inner,), "dt"),
            "a_log": ((inner, state), "a"),
            "d_skip": ((inner,), 0.1),
            "w_out": ((inner, d), inner ** -0.5)})
    elif kind == "gmu":
        shapes.update({"w_1g": ((d, inner), d ** -0.5),
                       "w_2g": ((inner, d), inner ** -0.5)})
    else:
        wide = d if kind == "cross" else d + 2 * kv
        shapes.update({
            "w_qkv": ((d, wide), d ** -0.5), "b_qkv": ((wide,), 0.1),
            "lq1": ((hd,), 0.1), "lk1": ((hd,), 0.1),
            "lq2": ((hd,), 0.1), "lk2": ((hd,), 0.1),
            "subln_scale": ((2 * hd,), 0.1),
            "w_o": ((d, d), d ** -0.5), "b_o": ((d,), 0.1)})
    return shapes


def weights(key, config: dict) -> dict:
    """Float32 weights on the device, one jitted call from the key: an array
    for the embedding and the final norm, and `layers`, a list with one dict
    a layer of that layer's own arrays (`_layer_shapes`: what a layer holds
    follows from its kind; a cross layer has `w_qkv` d x d, its queries'
    alone). A kernel is normal at 1/sqrt(fan in), so every pre-activation
    spreads like a unit normal, and so is the embedding, which is the head
    too (its logits then spread like a unit normal, as an untied head's); norm scales 1 + 0.1 n and their biases 0.1 n
    (a norm left out, in another's place or without its bias shows); the
    projections' biases 0.1 n; taps normal at 1/sqrt(taps), their bias at
    0.2. The scan's own are drawn as Mamba-1 initialises them: A uniform in
    `A_RANGE` (A_log its logarithm), dt's bias the inverse softplus of a
    log-uniform draw in `DT_RANGE` and the step's projection at half a unit
    normal's spread around it, so a channel's decay over a chunk of 128
    tokens spans forgetting everything to forgetting little and depends on
    the token; D normal around 1. The four lambda vectors normal at 0.1 (the
    published init), so lambda is near lambda_init and moves with them."""
    import jax
    import jax.numpy as jnp

    s = sizes(config)
    d = s["d_model"]
    layer_kinds = kinds(s["num_layers"])

    def draw(at, name, shape, std):
        if std == "a":
            return jnp.log(jax.random.uniform(at, shape, jnp.float32,
                                              *A_RANGE))
        if std == "dt":
            step = jnp.exp(jax.random.uniform(
                at, shape, jnp.float32, math.log(DT_RANGE[0]),
                math.log(DT_RANGE[1])))
            return step + jnp.log(-jnp.expm1(-step))
        drawn = std * jax.random.normal(at, shape, jnp.float32)
        centred = name.endswith("_scale") or name == "d_skip"
        return 1.0 + drawn if centred else drawn

    def some(at, shapes):
        return {name: draw(jax.random.fold_in(at, j), name, *shapes[name])
                for j, name in enumerate(sorted(shapes))}

    @jax.jit
    def make(key):
        out = some(jax.random.fold_in(key, 0), {
            "embed": ((s["vocab_size"], d), d ** -0.5),
            "ln_final_scale": ((d,), 0.1), "ln_final_bias": ((d,), 0.1)})
        out["layers"] = [
            some(jax.random.fold_in(key, 1 + i), _layer_shapes(s, kind))
            for i, kind in enumerate(layer_kinds)]
        return out

    return make(key)


def variables(w: dict, config: dict) -> dict:
    """The weights under the names the program's module takes (the
    parameter tree of `DecoderHybridDecoder`): the fused `w_qkv` split into
    q, k and v, `w_1` into gate and up."""
    s = sizes(config)
    d, ff = s["d_model"], s["d_ff_dense"]
    kv = s["num_kv_heads"] * (d // s["num_heads"])
    params = {"embed": {"embedding": w["embed"]},
              "ln_final": {"scale": w["ln_final_scale"],
                           "bias": w["ln_final_bias"]}}
    for i, kind in enumerate(kinds(s["num_layers"])):
        lw = w["layers"][i]
        for norm in ("ln_op", "ln_mlp"):
            params[f"{norm}_{i}"] = {"scale": lw[f"{norm}_scale"],
                                     "bias": lw[f"{norm}_bias"]}
        params[f"mlp_{i}"] = {"gate": {"kernel": lw["w_1"][:, :ff]},
                              "up": {"kernel": lw["w_1"][:, ff:]},
                              "down": {"kernel": lw["w_2"]}}
        if kind.startswith("mamba"):
            params[f"mamba_{i}"] = {
                "in_proj": {"kernel": lw["w_in"]},
                "conv_kernel": lw["conv_w"], "conv_bias": lw["conv_b"],
                "x_proj": {"kernel": lw["w_x"]},
                "dt_kernel": lw["w_dt"], "dt_bias": lw["dt_bias"],
                "A_log": lw["a_log"], "D": lw["d_skip"],
                "out_proj": {"kernel": lw["w_out"]}}
        elif kind == "gmu":
            params[f"gmu_{i}"] = {"in_proj": {"kernel": lw["w_1g"]},
                                  "out_proj": {"kernel": lw["w_2g"]}}
        else:
            tree = {"q_proj": {"kernel": lw["w_qkv"][:, :d],
                               "bias": lw["b_qkv"][:d]},
                    "lambda_q1": lw["lq1"], "lambda_k1": lw["lk1"],
                    "lambda_q2": lw["lq2"], "lambda_k2": lw["lk2"],
                    "norm_scale": lw["subln_scale"],
                    "out": {"kernel": lw["w_o"], "bias": lw["b_o"]}}
            if kind != "cross":
                tree["k_proj"] = {"kernel": lw["w_qkv"][:, d:d + kv],
                                  "bias": lw["b_qkv"][d:d + kv]}
                tree["v_proj"] = {"kernel": lw["w_qkv"][:, d + kv:],
                                  "bias": lw["b_qkv"][d + kv:]}
            name = "diff_swa" if kind == "sliding" else "diff_attn"
            params[f"{name}_{i}"] = tree
    return {"params": params}


def layer_norm(x, scale, bias, eps: float):
    import jax

    centred = x - x.mean(-1, keepdims=True)
    return centred * jax.lax.rsqrt(
        (centred * centred).mean(-1, keepdims=True) + eps) * scale + bias


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


def recurrence(x, dt, a, bm, cm, d_skip, state):
    """The selective scan a token at a time. x, dt (B, T, C); a (C, N); bm,
    cm (B, T, N); d_skip (C,); state (B, C, N) before the first token ->
    (y (B, T, C), the state after the last)."""
    import jax
    import jax.numpy as jnp

    def token(state, now):
        x_t, dt_t, b_t, c_t = now                # (B,C) (B,C) (B,N) (B,N)
        state = (jnp.exp(dt_t[..., None] * a) * state
                 + (dt_t * x_t)[..., None] * b_t[:, None, :])
        return state, (state * c_t[:, None, :]).sum(-1) + d_skip * x_t

    state, y = jax.lax.scan(token, state, tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, dt, bm, cm)))
    return jnp.moveaxis(y, 0, 1), state


def mamba(a, w: dict, s: dict, state, tail):
    """The Mamba mixer of a block of tokens. a (B, T, d), normed; `state`
    (B, C, N) and `tail` (B, taps - 1, C: the convolution's inputs of the
    tokens before the block) carried in -> (out (B, T, d), y (B, T, C): the
    scan's output before the gate, state, tail)."""
    import jax
    import jax.numpy as jnp

    t = a.shape[1]
    inner, n, rank = s["mamba_inner"], s["mamba_state"], s["mamba_dt_rank"]
    proj = a @ w["w_in"]
    x, z = proj[..., :inner], proj[..., inner:]
    padded = jnp.concatenate([tail, x], 1)
    conv = sum(w["conv_w"][:, j] * padded[:, j:j + t]
               for j in range(s["conv_taps"]))
    x = jax.nn.silu(conv + w["conv_b"])
    rbc = x @ w["w_x"]
    dt = jax.nn.softplus(rbc[..., :rank] @ w["w_dt"] + w["dt_bias"])
    y, state = recurrence(x, dt, -jnp.exp(w["a_log"]),
                          rbc[..., rank:rank + n], rbc[..., rank + n:],
                          w["d_skip"], state)
    return (y * jax.nn.silu(z)) @ w["w_out"], y, state, padded[:, t:]


def differential(a, k, v, w: dict, s: dict, first, index: int, window):
    """Differential attention of a block of queries at positions `first`
    onwards against the keys and values of the WHOLE row. a (B, T, d),
    normed; k, v (B, row, kv heads, D); `window`: None or the band."""
    import jax
    import jax.numpy as jnp

    b, t, d = a.shape
    heads, kv = s["num_heads"], s["num_kv_heads"]
    hd, row = d // heads, k.shape[1]
    pairs, key_pairs = heads // 2, kv // 2
    group = pairs // key_pairs
    q = (a @ w["w_qkv"][:, :d] + w["b_qkv"][:d]).reshape(
        b, t, key_pairs, group, 2, hd)
    init = lambda_init(index)
    lam = (jnp.exp(jnp.sum(w["lq1"] * w["lk1"]))
           - jnp.exp(jnp.sum(w["lq2"] * w["lk2"])) + init)
    # a banded block reads only the keys its band can reach
    span = row if window is None else min(row, t + window - 1)
    block = _divisor(t, max(1, SCORE_BYTES // (4 * 2 * b * group * span)))
    span = row if window is None else min(row, block + window - 1)
    k = k.reshape(b, row, key_pairs, 2, hd)
    v = v.reshape(b, row, key_pairs, 2, hd)

    def some_queries(xs):
        start, qb = xs                       # (B, block, key_pairs, g, 2, D)
        at = jnp.clip(start + block - span, 0, row - span)
        kpos = at + jnp.arange(span)
        behind = (start + jnp.arange(block))[:, None] - kpos[None, :]
        seen = behind >= 0
        if window is not None:
            seen = seen & (behind < window)
        kb, vb = (jax.lax.dynamic_slice_in_dim(x, at, span, 1)
                  for x in (k, v))

        def a_key_pair(ys):
            qm, km, vm = ys           # (B, block, g, 2, D), (B, span, 2, D)
            sums = []
            for softmax in (0, 1):
                scores = jnp.einsum("bqgc,btc->bgqt", qm[:, :, :, softmax],
                                    km[:, :, softmax]) * hd ** -0.5
                prob = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
                sums.append(jnp.concatenate(
                    [jnp.einsum("bgqt,btc->bqgc", prob, vm[:, :, half])
                     for half in (0, 1)], -1))
            o = sums[0] - lam * sums[1]                 # (B, block, g, 2 D)
            o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True)
                                  + s["layer_norm_eps"])
            return o * w["subln_scale"] * (1.0 - init)

        o = jax.lax.map(a_key_pair, tuple(
            jnp.moveaxis(x, 2, 0) for x in (qb, kb, vb)))
        return jnp.moveaxis(o, 0, 2).reshape(b, block, d)

    o = jax.lax.map(some_queries, (
        first + jnp.arange(0, t, block),
        jnp.moveaxis(q.reshape(b, t // block, block, *q.shape[2:]), 1, 0)))
    return jnp.moveaxis(o, 0, 1).reshape(b, t, d) @ w["w_o"] + w["b_o"]


def feed_forward(u, w: dict, s: dict):
    import jax

    ff = s["d_ff_dense"]
    gu = u @ w["w_1"]
    return (jax.nn.silu(gu[..., :ff]) * gu[..., ff:]) @ w["w_2"]


def _embed(embed, ids):
    return embed[ids]


def _layer(h, w: dict, kept, frozen_sizes: tuple, kind: str, index: int):
    """One layer over rows h (B, T, d), which is DONATED and rewritten a
    block of tokens at a time. `kept`: what this kind of layer reads of an
    earlier one (a "gmu": M (B, T, C); a "cross": (K, V)), else None. ->
    (h, what this layer keeps, or None)."""
    import jax
    import jax.numpy as jnp

    s = dict(frozen_sizes)
    b, t, d = h.shape
    eps = s["layer_norm_eps"]
    block = _divisor(t, TOKEN_BLOCK)
    inner = s["mamba_inner"]
    kv, hd = s["num_kv_heads"], d // s["num_heads"]

    def at(x, i):
        return jax.lax.dynamic_slice(
            x, (0, i * block, 0), (b, block, x.shape[-1]))

    def normed(hb):
        return layer_norm(hb, w["ln_op_scale"], w["ln_op_bias"], eps)

    def after(h, i, hb):
        """The block with its operator added -> the stream, the block's
        feed-forward added and the block written back."""
        u = layer_norm(hb, w["ln_mlp_scale"], w["ln_mlp_bias"], eps)
        ff = functools.partial(feed_forward, w=w, s=s)
        hb = hb + _in_blocks(ff, u.reshape(-1, d), FF_BLOCK).reshape(
            hb.shape)
        return jax.lax.dynamic_update_slice(h, hb, (0, i * block, 0))

    blocks = t // block
    if kind.startswith("mamba"):
        def one(i, carry):
            h, state, tail, memory = carry
            hb = at(h, i)
            out, y, state, tail = mamba(normed(hb), w, s, state, tail)
            if kind == "mamba_keeps":
                memory = jax.lax.dynamic_update_slice(
                    memory, y, (0, i * block, 0))
            return after(h, i, hb + out), state, tail, memory

        memory = jnp.zeros((b, t, inner) if kind == "mamba_keeps"
                           else (1, 1, 1), jnp.float32)
        h, _state, _tail, memory = jax.lax.fori_loop(0, blocks, one, (
            h, jnp.zeros((b, inner, s["mamba_state"]), jnp.float32),
            jnp.zeros((b, s["conv_taps"] - 1, inner), jnp.float32), memory))
        return h, memory if kind == "mamba_keeps" else None
    if kind == "gmu":
        def one(i, h):
            hb = at(h, i)
            gated = jax.nn.silu(normed(hb) @ w["w_1g"]) * at(kept, i)
            return after(h, i, hb + gated @ w["w_2g"])

        return jax.lax.fori_loop(0, blocks, one, h), None
    if kind == "cross":
        k, v = kept
    else:
        def block_keys(i):
            kvs = (normed(at(h, i)) @ w["w_qkv"][:, d:]
                   + w["b_qkv"][d:]).reshape(b, block, 2, kv, hd)
            return kvs[:, :, 0], kvs[:, :, 1]

        k, v = (jnp.moveaxis(x, 0, 1).reshape(b, t, kv, hd)
                for x in jax.lax.map(block_keys, jnp.arange(blocks)))
    window = s["window_size"] if kind == "sliding" else None

    def one(i, h):
        hb = at(h, i)
        return after(h, i, hb + differential(
            normed(hb), k, v, w, s, i * block, index, window))

    h = jax.lax.fori_loop(0, blocks, one, h)
    return h, (k, v) if kind == "full_keeps" else None


def _head(h, scale, bias, embed, ids, eps: float, fetch: str):
    """The fetched output from the stream after the last layer: the final
    norm, then the embedding, transposed, in blocks of tokens."""
    import jax
    import jax.numpy as jnp

    b, t, d = h.shape
    if fetch == "hidden":
        return layer_norm(h, scale, bias, eps)
    flat = h.reshape(b * t, d)
    if fetch == "logits":
        return (layer_norm(flat, scale, bias, eps) @ embed.T).reshape(
            b, t, -1)
    # the next token of every position but a row's last; the last scores a
    # target that is cut off below
    target = jnp.concatenate([ids[:, 1:], ids[:, :1]], 1).reshape(b * t)
    block = _divisor(b * t, HEAD_BLOCK)

    def one(xs):
        hb, tb = xs
        logp = jax.nn.log_softmax(
            layer_norm(hb, scale, bias, eps) @ embed.T, -1)
        return jnp.take_along_axis(logp, tb[:, None], -1)[:, 0]

    out = jax.lax.map(one, (flat.reshape(-1, block, d),
                            target.reshape(-1, block)))
    return out.reshape(b, t)[:, :t - 1]


_JIT = {"_embed": dict(),
        "_layer": dict(static_argnums=(3, 4, 5), donate_argnums=(0,)),
        "_head": dict(static_argnums=(5, 6))}


@functools.lru_cache(maxsize=None)
def _compiled(name: str):
    """One jitted function a name and a process, so that a second call at
    the same shapes (the next layer of a kind, another block of rows, the
    control, the next seed) traces nothing."""
    import jax

    return jax.jit(globals()[name], **_JIT[name])


def _forward(w: dict, ids, frozen_sizes: tuple, fetch: str):
    """The forward a LAYER at a time: one compiled program a KIND of layer,
    handed that layer's weights, what it reads of an earlier layer and the
    stream (one program over all layers carries every weight it closes over
    through its loops as a copy: a second float32 tree, which does not
    fit)."""
    s = dict(frozen_sizes)
    h = _compiled("_embed")(w["embed"], ids)
    memory = keys = None
    for i, kind in enumerate(kinds(s["num_layers"])):
        reads = memory if kind == "gmu" else keys if kind == "cross" else None
        # lambda_init is the only use of the index: a layer that has none
        # shares its kind's program
        index = i if kind in ("sliding", "full_keeps", "cross") else 0
        h, keeps = _compiled("_layer")(h, w["layers"][i], reads,
                                       frozen_sizes, kind, index)
        if kind == "mamba_keeps":
            memory = keeps
        elif kind == "full_keeps":
            keys = keeps
    return _compiled("_head")(h, w["ln_final_scale"], w["ln_final_bias"],
                              w["embed"], ids, s["layer_norm_eps"], fetch)


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
    a token meets; the head for the length - 1 positions that are scored.
    `diff_attn`: per (query, key) pair of a layer's mask (the causal
    TRIANGLE of a full or a cross layer, the BAND of a sliding one: a
    query's own key and the `window` - 1 before it) one multiply and one add
    a channel of every query head in the scores (heads x D) and of every
    softmax over its value, twice a head wide (heads x 2 D); its bytes the
    queries and the output read and written once a layer, the keys and the
    values once a layer that makes them: the layers that read another's
    add none. `selscan`: the recurrence's elementwise operations, `SCAN_OPS`
    a (token, channel, state) cell, whatever implements them, no product in
    it; x and y in the served type, dt in float32 and B and C, once a layer.
    The convolution is a memory pass. Bytes of the products: the weights
    read once at two bytes each, the ids read and the log-probabilities
    written. `parts` splits both, so that roofline readers divide by the
    same counts."""
    s = sizes(config)
    d, heads, kv = s["d_model"], s["num_heads"], s["num_kv_heads"]
    hd = d // heads
    inner, n, rank = s["mamba_inner"], s["mamba_state"], s["mamba_dt_rank"]
    window = s["window_size"]
    layer_kinds = kinds(s["num_layers"])
    layers = len(layer_kinds)
    mambas = sum(kind.startswith("mamba") for kind in layer_kinds)
    selfs = sum(kind in ("sliding", "full_keeps") for kind in layer_kinds)
    crosses, gmus = layer_kinds.count("cross"), layer_kinds.count("gmu")
    sliding = layer_kinds.count("sliding")
    mamba_w = d * 2 * inner + inner * (rank + 2 * n) + rank * inner \
        + inner * d
    self_w = d * (d + 2 * kv * hd) + d * d
    cross_w = 2 * d * d
    gmu_w = 2 * d * inner
    proj_w = (mambas * mamba_w + selfs * self_w + crosses * cross_w
              + gmus * gmu_w)
    ff_w = 3 * d * s["d_ff_dense"]
    head_w = d * s["vocab_size"]
    tokens = sum(length * rows for length, rows in lengths)
    scored = sum((length - 1) * rows for length, rows in lengths)
    triangle = sum(rows * length * (length + 1) / 2
                   for length, rows in lengths)

    def band(length):
        inside = min(window, length)
        return inside * (inside + 1) / 2 + (length - inside) * window

    banded = sum(rows * band(length) for length, rows in lengths)
    pairs = (layers - mambas - gmus - sliding) * triangle + sliding * banded
    parts = {
        "projections": {"ops": 2.0 * tokens * proj_w, "bytes": 2.0 * proj_w},
        "diff_attn": {
            "ops": 2.0 * pairs * heads * (hd + 2 * hd),
            "bytes": 2.0 * tokens * ((selfs + crosses) * 2 * d
                                     + selfs * 2 * kv * hd)},
        "selscan": {"ops": SCAN_OPS * mambas * tokens * inner * n,
                    "bytes": mambas * tokens * (
                        2.0 * 2 * inner + 4.0 * inner + 2.0 * 2 * n)},
        "convolution": {"ops": 2.0 * mambas * tokens * inner
                               * s["conv_taps"],
                        "bytes": mambas * tokens * 2.0 * 2 * inner},
        "feed_forward": {"ops": 2.0 * tokens * layers * ff_w,
                         "bytes": 2.0 * layers * ff_w},
        # tied: the embedding and the head are one matrix
        "head": {"ops": 2.0 * scored * head_w,
                 "bytes": 2.0 * head_w + 4.0 * tokens + 4.0 * scored},
    }
    return {"ops": sum(part["ops"] for part in parts.values()),
            "bytes": sum(part["bytes"] for part in parts.values()),
            "parts": parts}
