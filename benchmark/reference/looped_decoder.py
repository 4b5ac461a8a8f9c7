"""The plain reference of the program's `looped_decoder` family, as a neural
cell's configuration brings one (README, "Adding a neural cell"): a causal
decoder over token ids whose ONE stack of L layers is run T =
`total_ut_steps` times over the same weights (the Ouro block: "Scaling
Latent Reasoning via Looped Language Models", arXiv 2510.25741; HF
`modeling_ouro`). N are RMSNorms with their own scales, N(x) = x /
sqrt(mean(x^2) + eps) * w; every projection without bias; q =
`early_exit_threshold`:

    h_0 = Embed[ids]
    for t = 1 .. T:                                 # the SAME weights every t
        x = h_{t-1}
        for l = 1 .. L:
            a = x + N2_l( Attn_l( N1_l(x) ) )
            x = a + N4_l( FFN_l ( N3_l(a) ) )
        h_t = N_final(x)                            # step t + 1 reads h_t
        lambda_t = sigmoid( w_gate . h_t + b_gate ) # one number a token
    p_t = lambda_t prod_{j<t} (1 - lambda_j)  for t < T
    p_T = prod_{j<T} (1 - lambda_j)                 # what is left
    e   = T where q >= 1, else the first t with p_1 + .. + p_t >= q
    logits = h_e W_head                             # untied, per token

Attn: q, k, v = y W_q, y W_k, y W_v as heads of `head_dim` channels (query
head j reads key/value head j // (heads / kv heads); the published model
has as many of one as of the other), rotary positions 0 .. T_row - 1 over
the whole head in the rotate-half layout, the same in every step;
s[t, u] = q_t . k_u / sqrt(head_dim) for u <= t; softmax(s) v, heads
side by side, W_o. FFN(y) = down(silu(gate y) * up y). The fetched outputs:
`token_logprobs[r, t]` = log_softmax(logits[r, t])[ids[r, t + 1]], t = 0 ..
length - 2, and `exit_pdf[r, t]` = (p_1 .. p_T), which sums to 1.

Where this departs from, or fills in, the text above and the catalog's
row (the configuration's `assumed` lists the same):
- the catalog's row has no key for the four norms' places, the final
  norm's, the gate's form or any bias: they are as written above (no bias
  in q, k, v, o, gate, up, down; a bias in the exit gate), after the
  paper's description and the published modeling file, which could not be
  read again here (no network);
- no step's compute is skipped, whatever q is: a token that has left keeps
  being computed (its keys and values are what later tokens read), and
  only the state its logits are read from is chosen;
- p_T takes what is left even where the sum reached q before; the last
  step always counts as reaching q (a running sum of float32 that ends at
  0.99999994 still leaves at T);
- no norm on query or key heads; no scaling of the rotary frequencies
  (`rope_scaling` null).

Nothing here imports the program or takes anything the program has made.
Plain `jax.numpy` in float32 at the highest matmul precision; a Python
loop over steps and layers, each layer pass one call of ONE compiled
program that is handed that layer's weights and nothing else. Attention
is a masked softmax over ALL keys, a block of queries at a time (256
queries x 16 heads x 8192 keys of float32 scores, 134 MB), so that a row
of 8192 tokens fits beside the served model and the float32 tree; the
feed-forward and the head go in blocks of tokens. The largest program, a
layer over one row of 8192 at hidden 2048, is handed 273 MB (its layer's
weights, 206 MB, and the states) and holds 270 MB of its own besides; the
head's holds 101 MB (`tests/test_chipless_compile.py` compiles the layer
for the chip and holds it to that): the blocks are small because the
whole model's float32 tree and served copy leave 0.9 GB of the chip."""

from __future__ import annotations

import functools

import numpy as np

BLOCK_TOKENS = 1 << 13          # tokens of a block of rows (one row at 8192)
FF_BLOCK = 1 << 11              # tokens of a block of a feed-forward
HEAD_BLOCK = 1 << 9             # tokens of a block of the head's logits
SCORE_BYTES = 1 << 27           # attention scores of a block of queries
FETCHES = ("token_logprobs", "exit_pdf", "logits", "hidden")
GATE_BIAS_STD = 0.5


def sizes(config: dict) -> dict:
    """The family's sizes from a configuration's `model` group."""
    m = config["model"]
    s = {k: int(m[k]) for k in (
        "num_layers", "total_ut_steps", "d_model", "num_heads",
        "num_kv_heads", "head_dim", "d_ff_dense", "vocab_size")}
    s["early_exit_threshold"] = float(m.get("early_exit_threshold", 1.0))
    s["rms_norm_eps"] = float(m.get("rms_norm_eps", 1e-6))
    s["rope_theta"] = float(m.get("rope_theta", 1e6))
    if s["num_heads"] % s["num_kv_heads"]:
        raise ValueError("the key/value heads divide the query heads")
    return s


def weights(key, config: dict) -> dict:
    """Float32 weights on the device, one jitted call from the key: an
    array for the embedding, the head, the final norm and the gate, and
    for every other name a LIST with one array a layer. Kernels are normal
    at 1/sqrt(fan in), the embedding at 1, every RMSNorm's scale 1 + 0.1 n
    (not all ones: a norm left out or in another's place shows). A norm
    AFTER each operator makes the size of what a layer adds to the
    residual stream its scale and nothing else, so the kernels' spread
    cannot make 4 x L layer passes overflow: the stream grows like the
    square root of the layers passed and the final norm brings it back to
    1 every step. The exit gate's kernel is drawn at 1/sqrt(d) against a
    normed state of spread 1, so its logit spreads like a unit normal and
    lambda lies in (0.1, 0.9) for most tokens, not at 0 or 1; its bias at
    `GATE_BIAS_STD` (a bias left out shows)."""
    import jax
    import jax.numpy as jnp

    s = sizes(config)
    d, heads, kv, hd, ff = (s["d_model"], s["num_heads"], s["num_kv_heads"],
                            s["head_dim"], s["d_ff_dense"])
    layers = s["num_layers"]
    # name -> (layers, or None for a single array; shape; std)
    shapes = {
        "embed": (None, (s["vocab_size"], d), 1.0),
        "ln_attn_scale": (layers, (d,), 0.1),
        "wq": (layers, (d, heads, hd), d ** -0.5),
        "wk": (layers, (d, kv, hd), d ** -0.5),
        "wv": (layers, (d, kv, hd), d ** -0.5),
        "wo": (layers, (heads, hd, d), (heads * hd) ** -0.5),
        "ln_attn_post_scale": (layers, (d,), 0.1),
        "ln_mlp_scale": (layers, (d,), 0.1),
        "gate": (layers, (d, ff), d ** -0.5),
        "up": (layers, (d, ff), d ** -0.5),
        "down": (layers, (ff, d), ff ** -0.5),
        "ln_mlp_post_scale": (layers, (d,), 0.1),
        "ln_final_scale": (None, (d,), 0.1),
        "exit_kernel": (None, (d, 1), d ** -0.5),
        "exit_bias": (None, (1,), GATE_BIAS_STD),
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
    parameter tree of `LoopedDecoder`: the norm after an operator is the
    norm before it with `_post` in its name)."""
    s = sizes(config)
    params = {"embed": {"embedding": w["embed"]},
              "ln_final": {"scale": w["ln_final_scale"]},
              "exit_gate": {"kernel": w["exit_kernel"],
                            "bias": w["exit_bias"]},
              "head_kernel": w["head"]}
    for i in range(s["num_layers"]):
        params[f"ln_attn_{i}"] = {"scale": w["ln_attn_scale"][i]}
        params[f"gqa_attn_{i}"] = {"q_proj": {"kernel": w["wq"][i]},
                                   "k_proj": {"kernel": w["wk"][i]},
                                   "v_proj": {"kernel": w["wv"][i]},
                                   "out": {"kernel": w["wo"][i]}}
        params[f"ln_attn_post_{i}"] = {"scale": w["ln_attn_post_scale"][i]}
        params[f"ln_mlp_{i}"] = {"scale": w["ln_mlp_scale"][i]}
        params[f"mlp_{i}"] = {"gate": {"kernel": w["gate"][i]},
                              "up": {"kernel": w["up"][i]},
                              "down": {"kernel": w["down"][i]}}
        params[f"ln_mlp_post_{i}"] = {"scale": w["ln_mlp_post_scale"][i]}
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


def attention(y, w: dict, s: dict):
    """Causal attention. y: (B, T, d) -> (B, T, d); w: one layer's
    weights. A block of queries at a time against every key."""
    import jax
    import jax.numpy as jnp

    b, t, _d = y.shape
    heads, kv = s["num_heads"], s["num_kv_heads"]
    group = heads // kv
    q = rotary(jnp.einsum("btd,dhc->bthc", y, w["wq"]), s["rope_theta"])
    k = rotary(jnp.einsum("btd,dhc->bthc", y, w["wk"]), s["rope_theta"])
    v = jnp.einsum("btd,dhc->bthc", y, w["wv"])
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
    """fn over a (tokens, d) array a block of tokens at a time."""
    import jax

    n, d = flat.shape
    block = min(block, n)
    while n % block:
        block -= 1
    return jax.lax.map(fn, flat.reshape(n // block, block, d)).reshape(n, -1)


LAYER_NAMES = ("ln_attn_scale", "wq", "wk", "wv", "wo", "ln_attn_post_scale",
               "ln_mlp_scale", "gate", "up", "down", "ln_mlp_post_scale")


def layer_weights(w: dict, i: int) -> dict:
    """Layer i's own arrays, under the names of `weights`."""
    return {name: w[name][i] for name in LAYER_NAMES}


def exit_pdf(leave):
    """lambda_t, a list of T arrays (...) -> (..., T): p_t = lambda_t
    prod_{j<t} (1 - lambda_j), the last step taking what is left."""
    import jax.numpy as jnp

    left, out = jnp.ones_like(leave[0]), []
    for lam in leave[:-1]:
        out.append(lam * left)
        left = left * (1.0 - lam)
    return jnp.stack(out + [left], -1)


def exit_steps(pdf, threshold: float):
    """(..., T) exit distribution -> (...) int32, the step (from 0) each
    token leaves at: the last where the threshold is 1 or more, else the
    first whose running sum reaches it, the last where none does."""
    import jax.numpy as jnp

    last = pdf.shape[-1] - 1
    at, total = jnp.full(pdf.shape[:-1], last, jnp.int32), 0.0
    for t in range(last if threshold < 1 else 0):
        total = total + pdf[..., t]
        at = jnp.where((at == last) & (total >= threshold), t, at)
    return at


def _embed(embed, ids):
    return embed[ids]


def _layer(h, w: dict, frozen_sizes: tuple):
    """One layer: a = x + N2(Attn(N1(x))); a + N4(FFN(N3(a)))."""
    s = dict(frozen_sizes)
    d, eps = h.shape[-1], s["rms_norm_eps"]
    y = rms_norm(h, w["ln_attn_scale"], eps)
    h = h + rms_norm(attention(y, w, s), w["ln_attn_post_scale"], eps)
    y = rms_norm(h, w["ln_mlp_scale"], eps)
    ff = functools.partial(gated_ffn, gate=w["gate"], up=w["up"],
                           down=w["down"])
    out = _in_blocks(ff, y.reshape(-1, d), FF_BLOCK).reshape(y.shape)
    return h + rms_norm(out, w["ln_mlp_post_scale"], eps)


def _step_end(x, scale, kernel, bias, eps: float):
    """The end of a step -> (h_t, lambda_t): the final norm, inside the
    loop, and the exit gate on what it gives."""
    import jax

    h = rms_norm(x, scale, eps)
    return h, jax.nn.sigmoid((h @ kernel)[..., 0] + bias[0])


def _select(states, pdf, threshold: float):
    """Every step's h_t (a list) and the exit distribution -> each
    token's state at its exit step."""
    import jax.numpy as jnp

    at = exit_steps(pdf, threshold)
    return jnp.take_along_axis(jnp.stack(states), at[None, ..., None], 0)[0]


def _head(h, head, ids, fetch: str):
    """The fetched output from the states the tokens left with; `head`:
    (d, vocabulary)."""
    import jax
    import jax.numpy as jnp

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


_STATIC = {"_embed": (), "_layer": (2,), "_step_end": (4,), "exit_pdf": (),
           "_select": (2,), "_head": (3,)}


@functools.lru_cache(maxsize=None)
def _compiled(name: str):
    """One jitted function a name and a process, so that a second call at
    the same shapes (the next layer, the next step, another block of rows,
    the control, the next seed) traces nothing."""
    import jax

    return jax.jit(globals()[name], static_argnums=_STATIC[name])


def _steps(w: dict, ids, frozen_sizes: tuple):
    """The forward a LAYER PASS at a time: T x L calls of one compiled
    layer program, each handed that layer's weights and nothing else (one
    program over all layers carries every weight it closes over through
    its loops as a copy: a second float32 tree, which does not fit beside
    the first and the served model; PERF.md, PR 31). -> (h_T; every step's
    lambda_t; every step's h_t, kept only where a token may leave before
    the last)."""
    s = dict(frozen_sizes)
    early = s["early_exit_threshold"] < 1
    h = _compiled("_embed")(w["embed"], ids)
    states, leave = [], []
    for _t in range(s["total_ut_steps"]):
        for i in range(s["num_layers"]):
            h = _compiled("_layer")(h, layer_weights(w, i), frozen_sizes)
        h, lam = _compiled("_step_end")(
            h, w["ln_final_scale"], w["exit_kernel"], w["exit_bias"],
            s["rms_norm_eps"])
        leave.append(lam)
        if early:
            states.append(h)
    return h, leave, states


# the newest forward: (the head it was made with, the ids, the sizes) ->
# `_steps`' answer. A cell fetches both outputs, and the adapter asks for
# each by itself: the second finds the T x L layer passes made. The tree is
# told by its head, held weakly, so that a spent tree is not kept alive
_newest: list = []


def _forward(w: dict, ids, frozen_sizes: tuple, fetch: str):
    import weakref

    s = dict(frozen_sizes)
    rows = np.asarray(ids).tobytes()
    if _newest and _newest[0]() is w["head"] and _newest[1:3] == [
            rows, frozen_sizes]:
        h, leave, states = _newest[3]
    else:
        h, leave, states = _steps(w, ids, frozen_sizes)
        _newest[:] = [weakref.ref(w["head"]), rows, frozen_sizes,
                      (h, leave, states)]
    if fetch == "exit_pdf" or states:
        pdf = _compiled("exit_pdf")(leave)
        if fetch == "exit_pdf":
            return pdf
        h = _compiled("_select")(states, pdf, s["early_exit_threshold"])
    if fetch == "hidden":
        return h
    return _compiled("_head")(h, w["head"], ids, fetch)


def outputs(w: dict, config: dict, rows, fetch: str) -> np.ndarray:
    """The value of the fetched output `fetch` for `rows` ((n, length)
    token ids, one length), float64 on the host. Rows go through in blocks
    of at most `BLOCK_TOKENS` tokens (one row at 8192), as equal as the
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
    a token meets, EVERY LAYER T TIMES (a token passes each layer once a
    step) and the exit gate T times; per (query, key, channel) triple in
    the scores and in the weighted values, over all query heads, of the
    causal TRIANGLE, T times a layer too; the head ONCE, for the length -
    1 positions that are scored. Bytes: the weights read ONCE at two bytes
    each (the steps share them), the ids read, the log-probabilities and
    the exit distribution written; the attention's are the queries, the
    key and the value heads read and the output written once a LAYER
    PASS. `parts` splits both, so that roofline readers divide by the
    same counts."""
    s = sizes(config)
    d, heads, kv, hd = (s["d_model"], s["num_heads"], s["num_kv_heads"],
                        s["head_dim"])
    passes = s["num_layers"] * s["total_ut_steps"]
    attn_w = d * heads * hd + 2 * d * kv * hd + heads * hd * d
    ff_w = 3 * d * s["d_ff_dense"]
    head_w = d * s["vocab_size"]
    tokens = sum(length * n for length, n in lengths)
    scored = sum((length - 1) * n for length, n in lengths)
    triangle = sum(n * length * (length + 1) / 2 for length, n in lengths)
    moved = 2.0 * tokens * hd * (2 * heads + 2 * kv)       # q, k, v, out
    parts = {
        # the gate's one column with them: d weights a token a step
        "projections": {
            "ops": 2.0 * tokens * (passes * attn_w
                                   + s["total_ut_steps"] * d),
            "bytes": 2.0 * (s["num_layers"] * attn_w + d)},
        "attention": {"ops": 2.0 * passes * triangle * heads * (hd + hd),
                      "bytes": passes * moved},
        "feed_forward": {"ops": 2.0 * tokens * passes * ff_w,
                         "bytes": 2.0 * s["num_layers"] * ff_w},
        # untied: the embedding and the head are two matrices
        "head": {"ops": 2.0 * scored * head_w,
                 "bytes": 2.0 * head_w * 2 + 4.0 * tokens + 4.0 * scored
                          + 4.0 * tokens * s["total_ut_steps"]},
    }
    return {"ops": sum(p["ops"] for p in parts.values()),
            "bytes": sum(p["bytes"] for p in parts.values()),
            "parts": parts}
