"""The plain reference of the program's `transformer` family, as a neural
cell's configuration brings one (README, "Adding a neural cell"): a pre-LN
encoder over token ids with learned positions, full bidirectional softmax
attention, a tanh-GELU feed-forward, a final LayerNorm, mean pooling over
the positions and a linear head (Vaswani et al. 2017 with the pre-LN
ordering of Xiong et al. 2020). `configs/xlmr_xxl.json` serves it at the
widths of XLM-R XXL (Goyal et al. 2021), whose encoder layer is this one but
for the exact GELU and a LayerNorm epsilon of 1e-5; `tests/conftest.py` adds
two tiny cells of it.

Nothing here imports the program or takes anything the program has made.
Plain `jax.numpy` in float32 at the highest matmul precision; layers'
weights are stacked on a leading axis and the forward walks them."""

from __future__ import annotations

import functools

import numpy as np

LN_EPS = 1e-6                   # flax's LayerNorm default, the family's
SCORE_BYTES = 1 << 28           # a block's attention scores stay under this
BLOCK_TOKENS = 1 << 12          # and its tokens under this (the feed-forward's
#                                 float32 activations of a block: 0.27 GB at a
#                                 width of 16384)
FETCHES = ("logits", "probability", "pooled_features", "ln_final")


def _sizes(config: dict) -> tuple:
    m = config["model"]
    return (int(m["num_layers"]), int(m["d_model"]), int(m["num_heads"]),
            int(m["d_ff"]), int(m["num_outputs"]), int(m["vocab_size"]),
            int(m["max_len"]))


def weights(key, config: dict) -> dict:
    """Float32 weights on the device, one jitted call from the key. Kernels
    are normal at 1/sqrt(fan in), so that every layer moves the residual
    stream by about its own size; LayerNorm scales, biases and positions
    are drawn too (none at a value that would hide a term left out)."""
    import jax
    import jax.numpy as jnp

    layers, d, heads, ff, outputs, vocab, max_len = _sizes(config)
    hd = d // heads
    shapes = {
        "embed": ((vocab, d), 1.0), "pos": ((max_len, d), 0.3),
        "ln_attn_scale": ((layers, d), 0.1),
        "ln_attn_bias": ((layers, d), 0.1),
        "wq": ((layers, d, heads, hd), d ** -0.5),
        "wk": ((layers, d, heads, hd), d ** -0.5),
        "wv": ((layers, d, heads, hd), d ** -0.5),
        "bq": ((layers, heads, hd), 0.1), "bk": ((layers, heads, hd), 0.1),
        "bv": ((layers, heads, hd), 0.1),
        "wo": ((layers, heads, hd, d), d ** -0.5), "bo": ((layers, d), 0.1),
        "ln_mlp_scale": ((layers, d), 0.1), "ln_mlp_bias": ((layers, d), 0.1),
        "w_up": ((layers, d, ff), d ** -0.5), "b_up": ((layers, ff), 0.1),
        "w_down": ((layers, ff, d), ff ** -0.5), "b_down": ((layers, d), 0.1),
        "ln_final_scale": ((d,), 0.1), "ln_final_bias": ((d,), 0.1),
        "head": ((d, outputs), d ** -0.5), "head_bias": ((outputs,), 0.1),
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
    """The weights under the names the program's module takes (flax's
    parameter tree of `TransformerEncoder`)."""
    layers = _sizes(config)[0]
    params = {
        "embed": {"embedding": w["embed"]}, "pos_embed": w["pos"],
        "ln_final": {"scale": w["ln_final_scale"],
                     "bias": w["ln_final_bias"]},
        "head": {"kernel": w["head"], "bias": w["head_bias"]},
    }
    for i in range(layers):
        params[f"ln_attn_{i}"] = {"scale": w["ln_attn_scale"][i],
                                  "bias": w["ln_attn_bias"][i]}
        params[f"attn_{i}"] = {
            "query": {"kernel": w["wq"][i], "bias": w["bq"][i]},
            "key": {"kernel": w["wk"][i], "bias": w["bk"][i]},
            "value": {"kernel": w["wv"][i], "bias": w["bv"][i]},
            "out": {"kernel": w["wo"][i], "bias": w["bo"][i]}}
        params[f"ln_mlp_{i}"] = {"scale": w["ln_mlp_scale"][i],
                                 "bias": w["ln_mlp_bias"][i]}
        params[f"mlp_up_{i}"] = {"kernel": w["w_up"][i], "bias": w["b_up"][i]}
        params[f"mlp_down_{i}"] = {"kernel": w["w_down"][i],
                                   "bias": w["b_down"][i]}
    return {"params": params}


def _forward(w: dict, ids, layers: int, heads: int, fetch: str):
    import jax
    import jax.numpy as jnp

    def ln(x, scale, bias):
        mean = x.mean(-1, keepdims=True)
        var = ((x - mean) ** 2).mean(-1, keepdims=True)
        return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * scale + bias

    h = w["embed"][ids] + w["pos"][: ids.shape[1]][None]
    scale = (h.shape[-1] // heads) ** -0.5
    for i in range(layers):
        y = ln(h, w["ln_attn_scale"][i], w["ln_attn_bias"][i])
        q = jnp.einsum("btd,dhk->bthk", y, w["wq"][i]) + w["bq"][i]
        k = jnp.einsum("btd,dhk->bthk", y, w["wk"][i]) + w["bk"][i]
        v = jnp.einsum("btd,dhk->bthk", y, w["wv"][i]) + w["bv"][i]
        p = jax.nn.softmax(jnp.einsum("bqhk,bthk->bhqt", q, k) * scale, -1)
        o = jnp.einsum("bhqt,bthk->bqhk", p, v)
        h = h + jnp.einsum("bqhk,hkd->bqd", o, w["wo"][i]) + w["bo"][i]
        y = ln(h, w["ln_mlp_scale"][i], w["ln_mlp_bias"][i])
        y = jax.nn.gelu(y @ w["w_up"][i] + w["b_up"][i], approximate=True)
        h = h + y @ w["w_down"][i] + w["b_down"][i]
    h = ln(h, w["ln_final_scale"], w["ln_final_bias"])
    if fetch == "ln_final":
        return h
    pooled = h.mean(axis=1)
    if fetch == "pooled_features":
        return pooled
    logits = pooled @ w["head"] + w["head_bias"]
    return jax.nn.softmax(logits, -1) if fetch == "probability" else logits


@functools.lru_cache(maxsize=None)
def _jitted_forward():
    """One jitted forward a process, so that a second call at the same
    shapes (another length, the control, the next seed) traces nothing."""
    import jax

    return jax.jit(_forward, static_argnums=(2, 3, 4))


def outputs(w: dict, config: dict, rows, fetch: str) -> np.ndarray:
    """The value of the fetched output `fetch` for `rows` ((n, length)
    token ids, one length), float64 on the host. Rows go through in blocks
    that bound the attention scores' memory; a row's value depends on no
    other row."""
    import jax
    import jax.numpy as jnp

    if fetch not in FETCHES:
        raise ValueError(f"the reference knows the fetches {FETCHES}, not "
                         f"{fetch!r}")
    layers, _d, heads = _sizes(config)[:3]
    rows = np.asarray(rows)
    length = rows.shape[1]
    block = max(1, min(SCORE_BYTES // (4 * heads * length * length),
                       BLOCK_TOKENS // length))
    forward = _jitted_forward()
    out = []
    with jax.default_matmul_precision("highest"):
        for lo in range(0, len(rows), block):
            ids = jnp.asarray(rows[lo:lo + block], jnp.int32)
            out.append(np.asarray(forward(w, ids, layers, heads, fetch),
                                  np.float64))
    return np.concatenate(out)


def operations(config: dict, lengths) -> dict:
    """What scoring rows of the given lengths needs, from shapes alone:
    `lengths` is [(length, rows), ...]. One multiply and one add per weight
    of the four attention projections and the two feed-forward layers per
    token, per (query, key, channel) triple in the scores and in the
    weighted values, and per weight of the head per row; bytes are the
    weights read once at two bytes each, the ids read and the pooled row
    written."""
    layers, d, _heads, ff, outputs_, _vocab, _max_len = _sizes(config)
    per_token = 2.0 * layers * (4 * d * d + 2 * d * ff)
    ops = 0.0
    tokens = rows = 0
    for length, n in lengths:
        ops += n * (length * per_token + layers * 4.0 * length * length * d
                    + 2.0 * d * outputs_)
        tokens += n * length
        rows += n
    weights_ = layers * (4 * d * d + 2 * d * ff) + d * outputs_
    return {"ops": ops,
            "bytes": 2.0 * weights_ + 4.0 * tokens + 4.0 * rows * d}
