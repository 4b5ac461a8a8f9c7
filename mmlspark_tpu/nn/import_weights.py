"""External pretrained-weight ingestion: torch-layout state dicts -> flax.

Reference: the transfer-learning story rests on REAL pretrained models
pulled from a remote repo by `ModelDownloader` (ModelDownloader.scala:209+,
Schema.scala:30-119 — uri/hash/layerNames/inputNode) and cut at a layer by
`ImageFeaturizer` (ImageFeaturizer.scala:92-135). The CNTK-format model file
is the interchange artifact. Here the interchange artifact is the de-facto
standard for published CNN weights: a torch-style state dict (flat
name->tensor mapping, PyTorch/torchvision naming and layouts), shipped as
`.safetensors` or `.npz` — both readable without torch itself.

What the mapper translates (torchvision ResNet naming -> nn.models.ResNet):

  conv1.weight                 -> params/stem_conv/kernel   (OIHW -> HWIO)
  bn1.{weight,bias}            -> params/stem_bn/{scale,bias}
  bn1.running_{mean,var}       -> batch_stats/stem_bn/{mean,var}
  layer<L>.<B>.conv<N>.weight  -> params/stage<L-1>_block<B>/conv<N>/kernel
  layer<L>.<B>.bn<N>.*         -> params|batch_stats/.../bn<N>/*
  layer<L>.<B>.downsample.0.*  -> .../proj_conv/kernel
  layer<L>.<B>.downsample.1.*  -> .../proj_bn/*
  fc.{weight,bias}             -> params/head/{kernel,bias}  ((out,in) -> (in,out))

The result is validated leaf-for-leaf (path and shape) against the target
module's own `init` tree, so a wrong transpose or a missing block fails
loudly at import time, not silently at serving time.

Beyond the hand-written ResNet mapper, `MapRule`/`apply_mapping_spec`
define a DECLARATIVE mapping language (anchored regex -> target path +
layout transform) so new checkpoint families are a rule table, not a new
parser; `TRANSFORMER_SPEC` maps HF-style flat encoder state dicts
(`encoder.layer.<i>.attention.self.query.weight`, torch (out,in) layouts)
onto nn.models.TransformerEncoder. Note the architecture here is pre-LN
(ln before attention/mlp, final ln before pooling): checkpoints from
post-LN models (original BERT) carry the same tensor NAMES but different
math — importing one gives a well-formed model that is not
weight-equivalent to its source. The spec documents naming + layout, not
architectural equivalence.

`MLA_MOE_DECODER_SPEC` maps the `deepseek_v3` checkpoint naming (latent
attention: `q_proj`, `kv_a_proj_with_mqa`, `kv_a_layernorm`, `kv_b_proj`,
`o_proj`; routed experts: `mlp.gate.weight`,
`mlp.gate.e_score_correction_bias`, `mlp.experts.<n>.*`,
`mlp.shared_experts.*`) onto nn.models.MLAMoEDecoder, and here the math IS
the checkpoint's but for one layout: those checkpoints store a head's
rotary channels interleaved (x0, y0, x1, y1, ...), the module rotates
halves (x0, x1, ..., y0, y1, ...), so the rope columns of `q_proj` and of
`kv_a_proj_with_mqa` are permuted at import (scores are unchanged: queries
and keys are permuted alike).

`HYBRID_MOE_DECODER_SPEC` maps the `lfm2_moe` checkpoint naming
(`operator_norm`, `ffn_norm`; `conv.in_proj` / `conv.conv` /
`conv.out_proj`; `self_attn.q_proj` .. `out_proj` with `q_layernorm` /
`k_layernorm`; `feed_forward.w1` / `w3` / `w2` dense, and
`feed_forward.gate`, `expert_bias`, `experts.<n>.w1` / `w3` / `w2` routed;
`embedding_norm`) onto nn.models.HybridMoEDecoder. Those checkpoints
rotate halves already and tie the head to the embedding: nothing is
permuted, and an `lm_head.weight` is the embedding written twice.

`EVA_DECODER_SPEC` maps the `evabyte` checkpoint naming (`q_proj`,
`k_proj`, `v_proj`, `o_proj`, `adaptive_phi`, `adaptive_mu_k`;
`gate_proj`, `up_proj`, `down_proj`; `input_layernorm`,
`post_attention_layernorm`, `norm`; `embed_tokens`, `lm_head`) onto
nn.models.EvaDecoder. Those checkpoints multiply a norm by 1 + w
(`norm_add_unit_offset`): 1 is added to every norm's weight at import,
and the module's scale is what multiplies.

`window_moe_decoder_spec(layer_types)` maps the `smallthinker` checkpoint
naming (`self_attn.q_proj` .. `o_proj`; `block_sparse_moe.primary_router`
and `block_sparse_moe.experts.<n>.gate` / `up` / `down`;
`input_layernorm`, `post_attention_layernorm`, `norm`; `embed_tokens`,
`lm_head`) onto nn.models.WindowMoEDecoder: a layer's attention under
`gqa_attn_<i>` (a global layer) or `swa_attn_<i>` (a sliding one), its
router apart from its experts under `router_<i>`. Nothing is permuted.

`LOOPED_DECODER_SPEC` maps the `ouro` checkpoint naming (`self_attn.q_proj`
.. `o_proj`; `mlp.gate_proj` / `up_proj` / `down_proj`; FOUR norms a layer,
`input_layernorm` and `input_layernorm_2` before and after the attention,
`post_attention_layernorm` and `post_attention_layernorm_2` before and
after the feed-forward; `norm`; `early_exit_gate` with its bias;
`embed_tokens`, `lm_head`) onto nn.models.LoopedDecoder: one set of layers
for all `total_ut_steps` steps. Nothing is permuted.

`SSM_HYBRID_DECODER_SPEC` maps the `falcon_h1` checkpoint naming (a layer's
state-space mixer under `mamba.`: `in_proj` with its columns [z | x B C |
dt] as they lie, `conv1d.weight` (channels, 1, taps) and `conv1d.bias`,
`dt_bias`, `A_log`, `D`, `norm`, `out_proj`; `self_attn.q_proj` ..
`o_proj`; `feed_forward.gate_proj` / `up_proj` / `down_proj`;
`input_layernorm`, `pre_ff_layernorm`, `final_layernorm`; `embed_tokens`,
`lm_head`) onto nn.models.SSMHybridDecoder: a layer's mixers under
`ssm_<i>` and `gqa_attn_<i>`. The family's fixed multipliers are the
module's configuration, not weights (`mup_vector` is dropped). Nothing is
permuted.

`decoder_hybrid_decoder_spec` maps a `phi4flash`-style naming onto
nn.models.DecoderHybridDecoder. The checkpoint's own tensor names could not
be re-read when this was written (no network): the names EXPECTED are listed
in `torch_decoder_hybrid_decoder_to_flax` as assumed, every layer's operator
under `attn.` whatever its kind, and what a name means follows from the
layer's index (`models.hybrid_layer_kinds`). A fused `Wqkv` is split into q,
k and v and the feed-forward's `fc1` into gate and up. No published file has
been loaded through it.
"""

from __future__ import annotations

import os
import re
from typing import Any, Callable, Mapping, NamedTuple

import numpy as np

__all__ = [
    "load_state_dict",
    "torch_resnet_to_flax",
    "import_torch_resnet",
    "MapRule",
    "apply_mapping_spec",
    "TRANSFORMER_SPEC",
    "torch_transformer_to_flax",
    "import_torch_transformer",
    "MLA_MOE_DECODER_SPEC",
    "torch_mla_moe_decoder_to_flax",
    "import_torch_mla_moe_decoder",
    "HYBRID_MOE_DECODER_SPEC",
    "torch_hybrid_moe_decoder_to_flax",
    "import_torch_hybrid_moe_decoder",
    "window_moe_decoder_spec",
    "torch_window_moe_decoder_to_flax",
    "import_torch_window_moe_decoder",
    "LOOPED_DECODER_SPEC",
    "torch_looped_decoder_to_flax",
    "import_torch_looped_decoder",
    "SSM_HYBRID_DECODER_SPEC",
    "torch_ssm_hybrid_decoder_to_flax",
    "import_torch_ssm_hybrid_decoder",
    "decoder_hybrid_decoder_spec",
    "torch_decoder_hybrid_decoder_to_flax",
    "import_torch_decoder_hybrid_decoder",
    "import_external_weights",
    "IMPORTERS",
]


def load_state_dict(path: str) -> dict[str, np.ndarray]:
    """Read a flat name->array state dict from `.safetensors` or `.npz`.

    Both formats are readable with numpy-only code paths (safetensors via
    its numpy loader), so importing published weights needs no torch
    runtime — the analogue of the reference reading CNTK model bytes
    without the training toolchain (SerializableFunction.scala:85+)."""
    ext = os.path.splitext(path)[1].lower()
    if ext == ".safetensors":
        from safetensors.numpy import load_file

        return dict(load_file(path))
    if ext == ".npz":
        with np.load(path) as z:
            return {k: z[k] for k in z.files}
    raise ValueError(
        f"unsupported weight format {ext!r}; expected .safetensors or .npz"
    )


_LAYER_RE = re.compile(
    r"^layer(?P<stage>\d+)\.(?P<block>\d+)\.(?P<rest>.+)$"
)


def _assign(tree: dict, path: tuple[str, ...], value: np.ndarray) -> None:
    node = tree
    for p in path[:-1]:
        node = node.setdefault(p, {})
    node[path[-1]] = value


def _conv_kernel(w: np.ndarray) -> np.ndarray:
    """torch conv weight OIHW -> flax HWIO."""
    if w.ndim != 4:
        raise ValueError(f"conv weight must be 4-D, got {w.shape}")
    return np.transpose(w, (2, 3, 1, 0))


def _map_bn(rest: str, prefix: tuple[str, ...], value, params, batch_stats,
            bn_name: str) -> bool:
    leaf = rest.split(".")[-1]
    if leaf == "weight":
        _assign(params, prefix + (bn_name, "scale"), value)
    elif leaf == "bias":
        _assign(params, prefix + (bn_name, "bias"), value)
    elif leaf == "running_mean":
        _assign(batch_stats, prefix + (bn_name, "mean"), value)
    elif leaf == "running_var":
        _assign(batch_stats, prefix + (bn_name, "var"), value)
    elif leaf == "num_batches_tracked":
        return True                                  # torch-only bookkeeping
    else:
        return False
    return True


def torch_resnet_to_flax(
    state_dict: Mapping[str, np.ndarray],
) -> dict[str, Any]:
    """Map a torchvision-style ResNet state dict to nn.models.ResNet
    variables ({"params": ..., "batch_stats": ...}). Raises ValueError on
    any unrecognized key — silent drops are how transposed/missing weights
    slip through to produce garbage activations."""
    params: dict[str, Any] = {}
    batch_stats: dict[str, Any] = {}
    for name, value in state_dict.items():
        value = np.asarray(value)
        if name == "conv1.weight":
            _assign(params, ("stem_conv", "kernel"), _conv_kernel(value))
            continue
        if name.startswith("bn1."):
            if _map_bn(name, (), value, params, batch_stats, "stem_bn"):
                continue
            raise ValueError(f"unrecognized stem bn key {name!r}")
        if name == "fc.weight":
            _assign(params, ("head", "kernel"), np.transpose(value, (1, 0)))
            continue
        if name == "fc.bias":
            _assign(params, ("head", "bias"), value)
            continue
        m = _LAYER_RE.match(name)
        if m is None:
            raise ValueError(f"unrecognized state-dict key {name!r}")
        stage = int(m.group("stage")) - 1            # torch layer1 -> stage0
        block = f"stage{stage}_block{int(m.group('block'))}"
        rest = m.group("rest")
        cm = re.match(r"^conv(\d+)\.weight$", rest)
        if cm:
            _assign(params, (block, f"conv{cm.group(1)}", "kernel"),
                    _conv_kernel(value))
            continue
        bm = re.match(r"^bn(\d+)\.(.+)$", rest)
        if bm and _map_bn(rest, (block,), value, params, batch_stats,
                          f"bn{bm.group(1)}"):
            continue
        dm = re.match(r"^downsample\.(\d)\.(.+)$", rest)
        if dm:
            if dm.group(1) == "0" and dm.group(2) == "weight":
                _assign(params, (block, "proj_conv", "kernel"),
                        _conv_kernel(value))
                continue
            if dm.group(1) == "1" and _map_bn(
                rest, (block,), value, params, batch_stats, "proj_bn"
            ):
                continue
        raise ValueError(f"unrecognized state-dict key {name!r}")
    return {"params": params, "batch_stats": batch_stats}


# --------------------------------------------------------------------- #
# declarative mapping specs                                             #
# --------------------------------------------------------------------- #


class MapRule(NamedTuple):
    """One mapping rule: `pattern` is an anchored regex over state-dict
    keys; `target` is a '/'-joined destination path whose FIRST segment
    names the collection (params | batch_stats), either a template string
    (regex group expansion via m.expand) or a callable(match) -> str;
    None drops the tensor (framework-only bookkeeping). `transform`
    (value, ctx) -> value converts torch layouts to flax (ctx carries
    model config the shapes alone can't determine, e.g. num_heads)."""

    pattern: str
    target: "str | Callable | None"
    transform: "Callable[[np.ndarray, dict], np.ndarray] | None" = None


def apply_mapping_spec(
    state_dict: Mapping[str, np.ndarray],
    rules: "list[MapRule]",
    ctx: "dict | None" = None,
) -> dict[str, Any]:
    """Run a rule table over a flat state dict -> flax variables.

    First matching rule wins; a key no rule matches raises (silent drops
    are how transposed/missing weights slip through to garbage
    activations — same contract as the hand-written ResNet mapper)."""
    ctx = ctx or {}
    compiled = [(re.compile(r.pattern), r) for r in rules]
    out: dict[str, Any] = {"params": {}, "batch_stats": {}}
    for name, value in state_dict.items():
        for cre, rule in compiled:
            m = cre.fullmatch(name)
            if m is None:
                continue
            if rule.target is None:
                break
            target = (rule.target(m) if callable(rule.target)
                      else m.expand(rule.target))
            path = tuple(target.split("/"))
            if path[0] not in out:
                raise ValueError(
                    f"rule for {name!r} targets unknown collection {path[0]!r}"
                )
            v = np.asarray(value)
            if rule.transform is not None:
                v = rule.transform(v, ctx)
            _assign(out[path[0]], path[1:], v)
            break
        else:
            raise ValueError(f"unrecognized state-dict key {name!r}")
    return out


def _t_transpose(v, ctx):
    """torch Dense (out, in) -> flax (in, out)."""
    return np.transpose(v, (1, 0))


def _t_qkv_kernel(v, ctx):
    """torch (D, D) projection -> flax MHA DenseGeneral (D, H, D//H)."""
    d_model, h = v.shape[1], ctx["num_heads"]
    return np.transpose(v, (1, 0)).reshape(d_model, h, v.shape[0] // h)


def _t_qkv_bias(v, ctx):
    h = ctx["num_heads"]
    return v.reshape(h, v.shape[0] // h)


def _t_attn_out_kernel(v, ctx):
    """torch (D_out, D_in) output projection -> flax (H, D_in//H, D_out)."""
    h = ctx["num_heads"]
    return np.transpose(v, (1, 0)).reshape(h, v.shape[1] // h, v.shape[0])


# HF-style flat naming for a PRE-LN encoder (see module docstring for the
# post-LN caveat): attention.ln / mlp.ln are the pre-attention and pre-mlp
# layer norms, final_layer_norm closes the stack, classifier is the head.
TRANSFORMER_SPEC: "list[MapRule]" = [
    MapRule(r"embeddings\.word_embeddings\.weight", "params/embed/embedding"),
    MapRule(r"embeddings\.position_embeddings\.weight", "params/pos_embed"),
    MapRule(r"stem\.weight", "params/stem/kernel", _t_transpose),
    MapRule(r"stem\.bias", "params/stem/bias"),
    MapRule(r"encoder\.layer\.(?P<i>\d+)\.attention\.ln\.weight",
            r"params/ln_attn_\g<i>/scale"),
    MapRule(r"encoder\.layer\.(?P<i>\d+)\.attention\.ln\.bias",
            r"params/ln_attn_\g<i>/bias"),
    MapRule(r"encoder\.layer\.(?P<i>\d+)\.attention\.self\."
            r"(?P<proj>query|key|value)\.weight",
            r"params/attn_\g<i>/\g<proj>/kernel", _t_qkv_kernel),
    MapRule(r"encoder\.layer\.(?P<i>\d+)\.attention\.self\."
            r"(?P<proj>query|key|value)\.bias",
            r"params/attn_\g<i>/\g<proj>/bias", _t_qkv_bias),
    MapRule(r"encoder\.layer\.(?P<i>\d+)\.attention\.output\.dense\.weight",
            r"params/attn_\g<i>/out/kernel", _t_attn_out_kernel),
    MapRule(r"encoder\.layer\.(?P<i>\d+)\.attention\.output\.dense\.bias",
            r"params/attn_\g<i>/out/bias"),
    MapRule(r"encoder\.layer\.(?P<i>\d+)\.mlp\.ln\.weight",
            r"params/ln_mlp_\g<i>/scale"),
    MapRule(r"encoder\.layer\.(?P<i>\d+)\.mlp\.ln\.bias",
            r"params/ln_mlp_\g<i>/bias"),
    MapRule(r"encoder\.layer\.(?P<i>\d+)\.intermediate\.dense\.weight",
            r"params/mlp_up_\g<i>/kernel", _t_transpose),
    MapRule(r"encoder\.layer\.(?P<i>\d+)\.intermediate\.dense\.bias",
            r"params/mlp_up_\g<i>/bias"),
    MapRule(r"encoder\.layer\.(?P<i>\d+)\.output\.dense\.weight",
            r"params/mlp_down_\g<i>/kernel", _t_transpose),
    MapRule(r"encoder\.layer\.(?P<i>\d+)\.output\.dense\.bias",
            r"params/mlp_down_\g<i>/bias"),
    MapRule(r"final_layer_norm\.weight", "params/ln_final/scale"),
    MapRule(r"final_layer_norm\.bias", "params/ln_final/bias"),
    MapRule(r"classifier\.weight", "params/head/kernel", _t_transpose),
    MapRule(r"classifier\.bias", "params/head/bias"),
    MapRule(r".*\.num_batches_tracked", None),
]


def torch_transformer_to_flax(
    state_dict: Mapping[str, np.ndarray], num_heads: int,
) -> dict[str, Any]:
    """Map an HF-style flat encoder state dict onto
    nn.models.TransformerEncoder variables. num_heads is required: the
    fused (D, D) projection shapes cannot determine the head split."""
    return apply_mapping_spec(
        state_dict, TRANSFORMER_SPEC, {"num_heads": int(num_heads)}
    )


def _tree_leaves(tree: Any, prefix: str = "") -> dict[str, tuple[int, ...]]:
    out: dict[str, tuple[int, ...]] = {}
    if isinstance(tree, Mapping):
        for k, v in tree.items():
            out.update(_tree_leaves(v, f"{prefix}/{k}" if prefix else str(k)))
        return out
    out[prefix] = tuple(np.shape(tree))
    return out


def import_torch_resnet(
    path: str,
    architecture: str = "resnet50",
    num_outputs: int | None = None,
    input_shape: tuple[int, ...] = (224, 224, 3),
    preprocess: dict | None = None,
    class_labels=None,
    **config,
):
    """Load torch-layout ResNet weights into a ready-to-serve ModelBundle.

    The imported tree is validated leaf-for-leaf against the architecture's
    own init tree: every path must exist on both sides with the same shape.
    `num_outputs` defaults to the checkpoint's fc row count."""
    import jax.numpy as jnp

    from .models import ModelBundle

    sd = load_state_dict(path)
    variables = torch_resnet_to_flax(sd)
    if num_outputs is None:
        fc = sd.get("fc.weight")
        if fc is None:
            raise ValueError("state dict has no fc.weight; pass num_outputs")
        num_outputs = int(np.asarray(fc).shape[0])

    bundle = ModelBundle.init(
        architecture, input_shape=tuple(input_shape), seed=0,
        class_labels=class_labels,
        preprocess=dict(
            preprocess
            if preprocess is not None
            # torchvision ImageNet normalization, scaled to 0-255 inputs
            else {"mean": [123.675, 116.28, 103.53],
                  "std": [58.395, 57.12, 57.375]}
        ),
        num_outputs=int(num_outputs), **config,
    )
    return _validate_and_install(bundle, variables, architecture)


def _validate_and_install(bundle, variables, architecture: str):
    """Leaf-for-leaf validation against the architecture's own init tree
    (every path present on both sides, same shape), then install the
    imported arrays as float32 device arrays. Shared by every importer so
    a new family can't skip the check."""
    import jax.numpy as jnp

    want = _tree_leaves(bundle.variables)
    got = _tree_leaves(variables)
    missing = sorted(set(want) - set(got))
    extra = sorted(set(got) - set(want))
    mis = [k for k in want if k in got and want[k] != got[k]]
    if missing or extra or mis:
        detail = "; ".join(
            filter(None, [
                f"missing {missing[:5]}" if missing else "",
                f"unexpected {extra[:5]}" if extra else "",
                f"shape mismatch {[ (k, got[k], want[k]) for k in mis[:5] ]}"
                if mis else "",
            ])
        )
        raise ValueError(f"imported weights do not fit {architecture}: {detail}")
    bundle.variables = {
        k: _as_jnp(variables.get(k, {}), jnp) for k in bundle.variables
    }
    return bundle


def import_torch_transformer(
    path: str,
    architecture: str = "transformer",
    num_outputs: int | None = None,
    input_shape: tuple[int, ...] = (),
    preprocess: dict | None = None,
    class_labels=None,
    **config,
):
    """Load HF-style flat encoder weights into a ready-to-serve
    ModelBundle (the second imported family next to ResNet; reference
    parity anchor: ModelDownloader ingesting arbitrary published models,
    Schema.scala:30-119).

    Model dimensions are inferred from the checkpoint where shapes
    determine them (d_model/vocab_size from the embedding, num_layers
    from the layer indexes, d_ff from the mlp width, max_len from the
    position table, num_outputs from the classifier); `num_heads` cannot
    be inferred and must come from config (default 4)."""
    sd = load_state_dict(path)
    cfg = dict(config)
    emb = sd.get("embeddings.word_embeddings.weight")
    stem = sd.get("stem.weight")
    if emb is not None:
        cfg.setdefault("vocab_size", int(emb.shape[0]))
        cfg.setdefault("d_model", int(emb.shape[1]))
    elif stem is not None:
        cfg.setdefault("vocab_size", 0)
        cfg.setdefault("d_model", int(stem.shape[0]))
    else:
        raise ValueError(
            "state dict has neither embeddings.word_embeddings.weight nor "
            "stem.weight; not an encoder checkpoint this spec understands"
        )
    layer_ids = [
        int(m.group(1)) for m in
        (re.match(r"encoder\.layer\.(\d+)\.", k) for k in sd)
        if m is not None
    ]
    if not layer_ids:
        raise ValueError("state dict has no encoder.layer.<i> tensors")
    cfg.setdefault("num_layers", max(layer_ids) + 1)
    up0 = sd.get("encoder.layer.0.intermediate.dense.weight")
    if up0 is not None:
        cfg.setdefault("d_ff", int(up0.shape[0]))
    pos = sd.get("embeddings.position_embeddings.weight")
    if pos is not None:
        cfg.setdefault("max_len", int(pos.shape[0]))
    if num_outputs is None:
        head = sd.get("classifier.weight")
        if head is None:
            raise ValueError("state dict has no classifier.weight; "
                             "pass num_outputs")
        num_outputs = int(head.shape[0])
    cfg.setdefault("num_heads", 4)
    if cfg["d_model"] % cfg["num_heads"]:
        raise ValueError(
            f"d_model {cfg['d_model']} is not divisible by num_heads "
            f"{cfg['num_heads']}"
        )
    variables = torch_transformer_to_flax(sd, num_heads=cfg["num_heads"])

    from .models import ModelBundle

    if not input_shape:
        # one token position is enough to trace init; the pos table is
        # sized by max_len, not by the probe length
        input_shape = (8,) if cfg.get("vocab_size") else (8, 1)
    bundle = ModelBundle.init(
        architecture, input_shape=tuple(input_shape), seed=0,
        class_labels=class_labels, preprocess=dict(preprocess or {}),
        num_outputs=int(num_outputs), **cfg,
    )
    return _validate_and_install(bundle, variables, architecture)


# --------------------------------------------------------------------- #
# deepseek_v3 naming -> nn.models.MLAMoEDecoder                          #
# --------------------------------------------------------------------- #

def _rope_to_halves(rope: int) -> np.ndarray:
    """Where each channel of the rotate-half layout lies in the
    interleaved one: (x0, y0, x1, y1, ...) -> (x0, x1, ..., y0, y1, ...)."""
    return np.concatenate([np.arange(0, rope, 2), np.arange(1, rope, 2)])


def _t_mla_q_kernel(v, ctx):
    """torch (H * (nope + rope), D) -> (D, H, nope + rope), a head's rope
    channels from interleaved to halves."""
    h, nope, rope = (ctx["num_heads"], ctx["qk_nope_head_dim"],
                     ctx["qk_rope_head_dim"])
    w = np.transpose(v, (1, 0)).reshape(v.shape[1], h, nope + rope)
    return np.concatenate(
        [w[..., :nope], w[..., nope:][..., _rope_to_halves(rope)]], -1)


def _t_mla_kv_a_kernel(v, ctx):
    """torch (latent + rope, D) -> (D, latent + rope), the one rotary
    key's channels from interleaved to halves."""
    lat, rope = ctx["kv_lora_rank"], ctx["qk_rope_head_dim"]
    w = np.transpose(v, (1, 0))
    return np.concatenate(
        [w[:, :lat], w[:, lat:][:, _rope_to_halves(rope)]], -1)


def _t_mla_kv_b_kernel(v, ctx):
    """torch (H * (nope + v), latent) -> (latent, H, nope + v)."""
    h = ctx["num_heads"]
    return np.transpose(v, (1, 0)).reshape(v.shape[1], h, v.shape[0] // h)


_LAYER = r"model\.layers\.(?P<i>\d+)\."
_FFN = r"(?P<proj>gate|up|down)_proj\.weight"
# `experts_<proj>/<n>` are one expert's matrices; `torch_mla_moe_decoder_
# to_flax` stacks the experts held into the module's (held, in, out) arrays
MLA_MOE_DECODER_SPEC: "list[MapRule]" = [
    MapRule(r"model\.embed_tokens\.weight", "params/embed/embedding"),
    MapRule(_LAYER + r"input_layernorm\.weight",
            r"params/ln_attn_\g<i>/scale"),
    MapRule(_LAYER + r"self_attn\.q_proj\.weight",
            r"params/mla_attn_\g<i>/q_proj/kernel", _t_mla_q_kernel),
    MapRule(_LAYER + r"self_attn\.kv_a_proj_with_mqa\.weight",
            r"params/mla_attn_\g<i>/kv_a_proj/kernel", _t_mla_kv_a_kernel),
    MapRule(_LAYER + r"self_attn\.kv_a_layernorm\.weight",
            r"params/mla_attn_\g<i>/kv_a_norm/scale"),
    MapRule(_LAYER + r"self_attn\.kv_b_proj\.weight",
            r"params/mla_attn_\g<i>/kv_b_proj/kernel", _t_mla_kv_b_kernel),
    MapRule(_LAYER + r"self_attn\.o_proj\.weight",
            r"params/mla_attn_\g<i>/out/kernel", _t_attn_out_kernel),
    MapRule(_LAYER + r"post_attention_layernorm\.weight",
            r"params/ln_mlp_\g<i>/scale"),
    MapRule(_LAYER + r"mlp\.gate\.weight",
            r"params/moe_\g<i>/router_kernel", _t_transpose),
    MapRule(_LAYER + r"mlp\.gate\.e_score_correction_bias",
            r"params/moe_\g<i>/router_bias"),
    MapRule(_LAYER + r"mlp\.experts\.(?P<n>\d+)\." + _FFN,
            r"params/moe_\g<i>/experts_\g<proj>/\g<n>", _t_transpose),
    MapRule(_LAYER + r"mlp\.shared_experts\." + _FFN,
            r"params/moe_\g<i>/shared/\g<proj>/kernel", _t_transpose),
    MapRule(_LAYER + r"mlp\." + _FFN,
            r"params/mlp_\g<i>/\g<proj>/kernel", _t_transpose),
    MapRule(r"model\.norm\.weight", "params/ln_final/scale"),
    MapRule(r"lm_head\.weight", "params/head_kernel", _t_transpose),
    MapRule(r".*rotary_emb\.inv_freq", None),
]


def _stack_experts_held(out: dict, experts_held) -> dict:
    """`experts_<proj>/<n>` matrices -> the module's (held, in, out)
    arrays of the experts `experts_held` names (default: all)."""
    for layer in out["params"].values():
        for proj in ("gate", "up", "down"):
            experts = layer.get(f"experts_{proj}") if isinstance(
                layer, dict) else None
            if experts is None:
                continue
            first, count = experts_held or (0, len(experts))
            layer[f"experts_{proj}"] = np.stack(
                [experts[str(n)] for n in range(first, first + count)])
    out.pop("batch_stats")
    return out


def torch_mla_moe_decoder_to_flax(
    state_dict: Mapping[str, np.ndarray], num_heads: int,
    kv_lora_rank: int, qk_nope_head_dim: int, qk_rope_head_dim: int,
    experts_held: "tuple[int, int] | None" = None,
) -> dict[str, Any]:
    """Map a `deepseek_v3`-named state dict onto nn.models.MLAMoEDecoder
    variables. The head split and the latent's width cannot be read from
    the fused shapes; `experts_held` (first index, count) keeps a share of
    the routed experts (default: all the checkpoint has), while the router
    and its selection bias keep every expert's row."""
    out = apply_mapping_spec(state_dict, MLA_MOE_DECODER_SPEC, {
        "num_heads": int(num_heads), "kv_lora_rank": int(kv_lora_rank),
        "qk_nope_head_dim": int(qk_nope_head_dim),
        "qk_rope_head_dim": int(qk_rope_head_dim)})
    return _stack_experts_held(out, experts_held)


def import_torch_mla_moe_decoder(
    path: str, architecture: str = "mla_moe_decoder",
    input_shape: tuple[int, ...] = (8,), **config,
):
    """Load a `deepseek_v3`-named checkpoint into a ready-to-serve
    ModelBundle of the `mla_moe_decoder` family. `config` is the module's
    (`num_heads`, the latent's and the heads' widths, `experts_held`, ...):
    the checkpoint's own config.json states them, its shapes do not."""
    from .models import ModelBundle

    sd = load_state_dict(path)
    bundle = ModelBundle.init(architecture, input_shape=tuple(input_shape),
                              seed=0, **config)
    module = bundle.module
    variables = torch_mla_moe_decoder_to_flax(
        sd, module.num_heads, module.kv_lora_rank, module.qk_nope_head_dim,
        module.qk_rope_head_dim, tuple(module.experts_held))
    return _validate_and_install(bundle, variables, architecture)


# --------------------------------------------------------------------- #
# lfm2_moe naming -> nn.models.HybridMoEDecoder                          #
# --------------------------------------------------------------------- #

def _t_heads_kernel(v, ctx):
    """torch (heads * width, D) -> (D, heads, width): queries and the
    fewer key/value heads alike, told by the head's width."""
    width = ctx["head_dim"]
    return np.transpose(v, (1, 0)).reshape(v.shape[1], v.shape[0] // width,
                                           width)


def _t_taps(v, ctx):
    """torch depthwise Conv1d (D, 1, taps) -> (D, taps); the last tap
    meets the newest token in both."""
    return v.reshape(v.shape[0], v.shape[-1])


# the gated feed-forward's names in this family: w1 gate, w3 up, w2 down
_W = {"w1": "gate", "w3": "up", "w2": "down"}
_WN = r"(?P<w>w[123])\.weight"
HYBRID_MOE_DECODER_SPEC: "list[MapRule]" = [
    MapRule(r"model\.embed_tokens\.weight", "params/embed/embedding"),
    MapRule(_LAYER + r"operator_norm\.weight", r"params/ln_op_\g<i>/scale"),
    MapRule(_LAYER + r"ffn_norm\.weight", r"params/ln_mlp_\g<i>/scale"),
    MapRule(_LAYER + r"conv\.in_proj\.weight",
            r"params/conv_\g<i>/in_proj/kernel", _t_transpose),
    MapRule(_LAYER + r"conv\.conv\.weight",
            r"params/conv_\g<i>/conv_kernel", _t_taps),
    MapRule(_LAYER + r"conv\.out_proj\.weight",
            r"params/conv_\g<i>/out_proj/kernel", _t_transpose),
    MapRule(_LAYER + r"self_attn\.(?P<p>[qkv])_proj\.weight",
            r"params/gqa_attn_\g<i>/\g<p>_proj/kernel", _t_heads_kernel),
    MapRule(_LAYER + r"self_attn\.(?P<p>[qk])_layernorm\.weight",
            r"params/gqa_attn_\g<i>/\g<p>_norm/scale"),
    MapRule(_LAYER + r"self_attn\.out_proj\.weight",
            r"params/gqa_attn_\g<i>/out/kernel", _t_attn_out_kernel),
    MapRule(_LAYER + r"feed_forward\.gate\.weight",
            r"params/moe_\g<i>/router_kernel", _t_transpose),
    MapRule(_LAYER + r"feed_forward\.expert_bias",
            r"params/moe_\g<i>/router_bias"),
    MapRule(_LAYER + r"feed_forward\.experts\.(?P<n>\d+)\." + _WN,
            lambda m: f"params/moe_{m['i']}/experts_{_W[m['w']]}/{m['n']}",
            _t_transpose),
    MapRule(_LAYER + r"feed_forward\." + _WN,
            lambda m: f"params/mlp_{m['i']}/{_W[m['w']]}/kernel",
            _t_transpose),
    MapRule(r"model\.embedding_norm\.weight", "params/ln_final/scale"),
    # tied: a checkpoint that writes the head writes the embedding again
    MapRule(r"lm_head\.weight", None),
    MapRule(r".*rotary_emb\.inv_freq", None),
]


def torch_hybrid_moe_decoder_to_flax(
    state_dict: Mapping[str, np.ndarray], num_heads: int, head_dim: int,
    experts_held: "tuple[int, int] | None" = None,
) -> dict[str, Any]:
    """Map an `lfm2_moe`-named state dict onto nn.models.HybridMoEDecoder
    variables (head tied to the embedding; rotary already in the
    rotate-half layout). `experts_held` (first index, count) keeps a share
    of the routed experts (default: all the checkpoint has), while the
    router and its selection bias keep every expert's row."""
    out = apply_mapping_spec(state_dict, HYBRID_MOE_DECODER_SPEC, {
        "num_heads": int(num_heads), "head_dim": int(head_dim)})
    return _stack_experts_held(out, experts_held)


def import_torch_hybrid_moe_decoder(
    path: str, architecture: str = "hybrid_moe_decoder",
    input_shape: tuple[int, ...] = (8,), **config,
):
    """Load an `lfm2_moe`-named checkpoint into a ready-to-serve
    ModelBundle of the `hybrid_moe_decoder` family. `config` is the
    module's (`layer_types`, the head counts, `experts_held`, ...): the
    checkpoint's own config.json states them, its shapes do not."""
    from .models import ModelBundle

    sd = load_state_dict(path)
    bundle = ModelBundle.init(architecture, input_shape=tuple(input_shape),
                              seed=0, **config)
    module = bundle.module
    variables = torch_hybrid_moe_decoder_to_flax(
        sd, module.num_heads, module.d_model // module.num_heads,
        tuple(module.experts_held))
    return _validate_and_install(bundle, variables, architecture)


# --------------------------------------------------------------------- #
# evabyte naming -> nn.models.EvaDecoder                                 #
# --------------------------------------------------------------------- #

def _t_unit_offset(v, ctx):
    """`norm_add_unit_offset`: the checkpoint stores w and multiplies by
    1 + w; the module's scale is what multiplies."""
    return np.asarray(v, np.float32) + 1.0


def _t_head_vector(v, ctx):
    """A learned vector a head, stored with broadcast axes ((1, heads, 1,
    width)) or without -> (heads, width)."""
    return np.asarray(v).reshape(ctx["num_heads"], ctx["head_dim"])


EVA_DECODER_SPEC: "list[MapRule]" = [
    MapRule(r"model\.embed_tokens\.weight", "params/embed/embedding"),
    MapRule(_LAYER + r"input_layernorm\.weight",
            r"params/ln_attn_\g<i>/scale", _t_unit_offset),
    MapRule(_LAYER + r"post_attention_layernorm\.weight",
            r"params/ln_mlp_\g<i>/scale", _t_unit_offset),
    MapRule(_LAYER + r"self_attn\.(?P<p>[qkv])_proj\.weight",
            r"params/eva_attn_\g<i>/\g<p>_proj/kernel", _t_heads_kernel),
    MapRule(_LAYER + r"self_attn\.o_proj\.weight",
            r"params/eva_attn_\g<i>/out/kernel", _t_attn_out_kernel),
    MapRule(_LAYER + r"self_attn\.adaptive_phi",
            r"params/eva_attn_\g<i>/phi", _t_head_vector),
    MapRule(_LAYER + r"self_attn\.adaptive_mu_k",
            r"params/eva_attn_\g<i>/mu", _t_head_vector),
    MapRule(_LAYER + r"mlp\." + _FFN,
            r"params/mlp_\g<i>/\g<proj>/kernel", _t_transpose),
    MapRule(r"model\.norm\.weight", "params/ln_final/scale",
            _t_unit_offset),
    # (num_pred_heads x vocabulary, d): prediction p's rows together
    MapRule(r"lm_head\.weight", "params/head_kernel", _t_transpose),
    MapRule(r".*rotary_emb\.inv_freq", None),
]


def torch_eva_decoder_to_flax(
    state_dict: Mapping[str, np.ndarray], num_heads: int, head_dim: int,
) -> dict[str, Any]:
    """Map an `evabyte`-named state dict onto nn.models.EvaDecoder
    variables: every norm's weight plus 1 (`norm_add_unit_offset`), the
    two learned vectors a head as (heads, width), rotary already in the
    rotate-half layout. A name no rule places is an error that names it."""
    return apply_mapping_spec(state_dict, EVA_DECODER_SPEC, {
        "num_heads": int(num_heads), "head_dim": int(head_dim)})


def import_torch_eva_decoder(
    path: str, architecture: str = "eva_decoder",
    input_shape: tuple[int, ...] = (8,), **config,
):
    """Load an `evabyte`-named checkpoint into a ready-to-serve
    ModelBundle of the `eva_decoder` family. `config` is the module's
    (`num_layers`, the head count, the window and the chunk, ...): the
    checkpoint's own config.json states them, its shapes do not."""
    from .models import ModelBundle

    sd = load_state_dict(path)
    bundle = ModelBundle.init(architecture, input_shape=tuple(input_shape),
                              seed=0, **config)
    module = bundle.module
    variables = torch_eva_decoder_to_flax(
        sd, module.num_heads, module.d_model // module.num_heads)
    return _validate_and_install(bundle, variables, architecture)


# --------------------------------------------------------------------- #
# smallthinker naming -> nn.models.WindowMoEDecoder                      #
# --------------------------------------------------------------------- #

def window_moe_decoder_spec(layer_types) -> "list[MapRule]":
    """The rules for a `smallthinker`-named checkpoint whose layers are
    `layer_types` ("global" / "sliding"): a layer's attention goes to
    `gqa_attn_<i>` or `swa_attn_<i>` by its kind, its router (the
    `primary_router`, which reads the attention's input) to `router_<i>`
    apart from its experts."""
    kinds = tuple(layer_types)

    def attn(m) -> str:
        i = int(m["i"])
        if i >= len(kinds):
            raise ValueError(f"the checkpoint has a layer {i}; layer_types "
                             f"names {len(kinds)}")
        return ("swa_attn_" if kinds[i] == "sliding" else "gqa_attn_") + m["i"]

    moe = _LAYER + r"block_sparse_moe\."
    return [
        MapRule(r"model\.embed_tokens\.weight", "params/embed/embedding"),
        MapRule(_LAYER + r"input_layernorm\.weight",
                r"params/ln_attn_\g<i>/scale"),
        MapRule(_LAYER + r"post_attention_layernorm\.weight",
                r"params/ln_mlp_\g<i>/scale"),
        MapRule(_LAYER + r"self_attn\.(?P<p>[qkv])_proj\.weight",
                lambda m: f"params/{attn(m)}/{m['p']}_proj/kernel",
                _t_heads_kernel),
        MapRule(_LAYER + r"self_attn\.o_proj\.weight",
                lambda m: f"params/{attn(m)}/out/kernel",
                _t_attn_out_kernel),
        MapRule(moe + r"primary_router\.weight",
                r"params/router_\g<i>/kernel", _t_transpose),
        MapRule(moe + r"experts\.(?P<n>\d+)\.(?P<proj>gate|up|down)\.weight",
                r"params/moe_\g<i>/experts_\g<proj>/\g<n>", _t_transpose),
        MapRule(r"model\.norm\.weight", "params/ln_final/scale"),
        MapRule(r"lm_head\.weight", "params/head_kernel", _t_transpose),
        MapRule(r".*rotary_emb\.inv_freq", None),
    ]


def torch_window_moe_decoder_to_flax(
    state_dict: Mapping[str, np.ndarray], layer_types, num_heads: int,
    head_dim: int, experts_held: "tuple[int, int] | None" = None,
) -> dict[str, Any]:
    """Map a `smallthinker`-named state dict onto nn.models.
    WindowMoEDecoder variables (untied head; rotary already in the
    rotate-half layout; the heads' width is the config's `head_dim`, not
    the hidden width over the heads). `experts_held` (first index, count)
    keeps a share of the routed experts (default: all the checkpoint has),
    while the router keeps every expert's column."""
    out = apply_mapping_spec(
        state_dict, window_moe_decoder_spec(layer_types),
        {"num_heads": int(num_heads), "head_dim": int(head_dim)})
    return _stack_experts_held(out, experts_held)


def import_torch_window_moe_decoder(
    path: str, architecture: str = "window_moe_decoder",
    input_shape: tuple[int, ...] = (8,), **config,
):
    """Load a `smallthinker`-named checkpoint into a ready-to-serve
    ModelBundle of the `window_moe_decoder` family. `config` is the
    module's (`layer_types`, the head counts and width, `experts_held`,
    ...): the checkpoint's own config.json states them, its shapes do
    not."""
    from .models import ModelBundle

    sd = load_state_dict(path)
    bundle = ModelBundle.init(architecture, input_shape=tuple(input_shape),
                              seed=0, **config)
    module = bundle.module
    variables = torch_window_moe_decoder_to_flax(
        sd, module.layer_types, module.num_heads, module.head_dim,
        tuple(module.experts_held))
    return _validate_and_install(bundle, variables, architecture)


# --------------------------------------------------------------------- #
# ouro naming -> nn.models.LoopedDecoder                                 #
# --------------------------------------------------------------------- #

# the four norms of a layer: before and after the attention, before and
# after the feed-forward
_OURO_NORMS = {"input_layernorm": "ln_attn", "input_layernorm_2":
               "ln_attn_post", "post_attention_layernorm": "ln_mlp",
               "post_attention_layernorm_2": "ln_mlp_post"}
LOOPED_DECODER_SPEC: "list[MapRule]" = [
    MapRule(r"model\.embed_tokens\.weight", "params/embed/embedding"),
    MapRule(_LAYER + r"(?P<norm>(?:input|post_attention)_layernorm(?:_2)?)"
            r"\.weight",
            lambda m: f"params/{_OURO_NORMS[m['norm']]}_{m['i']}/scale"),
    MapRule(_LAYER + r"self_attn\.(?P<p>[qkv])_proj\.weight",
            r"params/gqa_attn_\g<i>/\g<p>_proj/kernel", _t_heads_kernel),
    MapRule(_LAYER + r"self_attn\.o_proj\.weight",
            r"params/gqa_attn_\g<i>/out/kernel", _t_attn_out_kernel),
    MapRule(_LAYER + r"mlp\." + _FFN,
            r"params/mlp_\g<i>/\g<proj>/kernel", _t_transpose),
    MapRule(r"model\.norm\.weight", "params/ln_final/scale"),
    # Linear(hidden -> 1), with its bias
    MapRule(r"model\.early_exit_gate\.weight", "params/exit_gate/kernel",
            _t_transpose),
    MapRule(r"model\.early_exit_gate\.bias", "params/exit_gate/bias"),
    MapRule(r"lm_head\.weight", "params/head_kernel", _t_transpose),
    MapRule(r".*rotary_emb\.inv_freq", None),
]


def torch_looped_decoder_to_flax(
    state_dict: Mapping[str, np.ndarray], num_heads: int, head_dim: int,
) -> dict[str, Any]:
    """Map an `ouro`-named state dict onto nn.models.LoopedDecoder
    variables: ONE set of layers whatever `total_ut_steps` is (the steps
    share it), four norms a layer, the exit gate with its bias, an untied
    head; rotary already in the rotate-half layout. A name no rule places
    is an error that names it."""
    return apply_mapping_spec(state_dict, LOOPED_DECODER_SPEC, {
        "num_heads": int(num_heads), "head_dim": int(head_dim)})


def import_torch_looped_decoder(
    path: str, architecture: str = "looped_decoder",
    input_shape: tuple[int, ...] = (8,), **config,
):
    """Load an `ouro`-named checkpoint into a ready-to-serve ModelBundle
    of the `looped_decoder` family. `config` is the module's (`num_layers`,
    `total_ut_steps`, the head counts and width, ...): the checkpoint's own
    config.json states them, its shapes do not."""
    from .models import ModelBundle

    sd = load_state_dict(path)
    bundle = ModelBundle.init(architecture, input_shape=tuple(input_shape),
                              seed=0, **config)
    module = bundle.module
    variables = torch_looped_decoder_to_flax(sd, module.num_heads,
                                             module.head_dim)
    return _validate_and_install(bundle, variables, architecture)


# --------------------------------------------------------------------- #
# falcon_h1 naming -> nn.models.SSMHybridDecoder                         #
# --------------------------------------------------------------------- #

_MAMBA = _LAYER + r"mamba\."
SSM_HYBRID_DECODER_SPEC: "list[MapRule]" = [
    MapRule(r"model\.embed_tokens\.weight", "params/embed/embedding"),
    MapRule(_LAYER + r"input_layernorm\.weight", r"params/ln_op_\g<i>/scale"),
    MapRule(_LAYER + r"pre_ff_layernorm\.weight",
            r"params/ln_mlp_\g<i>/scale"),
    # the projection's columns as the checkpoint lays them: [z | x B C | dt]
    MapRule(_MAMBA + r"in_proj\.weight",
            r"params/ssm_\g<i>/in_proj/kernel", _t_transpose),
    MapRule(_MAMBA + r"conv1d\.weight", r"params/ssm_\g<i>/conv_kernel",
            _t_taps),
    MapRule(_MAMBA + r"conv1d\.bias", r"params/ssm_\g<i>/conv_bias"),
    MapRule(_MAMBA + r"(?P<v>dt_bias|A_log|D)", r"params/ssm_\g<i>/\g<v>"),
    MapRule(_MAMBA + r"norm\.weight", r"params/ssm_\g<i>/norm_scale"),
    MapRule(_MAMBA + r"out_proj\.weight",
            r"params/ssm_\g<i>/out_proj/kernel", _t_transpose),
    # the fixed multipliers are the configuration's, not weights: a
    # checkpoint that writes the vector of them writes what the module holds
    MapRule(r".*mup_vector", None),
    MapRule(_LAYER + r"self_attn\.(?P<p>[qkv])_proj\.weight",
            r"params/gqa_attn_\g<i>/\g<p>_proj/kernel", _t_heads_kernel),
    MapRule(_LAYER + r"self_attn\.o_proj\.weight",
            r"params/gqa_attn_\g<i>/out/kernel", _t_attn_out_kernel),
    MapRule(_LAYER + r"feed_forward\." + _FFN,
            r"params/mlp_\g<i>/\g<proj>/kernel", _t_transpose),
    MapRule(r"model\.final_layernorm\.weight", "params/ln_final/scale"),
    MapRule(r"lm_head\.weight", "params/head_kernel", _t_transpose),
    MapRule(r".*rotary_emb\.inv_freq", None),
]


def torch_ssm_hybrid_decoder_to_flax(
    state_dict: Mapping[str, np.ndarray], num_heads: int, head_dim: int,
) -> dict[str, Any]:
    """Map a `falcon_h1`-named state dict onto nn.models.SSMHybridDecoder
    variables: every layer a state-space mixer (`mamba.*`: the input
    projection's columns [z | x B C | dt] as they lie, the depthwise taps
    with the last meeting the newest token, the vectors a head) beside the
    attention's four matrices; an untied head; rotary already in the
    rotate-half layout. The family's multipliers are not in a state dict:
    the module's configuration states them. A name no rule places is an
    error that names it."""
    return apply_mapping_spec(state_dict, SSM_HYBRID_DECODER_SPEC, {
        "num_heads": int(num_heads), "head_dim": int(head_dim)})


def import_torch_ssm_hybrid_decoder(
    path: str, architecture: str = "ssm_hybrid_decoder",
    input_shape: tuple[int, ...] = (8,), **config,
):
    """Load a `falcon_h1`-named checkpoint into a ready-to-serve
    ModelBundle of the `ssm_hybrid_decoder` family. `config` is the
    module's (`num_layers`, the heads' and the scan's counts and widths,
    every multiplier, ...): the checkpoint's own config.json states them,
    its shapes do not."""
    from .models import ModelBundle

    sd = load_state_dict(path)
    bundle = ModelBundle.init(architecture, input_shape=tuple(input_shape),
                              seed=0, **config)
    module = bundle.module
    variables = torch_ssm_hybrid_decoder_to_flax(sd, module.num_heads,
                                                 module.head_dim)
    return _validate_and_install(bundle, variables, architecture)


# --------------------------------------------------------------------- #
# phi4flash-style naming -> nn.models.DecoderHybridDecoder               #
# --------------------------------------------------------------------- #

def decoder_hybrid_decoder_spec(num_layers: int) -> "list[MapRule]":
    """The rules for a model of `num_layers` layers, after the fused tensors
    are split (`torch_decoder_hybrid_decoder_to_flax`): a layer's operator
    lies under `attn.` whatever it is, so the target follows from the
    layer's kind by its index."""
    from .models import hybrid_layer_kinds

    kinds = hybrid_layer_kinds(num_layers)

    def under(rest: str):
        def target(m):
            i = int(m["i"])
            kind = kinds[i]
            if kind.startswith("mamba"):
                home = "mamba"
            elif kind == "gmu":
                home = "gmu"
            else:
                home = "diff_swa" if kind == "sliding" else "diff_attn"
            return f"params/{home}_{i}/" + m.expand(rest)
        return target

    attn = _LAYER + r"attn\."
    return [
        MapRule(r"model\.embed_tokens\.weight", "params/embed/embedding"),
        MapRule(_LAYER + r"input_layernorm\.weight",
                r"params/ln_op_\g<i>/scale"),
        MapRule(_LAYER + r"input_layernorm\.bias",
                r"params/ln_op_\g<i>/bias"),
        MapRule(_LAYER + r"post_attention_layernorm\.weight",
                r"params/ln_mlp_\g<i>/scale"),
        MapRule(_LAYER + r"post_attention_layernorm\.bias",
                r"params/ln_mlp_\g<i>/bias"),
        MapRule(_LAYER + r"mlp\." + _FFN,
                r"params/mlp_\g<i>/\g<proj>/kernel", _t_transpose),
        # a Mamba mixer's and a gated memory unit's projections
        MapRule(attn + r"(?P<p>in|x|out)_proj\.weight",
                under(r"\g<p>_proj/kernel"), _t_transpose),
        MapRule(attn + r"conv1d\.weight", under("conv_kernel"), _t_taps),
        MapRule(attn + r"conv1d\.bias", under("conv_bias")),
        MapRule(attn + r"dt_proj\.weight", under("dt_kernel"), _t_transpose),
        MapRule(attn + r"dt_proj\.bias", under("dt_bias")),
        MapRule(attn + r"(?P<v>A_log|D)", under(r"\g<v>")),
        # differential attention, Wqkv split into its parts
        MapRule(attn + r"(?P<p>[qkv])_proj\.weight",
                under(r"\g<p>_proj/kernel"), _t_transpose),
        MapRule(attn + r"(?P<p>[qkv])_proj\.bias",
                under(r"\g<p>_proj/bias")),
        MapRule(attn + r"o_proj\.weight", under("out/kernel"), _t_transpose),
        MapRule(attn + r"o_proj\.bias", under("out/bias")),
        MapRule(attn + r"inner_cross_attn\.(?P<v>lambda_[qk][12])",
                under(r"\g<v>")),
        MapRule(attn + r"inner_cross_attn\.subln\.weight",
                under("norm_scale")),
        MapRule(r"model\.final_layernorm\.weight", "params/ln_final/scale"),
        MapRule(r"model\.final_layernorm\.bias", "params/ln_final/bias"),
        # tied: a checkpoint that writes the head writes the embedding
        MapRule(r"lm_head\.weight", None),
    ]


def torch_decoder_hybrid_decoder_to_flax(
    state_dict: Mapping[str, np.ndarray], num_layers: int, d_model: int,
) -> dict[str, Any]:
    """Map a `phi4flash`-style state dict onto
    nn.models.DecoderHybridDecoder variables. The names it EXPECTS are
    ASSUMED (from the published modeling file as remembered, not re-read: no
    network), under `model.layers.<i>.`:

    - `input_layernorm`, `post_attention_layernorm` (`weight`, `bias`);
      `mlp.fc1.weight` (2 x ff, d), its FIRST half the gate, and
      `mlp.fc2.weight` (d, ff);
    - a Mamba layer: `attn.in_proj.weight` (2 x inner, d: [x | z]),
      `attn.conv1d.weight` (inner, 1, taps) and `.bias`, `attn.x_proj.weight`
      (rank + 2 x state, inner: [dt | B | C]), `attn.dt_proj.weight` (inner,
      rank) and `.bias`, `attn.A_log`, `attn.D`, `attn.out_proj.weight`;
    - a gated memory unit: `attn.in_proj.weight` (inner, d),
      `attn.out_proj.weight` (d, inner);
    - differential attention: `attn.Wqkv.weight` (d + 2 x kv, d: [q | k |
      v]) and `.bias` (a cross layer's holds the queries alone: (d, d)),
      `attn.out_proj.weight` and `.bias`,
      `attn.inner_cross_attn.lambda_q1` .. `lambda_k2`,
      `attn.inner_cross_attn.subln.weight`;

    and `model.embed_tokens.weight`, `model.final_layernorm` (`weight`,
    `bias`), `lm_head.weight` (tied: dropped). The fused tensors are split
    here, then `decoder_hybrid_decoder_spec` places every name; a name no
    rule places is an error that names it."""
    from .models import hybrid_layer_kinds

    kinds = hybrid_layer_kinds(num_layers)
    layer = re.compile(_LAYER + r"(?P<rest>.+)")
    split: dict[str, np.ndarray] = {}
    for name, value in state_dict.items():
        m = layer.fullmatch(name)
        rest = m["rest"] if m else ""
        attends = bool(m) and kinds[int(m["i"])] in (
            "sliding", "full_keeps", "cross")
        stem = name[:len(name) - len(rest)]
        if rest in ("attn.Wqkv.weight", "attn.Wqkv.bias"):
            kind = rest.rsplit(".", 1)[1]
            kv = (value.shape[0] - d_model) // 2
            for part, lo, hi in (("q", 0, d_model),
                                 ("k", d_model, d_model + kv),
                                 ("v", d_model + kv, d_model + 2 * kv)):
                if hi > lo:
                    split[f"{stem}attn.{part}_proj.{kind}"] = value[lo:hi]
        elif attends and rest.startswith("attn.out_proj."):
            split[f"{stem}attn.o_proj.{rest.rsplit('.', 1)[1]}"] = value
        elif rest == "mlp.fc1.weight":
            half = value.shape[0] // 2
            split[f"{stem}mlp.gate_proj.weight"] = value[:half]
            split[f"{stem}mlp.up_proj.weight"] = value[half:]
        elif rest == "mlp.fc2.weight":
            split[f"{stem}mlp.down_proj.weight"] = value
        else:
            split[name] = value
    return apply_mapping_spec(split, decoder_hybrid_decoder_spec(num_layers))


def import_torch_decoder_hybrid_decoder(
    path: str, architecture: str = "decoder_hybrid_decoder",
    input_shape: tuple[int, ...] = (8,), **config,
):
    """Load a `phi4flash`-style checkpoint into a ready-to-serve ModelBundle
    of the `decoder_hybrid_decoder` family. `config` is the module's
    (`num_layers`, the widths, ...). The names expected are assumed
    (`torch_decoder_hybrid_decoder_to_flax`); no published file has been
    loaded through this."""
    from .models import ModelBundle

    sd = load_state_dict(path)
    bundle = ModelBundle.init(architecture, input_shape=tuple(input_shape),
                              seed=0, **config)
    module = bundle.module
    variables = torch_decoder_hybrid_decoder_to_flax(
        sd, module.num_layers, module.d_model)
    return _validate_and_install(bundle, variables, architecture)


# architecture name -> importer; zoo.import_external dispatches here, so
# registering a new family makes it fetchable/verifiable end to end
IMPORTERS: "dict[str, Callable]" = {
    "resnet": import_torch_resnet,
    "resnet50": import_torch_resnet,
    "resnet20_cifar": import_torch_resnet,
    "transformer": import_torch_transformer,
    "mla_moe_decoder": import_torch_mla_moe_decoder,
    "hybrid_moe_decoder": import_torch_hybrid_moe_decoder,
    "eva_decoder": import_torch_eva_decoder,
    "window_moe_decoder": import_torch_window_moe_decoder,
    "looped_decoder": import_torch_looped_decoder,
    "ssm_hybrid_decoder": import_torch_ssm_hybrid_decoder,
    "decoder_hybrid_decoder": import_torch_decoder_hybrid_decoder,
}


def import_external_weights(path: str, architecture: str, **kw):
    """Dispatch an external checkpoint to its family importer."""
    imp = IMPORTERS.get(architecture)
    if imp is None:
        raise ValueError(
            f"no weight importer registered for architecture "
            f"{architecture!r}; known: {sorted(IMPORTERS)}"
        )
    return imp(path, architecture=architecture, **kw)


def _as_jnp(tree, jnp):
    if isinstance(tree, Mapping):
        return {k: _as_jnp(v, jnp) for k, v in tree.items()}
    return jnp.asarray(np.asarray(tree, np.float32))
