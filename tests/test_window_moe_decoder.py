"""The `window_moe_decoder` family against its plain reference
(`benchmark/reference/window_moe_decoder.py`, which imports nothing of the
program), on seeded weights at tiny widths: hidden 64, 6 query heads over 2
key/value heads of 16 channels (96 query channels, not the hidden width),
global layers without positions among sliding layers (window 16) with
rotary ones, 8 experts of width 32 gated by ReLU, 3 a token, routed from
the attention's input by a softmax over the picked logits, an untied head
over 256 rows.

Limits, each with its reason:
- `F32_LIMIT` 1e-4 of the reference's standard deviation: float32 against
  float32, only the order of the sums differs (observed 2.5e-6);
- `BF16_BAND` 0.25 of it for the module in bfloat16: products round to 3
  digits and a flipped pick moves a token's row (observed 0.03 to 0.09),
  far under what a planted fault gives;
- a planted fault has to exceed `FAULT_FLOOR` 1e-2 of it (observed 0.06 to
  2.6): a term left out is not an order of sums."""

import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.core.schema import Table
from mmlspark_tpu.nn import attention, models
from mmlspark_tpu.nn.attention import causal_attention
from mmlspark_tpu.nn.models import (ExpertLayer, GroupedQueryAttention,
                                    HybridMoEDecoder, MLAMoEDecoder,
                                    ModelBundle, Router, WindowMoEDecoder,
                                    make_model)
from mmlspark_tpu.nn.runner import DeepModelTransformer
from mmlspark_tpu.observability.tracing import get_tracer
from mmlspark_tpu.parallel.moe import route_top_k

F32_LIMIT = 1e-4
BF16_BAND = 0.25
FAULT_FLOOR = 1e-2

FAMILY = "window_moe_decoder"
WINDOW = 16
MODEL = dict(
    layer_types=["global", "sliding", "sliding", "sliding", "global"],
    d_model=64, num_heads=6, num_kv_heads=2, head_dim=16,
    window_size=WINDOW, n_routed_experts=8, experts_held=[0, 8],
    num_experts_per_tok=3, d_ff_expert=32, n_shared_experts=0,
    rms_norm_eps=1e-6, rope_theta=1.5e6, vocab_size=256,
    attention_impl="chunked", head_chunk=16)


def _reference(name: str):
    path = (pathlib.Path(__file__).parent.parent / "benchmark" / "reference"
            / f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"ref_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def ref():
    return _reference("window_moe_decoder")


@pytest.fixture(scope="module")
def seeded(ref):
    """(config, the reference's float32 weights, the module's variables)."""
    config = {"model": MODEL}
    weights = ref.weights(jax.random.PRNGKey(7), config)
    return config, weights, ref.variables(weights, config)


def _ids(rows: int, length: int, seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        0, MODEL["vocab_size"], (rows, length), dtype=np.int32)


def _gap(got, want) -> float:
    want = np.asarray(want, np.float64)
    return float(np.abs(np.asarray(got, np.float64) - want).max()
                 / want.std())


def _interpreted_flash(monkeypatch):
    """Off the CPU the modules call the Pallas kernels; here they are
    interpreted, at tiles small enough for the band's edges to cross."""
    sound = attention.causal_attention
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        attention.flash, "causal_attention",
        lambda q, k, v, impl="flash", window=None: sound(
            q, k, v, impl, window=window, block_q=8, block_k=8,
            interpret=True))


# --------------------------------------------------------------------- #
# the module against the reference                                      #
# --------------------------------------------------------------------- #

class TestModuleAgainstReference:
    def test_tree_is_what_the_reference_names(self, seeded):
        _config, _w, variables = seeded
        init = make_model(FAMILY, **MODEL).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.float32))
        assert (jax.tree.structure(init["params"])
                == jax.tree.structure(variables["params"]))
        for ours, theirs in zip(jax.tree.leaves(init["params"]),
                                jax.tree.leaves(variables["params"])):
            assert ours.shape == theirs.shape
        params = init["params"]
        # a global layer's attention under the name the accepted reader
        # selects, a sliding layer's under its own; no norm on a head; the
        # router apart from its experts, which hold none; an untied head
        assert set(params["gqa_attn_0"]) == set(params["swa_attn_1"]) == {
            "q_proj", "k_proj", "v_proj", "out"}
        assert params["gqa_attn_0"]["q_proj"]["kernel"].shape == (64, 6, 16)
        assert params["gqa_attn_0"]["out"]["kernel"].shape == (6, 16, 64)
        assert set(params["router_2"]) == {"kernel"}
        assert set(params["moe_2"]) == {"experts_gate", "experts_up",
                                        "experts_down"}
        assert params["head_kernel"].shape == (64, 256)

    @pytest.mark.parametrize("impl", ["dense", "chunked", "flash"])
    def test_logits_and_logprobs_every_position(self, ref, seeded, impl,
                                                monkeypatch):
        """Rows of 40 tokens: two and a half windows, so the band slides."""
        config, weights, variables = seeded
        if impl == "flash":
            _interpreted_flash(monkeypatch)
        ids = _ids(3, 40)
        model = dict(MODEL, attention_impl=impl)
        want = ref.outputs(weights, config, ids, "logits")
        logits = make_model(FAMILY, **model, output="logits").apply(
            variables, ids)
        assert logits.shape == (3, 40, 256)
        assert _gap(logits, want) < F32_LIMIT
        logprobs = make_model(FAMILY, **model).apply(variables, ids)
        assert logprobs.shape == (3, 39)
        # in units of the LOGITS' spread, as the logits are
        assert np.abs(np.asarray(logprobs) - ref.outputs(
            weights, config, ids, "token_logprobs")).max() / want.std() \
            < F32_LIMIT

    def test_a_row_inside_the_window_is_plain_causal(self, ref, seeded):
        config, weights, variables = seeded
        ids = _ids(2, WINDOW)
        got = make_model(FAMILY, **MODEL, output="logits").apply(variables,
                                                                 ids)
        assert _gap(got, ref.outputs(weights, config, ids,
                                     "logits")) < F32_LIMIT

    def test_bfloat16_stays_in_its_band(self, ref, seeded):
        config, weights, variables = seeded
        ids = _ids(3, 40, seed=2)
        want = ref.outputs(weights, config, ids, "logits")
        served = jax.tree.map(lambda a: a.astype(jnp.bfloat16), variables)
        got = make_model(FAMILY, **MODEL, output="logits",
                         dtype=jnp.bfloat16).apply(served, ids)
        gap = np.abs(np.asarray(got, np.float64) - want) / want.std()
        assert F32_LIMIT < np.quantile(gap, 0.99) < BF16_BAND

    def test_the_families_share_one_skeleton(self):
        """The block loop, the chunked head and the counters are written
        once: no family overrides them; what this one states is attributes
        (`nn/runner.py` reads `batch_counters` as before)."""
        for name in ("__call__", "_token_logprobs", "batch_counters"):
            assert {vars(cls).get(name) for cls in (
                MLAMoEDecoder, HybridMoEDecoder, WindowMoEDecoder)} == {None}
        module = make_model(FAMILY, **MODEL)
        assert module.batch_counters == ("moe_picks",)
        assert (module.router_input, module.router_scoring,
                module.expert_activation) == ("operator", "softmax", "relu")
        # stated by the family, not options of a configuration
        with pytest.raises(TypeError):
            make_model(FAMILY, **MODEL, expert_activation="silu")
        for other in (MLAMoEDecoder(), HybridMoEDecoder()):
            assert (other.router_input, other.router_scoring,
                    other.expert_activation) == ("experts", "sigmoid",
                                                 "silu")

    def test_an_unknown_layer_type_is_refused(self):
        with pytest.raises(ValueError, match="unknown layer type"):
            make_model(FAMILY, **dict(MODEL, layer_types=["global", "conv"])
                       ).init(jax.random.PRNGKey(0), jnp.zeros((1, 8)))

    def test_tile_pairs_only_where_the_banded_kernel_runs(self, monkeypatch):
        module = make_model(FAMILY, **dict(MODEL, attention_impl="flash",
                                           window_size=256))
        assert module.window_tile_pairs(2, 1024) is None      # the CPU
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert module.window_tile_pairs(2, 256) is None       # one window
        visited, needed = module.window_tile_pairs(2, 1024)
        # tiles of 256: a query block reads its own key block and the one
        # before it (the first its own only), over 2 rows x 6 heads x 3
        # sliding layers
        assert visited == (1 + 3 * 2) * 2 * 6 * 3
        band = 256 * 257 / 2 + 768 * 256
        assert needed == pytest.approx(band / 256 ** 2 * 2 * 6 * 3)
        assert 1.0 < visited / needed < 2.0


# --------------------------------------------------------------------- #
# the attention layers alone                                            #
# --------------------------------------------------------------------- #

def _attention_layer(ref, seeded, i: int, t: int = 40):
    config, weights, variables = seeded
    kind = MODEL["layer_types"][i]
    name = f"swa_attn_{i}" if kind == "sliding" else f"gqa_attn_{i}"
    a = jax.random.normal(jax.random.PRNGKey(i), (2, t, 64))
    return (ref.sizes(config), ref.layer_weights(weights, i),
            {"params": variables["params"][name]}, a, kind == "sliding")


def _layer(impl: str = "dense", **kw):
    return GroupedQueryAttention(6, 2, 1.5e6, 1e-6, impl, head_dim=16,
                                 qk_norm=False, **kw)


class TestAttentionLayers:
    @pytest.mark.parametrize("i", [0, 1])
    @pytest.mark.parametrize("impl", ["dense", "chunked", "flash"])
    def test_equals_the_reference(self, ref, seeded, i, impl, monkeypatch):
        if impl == "flash":
            _interpreted_flash(monkeypatch)
        s, w, variables, a, sliding = _attention_layer(ref, seeded, i)
        with jax.default_matmul_precision("highest"):
            want = ref.attention(a, w, s, sliding)
        got = _layer(impl, rotary=sliding,
                     window=WINDOW if sliding else None).apply(variables, a)
        assert _gap(got, want) < F32_LIMIT

    def test_a_head_width_of_its_own(self):
        """28 heads of 128 on an input of 2560: the projections are not
        square, and the hidden width over the heads is not even whole."""
        layer = GroupedQueryAttention(28, 4, head_dim=128, qk_norm=False)
        shapes = jax.eval_shape(
            lambda: layer.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 8, 2560))))["params"]
        assert shapes["q_proj"]["kernel"].shape == (2560, 28, 128)
        assert shapes["k_proj"]["kernel"].shape == (2560, 4, 128)
        assert shapes["out"]["kernel"].shape == (28, 128, 2560)
        assert "q_norm" not in shapes and "k_norm" not in shapes
        with pytest.raises(ValueError, match="key/value heads"):
            GroupedQueryAttention(28, 4).init(jax.random.PRNGKey(0),
                                              jnp.zeros((1, 8, 2560)))

    def test_the_defaults_are_the_hybrid_familys(self):
        """LFM2's layer: the head width from the hidden width, both head
        norms, rotary positions, no window; its parameter tree as it was."""
        layer = GroupedQueryAttention(8, 2)
        assert (layer.head_dim, layer.qk_norm, layer.rotary,
                layer.window) == (None, True, True, None)
        params = layer.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 4, 64)))["params"]
        assert {k: jax.tree.map(jnp.shape, v) for k, v in params.items()} \
            == {"q_proj": {"kernel": (64, 8, 8)},
                "k_proj": {"kernel": (64, 2, 8)},
                "v_proj": {"kernel": (64, 2, 8)},
                "q_norm": {"scale": (8,)}, "k_norm": {"scale": (8,)},
                "out": {"kernel": (8, 8, 64)}}


# --------------------------------------------------------------------- #
# the early router and the expert layer                                 #
# --------------------------------------------------------------------- #

def _layer_inputs(seed: int = 0, tokens: int = 40, d: int = 64, n: int = 16,
                  w: int = 16):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    return dict(
        y=jax.random.normal(keys[0], (tokens, d)),
        a=jax.random.normal(keys[1], (tokens, d)),
        router=jax.random.normal(keys[2], (d, n)) * d ** -0.5,
        gate=jax.random.normal(keys[3], (n, d, w)) * d ** -0.5,
        up=jax.random.normal(keys[4], (n, d, w)) * d ** -0.5,
        down=jax.random.normal(keys[5], (n, w, d)) * w ** -0.5)


def _share(p, first: int, count: int, a=None, activation: str = "relu"):
    """The part of the layer that experts first .. first + count give,
    routed from `a` (the attention's input) through a `Router`."""
    routed = Router(16, 4, "softmax").apply(
        {"params": {"kernel": p["router"]}}, p["a"] if a is None else a)
    layer = ExpertLayer(16, (first, count), 4, 16, n_shared_experts=0,
                        scoring="softmax", activation=activation)
    return layer.apply({"params": {
        "experts_gate": p["gate"][first:first + count],
        "experts_up": p["up"][first:first + count],
        "experts_down": p["down"][first:first + count]}}, p["y"], routed)


def _uncut(ref, p):
    s = {"num_experts_per_tok": 4, "first_expert": 0, "experts_held": 16}
    w = {"router": p["router"], "expert_gate": p["gate"],
         "expert_up": p["up"], "expert_down": p["down"]}
    with jax.default_matmul_precision("highest"):
        return ref.expert_layer(p["y"], p["a"], w, s)


class TestEarlyRouterAndExperts:
    def test_the_router_holds_one_matrix_and_the_layer_none(self):
        p = _layer_inputs()
        router = Router(16, 4, "softmax")
        assert jax.tree.map(jnp.shape, router.init(
            jax.random.PRNGKey(0), p["a"])["params"]) == {"kernel": (64, 16)}
        layer = ExpertLayer(16, (0, 16), 4, 16, n_shared_experts=0,
                            scoring="softmax", activation="relu")
        routed = router.apply({"params": {"kernel": p["router"]}}, p["a"])
        assert set(layer.init(jax.random.PRNGKey(0), p["y"],
                              routed)["params"]) == {
            "experts_gate", "experts_up", "experts_down"}

    def test_the_picks_weights_are_a_softmax_over_the_picked_logits(self,
                                                                    ref):
        p = _layer_inputs(seed=1)
        picked, weights = Router(16, 4, "softmax").apply(
            {"params": {"kernel": p["router"]}}, p["a"])
        logits = p["a"] @ p["router"]
        best, want = jax.lax.top_k(logits, 4)
        assert np.array_equal(picked, want)
        np.testing.assert_allclose(weights, jax.nn.softmax(best, -1),
                                   rtol=1e-5)
        np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-6)
        gates = ref.routing(p["a"], p["router"], 4)
        np.testing.assert_allclose(jnp.take_along_axis(gates, picked, 1),
                                   weights, rtol=1e-5)

    def test_four_shares_of_4_experts_add_up_to_the_uncut_layer(self, ref):
        """The share ties to the model: 16 experts held 4 at a time by the
        four chips of a host, every chip routing over all 16 from the
        ATTENTION's input; the four parts (no shared expert to count once)
        are the uncut reference's layer, and every pick is counted once."""
        p = _layer_inputs(seed=8)
        want = _uncut(ref, p)
        total, counted = 0.0, []
        for first in (0, 4, 8, 12):
            out, picks = _share(p, first, 4)
            total = total + out
            counted.append(picks)
        assert _gap(total, want) < F32_LIMIT
        assert int(np.concatenate(counted).sum()) == 40 * 4
        # one share alone is not the layer
        assert _gap(_share(p, 0, 4)[0], want) > FAULT_FLOOR

    def test_the_whole_layer_equals_the_reference(self, ref):
        p = _layer_inputs(seed=9)
        out, picks = _share(p, 0, 16)
        assert _gap(out, _uncut(ref, p)) < F32_LIMIT
        assert int(picks.sum()) == 40 * 4

    def test_a_shared_feed_forward_is_silus(self):
        p = _layer_inputs()
        layer = ExpertLayer(16, (0, 16), 4, 16, n_shared_experts=1,
                            activation="relu")
        with pytest.raises(ValueError, match="silu"):
            layer.init(jax.random.PRNGKey(0), p["y"])


# --------------------------------------------------------------------- #
# a term left out or put in fails                                       #
# --------------------------------------------------------------------- #

class TestAPlantedFaultFails:
    @pytest.mark.parametrize("fault", [
        "router_reads_experts_input", "silu_for_relu",
        "rotary_on_a_global_layer", "no_rotary_on_a_sliding_layer",
        "window_one_short", "window_one_long", "sigmoid_for_softmax"])
    def test_fails(self, ref, seeded, monkeypatch, fault):
        """Each planted fault has to move the compared output by more than
        `FAULT_FLOOR` of its spread, or the tests above prove nothing. The
        reference is sound throughout; the fault is in the module."""
        config, weights, variables = seeded
        ids = _ids(2, 40, seed=3)
        want = ref.outputs(weights, config, ids, "logits")
        # the sound module, beside it, is sound
        assert _gap(make_model(FAMILY, **MODEL, output="logits").apply(
            variables, ids), want) < F32_LIMIT
        model = dict(MODEL)
        sound_layer = models.GroupedQueryAttention

        if fault == "router_reads_experts_input":
            # the router fed what the experts read, not the attention's
            # input: the family's switch turned back, the router's matrix
            # in the layer's own place
            params = dict(variables["params"])
            for i in range(len(MODEL["layer_types"])):
                params[f"moe_{i}"] = dict(
                    params[f"moe_{i}"],
                    router_kernel=params[f"router_{i}"]["kernel"],
                    router_bias=jnp.zeros(8))
                del params[f"router_{i}"]
            variables = {"params": params}
            monkeypatch.setattr(WindowMoEDecoder, "router_input", "experts")
        elif fault == "silu_for_relu":
            monkeypatch.setattr(WindowMoEDecoder, "expert_activation",
                                "silu")
        elif fault == "sigmoid_for_softmax":
            monkeypatch.setattr(WindowMoEDecoder, "router_scoring",
                                "sigmoid")
        elif fault in ("rotary_on_a_global_layer",
                       "no_rotary_on_a_sliding_layer"):
            sliding = fault.startswith("no_")

            def build(*args, **kw):
                if (kw["window"] is not None) == sliding:
                    kw["rotary"] = not sliding
                return sound_layer(*args, **kw)

            monkeypatch.setattr(models, "GroupedQueryAttention", build)
        else:
            model["window_size"] = WINDOW + (
                -1 if fault == "window_one_short" else 1)
        got = make_model(FAMILY, **model, output="logits").apply(variables,
                                                                 ids)
        assert _gap(got, want) > FAULT_FLOOR


# --------------------------------------------------------------------- #
# through the runner                                                    #
# --------------------------------------------------------------------- #

class TestThroughTheRunner:
    """`DeepModelTransformer.transform`, streamed path, a length past the
    window and one inside it, a ragged tail."""

    @pytest.fixture(scope="class")
    def stage(self, seeded):
        _config, _w, variables = seeded
        bundle = ModelBundle(architecture=FAMILY,
                             config=dict(MODEL, dtype="float32"),
                             variables=variables, input_shape=(40,))
        return DeepModelTransformer(
            input_col="tokens", fetch_dict={"logprob": "token_logprobs"},
            mini_batch_size=4, fused_dispatch=False).set_model(bundle)

    @pytest.mark.parametrize("length", [40, 9])
    def test_matches_reference_and_padding_changes_no_row(
            self, ref, seeded, stage, length):
        config, weights, _v = seeded
        ids = _ids(12, length, seed=length)
        scale = ref.outputs(weights, config, ids, "logits").std()
        # 11 rows: the tail of 3 is padded to 4 by the runner
        ragged = np.asarray(stage.transform(
            Table({"tokens": ids[:11]}))["logprob"])
        assert ragged.shape == (11, length - 1)
        want = ref.outputs(weights, config, ids[:11], "token_logprobs")
        assert np.abs(ragged - want).max() / scale < F32_LIMIT
        # 12 rows: the same batch shape with a real row where the padding
        # was: routing is dropless and attention reads its own row, so no
        # row changes
        full = np.asarray(stage.transform(
            Table({"tokens": ids}))["logprob"])
        assert np.array_equal(full[:11], ragged)

    def test_routing_counts_ride_the_readback(self, stage):
        ids = _ids(11, 40, seed=5)
        stage.transform(Table({"tokens": ids}))
        root = [s for s in get_tracer().spans()
                if s.name == "runner.transform"][-1]
        # 12 rows scored (the tail padded), 40 tokens, 3 picks, 5 layers
        assert root.args["moe_picks"] == 12 * 40 * 3 * 5
        assert root.args["moe_picks_held"] == root.args["moe_picks"]
        assert root.args["moe_load_max_over_mean"] >= 1.0
        # the CPU's tier is the chunked one: no banded kernel, no pairs
        assert "attn_window_tile_pairs" not in root.args

    def test_the_tile_pairs_are_written_where_the_kernel_runs(
            self, seeded, monkeypatch):
        """Told that the flash tier runs (the module's own answer, from
        shapes), the runner sums the batches' pairs on the root span."""
        _config, _w, variables = seeded
        bundle = ModelBundle(architecture=FAMILY,
                             config=dict(MODEL, dtype="float32"),
                             variables=variables, input_shape=(40,))
        stage = DeepModelTransformer(
            input_col="tokens", fetch_dict={"logprob": "token_logprobs"},
            mini_batch_size=4, fused_dispatch=False).set_model(bundle)
        monkeypatch.setattr(
            WindowMoEDecoder, "window_tile_pairs",
            lambda self, rows, length: (10 * rows, 4.0 * rows)
            if length > self.window_size else None)
        stage.transform(Table({"tokens": _ids(8, 40)}))
        root = [s for s in get_tracer().spans()
                if s.name == "runner.transform"][-1]
        # two batches of 4 rows
        assert root.args["attn_window_tile_pairs"] == 80
        assert root.args["attn_window_tile_pairs_needed"] == 32.0
        # computed tiles and fractions of one: both floats (an edge tile
        # folded in parts counts the part of it that is computed)
        assert all(isinstance(root.args[key], float) for key in (
            "attn_window_tile_pairs", "attn_window_tile_pairs_needed"))
        stage.transform(Table({"tokens": _ids(6, 9)}))
        root = [s for s in get_tracer().spans()
                if s.name == "runner.transform"][-1]
        assert "attn_window_tile_pairs" not in root.args


# --------------------------------------------------------------------- #
# the sibling families, unchanged                                       #
# --------------------------------------------------------------------- #

SIBLINGS = {
    "hybrid_moe_decoder": (dict(
        layer_types=["conv", "conv", "full_attention", "conv",
                     "full_attention"],
        d_model=64, num_heads=8, num_kv_heads=2, conv_taps=3, d_ff_dense=128,
        num_dense_layers=2, n_routed_experts=8, experts_held=[0, 8],
        num_experts_per_tok=4, d_ff_expert=32, n_shared_experts=0,
        routed_scaling_factor=1.0, norm_topk_prob=True, route_epsilon=1e-6,
        rms_norm_eps=1e-5, rope_theta=1e6, vocab_size=256,
        tie_embeddings=True, attention_impl="chunked", head_chunk=16),
        [1.2481364011764526, 1.677396535873413, 0.4904508888721466,
         2.1312808990478516]),
    "mla_moe_decoder": (dict(
        num_layers=3, d_model=64, num_heads=4, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        d_ff_dense=128, first_k_dense=1, n_routed_experts=8,
        experts_held=[0, 8], num_experts_per_tok=3, d_ff_expert=32,
        n_shared_experts=1, routed_scaling_factor=2.5, norm_topk_prob=True,
        rms_norm_eps=1e-5, rope_theta=1e4, vocab_size=256,
        attention_impl="chunked", head_chunk=16),
        [-0.39457255601882935, 1.6843814849853516, -1.9779101610183716,
         0.1863900125026703]),
}


@pytest.mark.parametrize("family", list(SIBLINGS))
def test_a_sibling_familys_tree_and_outputs_are_what_they_were(family):
    """LFM2's and Moonlight's families at tiny widths, on their own
    references' seeded weights: the parameter tree under the names and
    shapes the references give (no `router_<i>`, the router in its layer),
    and the first logits as the commit before this family read them (this
    box, float32; they were bit for bit the same there, and the whole
    jaxpr too)."""
    model, first = SIBLINGS[family]
    sibling = _reference(family)
    config = {"model": model}
    variables = sibling.variables(
        sibling.weights(jax.random.PRNGKey(7), config), config)
    module = make_model(family, **model, output="logits")
    init = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8)))
    assert (jax.tree.structure(init["params"])
            == jax.tree.structure(variables["params"]))
    assert not [k for k in init["params"] if k.startswith("router_")]
    moe = init["params"][sorted(
        k for k in init["params"] if k.startswith("moe_"))[0]]
    assert {"router_kernel", "router_bias"} <= set(moe)
    ids = np.random.default_rng(0).integers(0, 256, (3, 24), dtype=np.int32)
    logits = np.asarray(module.apply(variables, ids))
    np.testing.assert_allclose(logits[0, 0, :4], first, rtol=1e-5)


# --------------------------------------------------------------------- #
# weight import                                                         #
# --------------------------------------------------------------------- #

def _as_checkpoint(w: dict, s: dict) -> dict:
    """The reference's arrays under a `smallthinker` checkpoint's names
    and torch layouts ((out, in) matrices, fused heads)."""
    w = {k: [np.asarray(a) for a in v] if isinstance(v, list)
         else np.asarray(v) for k, v in w.items()}
    sd = {"model.embed_tokens.weight": w["embed"],
          "model.norm.weight": w["ln_final_scale"],
          "lm_head.weight": w["head"].T}
    for i in range(s["num_layers"]):
        at = f"model.layers.{i}."
        sd[at + "input_layernorm.weight"] = w["ln_attn_scale"][i]
        sd[at + "post_attention_layernorm.weight"] = w["ln_mlp_scale"][i]
        sd[at + "self_attn.rotary_emb.inv_freq"] = np.zeros(8)
        for p in "qkv":
            m = w["w" + p][i]
            sd[at + f"self_attn.{p}_proj.weight"] = m.reshape(
                m.shape[0], -1).T
        sd[at + "self_attn.o_proj.weight"] = w["wo"][i].reshape(
            -1, w["wo"][i].shape[-1]).T
        sd[at + "block_sparse_moe.primary_router.weight"] = w["router"][i].T
        for name in ("gate", "up", "down"):
            for n in range(s["n_routed_experts"]):
                sd[at + f"block_sparse_moe.experts.{n}.{name}.weight"] = (
                    w[f"expert_{name}"][i][n].T)
    return sd


class TestWeightImport:
    @pytest.mark.parametrize("held", [(0, 8), (4, 4)])
    def test_imported_module_equals_the_reference(self, ref, seeded,
                                                  tmp_path, held):
        """A tiny fabricated state dict under the checkpoint's names: the
        imported module gives what the reference gives from the same
        arrays, whole and as a share of the experts."""
        from mmlspark_tpu.nn.import_weights import import_external_weights

        config, weights, _v = seeded
        path = tmp_path / "tiny.npz"
        np.savez(path, **_as_checkpoint(weights, ref.sizes(config)))
        model = dict(MODEL, experts_held=list(held))
        bundle = import_external_weights(str(path), FAMILY, **model)
        part = dict(weights)
        for name in ("expert_gate", "expert_up", "expert_down"):
            part[name] = [a[held[0]:held[0] + held[1]]
                          for a in weights[name]]
        cfg = {"model": model}
        ids = _ids(2, 40, seed=8)
        got = bundle.module.apply(bundle.variables, ids)
        scale = ref.outputs(part, cfg, ids, "logits").std()
        want = ref.outputs(part, cfg, ids, "token_logprobs")
        assert np.abs(np.asarray(got) - want).max() / scale < F32_LIMIT

    def test_an_unknown_name_and_a_layer_too_many_are_refused(self):
        from mmlspark_tpu.nn.import_weights import (
            torch_window_moe_decoder_to_flax)

        with pytest.raises(ValueError, match="unrecognized"):
            torch_window_moe_decoder_to_flax(
                {"model.layers.0.block_sparse_moe.secondary.weight":
                 np.zeros((2, 2))}, ["global"], 6, 16)
        with pytest.raises(ValueError, match="layer_types names 1"):
            torch_window_moe_decoder_to_flax(
                {"model.layers.1.self_attn.q_proj.weight":
                 np.zeros((96, 64))}, ["global"], 6, 16)


def test_causal_attention_is_the_modules_door():
    """The module reaches the banded core through `causal_attention`'s
    `window` and nothing else."""
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 24, 2, 8))
    got = attention.decoder_attention(q, q, q, "dense", jnp.float32, band=8)
    want = causal_attention(q, q, q, "dense", window=8)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    with pytest.raises(ValueError, match="unknown router scoring"):
        route_top_k(q[0, :, 0], jnp.zeros((8, 4)), None, 2, scoring="tanh")
