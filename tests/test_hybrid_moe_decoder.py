"""The `hybrid_moe_decoder` family against its plain reference
(`benchmark/reference/hybrid_moe_decoder.py`, which imports nothing of the
program), on seeded weights at tiny widths: hidden 64, 8 query heads over 2
key/value heads of 8 channels, 3 taps, 8 experts of width 32 and 4 a token,
no shared expert, 2 dense layers, then conv and attention layers with
experts, a tied head over 256 rows.

Limits, each with its reason:
- `F32_LIMIT` 1e-4 of the reference's standard deviation: float32 against
  float32, only the order of the sums differs (observed 4e-6);
- a planted fault has to exceed `FAULT_FLOOR` 1e-2 of it (observed 1.2 to
  11): a term left out is not an order of sums."""

import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.core.schema import Table
from mmlspark_tpu.nn import attention
from mmlspark_tpu.nn.attention import dense_attention, flash_attention
from mmlspark_tpu.nn.models import (ExpertLayer, GroupedQueryAttention,
                                    MLAMoEDecoder, HybridMoEDecoder,
                                    ModelBundle, ShortConv, make_model)
from mmlspark_tpu.nn.runner import DeepModelTransformer
from mmlspark_tpu.observability.tracing import get_tracer
from mmlspark_tpu.parallel.moe import moe_ffn_dropless, route_top_k

F32_LIMIT = 1e-4
FAULT_FLOOR = 1e-2

FAMILY = "hybrid_moe_decoder"
MODEL = dict(
    layer_types=["conv", "conv", "full_attention", "conv", "full_attention"],
    d_model=64, num_heads=8, num_kv_heads=2, conv_taps=3, d_ff_dense=128,
    num_dense_layers=2, n_routed_experts=8, experts_held=[0, 8],
    num_experts_per_tok=4, d_ff_expert=32, n_shared_experts=0,
    routed_scaling_factor=1.0, norm_topk_prob=True, route_epsilon=1e-6,
    rms_norm_eps=1e-5, rope_theta=1e6, vocab_size=256, tie_embeddings=True,
    attention_impl="chunked", head_chunk=16)


@pytest.fixture(scope="module")
def ref():
    path = (pathlib.Path(__file__).parent.parent / "benchmark" / "reference"
            / "hybrid_moe_decoder.py")
    spec = importlib.util.spec_from_file_location("ref_hybrid_moe", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


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
    """Off the CPU the modules call the Pallas kernel; here it is
    interpreted, at tiles small enough to cross."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        attention.flash, "flash_attention",
        lambda q, k, v, **kw: flash_attention(
            q, k, v, block_q=16, block_k=16, interpret=True, **kw))


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
        # tied: no head of its own; no shared expert: no parameter for one
        assert "head_kernel" not in init["params"]
        assert "shared" not in init["params"]["moe_2"]

    @pytest.mark.parametrize("impl", ["dense", "chunked", "flash"])
    def test_logits_and_logprobs_every_position(self, ref, seeded, impl,
                                                monkeypatch):
        config, weights, variables = seeded
        if impl == "flash":
            _interpreted_flash(monkeypatch)
        ids = _ids(3, 24)
        model = dict(MODEL, attention_impl=impl)
        want = ref.outputs(weights, config, ids, "logits")
        logits = make_model(FAMILY, **model, output="logits").apply(
            variables, ids)
        assert logits.shape == (3, 24, 256)
        assert _gap(logits, want) < F32_LIMIT
        logprobs = make_model(FAMILY, **model).apply(variables, ids)
        assert logprobs.shape == (3, 23)
        # in units of the LOGITS' spread, as the logits are
        assert np.abs(np.asarray(logprobs) - ref.outputs(
            weights, config, ids, "token_logprobs")).max() / want.std() \
            < F32_LIMIT

    def test_the_two_families_share_one_skeleton(self):
        """The block loop, the chunked head and the counters are written
        once: neither family overrides them."""
        for name in ("__call__", "_token_logprobs", "batch_counters"):
            owners = {vars(cls).get(name) for cls in (MLAMoEDecoder,
                                                      HybridMoEDecoder)}
            assert owners == {None}, name
        module = make_model(FAMILY, **MODEL)
        assert module.batch_counters == ("moe_picks",)
        assert make_model(FAMILY, **dict(
            MODEL, layer_types=["conv", "full_attention"])).batch_counters \
            == ()
        for name in ("experts_held", "vocab_size", "attention_impl",
                     "head_chunk", "output", "dtype", "n_routed_experts",
                     "num_experts_per_tok"):
            assert hasattr(module, name)

    def test_an_unknown_layer_type_is_refused(self):
        with pytest.raises(ValueError, match="unknown layer type"):
            make_model(FAMILY, **dict(MODEL, layer_types=["conv", "mamba"])
                       ).init(jax.random.PRNGKey(0), jnp.zeros((1, 8)))


# --------------------------------------------------------------------- #
# the mixers alone                                                      #
# --------------------------------------------------------------------- #

def _conv_inputs(seed: int = 0, t: int = 12, d: int = 16):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return dict(y=jax.random.normal(keys[0], (2, t, d)),
                w_in=jax.random.normal(keys[1], (d, 3 * d)) * d ** -0.5,
                taps=jax.random.normal(keys[2], (d, 3)),
                w_out=jax.random.normal(keys[3], (d, d)) * d ** -0.5)


def _conv(p, y=None):
    variables = {"params": {"in_proj": {"kernel": p["w_in"]},
                            "conv_kernel": p["taps"],
                            "out_proj": {"kernel": p["w_out"]}}}
    return ShortConv().apply(variables, p["y"] if y is None else y)


class TestShortConv:
    def test_equals_the_reference(self, ref):
        p = _conv_inputs()
        want = ref.short_conv(p["y"], p["w_in"], p["taps"], p["w_out"])
        assert _gap(_conv(p), want) < F32_LIMIT

    def test_a_change_at_t_moves_nothing_before_t(self):
        p = _conv_inputs(seed=1)
        base = np.asarray(_conv(p))
        moved = np.asarray(_conv(p, p["y"].at[:, 7].add(1.0)))
        assert np.array_equal(moved[:, :7], base[:, :7])
        # and reaches exactly the taps' span after it
        assert (np.abs(moved[:, 7:10] - base[:, 7:10]).max(-1) > 1e-3).all()
        assert np.array_equal(moved[:, 10:], base[:, 10:])

    def test_the_first_two_positions_see_zeros(self):
        """c[0] = w2 z0 and c[1] = w1 z0 + w2 z1: nothing wraps around from
        the row's end, nothing leaks in from the row before."""
        p = _conv_inputs(seed=2)
        d = p["y"].shape[-1]
        gated = np.asarray(p["y"] @ p["w_in"], np.float64)
        b, c, u = gated[..., :d], gated[..., d:2 * d], gated[..., 2 * d:]
        z, w = b * u, np.asarray(p["taps"], np.float64)
        want0 = (c[:, 0] * (w[:, 2] * z[:, 0])) @ np.asarray(p["w_out"])
        want1 = (c[:, 1] * (w[:, 1] * z[:, 0] + w[:, 2] * z[:, 1])) \
            @ np.asarray(p["w_out"])
        got = np.asarray(_conv(p))
        np.testing.assert_allclose(got[:, 0], want0, atol=1e-4)
        np.testing.assert_allclose(got[:, 1], want1, atol=1e-4)
        # a row is scored alone: the second row's start sees no first row
        alone = np.asarray(_conv(p, p["y"][1:]))
        np.testing.assert_allclose(alone[0], got[1], atol=1e-6)


def _attention_layer(ref, seed: int = 0, t: int = 20):
    """One attention layer's reference weights and module variables."""
    config = {"model": dict(MODEL, layer_types=["full_attention"],
                            num_dense_layers=1)}
    w = ref.weights(jax.random.PRNGKey(seed), config)
    y = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, t, 64))
    variables = {"params": ref.variables(w, config)["params"]["gqa_attn_0"]}
    s = ref.sizes(config)
    return s, ref.layer_weights(w, s, 0), variables, y


class TestGroupedQueryAttention:
    @pytest.mark.parametrize("impl", ["dense", "chunked", "flash"])
    def test_equals_the_reference_with_the_head_norms(self, ref, impl,
                                                      monkeypatch):
        if impl == "flash":
            _interpreted_flash(monkeypatch)
        s, w, variables, y = _attention_layer(ref)
        with jax.default_matmul_precision("highest"):
            want = ref.attention(y, w, s)
        got = GroupedQueryAttention(8, 2, 1e6, 1e-5, impl).apply(variables, y)
        assert _gap(got, want) < F32_LIMIT

    def test_one_scale_vector_for_all_heads_of_a_kind(self, ref):
        _s, _w, variables, _y = _attention_layer(ref)
        assert variables["params"]["q_norm"]["scale"].shape == (8,)
        assert variables["params"]["k_norm"]["scale"].shape == (8,)
        assert variables["params"]["k_proj"]["kernel"].shape == (64, 2, 8)

    def test_heads_that_do_not_divide_are_refused(self):
        with pytest.raises(ValueError, match="key/value heads"):
            GroupedQueryAttention(8, 3).init(jax.random.PRNGKey(0),
                                             jnp.zeros((1, 4, 64)))


# --------------------------------------------------------------------- #
# the router and the expert layer                                       #
# --------------------------------------------------------------------- #

def _layer_inputs(seed: int = 0, tokens: int = 40, d: int = 64, n: int = 32,
                  w: int = 16):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    return dict(
        x=jax.random.normal(keys[0], (tokens, d)),
        router=jax.random.normal(keys[1], (d, n)) * d ** -0.5,
        bias=0.1 * jax.random.normal(keys[2], (n,)),
        gate=jax.random.normal(keys[3], (n, d, w)) * d ** -0.5,
        up=jax.random.normal(keys[4], (n, d, w)) * d ** -0.5,
        down=jax.random.normal(keys[5], (n, w, d)) * w ** -0.5)


def _faint(p):
    """Scores near 1e-7 (logits near -17), where the 1e-6 under the
    weights' sum is most of it."""
    return dict(p, x=jnp.concatenate(
        [p["x"][:, :-1], jnp.full((p["x"].shape[0], 1), 8.0)], 1),
        router=p["router"].at[-1].set(-17.0 / 8.0))


class TestRouter:
    def test_the_bias_selects_and_does_not_weigh(self, ref):
        p = _layer_inputs(seed=5)
        bias = jnp.zeros(32).at[3].set(1.0).at[30].set(-1.0)
        picked, weights = route_top_k(p["x"], p["router"], bias, 4,
                                      epsilon=1e-6)
        assert (np.asarray(picked) == 3).any(axis=1).all()
        assert not (np.asarray(picked) == 30).any()
        scores = jax.nn.sigmoid(p["x"] @ p["router"])
        chosen = jnp.take_along_axis(scores, picked, 1)
        np.testing.assert_allclose(
            weights, chosen / (chosen.sum(1, keepdims=True) + 1e-6),
            rtol=1e-6)
        gates = ref.routing(p["x"], p["router"], bias, 4, 1.0)
        np.testing.assert_allclose(
            jnp.take_along_axis(gates, picked, 1), weights, rtol=1e-5)

    def test_the_epsilon_is_the_familys(self, ref):
        """At faint scores the weights are s / (sum + 1e-6), the
        reference's; the sibling family's 1e-20 gives others."""
        p = _faint(_layer_inputs(seed=6))
        picked, weights = route_top_k(p["x"], p["router"], p["bias"], 4,
                                      epsilon=1e-6)
        gates = ref.routing(p["x"], p["router"], p["bias"], 4, 1.0)
        want = jnp.take_along_axis(gates, picked, 1)
        assert float(want.sum(1).max()) < 0.9      # the epsilon shows
        np.testing.assert_allclose(weights, want, rtol=1e-4)
        _p, parent = route_top_k(p["x"], p["router"], p["bias"], 4)
        np.testing.assert_allclose(parent.sum(1), 1.0, rtol=1e-5)

    def test_no_shared_term(self, ref):
        p = _layer_inputs(seed=7)
        layer = ExpertLayer(32, (0, 32), 4, 16, n_shared_experts=0,
                            epsilon=1e-6)
        variables = layer.init(jax.random.PRNGKey(0), p["x"])
        assert set(variables["params"]) == {
            "router_kernel", "router_bias", "experts_gate", "experts_up",
            "experts_down"}
        out, picks = layer.apply(variables, p["x"])
        routed, _n = moe_ffn_dropless(
            p["x"], *(variables["params"][k] for k in (
                "router_kernel", "router_bias", "experts_gate",
                "experts_up", "experts_down")),
            n_routed_experts=32, experts_held=(0, 32), top_k=4,
            epsilon=1e-6)
        assert np.array_equal(np.asarray(out), np.asarray(routed))
        assert int(picks.sum()) == 40 * 4

    def test_four_shares_of_8_experts_add_up_to_the_uncut_layer(self, ref):
        """32 experts held 8 at a time by the four chips of a host: the
        four parts (no shared expert to count once) are the uncut
        reference's layer."""
        p = _layer_inputs(seed=8)
        s = {"num_experts_per_tok": 4, "routed_scaling_factor": 1.0,
             "first_expert": 0, "experts_held": 32}
        w = {"router": p["router"], "router_bias": p["bias"],
             "expert_gate": p["gate"], "expert_up": p["up"],
             "expert_down": p["down"]}
        with jax.default_matmul_precision("highest"):
            want = ref.expert_layer(p["x"], w, s)
        total, counted = 0.0, []
        for first in (0, 8, 16, 24):
            layer = ExpertLayer(32, (first, 8), 4, 16, n_shared_experts=0,
                                epsilon=1e-6)
            out, picks = layer.apply({"params": {
                "router_kernel": p["router"], "router_bias": p["bias"],
                "experts_gate": p["gate"][first:first + 8],
                "experts_up": p["up"][first:first + 8],
                "experts_down": p["down"][first:first + 8]}}, p["x"])
            total = total + out
            counted.append(picks)
        assert _gap(total, want) < F32_LIMIT
        assert int(np.concatenate(counted).sum()) == 40 * 4


    @pytest.mark.parametrize("tokens", [32768, 2048, 1024])
    def test_the_combine_has_a_plan_at_the_cells_shapes(self, tokens):
        """4 picks of 32 routed, 8 held, hidden 2048, bfloat16, at the
        three batches `lfm2_8b_a1b.score_long_docs` makes: tiles of 256
        tokens, all 8 held experts in one group inside the kernel's VMEM,
        and a buffer smaller than the whole T x k."""
        from mmlspark_tpu.parallel import moe

        tile, chunk, group = moe._combine_shape(tokens, 4, 32, 8, 2048, 2)
        assert (tile, group) == (256, 8) and tokens % tile == 0
        # what even picks give a tile of one expert, and room before it
        assert chunk % 16 == 0 and chunk >= 256 * 4 // 32 + 16
        rows = moe.dropless_buffer_rows(tokens, 4, 8, 32)
        assert tokens * 4 * 8 // 32 < rows < tokens * 4

    def test_the_interpreted_combine_at_4_of_32_with_8_held(
            self, monkeypatch):
        import functools

        from mmlspark_tpu.parallel import moe

        p = _layer_inputs(seed=9, tokens=300)
        args = (p["x"], p["router"], p["bias"], p["gate"][8:16],
                p["up"][8:16], p["down"][8:16])
        kw = dict(n_routed_experts=32, experts_held=(8, 8), top_k=4,
                  epsilon=1e-6)
        want, picks_want = moe_ffn_dropless(*args, **kw)
        monkeypatch.setattr(moe, "_combine", functools.partial(
            moe._combine_pallas, interpret=True))
        got, picks = moe_ffn_dropless(*args, **kw)
        assert np.array_equal(picks, picks_want)
        # float32 both: only the order of up to 4 additions differs
        assert _gap(got, want) < F32_LIMIT


# --------------------------------------------------------------------- #
# a term left out fails                                                 #
# --------------------------------------------------------------------- #

def _faulty_reference(ref, monkeypatch, fault: str):
    """The reference with one term left out, for the whole-model faults."""
    sound_conv, sound_norm = ref.short_conv, ref.rms_norm
    if fault in ("gate_in", "gate_out"):
        def conv(y, w_in, taps, w_out):
            gate_in, gate_out, u = jnp.split(y @ w_in, 3, axis=-1)
            if fault == "gate_in":
                gate_in = jnp.ones_like(gate_in)
            else:
                gate_out = jnp.ones_like(gate_out)
            z = jnp.pad(gate_in * u, ((0, 0), (2, 0), (0, 0)))
            c = sum(taps[:, j] * z[:, j:j + y.shape[1]] for j in range(3))
            return (gate_out * c) @ w_out

        monkeypatch.setattr(ref, "short_conv", conv)
    elif fault == "tap_order":
        monkeypatch.setattr(
            ref, "short_conv", lambda y, w_in, taps, w_out: sound_conv(
                y, w_in, taps[:, ::-1], w_out))
    elif fault == "head_norm":
        # heads are (B, T, heads, c): the layers' norms see three axes
        monkeypatch.setattr(
            ref, "rms_norm", lambda x, scale, eps: x if x.ndim == 4
            else sound_norm(x, scale, eps))
    else:
        raise AssertionError(fault)


class TestATermLeftOutFails:
    @pytest.mark.parametrize("fault", [
        "gate_in", "gate_out", "tap_order", "head_norm", "epsilon", "tie",
        "j_over_group"])
    def test_fails(self, ref, seeded, monkeypatch, fault):
        """Seven planted faults: each has to move the compared output by
        more than `FAULT_FLOOR` of its spread, or the tests above prove
        nothing. The program is sound throughout; the fault is in what it
        is compared with (or, for the tie, in the module's switch)."""
        config, weights, variables = seeded
        ids = _ids(2, 20, seed=3)
        sound = make_model(FAMILY, **MODEL, output="logits")
        if fault in ("gate_in", "gate_out", "tap_order", "head_norm"):
            got = sound.apply(variables, ids)
            assert _gap(got, ref.outputs(weights, config, ids,
                                         "logits")) < F32_LIMIT
            _faulty_reference(ref, monkeypatch, fault)
            # traced anew: a compiled layer keeps the sound functions
            monkeypatch.setattr(ref, "_compiled",
                                lambda name: getattr(ref, name))
            want = ref.outputs(weights, config, ids, "logits")
        elif fault == "tie":
            want = ref.outputs(weights, config, ids, "logits")
            head = jax.random.normal(jax.random.PRNGKey(1), (64, 256)) / 8.0
            got = make_model(FAMILY, **dict(MODEL, tie_embeddings=False),
                             output="logits").apply(
                {"params": dict(variables["params"], head_kernel=head)}, ids)
        elif fault == "epsilon":
            p = _faint(_layer_inputs(seed=6))
            picked, got = route_top_k(p["x"], p["router"], p["bias"], 4)
            want = jnp.take_along_axis(ref.routing(
                p["x"], p["router"], p["bias"], 4, 1.0), picked, 1)
            _p, right = route_top_k(p["x"], p["router"], p["bias"], 4,
                                    epsilon=1e-6)
            assert _gap(right, want) < F32_LIMIT
        else:
            # query head j reads key/value head j // 4, not j % 2
            keys = jax.random.split(jax.random.PRNGKey(2), 3)
            q = jax.random.normal(keys[0], (2, 16, 8, 8))
            k, v = (jax.random.normal(key, (2, 16, 2, 8))
                    for key in keys[1:])
            got = dense_attention(q, k, v, causal=True)
            right = dense_attention(q, jnp.repeat(k, 4, 2),
                                    jnp.repeat(v, 4, 2), causal=True)
            assert _gap(got, right) < F32_LIMIT
            want = dense_attention(q, jnp.tile(k, (1, 1, 4, 1)),
                                   jnp.tile(v, (1, 1, 4, 1)), causal=True)
        assert _gap(got, want) > FAULT_FLOOR


# --------------------------------------------------------------------- #
# through the runner                                                    #
# --------------------------------------------------------------------- #

class TestThroughTheRunner:
    """`DeepModelTransformer.transform`, streamed path, two lengths, a
    ragged tail."""

    @pytest.fixture(scope="class")
    def stage(self, seeded):
        _config, _w, variables = seeded
        bundle = ModelBundle(architecture=FAMILY,
                             config=dict(MODEL, dtype="float32"),
                             variables=variables, input_shape=(24,))
        return DeepModelTransformer(
            input_col="tokens", fetch_dict={"logprob": "token_logprobs"},
            mini_batch_size=4, fused_dispatch=False).set_model(bundle)

    @pytest.mark.parametrize("length", [24, 9])
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
        # was: the convolution keeps no state across rows and routing is
        # dropless, so no row changes
        full = np.asarray(stage.transform(
            Table({"tokens": ids}))["logprob"])
        assert np.array_equal(full[:11], ragged)

    def test_routing_counts_ride_the_readback(self, stage):
        ids = _ids(11, 24, seed=5)
        stage.transform(Table({"tokens": ids}))
        root = [s for s in get_tracer().spans()
                if s.name == "runner.transform"][-1]
        # 12 rows scored (the tail padded), 24 tokens, 4 picks, 3 layers
        assert root.args["moe_picks"] == 12 * 24 * 4 * 3
        # every expert is held here, so every pick is
        assert root.args["moe_picks_held"] == root.args["moe_picks"]
        assert root.args["moe_whole_buffer"] == 0
        assert root.args["moe_load_max_over_mean"] >= 1.0


# --------------------------------------------------------------------- #
# weight import                                                         #
# --------------------------------------------------------------------- #

def _as_checkpoint(ref, w: dict, s: dict) -> dict:
    """The reference's arrays under an `lfm2_moe` checkpoint's names and
    torch layouts ((out, in) matrices, fused heads, Conv1d taps)."""
    w = {k: [np.asarray(a) for a in v] if isinstance(v, list)
         else np.asarray(v) for k, v in w.items()}
    sd = {"model.embed_tokens.weight": w["embed"],
          "model.embedding_norm.weight": w["ln_final_scale"],
          "lm_head.weight": w["embed"]}
    for i, kind in enumerate(s["layer_types"]):
        at, j = f"model.layers.{i}.", ref._slot(s, i)
        sd[at + "operator_norm.weight"] = w["ln_op_scale"][i]
        sd[at + "ffn_norm.weight"] = w["ln_mlp_scale"][i]
        if kind == "conv":
            sd[at + "conv.in_proj.weight"] = w["conv_in"][j].T
            sd[at + "conv.conv.weight"] = w["conv_taps"][j][:, None, :]
            sd[at + "conv.out_proj.weight"] = w["conv_out"][j].T
        else:
            for p in "qkv":
                m = w["w" + p][j]
                sd[at + f"self_attn.{p}_proj.weight"] = m.reshape(
                    m.shape[0], -1).T
            sd[at + "self_attn.q_layernorm.weight"] = w["q_norm_scale"][j]
            sd[at + "self_attn.k_layernorm.weight"] = w["k_norm_scale"][j]
            sd[at + "self_attn.out_proj.weight"] = w["wo"][j].reshape(
                -1, w["wo"][j].shape[-1]).T
        names = {"w1": "gate", "w3": "up", "w2": "down"}
        if i < s["dense_layers"]:
            for torch_name, ours in names.items():
                sd[at + f"feed_forward.{torch_name}.weight"] = (
                    w[f"dense_{ours}"][i].T)
            continue
        e = i - s["dense_layers"]
        sd[at + "feed_forward.gate.weight"] = w["router"][e].T
        sd[at + "feed_forward.expert_bias"] = w["router_bias"][e]
        for torch_name, ours in names.items():
            for n in range(s["n_routed_experts"]):
                sd[at + f"feed_forward.experts.{n}.{torch_name}.weight"] = (
                    w[f"expert_{ours}"][e][n].T)
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
        np.savez(path, **_as_checkpoint(ref, weights, ref.sizes(config)))
        model = dict(MODEL, experts_held=list(held))
        bundle = import_external_weights(str(path), FAMILY, **model)
        part = dict(weights)
        for name in ("expert_gate", "expert_up", "expert_down"):
            part[name] = [a[held[0]:held[0] + held[1]]
                          for a in weights[name]]
        cfg = {"model": model}
        ids = _ids(2, 20, seed=8)
        got = bundle.module.apply(bundle.variables, ids)
        scale = ref.outputs(part, cfg, ids, "logits").std()
        want = ref.outputs(part, cfg, ids, "token_logprobs")
        assert np.abs(np.asarray(got) - want).max() / scale < F32_LIMIT

    def test_an_unknown_name_is_refused(self):
        from mmlspark_tpu.nn.import_weights import (
            torch_hybrid_moe_decoder_to_flax)

        with pytest.raises(ValueError, match="unrecognized"):
            torch_hybrid_moe_decoder_to_flax(
                {"model.layers.0.conv.gate.weight": np.zeros((2, 2))}, 8, 8)
