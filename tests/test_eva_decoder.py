"""The `eva_decoder` family against its plain reference
(`benchmark/reference/eva_decoder.py`, which imports nothing of the
program), on seeded weights at tiny widths: hidden 32, 4 heads of 8
channels, a window of 32 and chunks of 4, a feed-forward of 64, a
vocabulary of 40 and a head of 3 predictions.

Limits, each with its reason:
- `F32_LIMIT` 1e-4 of the reference's standard deviation: float32 against
  float32, only the order of the sums differs (observed 2e-6);
- a planted fault has to exceed `FAULT_FLOOR` 1e-2 of it (observed 0.6 to
  5.3): a term left out is not an order of sums;
- `BF16_BAND`: a bfloat16 run stays within 0.15 of the logits' spread of
  the float32 one (observed 0.044 to 0.052 at three layers and three seeds:
  a rounding of 2^-9 a product, carried through the residual stream), and
  differs from it by more than 1e-3 (it IS another precision)."""

import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.core.schema import Table
from mmlspark_tpu.nn import attention
from mmlspark_tpu.nn import models
from mmlspark_tpu.nn.attention import eva_attention
from mmlspark_tpu.nn.models import (EvaAttention, EvaDecoder, ModelBundle,
                                    make_model)
from mmlspark_tpu.nn.runner import DeepModelTransformer
from mmlspark_tpu.observability.tracing import get_tracer

F32_LIMIT = 1e-4
FAULT_FLOOR = 1e-2
BF16_BAND = (1e-3, 0.15)

FAMILY = "eva_decoder"
MODEL = dict(
    num_layers=3, d_model=32, num_heads=4, window_size=32, chunk_size=4,
    d_ff_dense=64, rms_norm_eps=1e-5, rope_theta=1e5, vocab_size=40,
    num_pred_heads=3, max_len=128, attention_impl="chunked", head_chunk=16)


@pytest.fixture(scope="module")
def ref():
    path = (pathlib.Path(__file__).parent.parent / "benchmark" / "reference"
            / "eva_decoder.py")
    spec = importlib.util.spec_from_file_location("ref_eva_decoder", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _seeded(ref, model):
    config = {"model": model}
    weights = ref.weights(jax.random.PRNGKey(7), config)
    return config, weights, ref.variables(weights, config)


@pytest.fixture(scope="module")
def seeded(ref):
    """(config, the reference's float32 weights, the module's variables)."""
    return _seeded(ref, MODEL)


def _ids(rows: int, length: int, seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        0, MODEL["vocab_size"], (rows, length), dtype=np.int32)


def _gap(got, want) -> float:
    want = np.asarray(want, np.float64)
    return float(np.abs(np.asarray(got, np.float64) - want).max()
                 / want.std())


def _interpreted_flash(monkeypatch):
    """Off the CPU the module calls the Pallas kernels; here they are
    interpreted, at tiles small enough to cross."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        attention.eva, "eva_attention",
        lambda *a, **kw: eva_attention(*a, block_q=16, block_k=16,
                                       block_s=8, interpret=True, **kw))


class TestModuleAgainstReference:
    def test_tree_is_what_the_reference_names(self, seeded):
        _config, _w, variables = seeded
        init = make_model(FAMILY, **MODEL).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.float32))
        assert (jax.tree.structure(init["params"])
                == jax.tree.structure(variables["params"]))
        for ours, theirs in zip(jax.tree.leaves(init["params"]),
                                jax.tree.leaves(variables["params"])):
            assert ours.shape == theirs.shape and ours.dtype == jnp.float32
        # an untied head of 3 predictions of 40; two vectors a head
        assert init["params"]["head_kernel"].shape == (32, 3 * 40)
        assert init["params"]["eva_attn_0"]["phi"].shape == (4, 8)
        assert init["params"]["eva_attn_0"]["mu"].shape == (4, 8)
        assert not any(name.startswith("moe_") for name in init["params"])

    # 70: three windows, the last ragged and its last chunk too; 96: whole
    # windows; 24: inside one window, plain causal attention
    @pytest.mark.parametrize("length", [70, 96, 24])
    @pytest.mark.parametrize("depth", [1, 3])
    @pytest.mark.parametrize("impl", ["dense", "chunked", "flash"])
    def test_logits_of_every_prediction(self, ref, impl, depth, length,
                                        monkeypatch):
        model = dict(MODEL, num_layers=depth, attention_impl=impl)
        config, weights, variables = _seeded(ref, model)
        if impl == "flash":
            _interpreted_flash(monkeypatch)
        ids = _ids(2, length, seed=length)
        want = ref.outputs(weights, config, ids, "logits")
        logits = make_model(FAMILY, **model, output="logits").apply(
            variables, ids)
        assert logits.shape == want.shape == (2, length, 3, 40)
        assert _gap(logits, want) < F32_LIMIT
        logprobs = make_model(FAMILY, **model).apply(variables, ids)
        assert logprobs.shape == (2, length - 1)
        # in units of the LOGITS' spread, as the logits are
        assert np.abs(np.asarray(logprobs) - ref.outputs(
            weights, config, ids, "token_logprobs")).max() / want.std() \
            < F32_LIMIT

    def test_token_logprobs_are_prediction_0_at_the_next_byte(self, seeded):
        _config, _w, variables = seeded
        ids = _ids(2, 70, seed=3)
        logits = np.asarray(make_model(FAMILY, **MODEL, output="logits")
                            .apply(variables, ids))
        logp = jax.nn.log_softmax(logits[:, :, 0], -1)
        want = np.take_along_axis(np.asarray(logp)[:, :-1],
                                  ids[:, 1:, None], -1)[..., 0]
        got = np.asarray(make_model(FAMILY, **MODEL).apply(variables, ids))
        np.testing.assert_allclose(got, want, atol=1e-5)
        # a later prediction's columns are another distribution
        other = np.take_along_axis(np.asarray(jax.nn.log_softmax(
            logits[:, :, 1], -1))[:, :-1], ids[:, 1:, None], -1)[..., 0]
        assert np.abs(got - other).max() > 0.1

    def test_a_bfloat16_run_stays_in_its_band(self, ref, seeded):
        config, weights, variables = seeded
        ids = _ids(2, 70, seed=4)
        want = ref.outputs(weights, config, ids, "logits")
        served = jax.tree.map(lambda a: a.astype(jnp.bfloat16), variables)
        got = make_model(FAMILY, **MODEL, dtype=jnp.bfloat16,
                         output="logits").apply(served, ids)
        assert got.dtype == jnp.float32          # `fp32_logits`
        low, high = BF16_BAND
        assert low < _gap(got, want) < high

    def test_the_three_decoders_share_one_skeleton(self):
        assert issubclass(EvaDecoder, models._ScoringDecoder)
        module = make_model(FAMILY, **MODEL)
        assert module._dense_layers == module.num_layers == 3
        # nothing sown per batch for the runner to read back
        assert module.batch_counters == ()
        # the families with experts keep a head of `vocab_size` columns
        mla = make_model("mla_moe_decoder", vocab_size=48).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.float32))
        assert mla["params"]["head_kernel"].shape == (64, 48)
        assert make_model("mla_moe_decoder").num_pred_heads == 1

    def test_a_row_past_max_len_and_an_unknown_impl_are_refused(self,
                                                                seeded):
        _config, _w, variables = seeded
        with pytest.raises(ValueError, match="max_len"):
            make_model(FAMILY, **MODEL).apply(variables, _ids(1, 129))
        with pytest.raises(ValueError, match="flash.*chunked.*dense"):
            make_model(FAMILY, **dict(MODEL, attention_impl="sparse")).apply(
                variables, _ids(1, 40))
        with pytest.raises(ValueError, match="eva_decoder"):
            make_model("evabyte")


class TestATermLeftOutFails:
    """Each fault planted in the PROGRAM: the comparison has to see it."""

    @pytest.mark.parametrize("fault", [
        "no_mu", "plain_mean", "summaries_a_window_late",
        "window_a_chunk_short", "no_summaries", "wrong_prediction"])
    def test_fails(self, ref, seeded, monkeypatch, fault):
        config, weights, variables = seeded
        ids = _ids(2, 96, seed=6)
        want = ref.outputs(weights, config, ids, "logits")[:, :, 0]
        model = dict(MODEL, attention_impl="dense", output="logits")
        core = attention.eva_attention

        def plant(faulty):
            monkeypatch.setattr(attention.eva, "eva_attention", faulty)

        if fault == "no_mu":
            plant(lambda q, k, v, phi, mu, *a, **kw: core(
                q, k, v, phi, 0 * mu, *a, **kw))
        elif fault == "plain_mean":
            plant(lambda q, k, v, phi, mu, *a, **kw: core(
                q, k, v, 0 * phi, mu, *a, **kw))
        elif fault == "summaries_a_window_late":
            # window w reads the chunks of windows 1 .. w, its own among them
            plant(lambda q, k, v, phi, mu, window, chunk, **kw: core(
                q, k, v, phi, mu, window, chunk, summaries=tuple(
                    jnp.roll(x, -(window // chunk), 1) for x in
                    attention.eva_summaries(k, v, phi, mu, chunk)), **kw))
        elif fault == "window_a_chunk_short":
            model["window_size"] = MODEL["window_size"] - MODEL["chunk_size"]
        elif fault == "no_summaries":
            # the windows before are dropped: windowed attention alone
            plant(lambda q, k, v, phi, mu, window, chunk, **kw:
                  jnp.concatenate([attention.dense_attention(
                      q[:, i:i + window], k[:, i:i + window],
                      v[:, i:i + window], causal=True)
                      for i in range(0, q.shape[1], window)], 1))
        got = make_model(FAMILY, **model).apply(variables, ids)
        got = got[:, :, 1 if fault == "wrong_prediction" else 0]
        assert _gap(got, want) > FAULT_FLOOR


class TestEvaAttentionModule:
    def test_equals_the_reference(self, ref, seeded):
        config, weights, variables = seeded
        s = ref.sizes(config)
        y = jnp.asarray(np.random.default_rng(2).normal(size=(2, 70, 32)),
                        jnp.float32)
        with jax.default_matmul_precision("highest"):
            want = ref.attention(y, ref.layer_weights(weights, 1), s)
            got = EvaAttention(4, 32, 4, 1e5, "chunked").apply(
                {"params": variables["params"]["eva_attn_1"]}, y)
        assert _gap(got, want) < F32_LIMIT

    def test_heads_that_do_not_divide_are_refused(self):
        with pytest.raises(ValueError, match="divide"):
            EvaAttention(5).init(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8, 32), jnp.float32))


class TestThroughTheRunner:
    """`DeepModelTransformer.transform`, streamed path, two lengths, a
    ragged tail, and no counters to read back (the encoder's way, never
    taken by a decoder before)."""

    @pytest.fixture(scope="class")
    def stage(self, seeded):
        _config, _w, variables = seeded
        bundle = ModelBundle(architecture=FAMILY,
                             config=dict(MODEL, dtype="float32"),
                             variables=variables, input_shape=(70,))
        return DeepModelTransformer(
            input_col="tokens", fetch_dict={"logprob": "token_logprobs"},
            mini_batch_size=2, fused_dispatch=False).set_model(bundle)

    @pytest.mark.parametrize("length", [70, 24])
    def test_matches_reference_and_padding_changes_no_row(
            self, ref, seeded, stage, length):
        config, weights, _v = seeded
        ids = _ids(5, length, seed=length)
        scale = ref.outputs(weights, config, ids, "logits").std()
        # 5 rows in batches of 2: the last row alone on the rung of 1
        got = np.asarray(stage.transform(Table({"tokens": ids}))["logprob"])
        assert got.shape == (5, length - 1)
        want = ref.outputs(weights, config, ids, "token_logprobs")
        assert np.abs(got - want).max() / scale < F32_LIMIT
        # the same rows beside other rows: a row's value depends on no other
        again = np.asarray(stage.transform(
            Table({"tokens": ids[[4, 0, 1, 2]]}))["logprob"])
        assert np.abs(again[0] - got[4]).max() / scale < F32_LIMIT
        assert np.abs(again[1:] - got[:3]).max() / scale < F32_LIMIT

    def test_no_counters_ride_the_readback(self, stage):
        stage.transform(Table({"tokens": _ids(3, 70, seed=5)}))
        root = [s for s in get_tracer().spans()
                if s.name == "runner.transform"][-1]
        assert not any(name.startswith("moe_") for name in root.args)
        assert root.args["rows"] == 3
        assert stage.last_pipeline_stats["bucket_ladder"] == [1, 2]


# --------------------------------------------------------------------- #
# weight import                                                         #
# --------------------------------------------------------------------- #

def _as_checkpoint(w: dict, layers: int) -> dict:
    """The reference's arrays under an `evabyte` checkpoint's names and
    torch layouts: (out, in) matrices, fused heads, the two vectors a head
    with their broadcast axes, and every norm stored as w where the model
    multiplies by 1 + w (`norm_add_unit_offset`)."""
    w = {k: [np.asarray(a) for a in v] if isinstance(v, list)
         else np.asarray(v) for k, v in w.items()}
    sd = {"model.embed_tokens.weight": w["embed"],
          "model.norm.weight": w["ln_final_scale"] - 1.0,
          "lm_head.weight": w["head"].T,
          "model.layers.0.self_attn.rotary_emb.inv_freq": np.zeros(4)}
    for i in range(layers):
        at = f"model.layers.{i}."
        sd[at + "input_layernorm.weight"] = w["ln_attn_scale"][i] - 1.0
        sd[at + "post_attention_layernorm.weight"] = (
            w["ln_mlp_scale"][i] - 1.0)
        for p in "qkv":
            m = w["w" + p][i]
            sd[at + f"self_attn.{p}_proj.weight"] = m.reshape(
                m.shape[0], -1).T
        sd[at + "self_attn.o_proj.weight"] = w["wo"][i].reshape(
            -1, w["wo"][i].shape[-1]).T
        sd[at + "self_attn.adaptive_phi"] = w["phi"][i][None, :, None, :]
        sd[at + "self_attn.adaptive_mu_k"] = w["mu"][i][None, :, None, :]
        for name in ("gate", "up", "down"):
            sd[at + f"mlp.{name}_proj.weight"] = w[name][i].T
    return sd


class TestWeightImport:
    def test_imported_module_equals_the_reference(self, ref, seeded,
                                                  tmp_path):
        """A tiny fabricated state dict under the checkpoint's names, its
        norms stored as w: the imported module gives what the reference
        gives from the same arrays with norms 1 + w."""
        from mmlspark_tpu.nn.import_weights import import_external_weights

        config, weights, _v = seeded
        path = tmp_path / "tiny.npz"
        np.savez(path, **_as_checkpoint(weights, MODEL["num_layers"]))
        bundle = import_external_weights(str(path), FAMILY, **MODEL)
        np.testing.assert_allclose(
            bundle.variables["params"]["ln_attn_0"]["scale"],
            weights["ln_attn_scale"][0], atol=1e-6)
        ids = _ids(2, 70, seed=8)
        want = ref.outputs(weights, config, ids, "logits")
        got = make_model(FAMILY, **MODEL, output="logits").apply(
            bundle.variables, ids)
        assert _gap(got, want) < F32_LIMIT

    def test_an_unknown_name_is_refused_by_name(self):
        from mmlspark_tpu.nn.import_weights import torch_eva_decoder_to_flax

        with pytest.raises(ValueError,
                           match="model.layers.0.self_attn.adaptive_nu"):
            torch_eva_decoder_to_flax(
                {"model.layers.0.self_attn.adaptive_nu": np.zeros((4, 8))},
                4, 8)
