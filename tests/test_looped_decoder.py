"""The `looped_decoder` family against its plain reference
(`benchmark/reference/looped_decoder.py`, which imports nothing of the
program), on seeded weights at tiny widths: hidden 64, 4 heads of 16
channels, a gated feed-forward of 128, 3 layers run 1, 2 or 4 times over
the same weights, a norm on both sides of every operator, the final norm
inside the loop, an exit gate a step, an untied head over 256 rows.

Limits, each with its reason:
- `F32_LIMIT` 1e-4 of the reference's standard deviation: float32 against
  float32, only the order of the sums differs (observed 6e-6 at four
  steps);
- `BF16_BAND` 0.25 of it for the module in bfloat16: products round to 3
  digits, twelve layer passes deep (observed 0.03 to 0.06 at the 99th
  percentile), far under what a planted fault gives;
- a planted fault has to exceed `FAULT_FLOOR` 1e-2 of it (observed 0.1 to
  1.7): a norm left out is not an order of sums."""

import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.core.schema import Table
from mmlspark_tpu.nn import attention, models
from mmlspark_tpu.nn.models import (EvaDecoder, HybridMoEDecoder,
                                    LoopedDecoder, MLAMoEDecoder,
                                    ModelBundle, WindowMoEDecoder,
                                    make_model)
from mmlspark_tpu.nn.runner import DeepModelTransformer
from mmlspark_tpu.observability.metrics import get_registry
from mmlspark_tpu.observability.tracing import get_tracer

F32_LIMIT = 1e-4
BF16_BAND = 0.25
FAULT_FLOOR = 1e-2

FAMILY = "looped_decoder"
LAYERS = 3
MODEL = dict(
    num_layers=LAYERS, total_ut_steps=4, early_exit_threshold=1.0,
    d_model=64, num_heads=4, num_kv_heads=4, head_dim=16, d_ff_dense=128,
    rms_norm_eps=1e-6, rope_theta=1e6, vocab_size=256,
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
    return _reference("looped_decoder")


@pytest.fixture(scope="module")
def seeded(ref):
    """(the reference's float32 weights, the module's variables): the
    weights do not depend on the steps, which share them."""
    config = {"model": MODEL}
    weights = ref.weights(jax.random.PRNGKey(7), config)
    return weights, ref.variables(weights, config)


def _config(**changed) -> dict:
    return {"model": dict(MODEL, **changed)}


def _ids(rows: int, length: int, seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        0, MODEL["vocab_size"], (rows, length), dtype=np.int32)


def _gap(got, want) -> float:
    want = np.asarray(want, np.float64)
    return float(np.abs(np.asarray(got, np.float64) - want).max()
                 / want.std())


def _apply(variables, ids, **changed):
    """-> (what the module returns, what it sows)."""
    out, state = make_model(FAMILY, **dict(MODEL, **changed)).apply(
        variables, ids, capture_intermediates=True,
        mutable=["intermediates"])
    return out, {k: v[0] for k, v in state["intermediates"].items()
                 if isinstance(v, tuple)}


# --------------------------------------------------------------------- #
# the module against the reference                                      #
# --------------------------------------------------------------------- #

class TestModuleAgainstReference:
    def test_tree_is_what_the_reference_names(self, seeded):
        _w, variables = seeded
        for steps in (1, 4):
            init = make_model(FAMILY, **dict(MODEL, total_ut_steps=steps)
                              ).init(jax.random.PRNGKey(0),
                                     jnp.zeros((1, 8), jnp.float32))
            assert (jax.tree.structure(init["params"])
                    == jax.tree.structure(variables["params"]))
            for ours, theirs in zip(jax.tree.leaves(init["params"]),
                                    jax.tree.leaves(variables["params"])):
                assert ours.shape == theirs.shape
        params = init["params"]
        # ONE set of layers whatever the steps; four norms a layer; the
        # attention under the name the accepted reader selects, no norm on
        # a head; the gate with its bias; an untied head
        assert sorted(k for k in params if k.endswith("_1")) == [
            "gqa_attn_1", "ln_attn_1", "ln_attn_post_1", "ln_mlp_1",
            "ln_mlp_post_1", "mlp_1"]
        assert len(params) == 6 * LAYERS + 4
        assert set(params["gqa_attn_0"]) == {"q_proj", "k_proj", "v_proj",
                                             "out"}
        assert jax.tree.map(jnp.shape, params["exit_gate"]) == {
            "kernel": (64, 1), "bias": (1,)}
        assert params["head_kernel"].shape == (64, 256)

    @pytest.mark.parametrize("steps", [1, 2, 4])
    def test_both_outputs_every_position(self, ref, seeded, steps):
        weights, variables = seeded
        config = _config(total_ut_steps=steps)
        ids = _ids(3, 40)
        want = ref.outputs(weights, config, ids, "logits")
        logits, _ = _apply(variables, ids, total_ut_steps=steps,
                           output="logits")
        assert logits.shape == (3, 40, 256)
        assert _gap(logits, want) < F32_LIMIT
        logprobs, sown = _apply(variables, ids, total_ut_steps=steps)
        assert logprobs.shape == (3, 39)
        assert np.array_equal(logprobs, sown["token_logprobs"])
        # in units of the LOGITS' spread, as the logits are
        assert np.abs(np.asarray(logprobs) - ref.outputs(
            weights, config, ids, "token_logprobs")).max() / want.std() \
            < F32_LIMIT
        pdf = np.asarray(sown["exit_pdf"])
        assert pdf.shape == (3, 40, steps) and pdf.dtype == np.float32
        # a probability: the gap is absolute
        assert np.abs(pdf - ref.outputs(weights, config, ids,
                                        "exit_pdf")).max() < F32_LIMIT
        np.testing.assert_allclose(pdf.sum(-1), 1.0, atol=1e-6)
        if steps > 1:
            # the gate is not saturated: every step takes a real share
            assert 0.02 < pdf.mean((0, 1)).min()
        # at the published threshold every token leaves at the last step
        assert sown["loop_exit_at"].tolist() == [0] * (steps - 1) + [120]
        assert sown["loop_exit_at"].dtype == jnp.int32

    def test_a_threshold_under_one_selects_what_the_reference_selects(
            self, ref, seeded):
        """q = 0.5: a token's logits are read from its state at the first
        step whose running sum of the exit distribution reaches q."""
        weights, variables = seeded
        config = _config(early_exit_threshold=0.5)
        ids = _ids(3, 40, seed=1)
        pdf = ref.outputs(weights, config, ids, "exit_pdf")
        at = np.asarray(ref.exit_steps(jnp.asarray(pdf, jnp.float32), 0.5))
        counts = np.bincount(at.ravel(), minlength=4)
        assert (counts > 0).all()               # every step is some token's
        logits, sown = _apply(variables, ids, early_exit_threshold=0.5,
                              output="logits")
        assert sown["loop_exit_at"].tolist() == counts.tolist()
        want = ref.outputs(weights, config, ids, "logits")
        assert _gap(logits, want) < F32_LIMIT
        assert _gap(sown["hidden"], ref.outputs(weights, config, ids,
                                                "hidden")) < F32_LIMIT
        # ... which is not what the last step gives
        last = ref.outputs(weights, _config(), ids, "logits")
        assert _gap(logits, last) > FAULT_FLOOR
        # the distribution itself does not depend on the threshold
        np.testing.assert_array_equal(
            sown["exit_pdf"], _apply(variables, ids)[1]["exit_pdf"])

    def test_a_sum_that_never_reaches_the_threshold_leaves_at_the_last_step(
            self, ref):
        pdf = jnp.asarray([[0.2, 0.2, 0.2, 0.39999998],
                           [0.6, 0.1, 0.1, 0.2]], jnp.float32)
        assert ref.exit_steps(pdf, 0.99999999).tolist() == [3, 3]
        assert ref.exit_steps(pdf, 0.5).tolist() == [2, 0]
        assert ref.exit_steps(pdf, 1.0).tolist() == [3, 3]

    def test_bfloat16_stays_in_its_band(self, ref, seeded):
        weights, variables = seeded
        ids = _ids(3, 40, seed=2)
        want = ref.outputs(weights, _config(), ids, "logits")
        served = jax.tree.map(lambda a: a.astype(jnp.bfloat16), variables)
        got, sown = _apply(served, ids, output="logits", dtype=jnp.bfloat16)
        gap = np.abs(np.asarray(got, np.float64) - want) / want.std()
        assert F32_LIMIT < np.quantile(gap, 0.99) < BF16_BAND
        # the exit distribution is float32 whatever the module computes in
        pdf = sown["exit_pdf"]
        assert pdf.dtype == jnp.float32
        gap = np.abs(np.asarray(pdf) - ref.outputs(weights, _config(), ids,
                                                   "exit_pdf"))
        assert F32_LIMIT < np.quantile(gap, 0.99) < 0.05

    def test_the_seats_are_the_skeletons(self):
        """The steps, the norm after an operator and the gate are written
        once, in `_ScoringDecoder`: no family overrides the forward, this
        one fills the seats and the others leave them empty."""
        families = (MLAMoEDecoder, HybridMoEDecoder, EvaDecoder,
                    WindowMoEDecoder, LoopedDecoder)
        for name in ("__call__", "_stack", "_leave", "_token_logprobs",
                     "batch_counters"):
            assert {vars(cls).get(name) for cls in families} == {None}
        module = make_model(FAMILY, **MODEL)
        assert (module.total_ut_steps, module.sandwich_norms,
                module.exit_gate) == (4, True, True)
        assert module.batch_counters == ("loop_exit_at",)
        for other in families[:-1]:
            assert (other().total_ut_steps, other().sandwich_norms,
                    other().exit_gate) == (1, False, False)
        # stated by the family, not options of a configuration
        with pytest.raises(TypeError):
            make_model(FAMILY, **MODEL, sandwich_norms=False)


# --------------------------------------------------------------------- #
# each layer is lowered once                                            #
# --------------------------------------------------------------------- #

class TestTheStepsAreOneBody:
    def _jaxpr(self, variables, steps, **changed):
        module = make_model(FAMILY, **dict(MODEL, total_ut_steps=steps,
                                           **changed))
        return str(jax.make_jaxpr(lambda v, x: module.apply(
            v, x, capture_intermediates=True, mutable=["intermediates"]))(
                variables, _ids(2, 40)))

    def test_four_steps_hold_as_many_attention_calls_as_one(
            self, seeded, monkeypatch):
        """The Pallas calls of the traced forward: one a layer at one step
        and at four (the steps are a scan over one body, the parameters
        its constants), and so every product."""
        _w, variables = seeded
        sound = attention.causal_attention
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(
            attention.flash, "causal_attention",
            lambda q, k, v, impl="flash", window=None: sound(
                q, k, v, impl, window=window, block_q=8, block_k=8,
                interpret=True))
        one = self._jaxpr(variables, 1, attention_impl="flash")
        four = self._jaxpr(variables, 4, attention_impl="flash")
        assert one.count("pallas_call") == four.count("pallas_call") == LAYERS
        assert one.count("dot_general") == four.count("dot_general")
        assert " scan[" not in one.split("pallas_call")[0]
        assert four.count("length=4") == 1

    def test_the_scans_body_is_where_the_layers_are(self, seeded):
        """The one scan of four steps holds every layer's products; outside
        it are the embedding's lookup, the exit distribution and the head:
        the count above is not met by leaving the layers out."""
        _w, variables = seeded
        four = self._jaxpr(variables, 4)
        # a scan's body is printed before its length
        after = four[four.index("length=4"):]
        assert after.count("dot_general") <= 2
        # q, k, v, out, gate, up, down a layer, two products in its
        # attention at least, and the exit gate's
        assert four.count("dot_general") >= 9 * LAYERS + 1


# --------------------------------------------------------------------- #
# a term left out or moved fails                                        #
# --------------------------------------------------------------------- #

def _unrolled(ref, w, ids, fault=None):
    """The equations written out with the reference's own pieces, T = 4,
    with one fault: the final norm applied once, after the last step
    (`final_norm_outside_the_loop`), or every step its own weights (the
    layers' weights in another order from the second step on:
    `weights_not_shared`). -> logits."""
    s = ref.sizes(_config())
    frozen = tuple(sorted(s.items()))
    h = ref._embed(w["embed"], jnp.asarray(ids))
    for t in range(s["total_ut_steps"]):
        order = range(s["num_layers"])
        if fault == "weights_not_shared" and t:
            order = reversed(order)
        for i in order:
            h = ref._layer(h, ref.layer_weights(w, i), frozen)
        if fault != "final_norm_outside_the_loop":
            h = ref.rms_norm(h, w["ln_final_scale"], s["rms_norm_eps"])
    if fault == "final_norm_outside_the_loop":
        h = ref.rms_norm(h, w["ln_final_scale"], s["rms_norm_eps"])
    return ref._head(h, w["head"], jnp.asarray(ids), "logits")


class TestAPlantedFaultFails:
    @pytest.mark.parametrize("fault", [
        None, "final_norm_outside_the_loop", "weights_not_shared"])
    def test_the_loop_written_out_wrongly(self, ref, seeded, fault):
        """The sound module against the equations written out by hand:
        equal as they stand, apart once the final norm is moved outside
        the loop or the steps stop sharing their weights."""
        weights, variables = seeded
        ids = _ids(2, 40, seed=3)
        got, _ = _apply(variables, ids, output="logits")
        with jax.default_matmul_precision("highest"):
            gap = _gap(got, _unrolled(ref, weights, ids, fault))
        assert gap < F32_LIMIT if fault is None else gap > FAULT_FLOOR

    @pytest.mark.parametrize("dropped", [
        "ln_attn_", "ln_attn_post_", "ln_mlp_", "ln_mlp_post_", "ln_final",
        "both_post_norms", "post_norms_swapped", "no_gate_bias"])
    def test_a_norm_dropped_or_misplaced(self, ref, seeded, monkeypatch,
                                         dropped):
        """Each planted fault has to move a compared output by more than
        `FAULT_FLOOR` of its spread, or the tests above prove nothing. The
        reference is sound throughout; the fault is in the module."""
        weights, variables = seeded
        ids = _ids(2, 40, seed=4)
        want = ref.outputs(weights, _config(), ids, "logits")
        pdf = ref.outputs(weights, _config(), ids, "exit_pdf")
        # the sound module, beside it, is sound
        assert _gap(_apply(variables, ids, output="logits")[0],
                    want) < F32_LIMIT
        sound_norm = models.RMSNorm
        if dropped == "both_post_norms":
            monkeypatch.setattr(LoopedDecoder, "sandwich_norms", False)
        elif dropped == "post_norms_swapped":
            # each in the other's place: only their scales tell them apart
            params = dict(variables["params"])
            for i in range(LAYERS):
                params[f"ln_attn_post_{i}"], params[f"ln_mlp_post_{i}"] = (
                    params[f"ln_mlp_post_{i}"], params[f"ln_attn_post_{i}"])
            variables = {"params": params}
        elif dropped == "no_gate_bias":
            variables = {"params": dict(variables["params"], exit_gate=dict(
                variables["params"]["exit_gate"], bias=jnp.zeros(1)))}
        else:
            def norm(eps, dtype, name):
                if name.rstrip("0123456789") == dropped:
                    return lambda x: x
                return sound_norm(eps, dtype, name=name)

            monkeypatch.setattr(models, "RMSNorm", norm)
        got, sown = _apply(variables, ids, output="logits")
        if dropped == "no_gate_bias":
            # at the published threshold the gate moves no logit: only the
            # exit distribution shows it
            assert _gap(got, want) < F32_LIMIT
            assert _gap(sown["exit_pdf"], pdf) > FAULT_FLOOR
        else:
            assert _gap(got, want) > FAULT_FLOOR


# --------------------------------------------------------------------- #
# through the runner                                                    #
# --------------------------------------------------------------------- #

def _stage(architecture, model, variables, fetch, batch=4):
    bundle = ModelBundle(architecture=architecture,
                         config=dict(model, dtype="float32"),
                         variables=variables, input_shape=(40,))
    return DeepModelTransformer(
        input_col="tokens", fetch_dict=fetch, mini_batch_size=batch,
        fused_dispatch=False).set_model(bundle)


def _last_root():
    return [s for s in get_tracer().spans()
            if s.name == "runner.transform"][-1]


class TestThroughTheRunner:
    """`DeepModelTransformer.transform`, streamed path, both outputs
    fetched, a ragged tail."""

    @pytest.fixture(scope="class")
    def stage(self, seeded):
        _w, variables = seeded
        return _stage(FAMILY, MODEL, variables,
                      {"logprob": "token_logprobs", "exit": "exit_pdf"})

    def test_matches_reference_and_padding_changes_no_row(self, ref, seeded,
                                                          stage):
        weights, _v = seeded
        ids = _ids(12, 40, seed=5)
        scale = ref.outputs(weights, _config(), ids, "logits").std()
        # 11 rows: the tail of 3 is padded to 4 by the runner
        ragged = stage.transform(Table({"tokens": ids[:11]}))
        logprob, leave = (np.asarray(ragged[c]) for c in ("logprob", "exit"))
        assert logprob.shape == (11, 39) and leave.shape == (11, 40, 4)
        assert np.abs(logprob - ref.outputs(
            weights, _config(), ids[:11], "token_logprobs")).max() / scale \
            < F32_LIMIT
        assert np.abs(leave - ref.outputs(
            weights, _config(), ids[:11], "exit_pdf")).max() < F32_LIMIT
        full = stage.transform(Table({"tokens": ids}))
        assert np.array_equal(np.asarray(full["logprob"])[:11], logprob)
        assert np.array_equal(np.asarray(full["exit"])[:11], leave)

    def test_the_loops_counts_ride_the_readback(self, stage):
        passes = get_registry().counter(
            "mmlspark_tpu_loop_layer_passes_total",
            "layer passes of a looped stack: batches x layers x steps")
        before = passes.value
        stage.transform(Table({"tokens": _ids(11, 40, seed=6)}))
        args = _last_root().args
        # three batches (the tail padded to 4 rows), 3 layers, 4 steps;
        # 12 rows of 40 tokens scored, every one leaving at the last step
        assert args["loop_steps"] == 4
        assert args["loop_layer_passes"] == 3 * LAYERS * 4
        assert args["loop_exit_at"] == [0, 0, 0, 12 * 40]
        assert passes.value - before == 3 * LAYERS * 4
        # no expert layer: nothing of the experts' accounting
        assert not [k for k in args if k.startswith("moe_")]

    def test_a_threshold_under_one_is_counted_by_step(self, seeded):
        _w, variables = seeded
        stage = _stage(FAMILY, dict(MODEL, early_exit_threshold=0.5),
                       variables, {"logprob": "token_logprobs"})
        stage.transform(Table({"tokens": _ids(8, 40, seed=7)}))
        at = _last_root().args["loop_exit_at"]
        assert sum(at) == 8 * 40 and all(n > 0 for n in at)

    def test_the_fused_path_serves_both_outputs(self, seeded, stage):
        """One dispatch for the table: no counters are read back there,
        the fetched outputs are the streamed path's."""
        _w, variables = seeded
        bundle = ModelBundle(architecture=FAMILY,
                             config=dict(MODEL, dtype="float32"),
                             variables=variables, input_shape=(40,))
        fused = DeepModelTransformer(
            input_col="tokens", mini_batch_size=4, fused_dispatch=True,
            fetch_dict={"logprob": "token_logprobs",
                        "exit": "exit_pdf"}).set_model(bundle)
        ids = _ids(8, 40, seed=8)
        got, want = (s.transform(Table({"tokens": ids}))
                     for s in (fused, stage))
        for column in ("logprob", "exit"):
            np.testing.assert_allclose(np.asarray(got[column]),
                                       np.asarray(want[column]), atol=1e-5)


class _LoopedExperts(MLAMoEDecoder):
    """A module that sows BOTH counters: Moonlight's family with the loop's
    seats filled (two steps over one stack of expert layers, a gate)."""

    total_ut_steps = 2
    exit_gate = True


MLA = dict(
    num_layers=3, d_model=64, num_heads=4, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, d_ff_dense=128,
    first_k_dense=1, n_routed_experts=8, experts_held=[0, 8],
    num_experts_per_tok=3, d_ff_expert=32, n_shared_experts=1,
    vocab_size=256, attention_impl="chunked", head_chunk=16)


class TestCountersAreToldApartByName:
    def test_a_module_that_sows_both_records_each_where_it_belongs(
            self, monkeypatch):
        monkeypatch.setitem(
            models.ARCHITECTURES, "looped_experts",
            lambda **kw: _LoopedExperts(**models._hashable(kw)))
        module = make_model("looped_experts", **MLA)
        assert module.batch_counters == ("moe_picks", "loop_exit_at")
        variables = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8)))
        assert "exit_gate" in variables["params"]
        stage = _stage("looped_experts", MLA, variables,
                       {"logprob": "token_logprobs"})
        stage.transform(Table({"tokens": _ids(8, 40)}))
        args = _last_root().args
        # two batches of 4 rows of 40 tokens: the experts' picks over 2
        # expert layers, 3 a token and BOTH steps (the steps' picks are
        # added up); the loop's counts beside them
        assert args["moe_picks_held"] == 8 * 40 * 3 * 2 * 2
        assert args["moe_load_max_over_mean"] >= 1.0
        assert args["loop_steps"] == 2
        assert args["loop_layer_passes"] == 2 * 3 * 2
        assert args["loop_exit_at"] == [0, 8 * 40]

    def test_only_the_experts_counter(self):
        module = make_model("mla_moe_decoder", **MLA)
        assert module.batch_counters == ("moe_picks",)
        variables = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8)))
        _stage("mla_moe_decoder", MLA, variables,
               {"logprob": "token_logprobs"}).transform(
                   Table({"tokens": _ids(8, 40)}))
        args = _last_root().args
        assert args["moe_picks_held"] == 8 * 40 * 3 * 2
        assert not [k for k in args if k.startswith("loop_")]

    def test_a_counter_nothing_knows_is_read_back_and_left(self, seeded,
                                                           monkeypatch):
        _w, variables = seeded
        monkeypatch.setattr(
            LoopedDecoder, "batch_counters",
            property(lambda self: ("loop_exit_at", "hidden")))
        stage = _stage(FAMILY, MODEL, variables,
                       {"logprob": "token_logprobs"})
        out = stage.transform(Table({"tokens": _ids(4, 40)}))
        assert np.asarray(out["logprob"]).shape == (4, 39)
        assert _last_root().args["loop_exit_at"] == [0, 0, 0, 160]


# --------------------------------------------------------------------- #
# the sibling families, unchanged                                       #
# --------------------------------------------------------------------- #

SIBLINGS = {
    # family: (model, first logits, first log-probabilities, equations of
    # the traced forward, parameter arrays), as the commit before this
    # family read them (this box, float32; the whole jaxpr was the same
    # there to the character)
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
         2.1312808990478516], [-7.4985833168029785, -9.525734901428223],
        746, 54),
    "mla_moe_decoder": (dict(MLA, routed_scaling_factor=2.5,
                             norm_topk_prob=True, rms_norm_eps=1e-5,
                             rope_theta=1e4),
                        [-0.39457255601882935, 1.6843814849853516,
                         -1.9779101610183716, 0.1863900125026703],
                        [-7.718783378601074, -6.879295349121094], 727, 43),
    "eva_decoder": (dict(
        num_layers=2, d_model=64, num_heads=4, window_size=16, chunk_size=4,
        d_ff_dense=128, rms_norm_eps=1e-5, rope_theta=1e5, vocab_size=320,
        num_pred_heads=8, attention_impl="chunked", head_chunk=16),
        [0.4659785032272339, -0.01390037126839161, 0.834844172000885,
         -0.5618093609809875], [-5.424658298492432, -7.196560382843018],
        422, 25),
    "window_moe_decoder": (dict(
        layer_types=["global", "sliding", "sliding", "global"], d_model=64,
        num_heads=6, num_kv_heads=2, head_dim=16, window_size=16,
        n_routed_experts=8, experts_held=[0, 8], num_experts_per_tok=3,
        d_ff_expert=32, n_shared_experts=0, rms_norm_eps=1e-6,
        rope_theta=1.5e6, vocab_size=256, attention_impl="chunked",
        head_chunk=16),
        [-0.14259135723114014, 0.26802384853363037, -0.3682441711425781,
         -1.8095409870147705], [-4.862322807312012, -7.9271135330200195],
        768, 43),
}


@pytest.mark.parametrize("family", list(SIBLINGS))
def test_a_sibling_familys_tree_program_and_outputs_are_what_they_were(
        family):
    """The four families that leave the loop's seats empty, at tiny
    widths on their own references' seeded weights: the parameter tree
    under the names and shapes the references give (no `_post_` norm, no
    gate), what is sown, the traced forward's equation count and the first
    numbers of both outputs."""
    model, first, first_logprobs, equations, arrays = SIBLINGS[family]
    sibling = _reference(family)
    config = {"model": model}
    variables = sibling.variables(
        sibling.weights(jax.random.PRNGKey(7), config), config)
    module = make_model(family, **model, output="logits")
    init = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8)))
    assert (jax.tree.structure(init["params"])
            == jax.tree.structure(variables["params"]))
    assert len(jax.tree.leaves(init)) == arrays
    assert not [k for k in init["params"]
                if "_post_" in k or k == "exit_gate"]
    ids = np.random.default_rng(0).integers(0, 256, (3, 24), dtype=np.int32)

    def forward(v, x):
        return module.apply(v, x, capture_intermediates=True,
                            mutable=["intermediates"])

    logits, state = forward(variables, ids)
    sown = {k for k, v in state["intermediates"].items()
            if isinstance(v, tuple)}
    assert sown - {"moe_picks"} == {"__call__", "hidden", "token_logprobs"}
    np.testing.assert_allclose(
        np.asarray(logits).reshape(3, 24, -1)[0, 0, :4], first, rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(state["intermediates"]["token_logprobs"][0])[0, :2],
        first_logprobs, rtol=1e-5)
    traced = str(jax.make_jaxpr(forward)(variables, ids))
    assert traced.count(" = ") == equations


# --------------------------------------------------------------------- #
# weight import                                                         #
# --------------------------------------------------------------------- #

def _as_checkpoint(w: dict, layers: int) -> dict:
    """The reference's arrays under an `ouro` checkpoint's names and torch
    layouts ((out, in) matrices, fused heads)."""
    w = {k: [np.asarray(a) for a in v] if isinstance(v, list)
         else np.asarray(v) for k, v in w.items()}
    sd = {"model.embed_tokens.weight": w["embed"],
          "model.norm.weight": w["ln_final_scale"],
          "model.early_exit_gate.weight": w["exit_kernel"].T,
          "model.early_exit_gate.bias": w["exit_bias"],
          "model.rotary_emb.inv_freq": np.zeros(8),
          "lm_head.weight": w["head"].T}
    norms = {"input_layernorm": "ln_attn_scale",
             "input_layernorm_2": "ln_attn_post_scale",
             "post_attention_layernorm": "ln_mlp_scale",
             "post_attention_layernorm_2": "ln_mlp_post_scale"}
    for i in range(layers):
        at = f"model.layers.{i}."
        for theirs, ours in norms.items():
            sd[at + theirs + ".weight"] = w[ours][i]
        for p in "qkv":
            m = w["w" + p][i]
            sd[at + f"self_attn.{p}_proj.weight"] = m.reshape(
                m.shape[0], -1).T
        sd[at + "self_attn.o_proj.weight"] = w["wo"][i].reshape(
            -1, w["wo"][i].shape[-1]).T
        for name in ("gate", "up", "down"):
            sd[at + f"mlp.{name}_proj.weight"] = w[name][i].T
    return sd


class TestWeightImport:
    def test_an_ouro_named_state_dict_round_trips(self, ref, seeded,
                                                  tmp_path):
        """A tiny fabricated state dict under the checkpoint's names: the
        imported module holds the reference's arrays under the module's
        names, to the bit, and gives what the reference gives."""
        from mmlspark_tpu.nn.import_weights import (
            LOOPED_DECODER_SPEC, apply_mapping_spec, import_external_weights)

        weights, variables = seeded
        sd = _as_checkpoint(weights, LAYERS)
        mapped = apply_mapping_spec(sd, LOOPED_DECODER_SPEC,
                                    {"num_heads": 4, "head_dim": 16})
        assert not mapped["batch_stats"]
        assert (jax.tree.structure(mapped["params"])
                == jax.tree.structure(variables["params"]))
        for ours, theirs in zip(jax.tree.leaves(mapped["params"]),
                                jax.tree.leaves(variables["params"])):
            assert np.array_equal(ours, theirs)
        path = tmp_path / "tiny.npz"
        np.savez(path, **sd)
        bundle = import_external_weights(str(path), FAMILY, **MODEL)
        ids = _ids(2, 40, seed=8)
        got = bundle.module.apply(bundle.variables, ids)
        scale = ref.outputs(weights, _config(), ids, "logits").std()
        want = ref.outputs(weights, _config(), ids, "token_logprobs")
        assert np.abs(np.asarray(got) - want).max() / scale < F32_LIMIT

    def test_an_unknown_name_and_a_missing_norm_are_refused(self, seeded,
                                                            tmp_path):
        from mmlspark_tpu.nn.import_weights import (
            import_external_weights, torch_looped_decoder_to_flax)

        with pytest.raises(ValueError, match="unrecognized"):
            torch_looped_decoder_to_flax(
                {"model.layers.0.input_layernorm_3.weight": np.zeros(4)},
                4, 16)
        weights, _v = seeded
        sd = _as_checkpoint(weights, LAYERS)
        del sd["model.layers.1.post_attention_layernorm_2.weight"]
        path = tmp_path / "short.npz"
        np.savez(path, **sd)
        with pytest.raises(ValueError, match="ln_mlp_post_1"):
            import_external_weights(str(path), FAMILY, **MODEL)
