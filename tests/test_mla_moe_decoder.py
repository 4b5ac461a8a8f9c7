"""The `mla_moe_decoder` family against its plain reference
(`benchmark/reference/mla_moe_decoder.py`, which imports nothing of the
program), on seeded weights at tiny widths: hidden 64, 4 heads of 16 + 8
score channels and 16 value channels, latent 32, 8 experts of width 32,
3 a token, 1 shared, 1 dense + 2 expert layers, vocabulary 256.

Limits, each with its reason:
- `F32_LIMIT` 1e-4 of the reference's standard deviation: float32 against
  float32, only the order of the sums differs (observed 2e-6);
- a planted fault has to exceed `FAULT_FLOOR` 1e-2 of it (observed 0.1 to
  1): a term left out is not an order of sums."""

import functools
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.core.schema import Table
from mmlspark_tpu.nn.attention import dense_attention, flash_attention
from mmlspark_tpu.nn.models import ExpertLayer, ModelBundle, make_model
from mmlspark_tpu.nn.runner import DeepModelTransformer
from mmlspark_tpu.observability.metrics import get_registry
from mmlspark_tpu.observability.tracing import get_tracer
from mmlspark_tpu.parallel import moe
from mmlspark_tpu.parallel.moe import (dropless_buffer_rows, moe_ffn_dropless,
                                       route_top_k)

F32_LIMIT = 1e-4
FAULT_FLOOR = 1e-2

MODEL = dict(
    num_layers=3, d_model=64, num_heads=4, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, d_ff_dense=128,
    first_k_dense=1, n_routed_experts=8, experts_held=[0, 8],
    num_experts_per_tok=3, d_ff_expert=32, n_shared_experts=1,
    routed_scaling_factor=2.446, norm_topk_prob=True, rms_norm_eps=1e-5,
    rope_theta=50000.0, vocab_size=256, attention_impl="chunked",
    head_chunk=16)


@pytest.fixture(scope="module")
def ref():
    path = (pathlib.Path(__file__).parent.parent / "benchmark" / "reference"
            / "mla_moe_decoder.py")
    spec = importlib.util.spec_from_file_location("ref_mla_moe", path)
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


# --------------------------------------------------------------------- #
# the module against the reference                                      #
# --------------------------------------------------------------------- #

class TestModuleAgainstReference:
    def test_tree_is_what_the_reference_names(self, seeded):
        _config, _w, variables = seeded
        init = make_model("mla_moe_decoder", **MODEL).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.float32))
        assert (jax.tree.structure(init["params"])
                == jax.tree.structure(variables["params"]))
        for ours, theirs in zip(jax.tree.leaves(init["params"]),
                                jax.tree.leaves(variables["params"])):
            assert ours.shape == theirs.shape

    @pytest.mark.parametrize("impl", ["dense", "chunked"])
    def test_logits_and_logprobs_every_position(self, ref, seeded, impl):
        config, weights, variables = seeded
        ids = _ids(3, 24)
        model = dict(MODEL, attention_impl=impl)
        logits = make_model("mla_moe_decoder", **model,
                            output="logits").apply(variables, ids)
        assert logits.shape == (3, 24, 256)
        assert _gap(logits, ref.outputs(weights, config, ids,
                                        "logits")) < F32_LIMIT
        logprobs = make_model("mla_moe_decoder", **model).apply(variables,
                                                                 ids)
        assert logprobs.shape == (3, 23)
        want = ref.outputs(weights, config, ids, "token_logprobs")
        # in units of the LOGITS' spread, as the logits are
        scale = ref.outputs(weights, config, ids, "logits").std()
        assert np.abs(np.asarray(logprobs) - want).max() / scale < F32_LIMIT

    def test_chunked_head_is_log_softmax_of_full_logits(self, seeded):
        _config, _w, variables = seeded
        ids = _ids(3, 21, seed=4)           # 63 tokens: a padded last chunk
        logits = make_model("mla_moe_decoder", **MODEL,
                            output="logits").apply(variables, ids)
        want = jnp.take_along_axis(jax.nn.log_softmax(logits[:, :-1], -1),
                                   jnp.asarray(ids)[:, 1:, None], -1)[..., 0]
        for chunk in (16, 64, 1024):
            got = make_model("mla_moe_decoder", **dict(
                MODEL, head_chunk=chunk)).apply(variables, ids)
            np.testing.assert_allclose(got, want, atol=2e-5)

    def test_a_share_of_the_experts_matches_the_reference(self, ref, seeded):
        """Experts 2 to 5 of 8 held: routing stays over all 8."""
        config, weights, _v = seeded
        held = dict(MODEL, experts_held=[2, 4])
        part = dict(weights)
        for name in ("expert_gate", "expert_up", "expert_down"):
            part[name] = weights[name][:, 2:6]
        cfg = {"model": held}
        ids = _ids(2, 16, seed=2)
        got = make_model("mla_moe_decoder", **held).apply(
            ref.variables(part, cfg), ids)
        scale = ref.outputs(part, cfg, ids, "logits").std()
        want = ref.outputs(part, cfg, ids, "token_logprobs")
        assert np.abs(np.asarray(got) - want).max() / scale < F32_LIMIT


class TestThroughTheRunner:
    """`DeepModelTransformer.transform`, streamed path, two lengths, a
    ragged tail."""

    @pytest.fixture(scope="class")
    def stage(self, seeded):
        _config, _w, variables = seeded
        bundle = ModelBundle(architecture="mla_moe_decoder",
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
        # was. With experts, a padding row's tokens compete for nothing
        # only because routing is dropless
        full = np.asarray(stage.transform(
            Table({"tokens": ids}))["logprob"])
        assert np.array_equal(full[:11], ragged)

    def test_routing_counts_ride_the_readback(self, stage):
        ids = _ids(11, 24, seed=5)
        stage.transform(Table({"tokens": ids}))
        root = [s for s in get_tracer().spans()
                if s.name == "runner.transform"][-1]
        # 12 rows scored (the tail padded), 24 tokens, 3 picks, 2 layers
        assert root.args["moe_picks"] == 12 * 24 * 3 * 2
        # every expert is held here, so every pick is
        assert root.args["moe_picks_held"] == root.args["moe_picks"]
        assert root.args["moe_load_max_over_mean"] >= 1.0

    def test_a_module_without_experts_reads_nothing_back(self):
        bundle = ModelBundle.init("transformer", (8,), vocab_size=32,
                                  num_layers=1, d_model=16, num_heads=2,
                                  d_ff=32, max_len=8)
        stage = DeepModelTransformer(
            input_col="tokens", fetch_dict={"p": "pooled_features"},
            mini_batch_size=4, fused_dispatch=False).set_model(bundle)
        out = stage.transform(Table({"tokens": _ids(5, 8) % 32}))
        assert np.asarray(out["p"]).shape == (5, 16)
        root = [s for s in get_tracer().spans()
                if s.name == "runner.transform"][-1]
        assert "moe_picks" not in root.args


    @pytest.mark.parametrize("crowded", [False, True])
    def test_a_batch_that_outgrows_the_buffer_is_counted(self, ref, crowded):
        """Experts 0 and 1 of 8 held, batches of 4 x 64 tokens: the
        dispatch buffer is 512 of 768 picks. Seeded routers send a quarter
        of the picks here (about 192) and no batch leaves it; a selection
        bias that sends every token to both held experts fills it (512),
        and every (layer, batch) pair runs the whole T x k."""
        held = dict(MODEL, experts_held=[0, 2])
        config = {"model": held}
        weights = dict(ref.weights(jax.random.PRNGKey(11), config))
        if crowded:
            weights["router_bias"] = weights["router_bias"].at[:, :2].add(9.0)
        assert dropless_buffer_rows(4 * 64, 3, 2, 8) == 512
        stage = DeepModelTransformer(
            input_col="tokens", fetch_dict={"logprob": "token_logprobs"},
            mini_batch_size=4, fused_dispatch=False).set_model(ModelBundle(
                architecture="mla_moe_decoder",
                config=dict(held, dtype="float32"),
                variables=ref.variables(weights, config), input_shape=(64,)))
        family = get_registry().counter(
            "mmlspark_tpu_moe_whole_buffer_total",
            "batches whose picks outgrew the dispatch buffer, by expert layer",
            labels=("layer",))
        before = [family.labels(layer=j).value for j in range(2)]
        ids = _ids(7, 64, seed=8)           # two batches, the second padded
        out = np.asarray(stage.transform(Table({"tokens": ids}))["logprob"])
        want = ref.outputs(weights, config, ids, "token_logprobs")
        scale = ref.outputs(weights, config, ids, "logits").std()
        assert np.abs(out - want).max() / scale < F32_LIMIT
        root = [s for s in get_tracer().spans()
                if s.name == "runner.transform"][-1]
        counted = [family.labels(layer=j).value - before[j] for j in range(2)]
        if crowded:
            assert root.args["moe_picks_held"] == 2 * 2 * 4 * 64 * 2
            assert root.args["moe_whole_buffer"] == 4      # 2 layers x 2
            assert counted == [2.0, 2.0]
        else:
            assert root.args["moe_whole_buffer"] == 0
            assert counted == [0.0, 0.0]


# --------------------------------------------------------------------- #
# the expert layer                                                      #
# --------------------------------------------------------------------- #

def _layer_inputs(seed: int = 0, tokens: int = 40, d: int = 64, n: int = 8,
                  w: int = 32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    return dict(
        x=jax.random.normal(keys[0], (tokens, d)),
        router=jax.random.normal(keys[1], (d, n)) * d ** -0.5,
        bias=0.1 * jax.random.normal(keys[2], (n,)),
        gate=jax.random.normal(keys[3], (n, d, w)) * d ** -0.5,
        up=jax.random.normal(keys[4], (n, d, w)) * d ** -0.5,
        down=jax.random.normal(keys[5], (n, w, d)) * w ** -0.5)


def _routed(p, first: int, count: int, **kw):
    kw = {"top_k": 3, "scaling": 2.446, **kw}
    lo, hi = first, first + count
    return moe_ffn_dropless(
        p["x"], p["router"], p["bias"], p["gate"][lo:hi], p["up"][lo:hi],
        p["down"][lo:hi], n_routed_experts=8, experts_held=(first, count),
        **kw)


def _reference_routed(ref, p, first: int, count: int, top_k: int = 3,
                      scaling: float = 2.446):
    """The reference's way: every held expert for every token, weighted by
    its gate."""
    with jax.default_matmul_precision("highest"):
        gates = ref.routing(p["x"], p["router"], p["bias"], top_k, scaling)
        out = 0.0 * p["x"]
        for e in range(first, first + count):
            out = out + gates[:, e:e + 1] * ref.gated_ffn(
                p["x"], p["gate"][e], p["up"][e], p["down"][e])
    return out


class TestExpertLayer:
    def test_the_shares_add_up(self, ref):
        """8 experts held 2 at a time by four shares of one layer: the four
        routed parts, plus the shared expert counted once, equal the uncut
        layer's output."""
        p = _layer_inputs()
        whole, picks = _routed(p, 0, 8)
        parts = [_routed(p, first, 2) for first in (0, 2, 4, 6)]
        np.testing.assert_allclose(sum(out for out, _n in parts), whole,
                                   atol=1e-5)
        assert np.array_equal(np.concatenate([n for _o, n in parts]), picks)
        assert int(picks.sum()) == 40 * 3
        np.testing.assert_allclose(whole, _reference_routed(ref, p, 0, 8),
                                   atol=1e-5)
        # through the module: four shares' layers against the uncut layer
        def layer(held):
            return ExpertLayer(8, held, 3, 32, n_shared_experts=1,
                               scaling=2.446)

        uncut = layer((0, 8))
        variables = uncut.init(jax.random.PRNGKey(1), p["x"])
        full, _n = uncut.apply(variables, p["x"])
        shared = full - moe_ffn_dropless(
            p["x"], *(variables["params"][k] for k in (
                "router_kernel", "router_bias", "experts_gate",
                "experts_up", "experts_down")),
            n_routed_experts=8, experts_held=(0, 8), top_k=3,
            scaling=2.446)[0]
        total = 0.0
        for first in (0, 2, 4, 6):
            params = dict(variables["params"])
            for k in ("experts_gate", "experts_up", "experts_down"):
                params[k] = params[k][first:first + 2]
            out, _n = layer((first, 2)).apply({"params": params}, p["x"])
            total = total + out
        # each share added the (replicated) shared expert: count it once
        np.testing.assert_allclose(total - 3 * shared, full, atol=1e-5)

    @pytest.mark.parametrize("first,count", [(0, 8), (0, 2), (4, 2)])
    def test_dropless_under_skew(self, ref, first, count):
        """Router weights that send every token to expert 1 (and then 0
        and 2): no capacity, so the layer still gives the reference's
        answer, whether it holds the crowded experts or not."""
        p = _layer_inputs(seed=3)
        p["router"] = jnp.zeros_like(p["router"])
        p["bias"] = jnp.asarray([2.0, 3.0, 1.0, 0, 0, 0, 0, 0])
        out, picks = _routed(p, first, count)
        want = np.zeros(8, int)
        want[:3] = 40
        assert np.array_equal(picks, want[first:first + count])
        np.testing.assert_allclose(
            out, _reference_routed(ref, p, first, count), atol=1e-5)

    def test_the_bias_moves_picks_not_weights(self, ref):
        p = _layer_inputs(seed=5)
        plain, weights0 = route_top_k(p["x"], p["router"],
                                      jnp.zeros_like(p["bias"]), 3, 2.446)
        biased = dict(p, bias=jnp.asarray([1.0, 0, 0, 0, 0, 0, 0, -1.0]))
        picked, weights = route_top_k(biased["x"], biased["router"],
                                      biased["bias"], 3, 2.446)
        assert (np.asarray(picked) == 0).any(axis=1).all()    # all pick 0
        assert not (np.asarray(picked) == 7).any()
        assert not np.array_equal(np.sort(picked, 1), np.sort(plain, 1))
        # the weights are the scores at the picks, normalised, times the
        # factor: they add up to it whatever the bias
        np.testing.assert_allclose(weights.sum(1), 2.446, rtol=1e-5)
        np.testing.assert_allclose(weights0.sum(1), 2.446, rtol=1e-5)
        scores = jax.nn.sigmoid(p["x"] @ p["router"])
        chosen = jnp.take_along_axis(scores, picked, 1)
        np.testing.assert_allclose(
            weights, chosen / chosen.sum(1, keepdims=True) * 2.446,
            rtol=1e-5)
        out, _n = _routed(biased, 0, 8)
        np.testing.assert_allclose(
            out, _reference_routed(ref, biased, 0, 8), atol=1e-5)

    @pytest.mark.parametrize("fault", ["scaling", "normalise", "bias"])
    def test_a_term_left_out_fails(self, ref, fault):
        """Three planted faults: each has to move the output by more than
        `FAULT_FLOOR` of its spread, or the tests above prove nothing."""
        p = _layer_inputs(seed=6)
        want = np.asarray(_reference_routed(ref, p, 0, 8))
        sound, _n = _routed(p, 0, 8)
        assert _gap(sound, want) < F32_LIMIT
        if fault == "scaling":
            got, _n = _routed(p, 0, 8, scaling=1.0)
        elif fault == "normalise":
            got, _n = _routed(p, 0, 8, normalise=False)
        else:
            got, _n = _routed(dict(p, bias=jnp.zeros_like(p["bias"])), 0, 8)
        assert _gap(got, want) > FAULT_FLOOR


# --------------------------------------------------------------------- #
# the combine                                                           #
# --------------------------------------------------------------------- #

def _dropless_pr27(x, router, bias, gate, up, down, *, n_routed_experts,
                   experts_held, top_k, scaling=1.0, dtype=jnp.float32):
    """The layer as PR 27 had it, plain `jnp`: the scores gathered at the
    picks, a second argsort for each pick's place, and the combine as k
    slabs of (T, d) gathered one row a pick (an absent pick reads the
    last, zero, row) and added up."""
    t, _d = x.shape
    first, held = experts_held
    scores = jax.nn.sigmoid(jnp.dot(x, router.astype(x.dtype),
                                    preferred_element_type=jnp.float32))
    _best, picked = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    weights = jnp.take_along_axis(scores, picked, axis=-1)
    weights = weights / (weights.sum(-1, keepdims=True) + 1e-20) * scaling
    local = picked.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(key, stable=True)
    picks = (key[:, None] == jnp.arange(held)).sum(0, dtype=jnp.int32)
    n_here = picks.sum()
    place = jnp.argsort(order)
    rows = t * top_k
    xs = x.astype(dtype)[order // top_k]
    hidden = jax.lax.ragged_dot(
        xs, jnp.concatenate([gate, up], -1).astype(dtype), picks,
        preferred_element_type=dtype)
    w = hidden.shape[-1] // 2
    act = (jax.nn.silu(hidden[:, :w].astype(jnp.float32))
           * hidden[:, w:]).astype(dtype)
    ys = jax.lax.ragged_dot(act, down.astype(dtype), picks,
                            preferred_element_type=dtype)
    weighed = jnp.where(
        (jnp.arange(rows) < n_here)[:, None],
        ys.astype(jnp.float32) * weights.reshape(-1)[order][:, None],
        0.0).astype(dtype)
    at = jnp.where(place < n_here, place, rows - 1).reshape(t, top_k).T
    return weighed[at].astype(jnp.float32).sum(0).astype(dtype), picks


def _last_place(a: np.ndarray, bits: int) -> np.ndarray:
    """One unit in the last place of a float with `bits` bits of
    precision, at each value of `a`."""
    _m, e = np.frexp(np.abs(a))
    return np.ldexp(1.0, e - bits)


COMBINE_CASES = {
    # tokens, experts held (first, count), top_k, bias added to experts
    "all_held": (40, (0, 8), 3, {}),
    "a_quarter": (40, (2, 2), 3, {}),
    "none_picked": (40, (6, 2), 3, {6: -9.0, 7: -9.0}),
    # 768 picks, a buffer of 512: both held experts crowded fill it
    "whole_buffer": (256, (0, 2), 3, {0: 9.0, 1: 9.0}),
    # the buffer's branch taken, three tiles of tokens, the last ragged
    "small_buffer": (300, (0, 2), 3, {}),
    "ragged": (43, (1, 3), 3, {}),           # 129 picks
    "top_1": (40, (0, 4), 1, {}),
}


class TestCombine:
    @pytest.mark.parametrize("path", ["xla", "pallas", "pallas_in_groups"])
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("case", list(COMBINE_CASES))
    def test_equals_the_k_slab_gather(self, monkeypatch, case, dtype, path):
        """Rows weighed and rounded where PR 27 rounded them, a token's
        rows added in float32 and rounded once: only the order of up to k
        additions differs. `pallas` is the chip's kernel, interpreted;
        `pallas_in_groups` with VMEM for one expert's rows at a time."""
        tokens, (first, count), top_k, nudge = COMBINE_CASES[case]
        dtype = jnp.dtype(dtype)
        p = _layer_inputs(seed=4, tokens=tokens)
        for e, by in nudge.items():
            p["bias"] = p["bias"].at[e].add(by)
        if path == "pallas":
            monkeypatch.setattr(moe, "_combine", functools.partial(
                moe._combine_pallas, interpret=True))
        if path == "pallas_in_groups":
            monkeypatch.setattr(moe, "_combine", functools.partial(
                moe._combine_pallas, interpret=True, vmem=0))
            assert moe._combine_shape(tokens, top_k, 8, count, 64, 4, 0)[
                2] == 1
        lo, hi = first, first + count
        args = (p["x"], p["router"], p["bias"], p["gate"][lo:hi],
                p["up"][lo:hi], p["down"][lo:hi])
        kw = dict(n_routed_experts=8, experts_held=(first, count),
                  top_k=top_k, scaling=2.446, dtype=dtype)
        got, picks = moe_ffn_dropless(*args, **kw)
        want, picks_want = _dropless_pr27(*args, **kw)
        assert np.array_equal(picks, picks_want)
        n_here, whole = int(picks.sum()), tokens * top_k
        small = dropless_buffer_rows(tokens, top_k, count, 8)
        assert {"none_picked": n_here == 0,
                "whole_buffer": small <= n_here < whole,
                "small_buffer": n_here < small < whole}.get(case, True)
        assert got.dtype == dtype and got.shape == want.shape
        got, want = (np.asarray(a, np.float64) for a in (got, want))
        if dtype == jnp.float32:
            assert np.abs(got - want).max() <= 1e-6 * max(
                1.0, np.abs(want).max())
        else:
            assert (np.abs(got - want) <= _last_place(want, 8)).all()

    def test_the_weights_are_the_gathered_scores_bit_for_bit(self):
        p = _layer_inputs(seed=9, tokens=300)
        picked, weights = route_top_k(p["x"], p["router"], p["bias"], 3,
                                      scaling=1.0, normalise=False)
        scores = jax.nn.sigmoid(jnp.dot(
            p["x"], p["router"], preferred_element_type=jnp.float32))
        assert np.array_equal(
            weights, jnp.take_along_axis(scores, picked, axis=-1))


# --------------------------------------------------------------------- #
# the flash kernel                                                      #
# --------------------------------------------------------------------- #

_NEG_INF = -1e30


def _flash_kernel_pr26(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc,
                       acc_sc, *, block_q, block_k, num_kv, causal, tk_valid,
                       scale):
    """The kernel as it was before this family (commit bf90bb2), word for
    word: what `flash_attention` has to return for equal widths."""
    import jax.experimental.pallas as pl

    qi = pl.program_id(1)
    kv = pl.program_id(2)

    @pl.when(kv == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    qb = q_ref[0]
    kb = k_ref[0]
    s = jax.lax.dot_general(
        qb, kb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale

    kpos = kv * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    ok = kpos < tk_valid
    if causal:
        qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        ok = ok & (qpos >= kpos)
    s = jnp.where(ok, s, _NEG_INF)

    m_prev = m_sc[...]
    m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(ok, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_sc[...] = l_sc[...] * corr + p.sum(-1, keepdims=True)
    pv = jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    acc_sc[...] = acc_sc[...] * corr + pv
    m_sc[...] = m_new

    @pl.when(kv == num_kv - 1)
    def _finalize():
        l = l_sc[...]
        out = acc_sc[...] / jnp.maximum(l, 1e-30)
        out = jnp.where(l > 0, out, 0.0)
        o_ref[0] = out.astype(o_ref.dtype)
        lse_ref[0] = jnp.where(
            l > 0, m_sc[...] + jnp.log(jnp.maximum(l, 1e-30)), jnp.inf)


def _flash_pr26(q, k, v, causal, block_q, block_k):
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    b, tq, h, d = q.shape
    assert tq % block_q == 0 and k.shape[1] % block_k == 0

    def bh(x):
        return jnp.moveaxis(x, 2, 1).reshape(b * h, x.shape[1], d)

    qf, kf, vf = bh(q), bh(k), bh(v)
    nq, nk = qf.shape[1] // block_q, kf.shape[1] // block_k
    kernel = functools.partial(
        _flash_kernel_pr26, block_q=block_q, block_k=block_k, num_kv=nk,
        causal=causal, tk_valid=k.shape[1], scale=d ** -0.5)
    out, _lse = pl.pallas_call(
        kernel, grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh_, qi, kv: (bh_, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh_, qi, kv: (bh_, kv, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh_, qi, kv: (bh_, kv, 0))],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh_, qi, kv: (bh_, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh_, qi, kv: (bh_, qi, 0))],
        out_shape=[jax.ShapeDtypeStruct(qf.shape, q.dtype),
                   jax.ShapeDtypeStruct(qf.shape[:2] + (1,), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=True)(qf, kf, vf)
    return jnp.moveaxis(out.reshape(b, h, tq, d), 1, 2)


def _qkv(t: int, d: int, dv: int, b: int = 2, h: int = 3, seed: int = 0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(keys[0], (b, t, h, d)),
            jax.random.normal(keys[1], (b, t, h, d)),
            jax.random.normal(keys[2], (b, t, h, dv)))


class TestFlashKernel:
    @pytest.mark.parametrize("t,block_q,block_k", [
        (64, 16, 16),       # square blocks: one diagonal block a query block
        (50, 16, 16),       # a padded last block: the key mask stays
        (64, 32, 16),       # two key blocks cross a query block's diagonal
        (64, 16, 32),       # a key block spans two query blocks
    ])
    def test_score_width_is_not_value_width_causal(self, t, block_q,
                                                   block_k):
        q, k, v = _qkv(t, 24, 16)             # 16 + 8 for scores, 16 values
        got = flash_attention(q, k, v, causal=True, block_q=block_q,
                              block_k=block_k, interpret=True)
        assert got.shape == (2, t, 3, 16)
        np.testing.assert_allclose(
            got, dense_attention(q, k, v, causal=True), atol=2e-6)

    def test_skipped_blocks_are_never_read(self):
        """Key blocks above the diagonal hold NaN: a kernel that masked
        them after the product would return NaN (0 x NaN), one that skips
        them does not."""
        q, k, v = _qkv(64, 24, 16, b=1, h=1)
        got = flash_attention(q[:, :16], k.at[:, 16:].set(jnp.nan),
                              v.at[:, 16:].set(jnp.nan), causal=True,
                              block_q=16, block_k=16, interpret=True)
        assert bool(jnp.isfinite(got).all())
        np.testing.assert_allclose(
            got, dense_attention(q[:, :16], k[:, :16], v[:, :16],
                                 causal=True), atol=2e-6)

    def test_value_width_differentiates(self):
        q, k, v = _qkv(32, 24, 16)

        def loss(fn):
            return lambda q, k, v: (fn(q, k, v) ** 2).sum()

        got = jax.grad(loss(lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=16, block_k=16, bwd_chunk=16,
            interpret=True)),
            argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss(lambda q, k, v: dense_attention(
            q, k, v, causal=True)), argnums=(0, 1, 2))(q, k, v)
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, atol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_equal_widths_as_before(self, causal):
        """Equal widths: what the kernel returned before this family, to
        float32 rounding (bit for bit when not causal until PR 44, whose
        step sums a row by lanes first and scales inside the exponent).
        Causal: the blocks it now skips added exact zeros."""
        q, k, v = _qkv(64, 16, 16, seed=3)
        got = flash_attention(q, k, v, causal=causal, block_q=16,
                              block_k=16, interpret=True)
        before = _flash_pr26(q, k, v, causal, 16, 16)
        np.testing.assert_allclose(got, before, atol=1e-6)


# --------------------------------------------------------------------- #
# weight import                                                         #
# --------------------------------------------------------------------- #

def _interleave(cols: np.ndarray) -> np.ndarray:
    """Rotate-half channels (x0, x1, .., y0, y1, ..) on the last axis ->
    a checkpoint's interleaved ones (x0, y0, x1, y1, ..)."""
    half = cols.shape[-1] // 2
    out = np.empty_like(cols)
    out[..., 0::2], out[..., 1::2] = cols[..., :half], cols[..., half:]
    return out


def _as_checkpoint(w: dict, s: dict) -> dict:
    """The reference's arrays under a `deepseek_v3` checkpoint's names and
    torch layouts ((out, in) matrices, fused heads, interleaved rope)."""
    w = {k: np.asarray(v) for k, v in w.items()}
    nope, lat = s["qk_nope_head_dim"], s["kv_lora_rank"]
    sd = {"model.embed_tokens.weight": w["embed"],
          "model.norm.weight": w["ln_final_scale"],
          "lm_head.weight": w["head"].T}
    for i in range(s["num_layers"]):
        at = f"model.layers.{i}."
        q = np.concatenate([w["wq"][i][..., :nope],
                            _interleave(w["wq"][i][..., nope:])], -1)
        kv_a = np.concatenate([w["wkv_a"][i][:, :lat],
                               _interleave(w["wkv_a"][i][:, lat:])], -1)
        sd[at + "input_layernorm.weight"] = w["ln_attn_scale"][i]
        sd[at + "self_attn.q_proj.weight"] = q.reshape(q.shape[0], -1).T
        sd[at + "self_attn.kv_a_proj_with_mqa.weight"] = kv_a.T
        sd[at + "self_attn.kv_a_layernorm.weight"] = w["kv_norm_scale"][i]
        sd[at + "self_attn.kv_b_proj.weight"] = w["wkv_b"][i].reshape(
            lat, -1).T
        sd[at + "self_attn.o_proj.weight"] = w["wo"][i].reshape(
            -1, w["wo"].shape[-1]).T
        sd[at + "self_attn.rotary_emb.inv_freq"] = np.zeros(4, np.float32)
        sd[at + "post_attention_layernorm.weight"] = w["ln_mlp_scale"][i]
        if i < s["dense_layers"]:
            for proj in ("gate", "up", "down"):
                sd[at + f"mlp.{proj}_proj.weight"] = w[f"dense_{proj}"][i].T
            continue
        j = i - s["dense_layers"]
        sd[at + "mlp.gate.weight"] = w["router"][j].T
        sd[at + "mlp.gate.e_score_correction_bias"] = w["router_bias"][j]
        for proj in ("gate", "up", "down"):
            sd[at + f"mlp.shared_experts.{proj}_proj.weight"] = (
                w[f"shared_{proj}"][j].T)
            for n in range(s["n_routed_experts"]):
                sd[at + f"mlp.experts.{n}.{proj}_proj.weight"] = (
                    w[f"expert_{proj}"][j][n].T)
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
        bundle = import_external_weights(str(path), "mla_moe_decoder",
                                         **model)
        part = dict(weights)
        for name in ("expert_gate", "expert_up", "expert_down"):
            part[name] = weights[name][:, held[0]:held[0] + held[1]]
        cfg = {"model": model}
        ids = _ids(2, 20, seed=8)
        got = bundle.module.apply(bundle.variables, ids)
        scale = ref.outputs(part, cfg, ids, "logits").std()
        want = ref.outputs(part, cfg, ids, "token_logprobs")
        assert np.abs(np.asarray(got) - want).max() / scale < F32_LIMIT

    def test_the_rope_permutation_keeps_the_scores(self):
        """Interleaved rotary on a checkpoint's columns and rotate-half
        rotary on the imported ones give the same query-key products."""
        from mmlspark_tpu.nn.import_weights import _rope_to_halves
        from mmlspark_tpu.nn.attention import rotary_xla as _rotary

        rng = np.random.default_rng(0)
        q, k = rng.normal(size=(2, 1, 6, 1, 8)).astype(np.float32)

        def interleaved(x):
            # the checkpoints' own rotation: pairs (x[2i], x[2i + 1])
            half = x.shape[-1] // 2
            freq = 50000.0 ** (-np.arange(half) / half)
            ang = np.arange(x.shape[1])[:, None] * freq
            cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]
            a, b = x[..., 0::2], x[..., 1::2]
            return a * cos - b * sin, b * cos + a * sin

        want = sum((qa * ka).sum(-1) for qa, ka in zip(interleaved(q),
                                                       interleaved(k)))
        perm = _rope_to_halves(8)
        got = (_rotary(jnp.asarray(q[..., perm]), 50000.0)
               * _rotary(jnp.asarray(k[..., perm]), 50000.0)).sum(-1)
        np.testing.assert_allclose(got, want, atol=1e-5)

    def test_an_unknown_name_is_refused(self):
        from mmlspark_tpu.nn.import_weights import (
            torch_mla_moe_decoder_to_flax)

        with pytest.raises(ValueError, match="unrecognized"):
            torch_mla_moe_decoder_to_flax(
                {"model.layers.0.self_attn.q_a_proj.weight": np.zeros((2, 2))},
                4, 32, 16, 8)
