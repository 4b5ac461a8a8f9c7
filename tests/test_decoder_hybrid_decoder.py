"""The `decoder_hybrid_decoder` family against its plain reference
(`benchmark/reference/decoder_hybrid_decoder.py`, which imports nothing of
the program, computes the scan as the RECURRENCE a token at a time and the
attention as four softmax-weighted sums a pair), on seeded weights at tiny
widths: 8 layers, so that every kind of layer is there (two Mamba and two
banded, the Mamba that keeps its scan output, the full attention that keeps
its keys and values, a GMU and a cross layer), hidden 64, 4 query over 2
key/value heads of 16, a Mamba width of 128 with 16 states and a step rank
of 4, a window of 8, rows of 40 and 9 tokens, a tied head over 256 rows. And
the cores by themselves: `nn/scan.py`'s second form against the recurrence
(and its gradient), its kernel interpreted against its plain tier;
`nn/attention/diff.py` in each placement against its definition.

Limits, each with its reason:
- `F32_LIMIT` 1e-4 of the reference's standard deviation: float32 against
  float32, the same recurrence chunked, one softmax-weighted sum over a
  value twice as wide against two over its halves; only the order of the
  sums differs (observed 3e-6);
- `BF16_BAND` 0.3 of it at the 99th percentile for the module in bfloat16,
  and twice that at the largest: products round to 3 digits, eight layers
  deep, and the family is touchy (observed 0.15 at the 99th percentile and
  0.36 at the largest; the weights ALONE rounded to bfloat16 under a
  float32 program read 0.30 at the largest: a step's bias near -7 and A_log
  keep 3 digits, and a pair's subtraction under lambda up to 0.8 divides the
  relative error by 1 - lambda); bfloat16 FAILS float32's limit by a factor
  of a thousand;
- a planted fault has to exceed `FAULT_FLOOR` 1e-3 of it."""

import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.core.schema import Table
from mmlspark_tpu.nn import attention, models, scan
from mmlspark_tpu.nn.attention import diff
from mmlspark_tpu.nn.models import ModelBundle, make_model
from mmlspark_tpu.nn.runner import DeepModelTransformer
from mmlspark_tpu.observability.metrics import get_registry
from mmlspark_tpu.observability.tracing import get_tracer

F32_LIMIT = 1e-4
BF16_BAND = 0.3
FAULT_FLOOR = 1e-3

FAMILY = "decoder_hybrid_decoder"
LAYERS = 8
MODEL = dict(
    num_layers=LAYERS, d_model=64, num_heads=4, num_kv_heads=2,
    mamba_inner=128, mamba_state=16, mamba_dt_rank=4, conv_taps=4,
    window_size=8, d_ff_dense=96, layer_norm_eps=1e-5, vocab_size=256,
    attention_impl="chunked", head_chunk=16)
KINDS = ("mamba", "sliding", "mamba", "sliding", "mamba_keeps", "full_keeps",
         "gmu", "cross")


def _reference(name: str):
    path = (pathlib.Path(__file__).parent.parent / "benchmark" / "reference"
            / f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"ref_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def ref():
    module = _reference("decoder_hybrid_decoder")
    # a row of 40 tokens passes through several blocks: the scan's state and
    # the convolution's tail are carried, the kept arrays written in parts
    module.TOKEN_BLOCK = 8
    return module


@pytest.fixture(scope="module")
def seeded(ref):
    """(the reference's float32 weights, the module's variables)."""
    config = {"model": MODEL}
    weights = ref.weights(jax.random.PRNGKey(7), config)
    return weights, ref.variables(weights, config)


def _config(**changed) -> dict:
    return {"model": dict(MODEL, **changed)}


def _ids(rows: int, length: int, seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        0, MODEL["vocab_size"], (rows, length), dtype=np.int32)


def _gap(got, want, scale=None) -> float:
    want = np.asarray(want, np.float64)
    return float(np.abs(np.asarray(got, np.float64) - want).max()
                 / (want.std() if scale is None else scale))


def _apply(variables, ids, **changed):
    """-> (what the module returns, what it sows)."""
    out, state = make_model(FAMILY, **dict(MODEL, **changed)).apply(
        variables, ids, capture_intermediates=True,
        mutable=["intermediates"])
    return out, state["intermediates"]


# --------------------------------------------------------------------- #
# the module against the reference                                      #
# --------------------------------------------------------------------- #

class TestModuleAgainstReference:
    def test_the_layers_follow_the_published_rule(self, ref):
        assert models.hybrid_layer_kinds(LAYERS) == KINDS == ref.kinds(LAYERS)
        assert models.hybrid_layer_kinds(16) == ref.kinds(16)
        sixteen = models.hybrid_layer_kinds(16)
        assert sixteen[8:10] == ("mamba_keeps", "full_keeps")
        assert [sixteen.count(k) for k in ("mamba", "sliding", "gmu",
                                           "cross")] == [4, 4, 3, 3]
        assert models.hybrid_layer_kinds(32) == ref.kinds(32)

    @pytest.mark.parametrize("layers", [2, 6, 10, 15])
    def test_a_depth_that_is_no_multiple_of_four_raises(self, layers):
        with pytest.raises(ValueError, match="multiple of 4"):
            make_model(FAMILY, **dict(MODEL, num_layers=layers)).init(
                jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))

    def test_tree_is_what_the_reference_names(self, seeded):
        _w, variables = seeded
        init = make_model(FAMILY, **MODEL).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.float32))
        assert (jax.tree.structure(init["params"])
                == jax.tree.structure(variables["params"]))
        for ours, theirs in zip(jax.tree.leaves(init["params"]),
                                jax.tree.leaves(variables["params"])):
            assert ours.shape == theirs.shape
        params = init["params"]
        # an operator, a feed-forward and two norms a layer; the embedding
        # (which is the head) and the final norm
        assert len(params) == 4 * LAYERS + 2
        assert "head_kernel" not in params
        for norm in ("ln_op_0", "ln_mlp_7", "ln_final"):
            assert set(params[norm]) == {"scale", "bias"}
        assert jax.tree.map(jnp.shape, params["mamba_4"]) == {
            "in_proj": {"kernel": (64, 256)}, "conv_kernel": (128, 4),
            "conv_bias": (128,), "x_proj": {"kernel": (128, 36)},
            "dt_kernel": (4, 128), "dt_bias": (128,), "A_log": (128, 16),
            "D": (128,), "out_proj": {"kernel": (128, 64)}}
        assert jax.tree.map(jnp.shape, params["gmu_6"]) == {
            "in_proj": {"kernel": (64, 128)},
            "out_proj": {"kernel": (128, 64)}}

    def test_a_cross_layer_holds_no_key_or_value_projection(self, seeded):
        _w, variables = seeded
        params = variables["params"]
        own = {"q_proj", "out", "lambda_q1", "lambda_k1", "lambda_q2",
               "lambda_k2", "norm_scale"}
        assert set(params["diff_attn_7"]) == own
        for name in ("diff_swa_1", "diff_swa_3", "diff_attn_5"):
            assert set(params[name]) == own | {"k_proj", "v_proj"}
        assert set(params["diff_attn_5"]["q_proj"]) == {"kernel", "bias"}
        assert params["diff_attn_5"]["k_proj"]["kernel"].shape == (64, 32)
        assert params["diff_attn_5"]["norm_scale"].shape == (32,)

    @pytest.mark.parametrize("rows,length", [(3, 40), (2, 9)])
    def test_logits_and_logprobs_every_position(self, ref, seeded, rows,
                                                length):
        weights, variables = seeded
        ids = _ids(rows, length)
        want = ref.outputs(weights, _config(), ids, "logits")
        logits, _ = _apply(variables, ids, output="logits")
        assert logits.shape == (rows, length, 256)
        assert _gap(logits, want) < F32_LIMIT
        logprobs, sown = _apply(variables, ids)
        assert logprobs.shape == (rows, length - 1)
        assert np.array_equal(logprobs, sown["token_logprobs"][0])
        # in units of the LOGITS' spread, as the logits are
        assert _gap(logprobs, ref.outputs(weights, _config(), ids,
                                          "token_logprobs"),
                    want.std()) < F32_LIMIT

    def test_the_hidden_state_is_the_normed_stream(self, ref, seeded):
        weights, variables = seeded
        ids = _ids(2, 40, seed=3)
        _out, sown = _apply(variables, ids)
        assert _gap(sown["hidden"][0], ref.outputs(
            weights, _config(), ids, "hidden")) < F32_LIMIT

    @pytest.mark.parametrize("length", [40, 9])
    def test_bfloat16_stays_in_its_band_and_fails_float32s(
            self, ref, seeded, length):
        weights, variables = seeded
        ids = _ids(3, length, seed=4)
        logits = ref.outputs(weights, _config(), ids, "logits")
        want = ref.outputs(weights, _config(), ids, "token_logprobs")
        low = jax.tree.map(lambda a: a.astype(jnp.bfloat16), variables)
        got, _ = _apply(low, ids, dtype=jnp.bfloat16)
        gaps = np.abs(np.asarray(got, np.float64) - want) / logits.std()
        assert np.quantile(gaps, 0.99) < BF16_BAND
        assert gaps.max() > 10 * F32_LIMIT       # and it did round
        got, _ = _apply(low, ids, dtype=jnp.bfloat16, output="logits")
        assert 10 * F32_LIMIT < _gap(got, logits) < 2 * BF16_BAND

    @pytest.mark.parametrize("impl", ["dense", "chunked", "flash"])
    def test_every_attention_tier_gives_the_same(self, ref, seeded, impl):
        weights, variables = seeded
        ids = _ids(2, 40, seed=5)
        want = ref.outputs(weights, _config(), ids, "logits")
        got, _ = _apply(variables, ids, attention_impl=impl,
                        output="logits")
        assert _gap(got, want) < F32_LIMIT

    def test_a_rows_value_depends_on_no_other_row(self, seeded):
        _w, variables = seeded
        ids = _ids(3, 40, seed=6)
        together, _ = _apply(variables, ids)
        for r in range(3):
            alone, _ = _apply(variables, ids[r:r + 1])
            assert _gap(together[r:r + 1], alone, 1.0) < 1e-5

    def test_lambda_follows_the_layers_index(self, ref, seeded):
        """lambda_init by the index from 0, in the module and the reference
        alike: the reference told another index for ONE layer no longer
        agrees."""
        for i in (0, 1, 9, 31):
            want = 0.8 - 0.6 * np.exp(-0.3 * i)
            assert diff.lambda_init(i) == pytest.approx(want)
            assert ref.lambda_init(i) == pytest.approx(want)
        assert diff.lambda_init(0) == pytest.approx(0.2)
        weights, variables = seeded
        ids = _ids(2, 23, seed=8)
        sound = ref.lambda_init
        ref.lambda_init = lambda i: sound(i + 1 if i == 5 else i)
        try:
            want = ref.outputs(weights, _config(), ids, "logits")
        finally:
            ref.lambda_init = sound
        got, _ = _apply(variables, ids, output="logits")
        assert _gap(got, want) > FAULT_FLOOR

    def test_a_planted_fault_in_the_reference_shows(self, ref, seeded,
                                                    monkeypatch):
        """The comparison can fail: a reference whose state decays a token
        late is not what the module computes (a length of its own: the
        reference's programs are traced once a shape)."""
        weights, variables = seeded
        ids = _ids(2, 31, seed=9)
        sound = ref.recurrence

        def late(x, dt, a, bm, cm, d_skip, state):
            shifted = jnp.concatenate([jnp.zeros_like(dt[:, :1]),
                                       dt[:, :-1]], 1)
            return sound(x, shifted, a, bm, cm, d_skip, state)

        monkeypatch.setattr(ref, "recurrence", late)
        want = ref.outputs(weights, _config(), ids, "logits")
        monkeypatch.undo()
        got, _ = _apply(variables, ids, output="logits")
        assert _gap(got, want) > FAULT_FLOOR

    @pytest.mark.parametrize("what", ["memory", "keys"])
    def test_the_second_half_reads_what_the_two_layers_kept(
            self, seeded, what):
        """A GMU reads layer N/2's scan output and a cross layer layer
        N/2 + 1's keys and values: with THAT layer's weights changed (its
        A_log; its key projection) the layers before it are what they were,
        and what the later layer adds to the stream moves."""
        _w, variables = seeded
        ids = _ids(1, 24, seed=10)
        params = dict(variables["params"])
        if what == "memory":
            layer = dict(params["mamba_4"])
            layer["A_log"] = layer["A_log"] + 0.5
            params["mamba_4"] = layer
        else:
            layer = dict(params["diff_attn_5"])
            layer["k_proj"] = jax.tree.map(lambda a: 1.5 * a,
                                           layer["k_proj"])
            params["diff_attn_5"] = layer

        def reads(p):
            _out, state = make_model(FAMILY, **MODEL).apply(
                {"params": p}, ids, capture_intermediates=True,
                mutable=["intermediates"])
            sown = state["intermediates"]
            return sown["gmu_6" if what == "memory" else "diff_attn_7"][
                "__call__"][0]

        before, after = reads(variables["params"]), reads(params)
        before = before[0] if isinstance(before, tuple) else before
        after = after[0] if isinstance(after, tuple) else after
        assert _gap(after, before) > FAULT_FLOOR


# --------------------------------------------------------------------- #
# the keys and values are projected once                                #
# --------------------------------------------------------------------- #

def test_the_kept_keys_and_values_are_projected_once_a_batch(seeded):
    """The lowered program of a batch holds ONE product of the key width a
    self layer (three of them at 8 layers) and none for the cross layer, and
    no array of (tokens, inner, state): the scan never writes its states."""
    _w, variables = seeded
    ids = _ids(2, 40)
    module = make_model(FAMILY, **MODEL)
    text = str(jax.make_jaxpr(lambda v, x: module.apply(v, x))(variables,
                                                                ids))
    # k and v of the three self layers: (2, 40, 64) @ (64, 32)
    assert text.count("f32[2,40,32] = dot_general") == 2 * 3
    for whole in ("[2,40,128,16]", "[40,2,128,16]", "[2,128,16,40]",
                  "[80,128,16]"):
        assert whole not in text, whole


# --------------------------------------------------------------------- #
# the scan core's second form                                           #
# --------------------------------------------------------------------- #

def _recurrence(x, dt, a, bm, cm, d):
    """S_t[c,n] = exp(dt_t[c] A[c,n]) S_{t-1}[c,n] + dt_t[c] B_t[n] x_t[c];
    y_t[c] = sum_n C_t[n] S_t[c,n] + D[c] x_t[c]: float64, a token at a
    time."""
    b, t, c = x.shape
    state = np.zeros((b, c, a.shape[1]))
    out = []
    for i in range(t):
        state = (np.exp(dt[:, i][..., None] * a) * state
                 + (dt[:, i] * x[:, i])[..., None] * bm[:, i][:, None, :])
        out.append((state * cm[:, i][:, None, :]).sum(-1) + d * x[:, i])
    return np.stack(out, 1)


def _scan_inputs(b, t, c, n, seed=1):
    """A channel's step around its own size, from 0.001 to 0.1 over the
    channels (a token's draw moves it by a factor of e^0.5 or so), and A
    from 1 to 16 over the states, as Mamba-1 initialises them."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(b, t, c))
    dt = np.geomspace(1e-3, 0.1, c) * np.exp(0.5 * rng.normal(size=(b, t, c)))
    a = -np.tile(np.linspace(1.0, 16.0, n), (c, 1))
    return (x, dt, a, rng.normal(size=(b, t, n)), rng.normal(size=(b, t, n)),
            rng.normal(size=c))


def _f32(arrays):
    return tuple(jnp.asarray(a, jnp.float32) for a in arrays)


class TestChannelScan:
    # inside one chunk; one chunk; a ragged second; two; a ragged third
    @pytest.mark.parametrize("length", [5, 128, 200, 256, 300])
    def test_the_plain_tier_is_the_recurrence(self, length):
        inputs = _scan_inputs(2, length, 24, 16)
        want = _recurrence(*inputs)
        got = scan.sel_plain(*_f32(inputs))
        assert got.shape == want.shape
        assert _gap(got, want) < F32_LIMIT

    def test_the_decays_span_forgetting_all_to_forgetting_little(self):
        _x, dt, a, _b, _c, _d = _scan_inputs(2, 256, 24, 16)
        over_a_chunk = np.exp(
            dt.reshape(2, 2, 128, 24).sum(2)[..., None] * a).reshape(-1)
        assert over_a_chunk.min() < 1e-3 < 0.5 < over_a_chunk.max()

    @pytest.mark.parametrize("length", [40, 200])
    def test_the_plain_tiers_gradient_is_the_recurrences(self, length):
        inputs = _f32(_scan_inputs(1, length, 8, 16, seed=3))
        weigh = jnp.asarray(np.random.default_rng(4).normal(
            size=(1, length, 8)), jnp.float32)

        def token_by_token(x, dt, a, bm, cm, d):
            def token(state, now):
                x_t, dt_t, b_t, c_t = now
                state = (jnp.exp(dt_t[..., None] * a) * state
                         + (dt_t * x_t)[..., None] * b_t[:, None])
                return state, (state * c_t[:, None]).sum(-1) + d * x_t

            _, y = jax.lax.scan(token, jnp.zeros((1, 8, 16)), tuple(
                jnp.moveaxis(v, 1, 0) for v in (x, dt, bm, cm)))
            return jnp.moveaxis(y, 0, 1)

        want = jax.grad(lambda *v: (token_by_token(*v) * weigh).sum(),
                        argnums=tuple(range(6)))(*inputs)
        got = jax.grad(lambda *v: (scan.sel_plain(*v) * weigh).sum(),
                       argnums=tuple(range(6)))(*inputs)
        for ours, theirs in zip(got, want):
            assert _gap(ours, theirs) < F32_LIMIT

    @pytest.mark.parametrize("length,dtype", [(128, jnp.float32),
                                              (300, jnp.float32),
                                              (200, jnp.bfloat16)])
    def test_the_kernel_interpreted_is_the_plain_tier(self, length, dtype):
        x, dt, a, bm, cm, d = _f32(_scan_inputs(2, length, 256, 16, seed=2))
        x, bm, cm = (v.astype(dtype) for v in (x, bm, cm))
        want = scan.sel_plain(x, dt, a, bm, cm, d)
        got = scan.sel_kernel(x, dt, a, bm, cm, d, interpret=True)
        assert got.dtype == dtype and got.shape == want.shape
        # the same float32 arithmetic token by token; the reduction over the
        # states in another order, then one rounding to the output's type
        assert _gap(got, want) < (F32_LIMIT if dtype == jnp.float32
                                  else 2 ** -7)

    def test_the_kernels_gradient_is_the_plain_tiers(self):
        inputs = _f32(_scan_inputs(1, 150, 128, 8, seed=5))
        want = jax.grad(lambda *v: (scan.sel_plain(*v) ** 2).sum(),
                        argnums=tuple(range(6)))(*inputs)
        got = jax.grad(
            lambda *v: (scan.sel_kernel(*v, interpret=True) ** 2).sum(),
            argnums=tuple(range(6)))(*inputs)
        for ours, theirs in zip(got, want):
            assert _gap(ours, theirs) < F32_LIMIT

    def test_the_tier_is_picked_by_what_can_be_observed(self, monkeypatch):
        assert scan.sel_tier(5120, 16) == "plain"        # the CPU
        monkeypatch.setattr(attention.layout.jax, "default_backend",
                            lambda: "tpu")
        assert scan.sel_tier(5120, 16) == "kernel"
        assert scan.sel_tier(128, 16) == "kernel"
        assert scan.sel_tier(96, 16) == "plain"          # no lane block
        assert scan.sel_tier(128, 12) == "plain"         # no whole sublanes
        assert scan.sel_block(5120) == 512 and scan.sel_block(128) == 128
        assert scan.sel_block(640) == 128
        with pytest.raises(ValueError, match="whole lane blocks"):
            scan.sel_kernel(*_f32(_scan_inputs(1, 8, 96, 16)))

    def test_steps_are_rows_by_channel_blocks_by_chunks(self):
        assert scan.sel_scan_steps(1, 32768, 5120) == 10 * 256
        assert scan.sel_scan_steps(3, 130, 128) == 3 * 1 * 2


# --------------------------------------------------------------------- #
# the differential core                                                 #
# --------------------------------------------------------------------- #

def _definition(q, k, v, lam, heads, kv_heads, window):
    """Per pair of heads, the published form, float64: one masked softmax a
    head, four softmax-weighted sums a pair."""
    b, t, _ = q.shape
    d = q.shape[-1] // heads
    q = q.reshape(b, t, heads, d)
    k = k.reshape(b, t, kv_heads, d)
    v = v.reshape(b, t, kv_heads, d)
    behind = np.arange(t)[:, None] - np.arange(t)[None, :]
    seen = (behind >= 0) & ((behind < window) if window else True)
    group = heads // kv_heads

    def prob(qh, kh):
        s = np.einsum("btd,bsd->bts", qh, kh) / np.sqrt(d)
        s = np.where(seen, s, -np.inf)
        e = np.exp(s - s.max(-1, keepdims=True))
        return e / e.sum(-1, keepdims=True)

    out = []
    for j in range(heads // 2):
        m = j // group
        p1 = prob(q[:, :, 2 * j], k[:, :, 2 * m])
        p2 = prob(q[:, :, 2 * j + 1], k[:, :, 2 * m + 1])
        value = np.concatenate([v[:, :, 2 * m], v[:, :, 2 * m + 1]], -1)
        out.append(np.einsum("bts,bsd->btd", p1 - lam * p2, value))
    return np.stack(out, 2)


class TestDifferentialCore:
    # a window shorter than the row, one as long, one longer; none
    @pytest.mark.parametrize("window", [None, 8, 40, 64])
    @pytest.mark.parametrize("impl,options", [
        ("dense", {}), ("chunked", {}), ("flash", {"interpret": True})])
    @pytest.mark.parametrize("heads,kv_heads", [(8, 4), (4, 4)])
    def test_each_placement_is_its_definition(self, impl, options, window,
                                              heads, kv_heads):
        rng = np.random.default_rng(11)
        q = rng.normal(size=(2, 40, heads * 16))
        k, v = (rng.normal(size=(2, 40, kv_heads * 16)) for _ in range(2))
        want = _definition(q, k, v, 0.37, heads, kv_heads, window)
        q, k, v = _f32((q, k, v))
        got = diff.differential_attention(
            q, diff.key_pairs(k, v, kv_heads), 0.37, impl, window, **options)
        assert got.shape == (2, 40, heads // 2, 32)
        assert got.dtype == jnp.float32
        assert _gap(got, want) < F32_LIMIT

    def test_keys_are_laid_out_once_pair_major(self):
        k = jnp.arange(2 * 5 * 4 * 3, dtype=jnp.float32).reshape(2, 5, 12)
        k1, k2, v = diff.key_pairs(k, -k, 4)
        assert k1.shape == k2.shape == (4, 5, 1, 3) and v.shape == (4, 5, 1,
                                                                    6)
        heads = np.asarray(k).reshape(2, 5, 4, 3)
        # row 1's second pair: heads 2 and 3
        assert np.array_equal(k1[3, :, 0], heads[1, :, 2])
        assert np.array_equal(k2[3, :, 0], heads[1, :, 3])
        assert np.array_equal(v[3, :, 0], -heads[1, :, 2:].reshape(5, 6))
        with pytest.raises(ValueError, match="no pairs"):
            diff.key_pairs(k[..., :9], k[..., :9], 3)

    def test_a_cross_layer_reads_the_kept_keys_in_place(self):
        """What a self layer hands on is what the core reads: the module of
        a cross layer calls the core with the SAME arrays, no copy between
        (the jaxpr of the cross module has no transpose of the keys'
        shape)."""
        module = models.DifferentialAttention(4, 2, depth=7, cross=True,
                                              impl="chunked")
        y = jnp.ones((2, 12, 64))
        keys = diff.key_pairs(jnp.ones((2, 12, 32)), jnp.ones((2, 12, 32)), 2)
        variables = module.init(jax.random.PRNGKey(0), y, keys)
        (_out, handed) = module.apply(variables, y, keys)
        assert all(a is b for a, b in zip(handed, keys))
        text = str(jax.make_jaxpr(lambda v, y, k: module.apply(v, y, k)[0])(
            variables, y, keys))
        assert "f32[2,12,1,16] = transpose" not in text
        assert "f32[2,1,12,16] = transpose" not in text


# --------------------------------------------------------------------- #
# through the runner, unchanged                                         #
# --------------------------------------------------------------------- #

def test_the_family_runs_through_the_streamed_path_and_reports(seeded):
    """`DeepModelTransformer.transform`, batches of 2 over 5 rows, gives the
    module's log-probabilities; the call's root span carries
    `sel_scan_steps` and `shared_reads`, and the registry counts both."""
    _w, variables = seeded
    ids = _ids(5, 40, seed=12)
    bundle = ModelBundle(architecture=FAMILY, config=dict(MODEL),
                         variables=variables, input_shape=(40,))
    stage = DeepModelTransformer(
        input_col="tokens", fetch_dict={"logprob": "token_logprobs"},
        mini_batch_size=2, fused_dispatch=False).set_model(bundle)
    registry = get_registry()
    steps = registry.counter(
        "mmlspark_tpu_sel_scan_steps_total",
        "grid steps of a channel-decay selective scan: rows x channel "
        "blocks x chunks, over the layers and the batches")
    reads = registry.counter(
        "mmlspark_tpu_shared_reads_total",
        "(layer, batch) pairs that read an array an earlier layer kept")
    before = steps.value, reads.value
    tracer = get_tracer()
    was = tracer.enabled
    tracer.enabled = True
    try:
        scored = stage.transform(Table({"tokens": ids}))
        root = [s for s in tracer.spans()
                if s.name == "runner.transform"][-1]
    finally:
        tracer.enabled = was
    want, _ = _apply(variables, ids)
    assert _gap(np.asarray(scored["logprob"]), want, 1.0) < 1e-4
    # three batches of (2, 2, 1) rows, three Mamba layers, one channel block,
    # one chunk; a GMU and a cross layer read a kept array a batch
    assert root.args["sel_scan_steps"] == 5 * 3 * 1 * 1
    assert root.args["shared_reads"] == 2 * 3
    assert "attn_window_tile_pairs" not in root.args     # the chunked tier
    assert (steps.value - before[0], reads.value - before[1]) == (15, 6)


def test_what_a_call_reports_is_reckoned_from_the_batches_shapes(
        monkeypatch):
    """Nothing is sown or read back: rows x channel blocks x chunks over the
    Mamba layers, the layers that read a kept array times the batches, and,
    where the banded kernel runs under a tracer that keeps spans, its tiles
    as `window_moe_decoder` writes them."""
    module = make_model(FAMILY, **dict(MODEL, mamba_inner=1024, d_model=512,
                                       num_layers=16, window_size=512,
                                       attention_impl="flash"))
    assert module.batch_counters == ()
    got = module.call_span_arguments({}, [2, 2, 1], (300,))
    assert got == {"sel_scan_steps": 5 * 5 * 2 * 3, "shared_reads": 6 * 3}
    assert module.call_span_arguments({}, [2], (8, 8, 3)) == {}
    # on the chip's tier a row past the window takes the banded kernel
    assert module.window_tile_pairs(1, 4096) is None          # the CPU
    monkeypatch.setattr(attention.layout.jax, "default_backend",
                        lambda: "tpu")
    assert module.window_tile_pairs(1, 512) is None
    computed, needed = module.window_tile_pairs(2, 4096)
    one = attention.band_tile_pairs(4096, 512, 512, 512)
    # two softmaxes a pair: as many forwards a row as query heads, in the
    # four sliding layers
    assert (computed, needed) == (one[0] * 2 * 4 * 4, one[1] * 2 * 4 * 4)
    assert 1.0 < computed / needed < 2.0
    tracer = get_tracer()
    was = tracer.enabled
    tracer.enabled = True
    try:
        traced = module.call_span_arguments({}, [2], (4096,))
    finally:
        tracer.enabled = was
    assert traced["attn_window_tile_pairs"] == computed
    assert traced["attn_window_tile_pairs_needed"] == needed


# --------------------------------------------------------------------- #
# weight import                                                         #
# --------------------------------------------------------------------- #

def _as_checkpoint(w: dict) -> dict:
    """The reference's arrays under the names the importer EXPECTS of a
    `phi4flash` checkpoint (assumed: its docstring) and torch layouts ((out,
    in) matrices, Wqkv and fc1 fused, Conv1d's (channels, 1, taps))."""
    sd = {"model.embed_tokens.weight": np.asarray(w["embed"]),
          "model.final_layernorm.weight": np.asarray(w["ln_final_scale"]),
          "model.final_layernorm.bias": np.asarray(w["ln_final_bias"]),
          "lm_head.weight": np.asarray(w["embed"])}
    for i, kind in enumerate(KINDS):
        lw = {k: np.asarray(v) for k, v in w["layers"][i].items()}
        at = f"model.layers.{i}."
        for theirs, ours in (("input_layernorm", "ln_op"),
                             ("post_attention_layernorm", "ln_mlp")):
            sd[at + theirs + ".weight"] = lw[ours + "_scale"]
            sd[at + theirs + ".bias"] = lw[ours + "_bias"]
        sd[at + "mlp.fc1.weight"] = lw["w_1"].T
        sd[at + "mlp.fc2.weight"] = lw["w_2"].T
        if kind.startswith("mamba"):
            sd[at + "attn.in_proj.weight"] = lw["w_in"].T
            sd[at + "attn.conv1d.weight"] = lw["conv_w"][:, None, :]
            sd[at + "attn.conv1d.bias"] = lw["conv_b"]
            sd[at + "attn.x_proj.weight"] = lw["w_x"].T
            sd[at + "attn.dt_proj.weight"] = lw["w_dt"].T
            sd[at + "attn.dt_proj.bias"] = lw["dt_bias"]
            sd[at + "attn.A_log"] = lw["a_log"]
            sd[at + "attn.D"] = lw["d_skip"]
            sd[at + "attn.out_proj.weight"] = lw["w_out"].T
        elif kind == "gmu":
            sd[at + "attn.in_proj.weight"] = lw["w_1g"].T
            sd[at + "attn.out_proj.weight"] = lw["w_2g"].T
        else:
            sd[at + "attn.Wqkv.weight"] = lw["w_qkv"].T
            sd[at + "attn.Wqkv.bias"] = lw["b_qkv"]
            sd[at + "attn.out_proj.weight"] = lw["w_o"].T
            sd[at + "attn.out_proj.bias"] = lw["b_o"]
            for ours in ("lq1", "lk1", "lq2", "lk2"):
                sd[at + f"attn.inner_cross_attn.lambda_{ours[1:]}"] = lw[ours]
            sd[at + "attn.inner_cross_attn.subln.weight"] = lw["subln_scale"]
    return sd


class TestWeightImport:
    def test_a_state_dict_under_the_expected_names_round_trips(
            self, ref, seeded, tmp_path):
        """A tiny fabricated state dict under the names the importer
        expects: the fused Wqkv split into q, k and v (a cross layer's into
        q alone), fc1 into gate and up, the mixer's tensors mapped; the
        imported module holds the reference's arrays under the module's
        names, to the bit, and gives what the reference gives."""
        from mmlspark_tpu.nn.import_weights import (
            import_external_weights, torch_decoder_hybrid_decoder_to_flax)

        weights, variables = seeded
        sd = _as_checkpoint(weights)
        mapped = torch_decoder_hybrid_decoder_to_flax(sd, LAYERS, 64)
        assert not mapped["batch_stats"]
        assert (jax.tree.structure(mapped["params"])
                == jax.tree.structure(variables["params"]))
        for ours, theirs in zip(jax.tree.leaves(mapped["params"]),
                                jax.tree.leaves(variables["params"])):
            assert np.array_equal(ours, theirs)
        assert "k_proj" not in mapped["params"]["diff_attn_7"]
        path = tmp_path / "tiny.npz"
        np.savez(path, **sd)
        bundle = import_external_weights(str(path), FAMILY, **MODEL)
        ids = _ids(2, 40, seed=8)
        got = bundle.module.apply(bundle.variables, ids)
        scale = ref.outputs(weights, _config(), ids, "logits").std()
        want = ref.outputs(weights, _config(), ids, "token_logprobs")
        assert np.abs(np.asarray(got) - want).max() / scale < F32_LIMIT

    def test_an_unknown_name_and_a_missing_vector_are_refused(self, seeded,
                                                              tmp_path):
        from mmlspark_tpu.nn.import_weights import (
            import_external_weights, torch_decoder_hybrid_decoder_to_flax)

        with pytest.raises(ValueError, match="unrecognized"):
            torch_decoder_hybrid_decoder_to_flax(
                {"model.layers.0.attn.B_log": np.zeros(4)}, LAYERS, 64)
        weights, _v = seeded
        sd = _as_checkpoint(weights)
        del sd["model.layers.4.attn.dt_proj.bias"]
        path = tmp_path / "short.npz"
        np.savez(path, **sd)
        with pytest.raises(ValueError, match="dt_bias"):
            import_external_weights(str(path), FAMILY, **MODEL)
