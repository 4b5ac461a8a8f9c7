"""The `ssm_hybrid_decoder` family against its plain reference
(`benchmark/reference/ssm_hybrid_decoder.py`, which imports nothing of the
program and computes the scan as the RECURRENCE, a token at a time), on
seeded weights at tiny widths: hidden 64, 4 query heads over 2 key/value
heads of 16 channels, a state-space mixer of 4 heads of 16 channels with 2
groups of 32 state channels and 4 taps, a gated feed-forward of 128, 3
layers, the published multipliers, an untied head over 256 rows. And the
scan core (`nn/scan.py`) by itself: the plain tier against the recurrence,
the kernel tier interpreted against the plain tier.

Limits, each with its reason:
- `F32_LIMIT` 1e-4 of the reference's standard deviation: float32 against
  float32, the chunked algebra against the recurrence, only the order of
  the sums differs (observed 8e-6);
- `BF16_BAND` 0.3 of it for the module in bfloat16: products round to 3
  digits, three layers of two mixers deep (observed 0.04 at the 99th
  percentile, 0.09 at the largest), far under what a planted fault gives;
- a planted fault, and a multiplier moved alone, has to exceed
  `FAULT_FLOOR` 1e-3 of it: a scalar left out is not an order of sums."""

import functools
import importlib.util
import inspect
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.core.schema import Table
from mmlspark_tpu.nn import models, scan
from mmlspark_tpu.nn.models import ModelBundle, SSMHybridDecoder, make_model
from mmlspark_tpu.nn.runner import DeepModelTransformer
from mmlspark_tpu.observability.metrics import get_registry
from mmlspark_tpu.observability.tracing import get_tracer

F32_LIMIT = 1e-4
BF16_BAND = 0.3
FAULT_FLOOR = 1e-3

FAMILY = "ssm_hybrid_decoder"
LAYERS = 3
# the published scalars (Falcon-H1-34B-Instruct's config.json)
MULTIPLIERS = dict(
    embedding_multiplier=5.656854249492381,
    key_multiplier=0.011048543456039804, attention_in_multiplier=1.0,
    attention_out_multiplier=0.0375, ssm_in_multiplier=0.25,
    ssm_out_multiplier=0.08838834764831845,
    ssm_multipliers=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                     0.3535533905932738),
    mlp_multipliers=(0.1767766952966369, 0.011160714285714284),
    lm_head_multiplier=0.0078125)
MODEL = dict(
    num_layers=LAYERS, d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
    ssm_heads=4, ssm_head_dim=16, ssm_groups=2, ssm_state=32, conv_taps=4,
    d_ff_dense=128, rms_norm_eps=1e-5, rope_theta=1e11, vocab_size=256,
    attention_impl="chunked", head_chunk=16, **MULTIPLIERS)


def _reference(name: str):
    path = (pathlib.Path(__file__).parent.parent / "benchmark" / "reference"
            / f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"ref_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def ref():
    module = _reference("ssm_hybrid_decoder")
    # rows of a few hundred tokens pass through several blocks, the scan's
    # state and the convolution's tail carried between them
    module.TOKEN_BLOCK = 128
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
        # two mixers a layer under ONE norm, the attention under the name
        # the accepted reader selects; a feed-forward under its own
        assert sorted(k for k in params if k.endswith("_1")) == [
            "gqa_attn_1", "ln_mlp_1", "ln_op_1", "mlp_1", "ssm_1"]
        assert len(params) == 5 * LAYERS + 3
        assert set(params["gqa_attn_0"]) == {"q_proj", "k_proj", "v_proj",
                                             "out"}
        # [z | x B C | dt] = 64 + (64 + 2 x 64) + 4; the taps over x, B, C
        assert jax.tree.map(jnp.shape, params["ssm_0"]) == {
            "in_proj": {"kernel": (64, 260)}, "conv_kernel": (192, 4),
            "conv_bias": (192,), "dt_bias": (4,), "A_log": (4,), "D": (4,),
            "norm_scale": (64,), "out_proj": {"kernel": (64, 64)}}
        assert params["head_kernel"].shape == (64, 256)

    # one chunk; chunks and a ragged one; the reference's blocks ragged too
    @pytest.mark.parametrize("rows,length", [(3, 40), (2, 128), (2, 300)])
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
        ids = _ids(2, 72, seed=3)
        _out, sown = _apply(variables, ids)
        assert _gap(sown["hidden"][0], ref.outputs(
            weights, _config(), ids, "hidden")) < F32_LIMIT

    def test_bfloat16_stays_in_its_band(self, ref, seeded):
        weights, variables = seeded
        ids = _ids(3, 200, seed=4)
        scale = ref.outputs(weights, _config(), ids, "logits").std()
        want = ref.outputs(weights, _config(), ids, "token_logprobs")
        got, _ = _apply(jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                                     variables), ids, dtype=jnp.bfloat16)
        gaps = np.abs(np.asarray(got, np.float64) - want) / scale
        assert np.quantile(gaps, 0.99) < BF16_BAND
        assert gaps.max() > 10 * F32_LIMIT       # and it did round

    @pytest.mark.parametrize("impl", ["dense", "chunked", "flash"])
    def test_every_attention_tier_gives_the_same(self, ref, seeded, impl):
        weights, variables = seeded
        ids = _ids(2, 60, seed=5)
        want = ref.outputs(weights, _config(), ids, "logits")
        got, _ = _apply(variables, ids, attention_impl=impl,
                        output="logits")
        assert _gap(got, want) < F32_LIMIT

    def test_a_rows_value_depends_on_no_other_row(self, seeded):
        """No state of the scan, and no tap of the convolution, leaks from a
        row into the next row of its batch: a row scored in a batch is the
        row scored alone."""
        _w, variables = seeded
        ids = _ids(3, 200, seed=6)
        together, _ = _apply(variables, ids)
        for r in range(3):
            alone, _ = _apply(variables, ids[r:r + 1])
            assert _gap(together[r:r + 1], alone, 1.0) < 1e-5

    def test_a_planted_fault_in_the_reference_shows(self, ref, seeded,
                                                    monkeypatch):
        """The comparison can fail: a reference whose state decays a token
        late (a token's own decay left out of the state it reads) is not
        what the module computes. A length of its own: the reference's
        programs are traced once a shape."""
        weights, variables = seeded
        ids = _ids(2, 61, seed=9)
        sound = ref.recurrence

        def late(xs, bm, cm, dt, a, d_skip, state):
            shifted = jnp.concatenate([jnp.zeros_like(dt[:, :1]),
                                       dt[:, :-1]], 1)
            return sound(xs, bm, cm, shifted, a, d_skip, state)

        monkeypatch.setattr(ref, "recurrence", late)
        want = ref.outputs(weights, _config(), ids, "logits")
        monkeypatch.undo()
        got, _ = _apply(variables, ids, output="logits")
        assert _gap(got, want) > FAULT_FLOOR


# --------------------------------------------------------------------- #
# the multipliers                                                       #
# --------------------------------------------------------------------- #

def _moved(name):
    value = MULTIPLIERS[name]
    if isinstance(value, tuple):
        return [(f"{name}[{i}]", {name: tuple(
            1.5 * v if j == i else v for j, v in enumerate(value))})
            for i in range(len(value))]
    return [(name, {name: 1.5 * value})]


EVERY_MULTIPLIER = [case for name in MULTIPLIERS for case in _moved(name)]


class TestMultipliers:
    def test_there_are_fourteen_scalars(self):
        assert len(EVERY_MULTIPLIER) == 14

    @pytest.mark.parametrize("label,changed", EVERY_MULTIPLIER,
                             ids=[c[0] for c in EVERY_MULTIPLIER])
    def test_each_one_moved_alone_moves_the_output_as_the_reference_says(
            self, ref, seeded, label, changed):
        """None is silently 1, and none sits in another's seat: with one
        scalar moved by half (weights unchanged) the module still agrees
        with the reference under the same change, and both moved."""
        weights, variables = seeded
        ids = _ids(2, 60, seed=11)
        before = ref.outputs(weights, _config(), ids, "logits")
        want = ref.outputs(weights, _config(**changed), ids, "logits")
        assert _gap(want, before) > FAULT_FLOOR, label
        got, _ = _apply(variables, ids, output="logits", **changed)
        assert _gap(got, want) < F32_LIMIT, label

    def test_at_one_a_multiplier_is_no_operation(self):
        """A family without multipliers lowers to what it did: the seats
        the skeleton, the feed-forward and the attention gained add no
        equation at 1."""
        x = jnp.ones((2, 3), jnp.bfloat16)
        assert models._times(x, 1.0) is x
        assert models._times(x, 0.5).dtype == jnp.bfloat16

        def equations(**kw):
            module = models.GatedFFN(16, jnp.float32, **kw)
            v = module.init(jax.random.PRNGKey(0), jnp.ones((2, 8)))
            return str(jax.make_jaxpr(lambda v, x: module.apply(v, x))(
                v, jnp.ones((2, 8)))).count(" = ")

        assert equations(multipliers=(0.5, 0.25)) == equations() + 2


# --------------------------------------------------------------------- #
# the scan core                                                         #
# --------------------------------------------------------------------- #

def _recurrence(x, bm, cm, dt, a, d):
    """S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T; y_t = S_t C_t + D x_t,
    float64, a token at a time."""
    b, t, h, p = x.shape
    g, n = bm.shape[2:]
    per = h // g
    state = np.zeros((b, h, p, n))
    out = []
    for i in range(t):
        keep = np.exp(dt[:, i] * a)
        bh = np.repeat(bm[:, i], per, axis=1)
        ch = np.repeat(cm[:, i], per, axis=1)
        state = (keep[..., None, None] * state + dt[:, i][..., None, None]
                 * x[:, i][..., None] * bh[:, :, None, :])
        out.append(np.einsum("bhpn,bhn->bhp", state, ch)
                   + d[None, :, None] * x[:, i])
    return np.stack(out, 1)


def _scan_inputs(b, t, h, p, g, n, seed=1):
    """A head's step around its own size, from 0.001 to 0.1 over the heads
    (a token's draw moves it by a factor of e^0.5 or so), and A from 1 to
    16: a head's decay over a chunk spans forgetting little to forgetting
    everything, as Mamba-2's initial draws make it."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(b, t, h, p))
    bm = rng.normal(size=(b, t, g, n))
    cm = rng.normal(size=(b, t, g, n))
    dt = np.geomspace(1e-3, 0.1, h) * np.exp(0.5 * rng.normal(size=(b, t, h)))
    return x, bm, cm, dt, -np.linspace(1.0, 16.0, h), rng.normal(size=h)


def _f32(arrays):
    return tuple(jnp.asarray(a, jnp.float32) for a in arrays)


class TestScanCore:
    # inside one chunk; one chunk; a ragged second; two; ragged third
    @pytest.mark.parametrize("length", [5, 128, 200, 256, 300])
    def test_the_plain_tier_is_the_recurrence(self, length):
        inputs = _scan_inputs(2, length, 4, 8, 2, 16)
        want = _recurrence(*inputs)
        got = scan.ssd_plain(*_f32(inputs))
        assert got.shape == want.shape
        assert _gap(got, want) < F32_LIMIT

    def test_the_decays_span_forgetting_all_to_forgetting_little(self):
        _x, _b, _c, dt, a, _d = _scan_inputs(2, 256, 4, 8, 2, 16)
        over_a_chunk = np.exp(
            (dt * a).reshape(2, 2, 128, 4).sum(2)).reshape(-1)
        assert over_a_chunk.min() < 1e-3 < 0.2 < over_a_chunk.max()

    @pytest.mark.parametrize("length", [128, 300])
    def test_the_kernel_interpreted_is_the_plain_tier(self, length):
        b, h, p, g, n = 2, 4, 128, 2, 128
        x, bm, cm, dt, a, d = _scan_inputs(b, length, h, p, g, n, seed=2)
        xbc = jnp.asarray(np.concatenate(
            [x.reshape(b, length, -1), bm.reshape(b, length, -1),
             cm.reshape(b, length, -1)], -1), jnp.float32)
        got = scan.ssd_kernel(xbc, *_f32((dt, a, d)), heads=h, width=p,
                              groups=g, state=n, interpret=True)
        want = scan.ssd_plain(*_f32((x, bm, cm, dt, a, d)))
        assert got.shape == (b, length, h * p)
        assert _gap(got, want.reshape(b, length, -1)) < F32_LIMIT
        assert _gap(got, _recurrence(x, bm, cm, dt, a, d).reshape(
            b, length, -1)) < F32_LIMIT

    def test_the_kernel_in_bfloat16_rounds_as_the_plain_tier_does(self):
        b, t, h, p, g, n = 1, 256, 2, 128, 1, 256
        x, bm, cm, dt, a, d = _scan_inputs(b, t, h, p, g, n, seed=3)
        xbc = jnp.asarray(np.concatenate(
            [x.reshape(b, t, -1), bm.reshape(b, t, -1),
             cm.reshape(b, t, -1)], -1), jnp.bfloat16)
        got = scan.ssd_kernel(xbc, *_f32((dt, a, d)), heads=h, width=p,
                              groups=g, state=n, interpret=True)
        assert got.dtype == jnp.bfloat16
        want = _recurrence(x, bm, cm, dt, a, d).reshape(b, t, -1)
        gaps = np.abs(np.asarray(got, np.float64) - want) / want.std()
        assert np.quantile(gaps, 0.99) < 0.05

    def test_a_rows_state_does_not_reach_the_next_row(self):
        """The kernel's scratch carries a head's state along the chunk axis
        and is zeroed at a row's first chunk: the second row of a batch is
        that row scanned alone."""
        b, t, h, p, g, n = 2, 256, 2, 128, 1, 128
        x, bm, cm, dt, a, d = _scan_inputs(b, t, h, p, g, n, seed=4)
        xbc = jnp.asarray(np.concatenate(
            [x.reshape(b, t, -1), bm.reshape(b, t, -1),
             cm.reshape(b, t, -1)], -1), jnp.float32)
        dt = jnp.asarray(dt, jnp.float32)
        kw = dict(heads=h, width=p, groups=g, state=n, interpret=True)
        both = scan.ssd_kernel(xbc, dt, *_f32((a, d)), **kw)
        alone = scan.ssd_kernel(xbc[1:], dt[1:], *_f32((a, d)), **kw)
        assert np.array_equal(both[1:], alone)

    def test_the_tier_rule(self, monkeypatch):
        from mmlspark_tpu.nn import attention

        # the CPU runs the plain tier at any width; no option says otherwise
        assert scan.tier(128, 256) == "plain"
        monkeypatch.setattr(attention.layout.jax, "default_backend",
                            lambda: "tpu")
        assert scan.tier(128, 256) == "kernel"
        assert scan.tier(16, 32) == "plain"               # not whole lanes
        assert scan.tier(128, 32) == "plain"
        assert "impl" not in inspect.signature(
            scan.selective_scan).parameters
        assert not hasattr(make_model(FAMILY, **MODEL), "scan_impl")
        # ONE function under `nn/` asks the backend, and it is attention's
        source = (pathlib.Path(scan.__file__)).read_text()
        assert "default_backend" not in source.split('"""', 2)[2]

    def test_the_kernel_refuses_widths_it_cannot_read_in_place(self):
        with pytest.raises(ValueError, match="whole lane blocks"):
            scan.ssd_kernel(jnp.zeros((1, 8, 16 * 4 + 2 * 32)),
                            jnp.zeros((1, 8, 4)), jnp.zeros(4), jnp.zeros(4),
                            heads=4, width=16, groups=1, state=32)

    def test_the_plain_tier_has_a_backward(self):
        inputs = _f32(_scan_inputs(1, 130, 2, 4, 1, 8))

        def loss(x, dt):
            return scan.ssd_plain(x, inputs[1], inputs[2], dt,
                                  *inputs[4:]).sum()

        gx, gdt = jax.grad(loss, (0, 1))(inputs[0], inputs[3])
        assert np.isfinite(gx).all() and np.isfinite(gdt).all()
        assert float(jnp.abs(gdt).max()) > 0

    @pytest.mark.parametrize("length", [128, 200])
    def test_the_kernels_backward_is_the_plain_tiers(self, length):
        """`jax.grad` through the kernel tier (interpreted here) runs: its
        backward is the plain tier's VJP on what the forward was given, so
        every input's gradient is the plain tier's own."""
        b, h, p, g, n = 1, 2, 128, 1, 128
        x, bm, cm, dt, a, d = _scan_inputs(b, length, h, p, g, n, seed=5)
        xbc = jnp.asarray(np.concatenate(
            [x.reshape(b, length, -1), bm.reshape(b, length, -1),
             cm.reshape(b, length, -1)], -1), jnp.float32)
        weight = jnp.asarray(np.random.default_rng(6).normal(
            size=(b, length, h * p)), jnp.float32)
        sizes = dict(heads=h, width=p, groups=g, state=n)

        def through(run):
            return jax.grad(
                lambda *given: (run(*given, **sizes) * weight).sum(),
                (0, 1, 2, 3))(xbc, *_f32((dt, a, d)))

        got = through(functools.partial(scan.ssd_kernel, interpret=True))
        want = through(scan._plain_flat)
        for ours, theirs in zip(got, want):
            assert ours.shape == theirs.shape
            assert float(jnp.abs(theirs).max()) > 0
            assert _gap(ours, theirs) < F32_LIMIT


# --------------------------------------------------------------------- #
# the normal path: ModelBundle -> DeepModelTransformer, streamed        #
# --------------------------------------------------------------------- #

def _stage(variables, fetch, batch=2, **changed):
    bundle = ModelBundle(architecture=FAMILY, config=dict(MODEL, **changed),
                         variables=variables, input_shape=(40,))
    return DeepModelTransformer(
        input_col="tokens", fetch_dict=fetch, mini_batch_size=batch,
        fused_dispatch=False).set_model(bundle)


def _last_root():
    return [s for s in get_tracer().spans()
            if s.name == "runner.transform"][-1]


class TestThroughTheRunner:
    def test_the_streamed_path_scores_what_the_reference_does(self, ref,
                                                              seeded):
        weights, variables = seeded
        ids = _ids(5, 40, seed=12)
        tracer = get_tracer()
        was = tracer.enabled
        tracer.enabled = True
        steps = get_registry().counter(
            "mmlspark_tpu_ssd_steps_total",
            "chunks a state-space scan stepped through: rows x heads "
            "x chunks, over the layers and the batches")
        before = steps.value
        try:
            out = _stage(variables, {"logprob": "token_logprobs"}).transform(
                Table({"tokens": ids}))
        finally:
            tracer.enabled = was
        scale = ref.outputs(weights, _config(), ids, "logits").std()
        assert _gap(out["logprob"], ref.outputs(
            weights, _config(), ids, "token_logprobs"), scale) < F32_LIMIT
        # three batches of (2, 2, 1 padded to 1) rows: rows x 4 heads x 1
        # chunk x 3 layers, padding rows' included
        args = _last_root().args
        assert args["ssd_steps"] == 5 * 4 * 1 * LAYERS
        assert steps.value - before == 5 * 4 * 1 * LAYERS

    def test_the_steps_are_reckoned_from_the_batches_shapes(self):
        """Nothing is sown or read back for `ssd_steps`: it is the batches'
        rows x heads x chunks x layers, padding rows' included."""
        module = make_model(FAMILY, **MODEL)
        assert module.batch_counters == ()
        assert module._dense_layers == LAYERS
        assert module.call_span_arguments({}, [2, 2, 1], (300,)) == {
            "ssd_steps": 5 * 4 * 3 * LAYERS}
        assert "ssd_steps" not in module.call_span_arguments(
            {}, [2], (8, 8, 3))

    def test_long_rows_count_their_chunks(self, seeded):
        _w, variables = seeded
        ids = _ids(2, 300, seed=13)
        _stage(variables, {"logprob": "token_logprobs"}, batch=1).transform(
            Table({"tokens": ids}))
        assert _last_root().args["ssd_steps"] == 2 * 4 * 3 * LAYERS

    def test_a_row_past_max_len_is_refused(self, seeded):
        _w, variables = seeded
        with pytest.raises(ValueError, match="max_len"):
            make_model(FAMILY, **dict(MODEL, max_len=32)).apply(
                variables, _ids(1, 40))

    def test_the_registry_has_the_family(self):
        assert isinstance(make_model(FAMILY, **MODEL), SSMHybridDecoder)
        assert FAMILY in models.ARCHITECTURES
        # lists, as a JSON configuration brings them
        module = make_model(FAMILY, **dict(
            MODEL, ssm_multipliers=list(MODEL["ssm_multipliers"]),
            mlp_multipliers=list(MODEL["mlp_multipliers"])))
        assert module.ssm_multipliers == MODEL["ssm_multipliers"]


# --------------------------------------------------------------------- #
# weight import                                                         #
# --------------------------------------------------------------------- #

def _as_checkpoint(w: dict, layers: int) -> dict:
    """The reference's arrays under a `falcon_h1` checkpoint's names and
    torch layouts ((out, in) matrices, fused heads, Conv1d's (channels, 1,
    taps))."""
    w = {k: [np.asarray(a) for a in v] if isinstance(v, list)
         else np.asarray(v) for k, v in w.items()}
    sd = {"model.embed_tokens.weight": w["embed"],
          "model.final_layernorm.weight": w["ln_final_scale"],
          "model.rotary_emb.inv_freq": np.zeros(8),
          "lm_head.weight": w["head"].T}
    for i in range(layers):
        at = f"model.layers.{i}."
        sd[at + "input_layernorm.weight"] = w["ln_op_scale"][i]
        sd[at + "pre_ff_layernorm.weight"] = w["ln_mlp_scale"][i]
        sd[at + "mamba.in_proj.weight"] = w["w_in"][i].T
        sd[at + "mamba.conv1d.weight"] = w["conv_w"][i][:, None, :]
        sd[at + "mamba.conv1d.bias"] = w["conv_b"][i]
        sd[at + "mamba.dt_bias"] = w["dt_bias"][i]
        sd[at + "mamba.A_log"] = w["a_log"][i]
        sd[at + "mamba.D"] = w["d_skip"][i]
        sd[at + "mamba.norm.weight"] = w["gate_norm_scale"][i]
        sd[at + "mamba.out_proj.weight"] = w["w_out"][i].T
        sd[at + "mamba.mup_vector"] = np.ones(260)
        for p in "qkv":
            m = w["w" + p][i]
            sd[at + f"self_attn.{p}_proj.weight"] = m.reshape(
                m.shape[0], -1).T
        sd[at + "self_attn.o_proj.weight"] = w["wo"][i].reshape(
            -1, w["wo"][i].shape[-1]).T
        for name in ("gate", "up", "down"):
            sd[at + f"feed_forward.{name}_proj.weight"] = w[name][i].T
    return sd


class TestWeightImport:
    def test_a_falcon_h1_named_state_dict_round_trips(self, ref, seeded,
                                                      tmp_path):
        """A tiny fabricated state dict under the checkpoint's names: the
        imported module holds the reference's arrays under the module's
        names, to the bit, and gives what the reference gives."""
        from mmlspark_tpu.nn.import_weights import (
            SSM_HYBRID_DECODER_SPEC, apply_mapping_spec,
            import_external_weights)

        weights, variables = seeded
        sd = _as_checkpoint(weights, LAYERS)
        mapped = apply_mapping_spec(sd, SSM_HYBRID_DECODER_SPEC,
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

    def test_an_unknown_name_and_a_missing_vector_are_refused(self, seeded,
                                                              tmp_path):
        from mmlspark_tpu.nn.import_weights import (
            import_external_weights, torch_ssm_hybrid_decoder_to_flax)

        with pytest.raises(ValueError, match="unrecognized"):
            torch_ssm_hybrid_decoder_to_flax(
                {"model.layers.0.mamba.B_log": np.zeros(4)}, 4, 16)
        weights, _v = seeded
        sd = _as_checkpoint(weights, LAYERS)
        del sd["model.layers.1.mamba.dt_bias"]
        path = tmp_path / "short.npz"
        np.savez(path, **sd)
        with pytest.raises(ValueError, match="dt_bias"):
            import_external_weights(str(path), FAMILY, **MODEL)
