"""The yardstick's own arithmetic: the window rule, operation and byte
counts against hand-worked values, the trace reduction on a recorded
trace, the seeded generators."""

import json
import os

import numpy as np
import pytest

from harness import cells, counts, data, window
from harness.cells import load_module
from harness.trace import (Event, Trace, gaps, is_matmul_fusion, is_pallas,
                           is_top_k, self_seconds, short_name, union_seconds)

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
RECORDED = os.path.join(HERE, "data", "tiny_v5e.xplane.pb")
RECORDED_SAR = os.path.join(HERE, "data", "sar_v5e.xplane.pb")


# ---- the window rule -------------------------------------------------- #

class FakeClock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


def _window(call_seconds, seconds, fail_at=()):
    clock = FakeClock()

    def call(i):
        clock.now += call_seconds[min(i, len(call_seconds) - 1)]
        if i in fail_at:
            raise RuntimeError("boom")
        return i

    return window.run_window(call, seconds, clock=clock)


@pytest.mark.parametrize("seconds,expected_calls", [
    (5.0, 1),      # one call longer than the window: it still runs whole
    (25.0, 2),     # 10 + 10 fits, a third would end at 30 > 25
    (30.0, 3),     # exactly fits
    (10.5, 1),
])
def test_whole_calls_and_one_rate(seconds, expected_calls):
    calls, elapsed = _window([10.0], seconds)
    assert len(calls) == expected_calls
    assert elapsed == pytest.approx(10.0 * expected_calls)
    # one call or three: the same rate, nothing quantised by a cut call
    assert window.rate(calls, elapsed, 500.0) == pytest.approx(50.0)


def test_longest_call_decides_whether_another_starts():
    calls, elapsed = _window([4.0, 9.0, 4.0], 20.0)
    # after 4 + 9 = 13 s the longest call seen is 9 s: 22 > 20, stop
    assert [c.seconds for c in calls] == [4.0, 9.0]
    assert elapsed == pytest.approx(13.0)


def test_failed_call_is_counted_and_earns_nothing():
    calls, elapsed = _window([10.0], 30.0, fail_at=(1,))
    assert [c.error is None for c in calls] == [True, False, True]
    assert window.rate(calls, elapsed, 300.0) == pytest.approx(20.0)


# ---- operations and bytes --------------------------------------------- #

def test_sar_scores_are_bound_by_operations():
    need = counts.sar_scores(69878, 10677)
    assert need["ops"] == 2 * 69878 * 10677 * 10677           # 1.59e13
    assert need["bytes"] == 69878 * 10677 * 5 + 10677 * 10677 * 4   # 4.19 GB
    least, bound = counts.least_seconds(
        need, {"flops_per_s": 197e12, "bytes_per_s": 819e9})
    assert bound == "ops" and least == pytest.approx(80.9e-3, rel=1e-2)


def _load_reference():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "reference_transformer",
        os.path.join(os.path.dirname(HERE), "reference", "transformer.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_encoder_operations_against_hand_worked_values():
    # 2 layers, width 32, feed-forward 64, 3 outputs; per token and layer
    # 4 x 32 x 32 + 2 x 32 x 64 = 8192 weights, two operations each
    ref = _load_reference()
    config = {"model": {"num_layers": 2, "d_model": 32, "num_heads": 4,
                        "d_ff": 64, "num_outputs": 3, "vocab_size": 50,
                        "max_len": 24}}
    need = ref.operations(config, [(24, 37), (12, 24)])
    tokens = 37 * 24 + 24 * 12
    scores = 2 * 4 * 32 * (37 * 24 * 24 + 24 * 12 * 12)
    assert need["ops"] == tokens * 2 * 2 * 8192 + scores + 61 * 2 * 32 * 3
    assert need["bytes"] == 2 * (2 * 8192 + 96) + 4 * tokens + 4 * 61 * 32


def test_xlmr_xxl_runs_the_published_widths():
    """The configuration's file states the source's keys; what the program
    is built from (`model`) repeats them, every key `reduced` in
    `BENCHMARK.json` is explained in the file, and the operations of its
    cell's table are the hand-worked 283.2 TFLOP a call."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    entry = {c["name"]: c for c in bench["configs"]}["xlmr_xxl"]
    with open(os.path.join(REPO, entry["file"])) as fh:
        config = json.load(fh)
    model = config["model"]
    assert (config["hidden_size"], config["intermediate_size"],
            config["num_attention_heads"], config["vocab_size"],
            config["max_position_embeddings"]) == (4096, 16384, 32, 250880,
                                                   514)
    assert (model["d_model"], model["d_ff"], model["num_heads"],
            model["vocab_size"], model["max_len"], model["num_layers"]) == (
        config["hidden_size"], config["intermediate_size"],
        config["num_attention_heads"], config["vocab_size"],
        config["max_position_embeddings"], config["num_hidden_layers"])
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    cell = cells.load_cell("xlmr_xxl.score_table")
    groups = data.length_groups(cell.traffic["rows"],
                                cell.traffic["lengths"])
    assert groups == [(512, 180), (128, 180)]
    need = _load_reference().operations(config, groups)
    layer = 4 * 4096 * 4096 + 2 * 4096 * 16384          # 201.3M weights
    assert need["ops"] == pytest.approx(
        115200 * 2 * 6 * layer                          # projections, ffn
        + 6 * 4 * 4096 * 180 * (512 * 512 + 128 * 128)  # scores, values
        + 360 * 2 * 4096 * 16, rel=1e-12)               # the head
    assert need["ops"] == pytest.approx(283.2e12, rel=1e-3)
    assert [m["name"] for m in cell.end_to_end] == [
        "transform_tokens_per_s", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == [
        "runner.call_s", "runner.host_s", "runner.mfu", "runner.h2d_share",
        "runner.pad_share"]


def test_the_reference_is_the_programs_module_to_float32():
    """The plain reference and the program's `TransformerEncoder` given the
    reference's weights under the module's names agree to float32's
    rounding, for each output the reference knows."""
    import jax

    from mmlspark_tpu.nn.models import make_model

    ref = _load_reference()
    config = {"model": {"num_layers": 2, "d_model": 32, "num_heads": 4,
                        "d_ff": 64, "num_outputs": 3, "vocab_size": 50,
                        "max_len": 24}}
    w = ref.weights(data.device_key(5, 21), config)
    ids = data.rng_for(5, 22).integers(0, 50, (6, 24), dtype=np.int32)
    module = make_model("transformer", **config["model"])
    with jax.default_matmul_precision("highest"):
        logits, state = module.apply(
            ref.variables(w, config), ids, train=False,
            capture_intermediates=True, mutable=["intermediates"])
    caught = state["intermediates"]
    got = {"logits": logits, "probability": jax.nn.softmax(logits, -1),
           "pooled_features": caught["pooled_features"][0],
           "ln_final": caught["ln_final"]["__call__"][0]}
    for fetch, value in got.items():
        np.testing.assert_allclose(ref.outputs(w, config, ids, fetch),
                                   np.asarray(value), rtol=0, atol=2e-5,
                                   err_msg=fetch)


# ---- seeded sizes and the control's rounding --------------------------- #

@pytest.mark.parametrize("rows,lengths,expected", [
    (44, 16, [(16, 44)]),
    (61, [[24, 0.6], [12, 0.4]], [(24, 37), (12, 24)]),   # 36.6 -> 36 + 1
    (10, [[8, 1], [64, 1], [16, 0]], [(64, 5), (8, 5)]),  # longest first
])
def test_length_groups_fix_the_sizes_for_every_seed(rows, lengths, expected):
    assert data.length_groups(rows, lengths) == expected
    assert sum(n for _l, n in expected) == rows


def test_through_rounds_matrices_and_keeps_vectors():
    import jax.numpy as jnp

    from harness import precision

    def tree():                 # `through` spends the tree it is given
        return {"kernel": jnp.linspace(-1.0, 1.0, 64).reshape(8, 8) / 3.0,
                "bias": jnp.linspace(-1.0, 1.0, 8) / 3.0}

    kept = {k: np.asarray(v) for k, v in tree().items()}
    worst = {}
    for name in ("bfloat16", "int8", "fp8"):
        spent = tree()
        low = precision.through(spent, name)
        assert spent["kernel"].is_deleted()
        assert np.array_equal(low["bias"], kept["bias"])
        worst[name] = float(np.abs(np.asarray(low["kernel"])
                                   - kept["kernel"]).max())
    # half a step of each grid at the tensor's largest value, 1/3: 8 bits
    # of [1/4, 1/2); 127 steps to 1/3; 4 bits of [256, 512) scaled to 448
    assert 0 < worst["bfloat16"] <= 2.0 ** -10
    assert worst["bfloat16"] < worst["int8"] <= 1 / 3 / 127 / 2 * 1.01
    assert worst["int8"] < worst["fp8"] <= 1 / 3 * 16 / 448 * 1.01
    with pytest.raises(ValueError):
        precision.through(tree(), "int3")


# ---- the trace reduction ---------------------------------------------- #

def test_interval_arithmetic():
    assert union_seconds([(0, 2), (1, 3), (5, 6)]) == 4
    assert gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
    outer, a, b = Event("while", 0, 10), Event("a", 1, 4), Event("b", 5, 6)
    own = {e.name: s for e, s in self_seconds([a, outer, b])}
    assert own == {"while": 6, "a": 3, "b": 1}


def test_recorded_v5e_trace_reduces():
    """Three calls of one tiny jitted program (a convolution fusion and the
    Pallas histogram kernel) recorded on a v5e under `tiny.call`."""
    trace = Trace.from_file(RECORDED, annotations=("tiny.call",))
    assert list(trace.device_ops) == ["/device:TPU:0"]
    spans = trace.spans("tiny.call")
    assert len(spans) == 3
    ops = trace.op_seconds()
    assert all(count == 3 for count, _s in ops.values())
    pallas = trace.op_seconds(select=is_pallas)
    convs = trace.op_seconds(select=lambda name: "kind=kOutput" in name)
    assert [short_name(n) for n in pallas] == ["tiny.1"]
    assert [short_name(n) for n in convs] == ["fusion.5"]
    # the kernel took 67.7 us a call and the fusion 9.9 us (read by hand)
    assert sum(s for _c, s in pallas.values()) / 3 == pytest.approx(
        67.7e-6, rel=0.01)
    assert sum(s for _c, s in convs.values()) / 3 == pytest.approx(
        9.87e-6, rel=0.01)
    busy = trace.busy_seconds()
    assert 0 < busy < trace.window_s
    assert busy == pytest.approx(sum(
        trace.busy_seconds(s.start, s.end) for s in spans), rel=1e-6)
    breakdown = trace.breakdown(("tiny.call",))
    assert breakdown["device_ops"][0][0] == "tiny.1"
    labels = [n for n, _s in breakdown["idle_gaps"]]
    assert labels[0] == "between_calls" and "tiny.call" in labels


def test_recorded_sar_trace_reduces():
    """Two whole `sar_recommend_all` passes recorded on a v5e under
    `recommend.call` (PR 23): 17 blocks of 4096 users and one of 246 a
    pass, each a product fusion with the seen mask and a `TopK`."""
    trace = Trace.from_file(RECORDED_SAR, annotations=("recommend.call",))
    assert len(trace.spans("recommend.call")) == 2
    products = trace.op_seconds(select=is_matmul_fusion)
    top_ks = trace.op_seconds(select=is_top_k)
    assert sorted(c for c, _s in products.values()) == [2, 34]
    assert sorted(c for c, _s in top_ks.values()) == [2, 34]
    assert {short_name(n) for n in products} == {"convolution_select_fusion"}
    # read by hand: 6.37 ms a full block's product, 1.67 ms its top-k
    assert sum(s for _c, s in products.values()) == pytest.approx(
        217.68e-3, rel=1e-3)
    assert sum(s for _c, s in top_ks.values()) == pytest.approx(
        57.27e-3, rel=1e-3)
    assert trace.busy_seconds() == pytest.approx(0.29802, rel=1e-4)
    labels = [n for n, _s in trace.breakdown(("recommend.call",))["idle_gaps"]]
    assert labels[0] == "recommend.call/np.asarray(jax.Array)"


# ---- seeded inputs ---------------------------------------------------- #

def test_same_seed_same_bytes_any_seed_same_sizes():
    big = 2 ** 31 + 12345          # the driver's seeds pass 32 signed bits
    sar = load_module("reference", "sar")
    make = lambda seed: {k: np.asarray(v) for k, v in sar.weights(  # noqa: E731
        data.device_key(seed, 11), 1300, 150, 20000, 4).items()}
    w, w2, w3 = make(big), make(big), make(big + 1)
    for k in w:
        assert np.array_equal(w[k], w2[k])
        assert w[k].shape == w3[k].shape and not np.array_equal(w[k], w3[k])
    # a seed's high bits count too
    assert not np.array_equal(w["seen"], make(big + 2 ** 32)["seen"])
    seen = w["seen"]
    assert np.array_equal(seen, w["affinity"] > 0)
    assert 0.8 * 20000 < seen.sum() <= 1.02 * 20000
    # the similarity is the Jaccard index of the items' user sets, kept at
    # four common users or more: against a plain count on the host, with
    # the last block of rows (which reaches back) counted once
    b = seen.astype(np.float64)
    both = b.T @ b
    alone = np.diag(both)
    either = alone[:, None] + alone[None, :] - both
    want = np.where((both >= 4) & (either > 0),
                    both / np.maximum(either, 1), 0.0)
    assert np.allclose(w["similarity"], want, rtol=1e-6, atol=0)
