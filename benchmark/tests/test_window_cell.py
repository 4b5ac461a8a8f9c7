"""A tiny cell of the `window_moe_decoder` family (sliding-window layers
with rotary positions among global layers without any, ReLU-gated experts
whose router reads the attention's input), added AS FILES ONLY beside the
benchmark's own, as `test_eva_cell.py` does for its family, and run end to
end on the CPU through `run.py`: the lane scores it `correct` against
`reference/window_moe_decoder.py`, a reference whose window is one short
FAILS it, the three new readers return a number from a recorded trace and
`None` from an untraced run, and the parts of `operations` add up."""

import json
import os
import shutil
import types

import pytest

from conftest import BENCH_DIR, REPO, run_cell, run_tool

from harness import cells
from harness.trace import Event, Trace
from harness.window import Call

CELL = "tiny_window.score_mixed_context"
REAL_CELL = "smallthinker_21b_a3b.score_mixed_context"
SCORE_RATE = "transform_tokens_per_s"
TINY_WINDOW = {
    "name": "tiny_window", "family": "window_moe_decoder",
    "reference": "window_moe_decoder", "architecture": "window_moe_decoder",
    "precision": "float32", "vocab_size": 40,
    "model": {"layer_types": ["global", "sliding", "sliding", "sliding",
                              "global"],
              "d_model": 64, "num_heads": 6, "num_kv_heads": 2,
              "head_dim": 16, "window_size": 32, "n_routed_experts": 8,
              "experts_held": [0, 4], "num_experts_per_tok": 3,
              "d_ff_expert": 32, "n_shared_experts": 0,
              "rms_norm_eps": 1e-6, "rope_theta": 1.5e6, "vocab_size": 40,
              "max_len": 128, "attention_impl": "flash", "head_chunk": 64},
}
# two lengths: 2 rows of 104 (over three windows: the band slides) and 5 of
# 24 (inside one window), batches of 2: the short rows end in a batch of one
TINY_TRAFFIC = {
    "adapter": "dnn_transform", "rows": 7, "lengths": [[104, 2], [24, 5]],
    "mini_batch_size": 2, "bfloat16": False, "fused_dispatch": False,
    "fetch_dict": {"logprob": "token_logprobs"}, "sample_rows": 7,
    "trace_calls": 1,
    # float32 against float32: only the order of the sums differs
    "limits": {"output_gap_p99": 1e-4, "output_gap_max": 1e-4,
               "pad_leak": 1e-4, "nonfinite": 0,
               "rows_or_positions_missing": 0, "call_mismatch": 0}}
NEW_READERS = ("swa_attn_roofline", "swa.share", "swa.tiles_over_band")


@pytest.fixture(scope="module")
def window_checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("window_checkout")
    shutil.copytree(BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    with open(root / "benchmark" / "configs" / "tiny_window.json",
              "w") as fh:
        json.dump(TINY_WINDOW, fh)
    with open(root / "benchmark" / "traffic" / "tiny_mixed_context.json",
              "w") as fh:
        json.dump(TINY_TRAFFIC, fh)
    bench["configs"].append({
        "name": "tiny_window", "source": "test", "reduced": [],
        "file": "benchmark/configs/tiny_window.json", "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny_window",
                               "traffic": "tiny_mixed_context", "chips": 1,
                               "why": "test"})
    # the tiny cell reports what the real cell of the family reports
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if REAL_CELL in metric.get("workloads", ()):
            metric["workloads"].append(CELL)
    with open(root / "BENCHMARK.json", "w") as fh:
        json.dump(bench, fh)
    return root


def _result(proc) -> dict:
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.splitlines()[-1])


def test_the_cell_runs_end_to_end_and_is_correct(window_checkout):
    out = _result(run_cell(window_checkout, CELL))
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {SCORE_RATE, "setup_s"}
    assert out["metrics"][SCORE_RATE]["value"] > 0
    for name in ("output_gap_p99", "output_gap_max", "pad_leak"):
        assert 0 <= out["checks"][name]["value"] < 1e-4


def test_an_untraced_device_reads_nothing(window_checkout):
    """No device plane on the CPU, and no banded kernel either (the CPU's
    tier is the chunked one, so the runner writes no tile pairs): the
    device-trace readers and the tile reader leave their metrics out; the
    span readers report, the expert counts among them."""
    out = _result(run_cell(window_checkout, CELL, "--trace", "1"))
    assert out["correct"] is True
    assert out["metrics"]["runner.pad_share"]["value"] == pytest.approx(0.0)
    assert out["metrics"]["moe.load_max_over_mean"]["value"] >= 1.0
    for name in (*NEW_READERS, "gqa_attn_roofline", "moe_expert_roofline",
                 "loglik_head.share", "runner.mfu"):
        assert name not in out["metrics"]


def test_a_window_one_short_is_not_correct(window_checkout):
    """The planted fault, on the reference's side: its band is one key
    short, so the program, which is right, is scored not correct by it."""
    proc = run_tool(window_checkout, [
        "benchmark/run.py", "--workload", CELL, "--seed", "5", "--seconds",
        "1"], prelude="""
import sys
sys.path.insert(0, "benchmark")
from harness import cells
_load = cells.load_module
def _short(kind, name):
    module = _load(kind, name)
    if (kind, name) == ("reference", "window_moe_decoder"):
        sizes = module.sizes
        def one_short(config):
            s = sizes(config)
            s["window_size"] -= 1
            return s
        module.sizes = one_short
    return module
cells.load_module = _short
""")
    out = _result(proc)
    assert out["correct"] is False
    assert out["checks"]["output_gap_p99"]["value"] > 1e-3
    assert out["checks"]["nonfinite"]["value"] == 0
    assert out["checks"]["call_mismatch"]["value"] == 0


def test_correct_can_fail_a_control_through_int8(window_checkout):
    proc = run_tool(window_checkout, [
        "benchmark/controls.py", "--workload", CELL, "--seeds", "21,22",
        "--control", "int8"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(l) for l in proc.stdout.splitlines()
             if l.startswith("{")]
    assert len(lines) == 2
    limits = TINY_TRAFFIC["limits"]
    for line in lines:
        assert [k for k, v in line["sound"].items()
                if not v <= limits[k]] == [], line
        assert line["control.int8"]["output_gap_p99"] > 10 * limits[
            "output_gap_p99"], line


# --------------------------------------------------------------------- #
# the readers, from a recorded trace                                    #
# --------------------------------------------------------------------- #

def _pallas(name: str, shape: str) -> str:
    return (f"%{name} = {shape} custom-call(%a, %b), "
            'custom_call_target="tpu_custom_call"')


def _recorded_run(root) -> dict:
    """One traced call of the tiny cell as a v5e shows it: the names are
    instructions' texts (the banded forward under its own name, a sliding
    layer's call for rows inside the window under the layer's, a global
    layer's under `gqa_attn_<i>`), the times made up."""
    with open(root / "BENCHMARK.json") as fh:
        bench = json.load(fh)
    cell = cells.Cell(
        name=CELL, chips=1, config=TINY_WINDOW, traffic=TINY_TRAFFIC,
        per_layer=[m for m in bench["per_layer"]
                   if CELL in m.get("workloads", ())])
    ops, at = [], [0.0]

    def op(name: str, seconds: float):
        ops.append(Event(name, at[0], at[0] + seconds))
        at[0] += seconds + 1e-6

    for layer in (0, 4):                                   # global layers
        op(_pallas(f"gqa_attn_{layer}.1", "f32[12,104,16]"), 2e-3)
    for layer in (1, 2, 3):                                # sliding layers
        op(_pallas(f"swa_attn_w32.{layer}", "f32[12,104,16]"), 1e-3)
        op(_pallas(f"swa_attn_{layer}.1", "f32[12,24,16]"), 5e-4)
    op("%fusion.3 = f32[208,64] fusion(%x), kind=kOutput", 4e-3)
    op("%fusion.7 = f32[64,40] fusion(%x), kind=kLoop", 1e-3)  # the head's
    call = Event("transform.call", 0.0, at[0] + 1e-3)
    trace = Trace({"/device:TPU:0": ops}, [call], 0.0, call.end,
                  [Event("tpu::System::TransferToDevice", 1e-3, 2e-3)])
    steps = [types.SimpleNamespace(
        name="runner.step", args={"padded": 2, "rows": rows})
        for rows in (2, 2, 2, 1)]
    counted = {"moe_picks": 100, "moe_picks_held": 50,
               "moe_load_max_over_mean": 1.25}
    spans = [[(types.SimpleNamespace(
                   name="runner.transform",
                   args=dict(counted, attn_window_tile_pairs=90,
                             attn_window_tile_pairs_needed=60.0)), steps[:1]),
              (types.SimpleNamespace(name="runner.transform",
                                     args=dict(counted)), steps[1:])]]
    return {"cell": cell, "calls": [Call(0.0, 0.03, {"same": True})],
            "elapsed": 0.03, "work_per_call": 328.0, "setup_s": 1.0,
            "trace": trace, "annotation": "transform.call",
            "peaks": {"flops_per_s": 1e12, "bytes_per_s": 1e11},
            ("program_spans", "runner.transform", 2): spans}


def test_the_new_readers_return_a_number(window_checkout):
    run = _recorded_run(window_checkout)
    listed = [m["name"] for m in run["cell"].per_layer]
    assert set(NEW_READERS) <= set(listed)
    assert {"gqa_attn_roofline", "moe.load_max_over_mean",
            "loglik_head.share", "runner.mfu"} <= set(listed)
    values = {name: cells.load_module("metrics", name).read(run)
              for name in (*NEW_READERS, "gqa_attn_roofline",
                           "moe.load_max_over_mean", "loglik_head.share")}
    assert all(isinstance(v, float) for v in values.values()), values
    busy = run["trace"].busy_seconds()
    assert busy == pytest.approx(13.5e-3)
    # the banded calls and the sliding layers' short-row calls, not the
    # global layers'
    assert values["swa.share"] == pytest.approx(100 * 4.5e-3 / busy)
    parts = cells.load_module("reference", "window_moe_decoder").operations(
        TINY_WINDOW, [(104, 2), (24, 5)])["parts"]
    for name, part, taken in (("swa_attn_roofline", "window_attention",
                               4.5e-3),
                              ("gqa_attn_roofline", "attention", 4e-3)):
        need = parts[part]
        assert values[name] == pytest.approx(100 * max(
            need["ops"] / 1e12, need["bytes"] / 1e11) / taken)
        assert 0 < values[name] < 100
    # the one table with a banded kernel: 90 pairs visited, 60 needed
    assert values["swa.tiles_over_band"] == pytest.approx(1.5)
    assert values["moe.load_max_over_mean"] == pytest.approx(1.25)


def test_a_trace_without_the_family_reads_nothing(window_checkout):
    """What a program without the family gives: no `swa_attn_*` call and
    no tile pairs on the root span. The new readers return None and raise
    nothing; so they do untraced."""
    run = _recorded_run(window_checkout)
    run["trace"] = Trace(
        {"/device:TPU:0": [
            Event("%fusion.9 = f32[96,64] fusion(%x)", 0.0, 1e-3),
            Event(_pallas("gqa_attn_2.1", "f32[16,40,8]"), 1e-3, 2e-3)]},
        [], 0.0, 2e-3)
    for call in run[("program_spans", "runner.transform", 2)]:
        for root, _steps in call:
            root.args = {}
    for name in NEW_READERS:
        assert cells.load_module("metrics", name).read(run) is None
    run["trace"] = None
    for name in NEW_READERS:
        assert cells.load_module("metrics", name).read(run) is None


def test_operations_parts_add_up():
    ref = cells.load_module("reference", "window_moe_decoder")
    with open(os.path.join(BENCH_DIR, "configs",
                           "smallthinker_21b_a3b.json")) as fh:
        config = json.load(fh)
    with open(os.path.join(BENCH_DIR, "traffic",
                           "score_mixed_context.json")) as fh:
        traffic = json.load(fh)
    lengths = [(16384, 6), (2048, 26)]
    assert (traffic["rows"], traffic["lengths"], traffic["mini_batch_size"],
            traffic["fetch_dict"]) == (
                32, [[16384, 6], [2048, 26]], 2,
                {"logprob": "token_logprobs"})
    need = ref.operations(config, lengths)
    parts = need["parts"]
    assert set(parts) == {"projections", "attention", "window_attention",
                          "routed_experts", "head"}
    for key in ("ops", "bytes"):
        assert need[key] == pytest.approx(sum(p[key]
                                              for p in parts.values()))
    s = ref.sizes(config)
    tokens = 6 * 16384 + 26 * 2048
    assert tokens == 151552
    # a long row: the triangle of the first window, then 4096 keys a query
    assert ref.band_pairs(16384, 4096) == 4096 * 4097 // 2 + 12288 * 4096
    assert ref.band_pairs(2048, 4096) == 2048 * 2049 // 2     # the triangle
    assert ref.band_pairs(104, 32) == 32 * 33 // 2 + 72 * 32
    assert (s["global_layers"], s["sliding_layers"]) == (5, 13)
    triangle = 6 * 16384 * 16385 // 2 + 26 * 2048 * 2049 // 2
    band = 6 * ref.band_pairs(16384, 4096) + 26 * 2048 * 2049 // 2
    assert parts["attention"]["ops"] == pytest.approx(
        2.0 * 5 * triangle * 28 * 256)
    assert parts["window_attention"]["ops"] == pytest.approx(
        2.0 * 13 * band * 28 * 256)
    # q and the output over 28 heads, k and v over 4, two bytes each
    assert parts["window_attention"]["bytes"] == pytest.approx(
        13 * 2.0 * tokens * 128 * 64)
    assert parts["routed_experts"]["per_pick"]["ops"] == 2.0 * 3 * 2560 * 768
    assert parts["routed_experts"]["bytes"] == 2.0 * 18 * 16 * 3 * 2560 * 768
    assert parts["head"]["ops"] == pytest.approx(
        2.0 * (tokens - 32) * 2560 * 37984)
    # every published width is in the file; depth, experts held and the
    # vocabulary are what is reduced
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["sliding_window_size"], config["moe_ffn_hidden_size"],
            config["moe_num_active_primary_experts"],
            config["published_moe_num_primary_experts"],
            config["rope_theta"], config["rms_norm_eps"],
            config["max_position_embeddings"],
            config["moe_primary_router_apply_softmax"],
            config["norm_topk_prob"], config["tie_word_embeddings"]) == (
                2560, 28, 4, 128, 4096, 768, 6, 64, 1500000, 1e-6, 16384,
                True, True, False)
    assert set(config["reduced"]) == {
        "num_hidden_layers", "moe_num_primary_experts", "vocab_size"}
    assert (config["num_hidden_layers"], config["moe_num_primary_experts"],
            config["vocab_size"]) == (18, 16, 37984)
    assert (config["published_num_hidden_layers"],
            config["published_vocab_size"]) == (52, 151936)
    assert len(config["rope_layout"]) == len(
        config["sliding_window_layout"]) == 52
    m = config["model"]
    assert m["layer_types"] == [
        "sliding" if flag else "global"
        for flag in config["sliding_window_layout"][:18]]
    assert (m["d_model"], m["num_heads"], m["num_kv_heads"], m["head_dim"],
            m["window_size"], m["d_ff_expert"], m["n_routed_experts"],
            m["num_experts_per_tok"], m["experts_held"], m["vocab_size"],
            m["max_len"]) == (2560, 28, 4, 128, 4096, 768, 64, 6, [0, 16],
                              37984, 16384)
    # 4.547 GB served at two bytes a parameter, over the benchmark's floor
    layer = (2560 * 3584 * 2 + 2 * 2560 * 512 + 2560 * 64 + 2 * 2560
             + 16 * 3 * 2560 * 768)
    served = 2 * (18 * layer + 2 * 37984 * 2560 + 2560)
    assert served == 4547404800 and served > 4294967296
