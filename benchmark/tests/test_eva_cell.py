"""A tiny cell of the `eva_decoder` family (attention that reads its own
window exactly and the windows before it a summary a chunk, a head of
several predictions, no expert layer), added AS FILES ONLY beside the
benchmark's own, as `test_hybrid_cell.py` does for its family, and run end
to end on the CPU through `run.py`: the lane scores it `correct` against
`reference/eva_decoder.py`, a reference whose window is one chunk short
FAILS it, both new readers return a number from a recorded trace and
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

CELL = "tiny_eva.score_byte_docs"
REAL_CELL = "evabyte_6_5b.score_byte_docs"
SCORE_RATE = "transform_tokens_per_s"
TINY_EVA = {
    "name": "tiny_eva", "family": "eva_decoder", "reference": "eva_decoder",
    "architecture": "eva_decoder", "precision": "float32", "vocab_size": 40,
    "hidden_size": 64,
    "model": {"num_layers": 2, "d_model": 64, "num_heads": 4,
              "window_size": 32, "chunk_size": 4, "d_ff_dense": 96,
              "rms_norm_eps": 1e-5, "rope_theta": 1e5, "vocab_size": 40,
              "num_pred_heads": 3, "max_len": 128, "attention_impl": "flash",
              "head_chunk": 64},
}
# two lengths: 2 rows of 104 (four windows, the last ragged, its last chunk
# too: 104 = 3 x 32 + 8) and 5 of 40 (two windows), batches of 2: the short
# rows end in a batch of one row
TINY_TRAFFIC = {
    "adapter": "dnn_transform", "rows": 7, "lengths": [[104, 2], [40, 5]],
    "mini_batch_size": 2, "bfloat16": False, "fused_dispatch": False,
    "fetch_dict": {"logprob": "token_logprobs"}, "sample_rows": 7,
    "trace_calls": 1,
    # float32 against float32: only the order of the sums differs
    "limits": {"output_gap_p99": 1e-4, "output_gap_max": 1e-4,
               "pad_leak": 1e-4, "nonfinite": 0,
               "rows_or_positions_missing": 0, "call_mismatch": 0}}


@pytest.fixture(scope="module")
def eva_checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("eva_checkout")
    shutil.copytree(BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    with open(root / "benchmark" / "configs" / "tiny_eva.json", "w") as fh:
        json.dump(TINY_EVA, fh)
    with open(root / "benchmark" / "traffic" / "tiny_byte_docs.json",
              "w") as fh:
        json.dump(TINY_TRAFFIC, fh)
    bench["configs"].append({
        "name": "tiny_eva", "source": "test", "reduced": [],
        "file": "benchmark/configs/tiny_eva.json", "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny_eva",
                               "traffic": "tiny_byte_docs", "chips": 1,
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


def test_the_cell_runs_end_to_end_and_is_correct(eva_checkout):
    out = _result(run_cell(eva_checkout, CELL))
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {SCORE_RATE, "setup_s"}
    assert out["metrics"][SCORE_RATE]["value"] > 0
    for name in ("output_gap_p99", "output_gap_max", "pad_leak"):
        assert 0 <= out["checks"][name]["value"] < 1e-4


def test_an_untraced_device_reads_nothing(eva_checkout):
    """No device plane on the CPU: the device-trace readers find nothing
    and leave their metric out; the span readers report, and the runner's
    streamed path reads back no counters (the family sows none)."""
    out = _result(run_cell(eva_checkout, CELL, "--trace", "1"))
    assert out["correct"] is True
    assert out["metrics"]["runner.pad_share"]["value"] == pytest.approx(0.0)
    for name in ("eva_attn_roofline", "eva.share", "loglik_head.share",
                 "runner.mfu"):
        assert name not in out["metrics"]


def test_a_window_one_chunk_short_is_not_correct(eva_checkout):
    """The planted fault, on the reference's side: it reads every window
    one chunk short (its own keys and the summaries alike), so the program,
    which is right, is scored not correct by it."""
    proc = run_tool(eva_checkout, [
        "benchmark/run.py", "--workload", CELL, "--seed", "5", "--seconds",
        "1"], prelude="""
import sys
sys.path.insert(0, "benchmark")
from harness import cells
_load = cells.load_module
def _short(kind, name):
    module = _load(kind, name)
    if (kind, name) == ("reference", "eva_decoder"):
        sizes = module.sizes
        def one_chunk_short(config):
            s = sizes(config)
            s["window_size"] -= s["chunk_size"]
            return s
        module.sizes = one_chunk_short
    return module
cells.load_module = _short
""")
    out = _result(proc)
    assert out["correct"] is False
    assert out["checks"]["output_gap_p99"]["value"] > 1e-3
    assert out["checks"]["nonfinite"]["value"] == 0
    assert out["checks"]["call_mismatch"]["value"] == 0


def test_correct_can_fail_a_control_through_int8(eva_checkout):
    proc = run_tool(eva_checkout, [
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
    instructions' texts (the kernels' as PR 33's traces have them), the
    times made up."""
    with open(root / "BENCHMARK.json") as fh:
        bench = json.load(fh)
    cell = cells.Cell(
        name=CELL, chips=1, config=TINY_EVA, traffic=TINY_TRAFFIC,
        per_layer=[m for m in bench["per_layer"]
                   if CELL in m.get("workloads", ())])
    ops, at = [], [0.0]

    def op(name: str, seconds: float):
        ops.append(Event(name, at[0], at[0] + seconds))
        at[0] += seconds + 1e-6

    for layer in range(2):
        op(_pallas(f"eva_pool_w32c4.{layer}", "(f32[8,32,16], f32[8,32,16])"),
           1e-3)
        op(_pallas(f"eva_attn_w32c4.{layer}", "f32[8,128,16]"), 3e-3)
        op(f"%fusion.{layer} = f32[2,104,96] fusion(%x), kind=kOutput",
           4e-3)                                               # feed-forward
    op("%fusion.7 = f32[64,40] fusion(%x), kind=kLoop", 1e-3)  # the head's cut
    op("%fusion.8 = f32[64,40] fusion(%x), kind=kOutput", 1e-3)
    call = Event("transform.call", 0.0, at[0] + 1e-3)
    trace = Trace({"/device:TPU:0": ops}, [call], 0.0, call.end,
                  [Event("tpu::System::TransferToDevice", 1e-3, 2e-3)])
    steps = [types.SimpleNamespace(
        name="runner.step", args={"padded": rows, "rows": rows})
        for rows in (2, 2, 2, 1)]
    spans = [[(types.SimpleNamespace(name="runner.transform", args={}),
               steps[:1]),
              (types.SimpleNamespace(name="runner.transform", args={}),
               steps[1:])]]
    return {"cell": cell, "calls": [Call(0.0, 0.03, {"same": True})],
            "elapsed": 0.03, "work_per_call": 408.0, "setup_s": 1.0,
            "trace": trace, "annotation": "transform.call",
            "peaks": {"flops_per_s": 1e12, "bytes_per_s": 1e11},
            ("program_spans", "runner.transform", 2): spans}


def test_every_listed_reader_returns_a_number(eva_checkout):
    run = _recorded_run(eva_checkout)
    listed = [m["name"] for m in run["cell"].per_layer]
    assert {"eva_attn_roofline", "eva.share", "loglik_head.share",
            "runner.call_s", "runner.host_s", "runner.mfu",
            "runner.h2d_share", "runner.pad_share"} == set(listed)
    values = {name: cells.load_module("metrics", name).read(run)
              for name in listed}
    assert all(isinstance(v, float) for v in values.values()), values
    busy = run["trace"].busy_seconds()
    assert busy == pytest.approx(18e-3)
    # both kernels of both layers; the roofline reads the attention alone
    assert values["eva.share"] == pytest.approx(100 * 8e-3 / busy)
    need = cells.load_module("reference", "eva_decoder").operations(
        TINY_EVA, [(104, 2), (40, 5)])["parts"]["attention"]
    assert values["eva_attn_roofline"] == pytest.approx(
        100 * max(need["ops"] / 1e12, need["bytes"] / 1e11) / 6e-3)
    assert 0 < values["eva_attn_roofline"] < 100
    assert values["loglik_head.share"] == pytest.approx(100 * 2e-3 / busy)
    assert values["runner.pad_share"] == pytest.approx(0.0)
    assert 0 < values["runner.mfu"] < 100


def test_a_trace_without_the_family_reads_nothing(eva_checkout):
    """What a program without the family gives: no `eva_*` call. The new
    readers return None and raise nothing; so they do untraced."""
    run = _recorded_run(eva_checkout)
    run["trace"] = Trace(
        {"/device:TPU:0": [
            Event("%fusion.9 = f32[96,64] fusion(%x)", 0.0, 1e-3),
            Event(_pallas("gqa_attn_2.1", "f32[16,40,8]"), 1e-3, 2e-3)]},
        [], 0.0, 2e-3)
    for name in ("eva_attn_roofline", "eva.share"):
        assert cells.load_module("metrics", name).read(run) is None
    run["trace"] = None
    for name in ("eva_attn_roofline", "eva.share"):
        assert cells.load_module("metrics", name).read(run) is None


def test_operations_parts_add_up():
    ref = cells.load_module("reference", "eva_decoder")
    with open(os.path.join(BENCH_DIR, "configs", "evabyte_6_5b.json")) as fh:
        config = json.load(fh)
    lengths = [(32768, 2), (4096, 7)]
    need = ref.operations(config, lengths)
    parts = need["parts"]
    assert set(parts) == {"projections", "summaries", "attention",
                          "feed_forward", "head"}
    for key in ("ops", "bytes"):
        assert need[key] == pytest.approx(sum(p[key]
                                              for p in parts.values()))
    s = ref.sizes(config)
    tokens = 2 * 32768 + 7 * 4096
    assert tokens == 94208
    # a long row: 16 triangles of 2048 and 128 w summaries for window w
    assert ref.attended_pairs(32768, 2048, 16) == (
        16 * 2048 * 2049 // 2, 2048 * 128 * 120)
    assert ref.attended_pairs(4096, 2048, 16) == (
        2 * 2048 * 2049 // 2, 2048 * 128)
    # ragged: the last window of 8 positions sees itself and 3 x 8 summaries
    assert ref.attended_pairs(104, 32, 4) == (
        3 * 32 * 33 // 2 + 8 * 9 // 2, 32 * 8 + 32 * 16 + 8 * 24)
    pairs = 2 * (16 * 2048 * 2049 // 2 + 2048 * 128 * 120) + 7 * (
        2048 * 2049 + 2048 * 128)
    assert parts["attention"]["ops"] == pytest.approx(
        2.0 * s["num_layers"] * pairs * 32 * 256)
    # 1984.5 keys and summaries a query of a long row: 32.5 MFLOP a token
    assert parts["attention"]["ops"] / s["num_layers"] < 0.1 * (
        parts["projections"]["ops"] + parts["feed_forward"]["ops"]
    ) / s["num_layers"]
    # q, k, v and the output of every token; the summaries of every window
    # but a row's last (1920 and 128 a row), keys and values
    pooled = 2 * 30720 + 7 * 2048
    assert parts["attention"]["bytes"] == pytest.approx(
        2.0 * s["num_layers"] * 32 * 128 * (4 * tokens + 2 * pooled // 16))
    assert parts["feed_forward"]["ops"] == pytest.approx(
        2.0 * tokens * s["num_layers"] * 3 * 4096 * 11008)
    assert parts["projections"]["ops"] == pytest.approx(
        2.0 * tokens * s["num_layers"] * 4 * 4096 * 4096)
    # prediction 0's columns for the positions that are scored
    assert parts["head"]["ops"] == pytest.approx(
        2.0 * (tokens - 9) * 4096 * 320)
    # every published width is in the file, and only the depth is reduced
    assert (config["hidden_size"], config["intermediate_size"],
            config["num_attention_heads"], config["num_key_value_heads"],
            config["window_size"], config["chunk_size"],
            config["vocab_size"], config["num_pred_heads"],
            config["rope_theta"], config["rms_norm_eps"],
            config["max_position_embeddings"]) == (
                4096, 11008, 32, 32, 2048, 16, 320, 8, 100000, 1e-5, 32768)
    assert set(config["reduced"]) == {"num_hidden_layers"}
    assert config["num_hidden_layers"] == s["num_layers"] >= 11
    m = config["model"]
    assert (m["d_model"], m["d_ff_dense"], m["num_heads"], m["window_size"],
            m["chunk_size"], m["vocab_size"], m["num_pred_heads"]) == (
                4096, 11008, 32, 2048, 16, 320, 8)
    # 4.47 GB served at two bytes a parameter
    layer = 4 * 4096 ** 2 + 3 * 4096 * 11008 + 2 * 32 * 128 + 2 * 4096
    served = 2 * (s["num_layers"] * layer + 320 * 4096 + 4096 * 2560 + 4096)
    assert served >= 4.47e9
