"""A tiny cell of the `hybrid_moe_decoder` family (gated short convolutions
among grouped-query attention layers, routed experts of which this "chip"
holds a share and no shared one, a head tied to the embedding), added AS
FILES ONLY beside the benchmark's own, as `test_mla_moe_cell.py` does for
its family, and run end to end on the CPU through `run.py`: the lane scores
it `correct` against `reference/hybrid_moe_decoder.py`, a control through
int8 breaks a limit, every reader the real cell lists returns a number from
a synthetic trace, and the parts of `operations` add up."""

import json
import os
import shutil
import types

import pytest

from conftest import BENCH_DIR, REPO, run_cell, run_tool

from harness import cells
from harness.trace import Event, Trace
from harness.window import Call

CELL = "tiny_hybrid.score_long_docs"
REAL_CELL = "lfm2_8b_a1b.score_long_docs"
SCORE_RATE = "transform_tokens_per_s"
TINY_HYBRID = {
    "name": "tiny_hybrid", "family": "hybrid_moe_decoder",
    "reference": "hybrid_moe_decoder", "architecture": "hybrid_moe_decoder",
    "precision": "float32", "vocab_size": 256, "hidden_size": 64,
    "model": {
        "layer_types": ["conv", "conv", "full_attention", "conv",
                        "full_attention"],
        "d_model": 64, "num_heads": 8, "num_kv_heads": 2, "conv_taps": 3,
        "d_ff_dense": 128, "num_dense_layers": 2, "n_routed_experts": 8,
        # a share: experts 2 to 5 of the 8 routed over
        "experts_held": [2, 4], "num_experts_per_tok": 4, "d_ff_expert": 32,
        "n_shared_experts": 0, "routed_scaling_factor": 1.0,
        "norm_topk_prob": True, "route_epsilon": 1e-6, "rms_norm_eps": 1e-5,
        "rope_theta": 1e6, "vocab_size": 256, "tie_embeddings": True,
        "max_len": 64, "attention_impl": "flash", "head_chunk": 64},
}
# two lengths (5 rows of 40, 18 of 12), batches of 2: both lengths end in a
# batch of one row (no extent of one batch is another's picks: 80, 24, 320, 96)
TINY_TRAFFIC = {
    "adapter": "dnn_transform", "rows": 23, "lengths": [[40, 5], [12, 18]],
    "mini_batch_size": 2, "bfloat16": False, "fused_dispatch": False,
    "fetch_dict": {"logprob": "token_logprobs"}, "sample_rows": 23,
    "trace_calls": 1,
    # float32 against float32: only the order of the sums differs
    "limits": {"output_gap_p99": 1e-4, "output_gap_max": 1e-4,
               "pad_leak": 1e-4, "nonfinite": 0,
               "rows_or_positions_missing": 0, "call_mismatch": 0}}


@pytest.fixture(scope="module")
def hybrid_checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("hybrid_checkout")
    shutil.copytree(BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    with open(root / "benchmark" / "configs" / "tiny_hybrid.json", "w") as fh:
        json.dump(TINY_HYBRID, fh)
    with open(root / "benchmark" / "traffic" / "tiny_long_docs.json",
              "w") as fh:
        json.dump(TINY_TRAFFIC, fh)
    bench["configs"].append({
        "name": "tiny_hybrid", "source": "test", "reduced": [],
        "file": "benchmark/configs/tiny_hybrid.json", "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny_hybrid",
                               "traffic": "tiny_long_docs", "chips": 1,
                               "why": "test"})
    # the tiny cell reports what the real cell of the family reports, and
    # the two shares of the expert layer that the real cell cannot (there
    # one batch's buffer has as many rows as the model is wide:
    # `test_a_buffer_as_long_as_the_model_is_wide_is_not_told_apart`)
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if REAL_CELL in metric.get("workloads", ()) or metric["name"] in (
                "moe.share", "moe.dispatch_share"):
            metric["workloads"].append(CELL)
    with open(root / "BENCHMARK.json", "w") as fh:
        json.dump(bench, fh)
    return root


def _result(proc) -> dict:
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.splitlines()[-1])


def test_the_cell_runs_end_to_end_and_is_correct(hybrid_checkout):
    out = _result(run_cell(hybrid_checkout, CELL))
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {SCORE_RATE, "setup_s"}
    assert out["metrics"][SCORE_RATE]["value"] > 0
    for name in ("output_gap_p99", "output_gap_max", "pad_leak"):
        assert 0 <= out["checks"][name]["value"] < 1e-4


def test_a_traced_run_reports_what_the_host_can_see(hybrid_checkout):
    """No device plane on the CPU: the device-trace readers find nothing
    and leave their metric out; the span readers report."""
    out = _result(run_cell(hybrid_checkout, CELL, "--trace", "1"))
    assert out["correct"] is True
    # 5 rows in batches of 2 end in a batch of one (no padding: the bucket
    # ladder has a rung of 1); so do the 18
    assert out["metrics"]["runner.pad_share"]["value"] == pytest.approx(0.0)
    # experts 2 to 5 of 8, drawn evenly: near 1, never under it
    assert 1.0 <= out["metrics"]["moe.load_max_over_mean"]["value"] < 2.0
    for name in ("moe_expert_roofline", "gqa_attn_roofline", "moe.share",
                 "short_conv.share", "loglik_head.share"):
        assert name not in out["metrics"]


def test_correct_can_fail_a_control_through_int8(hybrid_checkout):
    proc = run_tool(hybrid_checkout, [
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


def test_taps_in_the_wrong_order_are_not_correct(hybrid_checkout):
    """The planted fault: inside the program the convolution's first tap
    meets the newest token, not the last."""
    proc = run_tool(hybrid_checkout, [
        "benchmark/run.py", "--workload", CELL, "--seed", "5", "--seconds",
        "1"], prelude="""
from mmlspark_tpu.nn import models
_param = models.ShortConv.param
def _reversed(self, name, *args, **kw):
    value = _param(self, name, *args, **kw)
    return value[:, ::-1] if name == "conv_kernel" else value
models.ShortConv.param = _reversed
""")
    out = _result(proc)
    assert out["correct"] is False
    assert out["checks"]["output_gap_p99"]["value"] > 1e-3
    assert out["checks"]["nonfinite"]["value"] == 0
    assert out["checks"]["call_mismatch"]["value"] == 0


# --------------------------------------------------------------------- #
# the readers, from a synthetic trace                                   #
# --------------------------------------------------------------------- #

def _pallas(name: str, shape: str) -> str:
    return (f"%{name} = {shape} custom-call(%a, %b), "
            'custom_call_target="tpu_custom_call"')


def _synthetic_run(root) -> dict:
    """One traced call of the tiny cell as a v5e would show it: the names
    are instructions' texts, the times made up. Batches of 2 x 40 = 80 and
    2 x 12 = 24 tokens; 4 picks a token; a buffer of 320 rows
    (`dropless_buffer_rows`)."""
    with open(root / "BENCHMARK.json") as fh:
        bench = json.load(fh)
    cell = cells.Cell(
        name=CELL, chips=1, config=TINY_HYBRID, traffic=TINY_TRAFFIC,
        per_layer=[m for m in bench["per_layer"]
                   if CELL in m.get("workloads", ())])
    ops, at = [], [0.0]

    def op(name: str, seconds: float):
        ops.append(Event(name, at[0], at[0] + seconds))
        at[0] += seconds + 1e-6

    op(_pallas("gqa_attn_2.1", "(f32[16,40,8], f32[16,40,1])"), 3e-3)
    op(_pallas("gqa_attn_4.1", "(f32[16,40,8], f32[16,40,1])"), 3e-3)
    op(_pallas("ragged-dot.1", "f32[320,64]"), 4e-3)     # 80 x 4 picks
    op("%fusion.1 = f32[320,64] fusion(%x), kind=kLoop", 1e-3)
    op("%fusion.2 = f32[80,8] fusion(%x), kind=kOutput", 1e-3)   # router
    op("%fusion.3 = f32[2,40,192] fusion(%x), kind=kOutput", 2e-3)  # in_proj
    op("%fusion.4 = f32[2,40,64] fusion(%p), kind=kLoop, "
       "calls=%c(f32[2,40,192])", 1e-3)                  # the taps read it
    op("%fusion.5 = f32[64,256] fusion(%x), kind=kOutput", 2e-3)    # head
    op("%fusion.6 = f32[80,128] fusion(%x), kind=kOutput", 3e-3)    # dense
    call = Event("transform.call", 0.0, at[0] + 1e-3)
    trace = Trace({"/device:TPU:0": ops}, [call], 0.0, call.end,
                  [Event("tpu::System::TransferToDevice", 1e-3, 2e-3)])
    root_args = {"moe_picks": 3 * 4 * 272, "moe_picks_held": 1700,
                 "moe_whole_buffer": 0, "moe_load_max_over_mean": 1.25}
    steps = [types.SimpleNamespace(
        name="runner.step", args={"padded": 2, "rows": rows})
        for rows in (2, 2, 1)]
    spans = [[(types.SimpleNamespace(name="runner.transform",
                                     args=dict(root_args)), steps)
              for _table in range(2)]]
    return {"cell": cell, "calls": [Call(0.0, 0.03, {"same": True})],
            "elapsed": 0.03, "work_per_call": 416.0, "setup_s": 1.0,
            "trace": trace, "annotation": "transform.call",
            "peaks": {"flops_per_s": 1e12, "bytes_per_s": 1e11},
            # what `harness/program_spans.py` reads from the tracer's ring
            ("program_spans", "runner.transform", 2): spans}


def test_every_listed_reader_returns_a_number(hybrid_checkout):
    run = _synthetic_run(hybrid_checkout)
    listed = [m["name"] for m in run["cell"].per_layer]
    assert {"gqa_attn_roofline", "short_conv.share", "moe.share",
            "moe.dispatch_share", "moe_expert_roofline", "loglik_head.share",
            "moe.load_max_over_mean", "runner.call_s", "runner.host_s",
            "runner.mfu", "runner.h2d_share", "runner.pad_share"} == set(
                listed)
    values = {name: cells.load_module("metrics", name).read(run)
              for name in listed}
    assert all(isinstance(v, float) for v in values.values()), values
    busy = run["trace"].busy_seconds()
    assert busy == pytest.approx(20e-3)
    # the input projection and the fusion whose operand has its extent
    assert values["short_conv.share"] == pytest.approx(100 * 3e-3 / busy)
    assert values["loglik_head.share"] == pytest.approx(100 * 2e-3 / busy)
    # grouped product, its buffer's fusion and the router's scores
    assert values["moe.share"] == pytest.approx(100 * 6e-3 / busy)
    assert values["moe.dispatch_share"] == pytest.approx(100 * 2 / 6)
    assert values["moe.load_max_over_mean"] == 1.25
    assert values["runner.pad_share"] == pytest.approx(100 * (1 - 10 / 12))
    assert 0 < values["gqa_attn_roofline"] < 100
    assert 0 < values["moe_expert_roofline"] < 100
    assert 0 < values["runner.mfu"] < 100


def test_a_buffer_as_long_as_the_model_is_wide_is_not_told_apart(
        hybrid_checkout):
    """Why the real cell is not on `moe.share`'s list: its last batch (one
    row of 1024 tokens, 4 picks, 8 of 32 experts held) has a dispatch
    buffer of 2048 rows, the model's width, and the reader, which tells the
    layer by the buffer's rows, then takes every operation on a hidden
    state for the layer's. Here: one more grouped product, of 64 rows."""
    run = _synthetic_run(hybrid_checkout)
    read = cells.load_module("metrics", "moe.share").read
    sound = read(run)
    ops = run["trace"].device_ops["/device:TPU:0"]
    ops.append(Event(_pallas("ragged-dot.2", "f32[64,64]"), 0.05, 0.05001))
    run["trace"] = Trace({"/device:TPU:0": ops}, [], 0.0, 0.06)
    busy = run["trace"].busy_seconds()
    assert sound == pytest.approx(100 * 6e-3 / 20e-3)
    # the convolution's taps and the head's product, (.., 64) both, join it
    assert read(run) == pytest.approx(100 * (6e-3 + 3e-3 + 1e-5) / busy)


def test_a_trace_without_the_family_reads_nothing(hybrid_checkout):
    """What the parent commit's program gives: no `gqa_attn_<i>` call and
    no array of the projection's extent. The new readers return None and
    raise nothing."""
    run = _synthetic_run(hybrid_checkout)
    run["trace"] = Trace(
        {"/device:TPU:0": [Event("%fusion.9 = f32[96,64] fusion(%x)",
                                 0.0, 1e-3)]}, [], 0.0, 1e-3)
    for name in ("gqa_attn_roofline", "short_conv.share"):
        assert cells.load_module("metrics", name).read(run) is None
    run["trace"] = None
    for name in ("gqa_attn_roofline", "short_conv.share"):
        assert cells.load_module("metrics", name).read(run) is None


def test_operations_parts_add_up():
    ref = cells.load_module("reference", "hybrid_moe_decoder")
    with open(os.path.join(BENCH_DIR, "configs", "lfm2_8b_a1b.json")) as fh:
        config = json.load(fh)
    lengths = [(16384, 8), (1024, 65)]
    need = ref.operations(config, lengths)
    parts = need["parts"]
    assert set(parts) == {"projections", "conv", "attention",
                          "routed_experts", "head"}
    for key in ("ops", "bytes"):
        assert need[key] == pytest.approx(sum(p[key]
                                              for p in parts.values()))
    s = ref.sizes(config)
    tokens = 8 * 16384 + 65 * 1024
    triangle = 8 * 16384 * 16385 / 2 + 65 * 1024 * 1025 / 2
    # 32 heads of 64 + 64 over the causal triangle, a layer
    assert parts["attention"]["ops"] == pytest.approx(
        2.0 * s["attn_layers"] * triangle * 32 * 128)
    # q and the output at 32 heads, k and v at 8, two bytes each
    assert parts["attention"]["bytes"] == pytest.approx(
        2.0 * s["attn_layers"] * tokens * 64 * (32 + 32 + 8 + 8))
    # (T, 6144) read and (T, 2048) written a layer
    assert parts["conv"]["bytes"] == pytest.approx(
        2.0 * s["conv_layers"] * tokens * (6144 + 2048))
    # a pick is three products of 2048 x 1792; 4 of 32 picks land on 8
    assert parts["routed_experts"]["per_pick"]["ops"] == 2.0 * 3 * 2048 * 1792
    assert parts["routed_experts"]["ops"] == pytest.approx(
        tokens * s["expert_layers"] * parts["routed_experts"]["per_pick"][
            "ops"])
    # the head for the positions that are scored, the matrix read once
    assert parts["head"]["ops"] == pytest.approx(
        2.0 * (tokens - 73) * 2048 * 65536)
    # every published width is in the file, and the depth and the experts
    # held are what `reduced` names
    assert (config["hidden_size"], config["intermediate_size"],
            config["moe_intermediate_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["num_experts_per_tok"],
            config["vocab_size"], config["conv_L_cache"]) == (
                2048, 7168, 1792, 32, 8, 4, 65536, 3)
    assert set(config["reduced"]) == {"num_hidden_layers", "num_experts"}
    assert config["num_hidden_layers"] == s["num_layers"] == len(
        config["model"]["layer_types"])
    assert config["layer_types"][:s["num_layers"]] == list(
        config["model"]["layer_types"])
    assert config["num_experts"] == s["experts_held"] == 8
