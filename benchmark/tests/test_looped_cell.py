"""A tiny cell of the `looped_decoder` family (one stack of layers run four
times over the same weights, a norm on both sides of every operator, an
exit gate a step), added AS FILES ONLY beside the benchmark's own, as
`test_window_cell.py` does for its family, and run end to end on the CPU
through `run.py`: the lane scores it `correct` against
`reference/looped_decoder.py` with BOTH outputs fetched, a reference whose
final norm is taken out of the loop FAILS it, the control through int8
fails it, the new reader and the appended ones return a number from a
recorded trace and `None` from a program without the loop, and the parts
of `operations` add up."""

import json
import os
import shutil
import types

import pytest

from conftest import BENCH_DIR, REPO, run_cell, run_tool

from harness import cells
from harness.trace import Event, Trace
from harness.window import Call

CELL = "tiny_looped.score_reasoning_traces"
REAL_CELL = "ouro_2_6b.score_reasoning_traces"
SCORE_RATE = "transform_tokens_per_s"
LAYERS, STEPS = 3, 4
TINY_LOOPED = {
    "name": "tiny_looped", "family": "looped_decoder",
    "reference": "looped_decoder", "architecture": "looped_decoder",
    "precision": "float32", "vocab_size": 40,
    "model": {"num_layers": LAYERS, "total_ut_steps": STEPS,
              "early_exit_threshold": 1.0, "d_model": 64, "num_heads": 4,
              "num_kv_heads": 4, "head_dim": 16, "d_ff_dense": 128,
              "rms_norm_eps": 1e-6, "rope_theta": 1e6, "vocab_size": 40,
              "max_len": 128, "attention_impl": "flash", "head_chunk": 64},
}
# the real mix in small: 2 long rows and 5 short ones, batches of 2, so
# the short rows end in a batch of one (`pad_leak` compares its row)
TINY_TRAFFIC = {
    "adapter": "dnn_transform", "rows": 7, "lengths": [[104, 2], [24, 5]],
    "mini_batch_size": 2, "bfloat16": False, "fused_dispatch": False,
    "fetch_dict": {"logprob": "token_logprobs", "exit": "exit_pdf"},
    "sample_rows": 7, "trace_calls": 1,
    # float32 against float32: only the order of the sums differs
    "limits": {"output_gap_p99": 1e-4, "output_gap_max": 1e-4,
               "pad_leak": 1e-4, "nonfinite": 0,
               "rows_or_positions_missing": 0, "call_mismatch": 0}}
NEW_READER = "loop.pass_ms"
APPENDED = ("runner.mfu", "gqa_attn_roofline", "loglik_head.share",
            "runner.h2d_share", "runner.host_s")


@pytest.fixture(scope="module")
def looped_checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("looped_checkout")
    shutil.copytree(BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    with open(root / "benchmark" / "configs" / "tiny_looped.json",
              "w") as fh:
        json.dump(TINY_LOOPED, fh)
    with open(root / "benchmark" / "traffic" / "tiny_reasoning_traces.json",
              "w") as fh:
        json.dump(TINY_TRAFFIC, fh)
    bench["configs"].append({
        "name": "tiny_looped", "source": "test", "reduced": [],
        "file": "benchmark/configs/tiny_looped.json", "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny_looped",
                               "traffic": "tiny_reasoning_traces",
                               "chips": 1, "why": "test"})
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


def test_the_real_cell_is_on_the_lists_the_issue_names():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if REAL_CELL in m.get("workloads", ())}
    assert listed == {
        SCORE_RATE, "runner.call_s", "runner.host_s", "runner.mfu",
        "runner.h2d_share", "runner.pad_share", "loglik_head.share",
        "gqa_attn_roofline", NEW_READER, "setup.import_s", "setup.trace_s",
        "setup.lower_s", "setup.compile_s", "setup.first_run_s",
        "setup.traces", "setup.unspanned_s"}
    entry = bench["per_layer"][-1]
    assert entry == {
        "name": NEW_READER, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "runner forward",
        "moves": SCORE_RATE, "workloads": [REAL_CELL]}
    assert bench["workloads"][-1]["name"] == REAL_CELL
    assert bench["workloads"][-1]["chips"] == 1


def test_the_cell_runs_end_to_end_and_is_correct(looped_checkout):
    out = _result(run_cell(looped_checkout, CELL))
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {SCORE_RATE, "setup_s"}
    assert out["metrics"][SCORE_RATE]["value"] > 0
    for name in ("output_gap_p99", "output_gap_max", "pad_leak"):
        assert 0 <= out["checks"][name]["value"] < 1e-4
    # the ragged batch's one row is compared by itself
    assert out["checks"]["pad_leak"]["value"] > 0


def test_an_untraced_device_reads_what_the_spans_give(looped_checkout):
    """No device plane on the CPU: the device-trace readers, the new one
    among them, leave their metrics out; the span readers report."""
    out = _result(run_cell(looped_checkout, CELL, "--trace", "1"))
    assert out["correct"] is True
    # the short rows' last batch is one row on the ladder's rung of one
    assert out["metrics"]["runner.pad_share"]["value"] == pytest.approx(0.0)
    assert out["metrics"]["setup.traces"]["value"] > 0
    for name in (NEW_READER, *APPENDED):
        assert name not in out["metrics"]


def test_a_final_norm_taken_out_of_the_loop_is_not_correct(looped_checkout):
    """The planted fault, on the reference's side: the next step and the
    head read the state BEFORE the final norm (only the gate still reads
    the normed one), so the program, which is right, is scored not correct
    by it."""
    proc = run_tool(looped_checkout, [
        "benchmark/run.py", "--workload", CELL, "--seed", "5", "--seconds",
        "1"], prelude="""
import sys
sys.path.insert(0, "benchmark")
from harness import cells
_load = cells.load_module
def _moved(kind, name):
    module = _load(kind, name)
    if (kind, name) == ("reference", "looped_decoder"):
        sound = module._step_end
        def outside(x, scale, kernel, bias, eps):
            h, lam = sound(x, scale, kernel, bias, eps)
            return x, lam
        module._step_end = outside
    return module
cells.load_module = _moved
""")
    out = _result(proc)
    assert out["correct"] is False
    assert out["checks"]["output_gap_p99"]["value"] > 1e-2
    assert out["checks"]["nonfinite"]["value"] == 0
    assert out["checks"]["call_mismatch"]["value"] == 0


def test_correct_can_fail_a_control_through_int8(looped_checkout):
    proc = run_tool(looped_checkout, [
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
    """One traced call of the tiny cell as a v5e shows it: every layer's
    Pallas call under its own name, run once a step inside the loop's
    body, for each of the two batch shapes; the times made up."""
    with open(root / "BENCHMARK.json") as fh:
        bench = json.load(fh)
    cell = cells.Cell(
        name=CELL, chips=1, config=TINY_LOOPED, traffic=TINY_TRAFFIC,
        per_layer=[m for m in bench["per_layer"]
                   if CELL in m.get("workloads", ())])
    ops, at = [], [0.0]

    def op(name: str, seconds: float):
        ops.append(Event(name, at[0], at[0] + seconds))
        at[0] += seconds + 1e-6

    for _step in range(STEPS):
        for layer in range(LAYERS):
            op(_pallas(f"gqa_attn_{layer}.1", "f32[2,104,64]"), 1e-3)
            op("%fusion.3 = f32[208,64] fusion(%x), kind=kOutput", 5e-4)
    op("%fusion.7 = f32[64,40] fusion(%x), kind=kLoop", 1e-3)  # the head's
    call = Event("transform.call", 0.0, at[0] + 1e-3)
    trace = Trace({"/device:TPU:0": ops}, [call], 0.0, call.end,
                  [Event("tpu::System::TransferToDevice", 1e-3, 2e-3)])
    steps = [types.SimpleNamespace(
        name="runner.step", args={"padded": 2, "rows": rows})
        for rows in (2, 2, 2, 1)]

    def root_span(batches, tokens):
        return types.SimpleNamespace(
            name="runner.transform",
            args={"loop_steps": STEPS,
                  "loop_layer_passes": batches * LAYERS * STEPS,
                  "loop_exit_at": [0, 0, 0, tokens]})

    spans = [[(root_span(1, 208), steps[:1]),
              (root_span(3, 144), steps[1:])]]
    return {"cell": cell, "calls": [Call(0.0, 0.03, {"same": True})],
            "elapsed": 0.03, "work_per_call": 328.0, "setup_s": 1.0,
            "trace": trace, "annotation": "transform.call",
            "peaks": {"flops_per_s": 1e12, "bytes_per_s": 1e11},
            ("program_spans", "runner.transform", 2): spans}


def test_the_new_reader_and_the_appended_ones_return_a_number(
        looped_checkout):
    run = _recorded_run(looped_checkout)
    listed = [m["name"] for m in run["cell"].per_layer]
    assert {NEW_READER, *APPENDED} <= set(listed)
    values = {name: cells.load_module("metrics", name).read(run)
              for name in (NEW_READER, *APPENDED)}
    assert all(isinstance(v, float) for v in values.values()), values
    busy = run["trace"].busy_seconds()
    assert busy == pytest.approx(19e-3)
    # four batches a call pass three layers four times each
    assert values[NEW_READER] == pytest.approx(1e3 * busy / 48)
    need = cells.load_module("reference", "looped_decoder").operations(
        TINY_LOOPED, [(104, 2), (24, 5)])
    attention = need["parts"]["attention"]
    assert values["gqa_attn_roofline"] == pytest.approx(100 * max(
        attention["ops"] / 1e12, attention["bytes"] / 1e11) / 12e-3)
    assert values["runner.mfu"] == pytest.approx(
        100 * need["ops"] / busy / 1e12)
    assert 0 < values["gqa_attn_roofline"] < 100
    assert 0 < values["runner.mfu"] < 100
    # the one operation with the vocabulary's extent
    assert values["loglik_head.share"] == pytest.approx(100 * 1e-3 / busy)


def test_a_program_without_the_loop_reads_nothing(looped_checkout):
    """What the parent gives: root spans without the loop's counts. The
    new reader returns None and raises nothing; so it does untraced, and
    where the ring does not hold the spans."""
    run = _recorded_run(looped_checkout)
    read = cells.load_module("metrics", NEW_READER).read
    for call in run[("program_spans", "runner.transform", 2)]:
        for root, _steps in call:
            root.args = {"moe_picks_held": 3}
    assert read(run) is None
    run[("program_spans", "runner.transform", 2)] = None
    assert read(run) is None
    run = _recorded_run(looped_checkout)
    run["trace"] = None
    assert read(run) is None


def test_operations_parts_add_up():
    ref = cells.load_module("reference", "looped_decoder")
    with open(os.path.join(BENCH_DIR, "configs", "ouro_2_6b.json")) as fh:
        config = json.load(fh)
    with open(os.path.join(BENCH_DIR, "traffic",
                           "score_reasoning_traces.json")) as fh:
        traffic = json.load(fh)
    lengths = [(8192, 2), (1024, 8)]
    assert (traffic["rows"], traffic["lengths"], traffic["mini_batch_size"],
            traffic["fetch_dict"], traffic["sample_rows"],
            traffic["trace_calls"], traffic["bfloat16"],
            traffic["fused_dispatch"]) == (
                10, [[8192, 2], [1024, 8]], 2,
                {"logprob": "token_logprobs", "exit": "exit_pdf"}, 6, 2,
                False, False)
    need = ref.operations(config, lengths)
    parts = need["parts"]
    assert set(parts) == {"projections", "attention", "feed_forward", "head"}
    for key in ("ops", "bytes"):
        assert need[key] == pytest.approx(sum(p[key]
                                              for p in parts.values()))
    s = ref.sizes(config)
    layers, steps = s["num_layers"], s["total_ut_steps"]
    assert steps == 4 and 40 <= layers <= 48
    tokens = 2 * 8192 + 8 * 1024
    assert tokens == 24576
    triangle = 2 * 8192 * 8193 // 2 + 8 * 1024 * 1025 // 2
    # every layer four times: the products, the triangle, q, k, v and the
    # output; the weights' bytes ONCE; the gate a step; the head once
    assert parts["projections"]["ops"] == pytest.approx(
        2.0 * tokens * (4 * layers * 4 * 2048 * 2048 + 4 * 2048))
    assert parts["projections"]["bytes"] == 2.0 * (
        layers * 4 * 2048 * 2048 + 2048)
    assert parts["feed_forward"]["ops"] == pytest.approx(
        2.0 * tokens * 4 * layers * 3 * 2048 * 5632)
    assert parts["feed_forward"]["bytes"] == 2.0 * layers * 3 * 2048 * 5632
    assert parts["attention"]["ops"] == pytest.approx(
        2.0 * 4 * layers * triangle * 16 * 256)
    assert parts["attention"]["bytes"] == pytest.approx(
        4 * layers * 2.0 * tokens * 128 * 64)
    assert parts["head"]["ops"] == pytest.approx(
        2.0 * (tokens - 10) * 2048 * 49152)
    one_step = ref.operations(
        {"model": dict(config["model"], total_ut_steps=1)}, lengths)
    for part in ("attention", "feed_forward"):
        assert parts[part]["ops"] == pytest.approx(
            4 * one_step["parts"][part]["ops"])
    assert parts["feed_forward"]["bytes"] == one_step["parts"][
        "feed_forward"]["bytes"]
    assert parts["head"]["ops"] == one_step["parts"]["head"]["ops"]
    # every published width and count is in the file; depth alone may be
    # reduced, and the file says to what and from what
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["intermediate_size"], config["hidden_act"],
            config["rope_theta"], config["rms_norm_eps"],
            config["max_position_embeddings"], config["vocab_size"],
            config["tie_word_embeddings"], config["total_ut_steps"],
            config["early_exit_threshold"], config["model_type"],
            config["max_window_layers"], config["use_sliding_window"]) == (
                2048, 16, 16, 128, 5632, "silu", 1000000, 1e-6, 65536, 49152,
                False, 4, 1, "ouro", 48, False)
    assert config["layer_types"] == ["full_attention"] * 48
    assert config["published_num_hidden_layers"] == 48
    assert config["num_hidden_layers"] == layers
    assert set(config["reduced"]) == (
        set() if layers == 48 else {"num_hidden_layers"})
    m = config["model"]
    assert (m["d_model"], m["num_heads"], m["num_kv_heads"], m["head_dim"],
            m["d_ff_dense"], m["vocab_size"], m["total_ut_steps"],
            m["early_exit_threshold"], m["max_len"], m["rope_theta"]) == (
                2048, 16, 16, 128, 5632, 49152, 4, 1.0, 65536, 1e6)
    # served at two bytes a parameter, over the benchmark's floor
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    served = 2 * (layers * layer + 2 * 49152 * 2048 + 2048 + 2049)
    assert f"{served // 2:,} parameters" in config["layout"]
    assert served > 4294967296
