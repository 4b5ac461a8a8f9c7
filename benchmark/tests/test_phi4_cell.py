"""A tiny cell of the `decoder_hybrid_decoder` family (Mamba-1 scans,
differential attention behind a band, full and over ANOTHER layer's keys and
values, gated memory units over one layer's scan output), added AS FILES
ONLY beside the benchmark's own, as `test_ssm_cell.py` does for its family,
and run end to end on the CPU through `run.py`: the lane scores it `correct`
against `reference/decoder_hybrid_decoder.py` (the scan a token at a time,
the attention four softmax-weighted sums a pair), a reference whose lambda
follows the wrong index FAILS it, the control through int8 fails it, the
five new readers and the appended ones return a number from a recorded
trace and `None` from a program without their kernels, and the parts of
`operations` are what a count by hand gives."""

import json
import os
import shutil
import types

import pytest

from conftest import BENCH_DIR, REPO, run_cell, run_tool

from harness import cells
from harness.trace import Event, Trace
from harness.window import Call

CELL = "tiny_phi4.score_long_traces"
REAL_CELL = "phi4_mini_flash.score_long_traces"
SCORE_RATE = "transform_tokens_per_s"
LAYERS, MAMBAS, INNER = 8, 3, 128
TINY = {
    "name": "tiny_phi4", "family": "decoder_hybrid_decoder",
    "reference": "decoder_hybrid_decoder",
    "architecture": "decoder_hybrid_decoder",
    "precision": "float32", "vocab_size": 40,
    "model": {"num_layers": LAYERS, "d_model": 64, "num_heads": 4,
              "num_kv_heads": 2, "mamba_inner": INNER, "mamba_state": 16,
              "mamba_dt_rank": 4, "conv_taps": 4, "window_size": 16,
              "d_ff_dense": 96, "layer_norm_eps": 1e-5, "vocab_size": 40,
              "max_len": 512, "attention_impl": "flash", "head_chunk": 64},
}
# the real mix in small: one long row of three chunks, the last ragged, well
# past the window, and short rows inside one chunk; batches of 1
TINY_TRAFFIC = {
    "adapter": "dnn_transform", "rows": 5, "lengths": [[300, 1], [40, 4]],
    "mini_batch_size": 1, "bfloat16": False, "fused_dispatch": False,
    "fetch_dict": {"logprob": "token_logprobs"},
    "sample_rows": 5, "trace_calls": 1,
    # float32 against float32: the same recurrence chunked, one sum over a
    # value twice as wide against two over its halves
    "limits": {"output_gap_p99": 1e-4, "output_gap_max": 1e-4,
               "pad_leak": 1e-4, "nonfinite": 0,
               "rows_or_positions_missing": 0, "call_mismatch": 0}}
NEW_READERS = ("sel_scan_roofline", "sel_scan.share", "sel_scan.step_us",
               "diff_attn_roofline", "diff_attn.share")
APPENDED = ("runner.mfu", "loglik_head.share", "runner.h2d_share",
            "runner.host_s", "swa.tiles_over_band")


@pytest.fixture(scope="module")
def phi4_checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("phi4_checkout")
    shutil.copytree(BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    with open(root / "benchmark" / "configs" / "tiny_phi4.json", "w") as fh:
        json.dump(TINY, fh)
    with open(root / "benchmark" / "traffic" / "tiny_long_traces.json",
              "w") as fh:
        json.dump(TINY_TRAFFIC, fh)
    bench["configs"].append({
        "name": "tiny_phi4", "source": "test", "reduced": [],
        "file": "benchmark/configs/tiny_phi4.json", "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny_phi4",
                               "traffic": "tiny_long_traces",
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
        "swa.tiles_over_band", *NEW_READERS, "setup.import_s",
        "setup.trace_s", "setup.lower_s", "setup.compile_s",
        "setup.first_run_s", "setup.traces", "setup.unspanned_s"}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_READERS:
        assert by_name[name]["workloads"] == [REAL_CELL]
        assert by_name[name]["moves"] == SCORE_RATE
        assert by_name[name]["source"] == "device_trace"
    assert [m["name"] for m in bench["per_layer"]][-5:] == list(NEW_READERS)
    assert by_name["sel_scan.step_us"]["unit"] == "us"
    assert by_name["sel_scan_roofline"]["layer"] == by_name[
        "ssd_scan_roofline"]["layer"]
    assert by_name["diff_attn.share"]["layer"] == by_name["swa.share"][
        "layer"]
    assert bench["workloads"][-1] == {
        "name": REAL_CELL, "config": "phi4_mini_flash",
        "traffic": "score_long_traces", "chips": 1,
        "why": bench["workloads"][-1]["why"]}
    entry = bench["configs"][-1]
    assert (entry["name"], entry["reduced"], entry["file"]) == (
        "phi4_mini_flash", ["num_hidden_layers"],
        "benchmark/configs/phi4_mini_flash.json")


def test_the_cell_runs_end_to_end_and_is_correct(phi4_checkout):
    out = _result(run_cell(phi4_checkout, CELL))
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {SCORE_RATE, "setup_s"}
    assert out["metrics"][SCORE_RATE]["value"] > 0
    for name in ("output_gap_p99", "output_gap_max"):
        assert 0 < out["checks"][name]["value"] < 1e-4


def test_an_untraced_device_reads_what_the_spans_give(phi4_checkout):
    """No device plane on the CPU: the device-trace readers, the new ones
    among them, leave their metrics out; the span readers report (no banded
    kernel runs on the CPU's tier, so no tiles are written either)."""
    out = _result(run_cell(phi4_checkout, CELL, "--trace", "1"))
    assert out["correct"] is True
    assert out["metrics"]["runner.pad_share"]["value"] == pytest.approx(0.0)
    assert out["metrics"]["setup.traces"]["value"] > 0
    for name in (*NEW_READERS, *APPENDED):
        assert name not in out["metrics"]


def test_a_lambda_by_the_wrong_index_is_not_correct(phi4_checkout):
    """The planted fault, on the reference's side: lambda_init counted from
    1, not from 0, so the program, which is right, is scored not correct."""
    proc = run_tool(phi4_checkout, [
        "benchmark/run.py", "--workload", CELL, "--seed", "5", "--seconds",
        "1"], prelude="""
import sys
sys.path.insert(0, "benchmark")
from harness import cells
_load = cells.load_module
def _moved(kind, name):
    module = _load(kind, name)
    if (kind, name) == ("reference", "decoder_hybrid_decoder"):
        sound = module.lambda_init
        module.lambda_init = lambda i: sound(i + 1)
    return module
cells.load_module = _moved
""")
    out = _result(proc)
    assert out["correct"] is False
    assert out["checks"]["output_gap_p99"]["value"] > 1e-2
    assert out["checks"]["nonfinite"]["value"] == 0
    assert out["checks"]["call_mismatch"]["value"] == 0


def test_correct_can_fail_a_control_through_int8(phi4_checkout):
    proc = run_tool(phi4_checkout, [
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


def _steps(rows: int, length: int) -> int:
    return rows * (INNER // 128) * -(-length // 128) * MAMBAS


def _recorded_run(root) -> dict:
    """One traced call of the tiny cell as a v5e shows it: every Mamba
    layer's scan, both forwards of every differential layer (the long row's
    sliding layers under the banded forward's own name, the short rows'
    under the layer's) and the products between; the times made up."""
    with open(root / "BENCHMARK.json") as fh:
        bench = json.load(fh)
    cell = cells.Cell(
        name=CELL, chips=1, config=TINY, traffic=TINY_TRAFFIC,
        per_layer=[m for m in bench["per_layer"]
                   if CELL in m.get("workloads", ())])
    ops, at = [], [0.0]

    def op(name: str, seconds: float):
        ops.append(Event(name, at[0], at[0] + seconds))
        at[0] += seconds + 1e-6

    for length, batches in ((300, 1), (40, 4)):
        for _batch in range(batches):
            for layer in (0, 2, 4):
                op(_pallas(f"sel_scan_{layer}.1", f"f32[1,{length},128]"),
                   2e-4)
            for layer in (1, 3):
                name = "diff_swa_w16" if length > 16 else f"diff_swa_{layer}"
                for _softmax in range(2):
                    op(_pallas(f"{name}.1",
                               f"(f32[2,{length},32], f32[2,{length},1])"),
                       3e-4)
            for layer in (5, 7):
                for _softmax in range(2):
                    op(_pallas(f"diff_attn_{layer}.1",
                               f"(f32[2,{length},32], f32[2,{length},1])"),
                       5e-4)
            op("%fusion.3 = f32[300,64] fusion(%x), kind=kOutput", 5e-4)
    op("%fusion.7 = f32[64,40] fusion(%x), kind=kLoop", 1e-3)  # the head's
    call = Event("transform.call", 0.0, at[0] + 1e-3)
    trace = Trace({"/device:TPU:0": ops}, [call], 0.0, call.end,
                  [Event("tpu::System::TransferToDevice", 1e-3, 2e-3)])
    steps = [types.SimpleNamespace(
        name="runner.step", args={"padded": 1, "rows": 1})
        for _ in range(5)]

    def root_span(batches, length):
        return types.SimpleNamespace(
            name="runner.transform",
            args={"sel_scan_steps": batches * _steps(1, length),
                  "shared_reads": 2 * batches,
                  "attn_window_tile_pairs": 30.0 * batches,
                  "attn_window_tile_pairs_needed": 20.0 * batches})

    spans = [[(root_span(1, 300), steps[:1]), (root_span(4, 40), steps[1:])]]
    return {"cell": cell, "calls": [Call(0.0, 0.03, {"same": True})],
            "elapsed": 0.03, "work_per_call": 460.0, "setup_s": 1.0,
            "trace": trace, "annotation": "transform.call",
            "peaks": {"flops_per_s": 1e12, "bytes_per_s": 1e11},
            ("program_spans", "runner.transform", 2): spans}


def test_the_new_readers_and_the_appended_ones_return_a_number(
        phi4_checkout):
    run = _recorded_run(phi4_checkout)
    listed = [m["name"] for m in run["cell"].per_layer]
    assert {*NEW_READERS, *APPENDED} <= set(listed)
    values = {name: cells.load_module("metrics", name).read(run)
              for name in (*NEW_READERS, *APPENDED)}
    assert all(isinstance(v, float) for v in values.values()), values
    busy = run["trace"].busy_seconds()
    scans = 5 * MAMBAS * 2e-4
    attends = 5 * (2 * 2 * 3e-4 + 2 * 2 * 5e-4)
    assert busy == pytest.approx(scans + attends + 5 * 5e-4 + 1e-3)
    assert values["sel_scan.share"] == pytest.approx(100 * scans / busy)
    assert values["diff_attn.share"] == pytest.approx(100 * attends / busy)
    # one row of three chunks and four of one, one channel block, three
    # Mamba layers
    steps = _steps(1, 300) + 4 * _steps(1, 40)
    assert steps == (3 + 4) * MAMBAS
    assert values["sel_scan.step_us"] == pytest.approx(1e6 * scans / steps)
    need = cells.load_module(
        "reference", "decoder_hybrid_decoder").operations(
            TINY, [(300, 1), (40, 4)])
    for part, name, taken in (("selscan", "sel_scan_roofline", scans),
                              ("diff_attn", "diff_attn_roofline", attends)):
        assert values[name] == pytest.approx(100 * max(
            need["parts"][part]["ops"] / 1e12,
            need["parts"][part]["bytes"] / 1e11) / taken)
        assert 0 < values[name] < 100
    assert values["runner.mfu"] == pytest.approx(
        100 * need["ops"] / busy / 1e12)
    assert values["loglik_head.share"] == pytest.approx(100 * 1e-3 / busy)
    assert values["swa.tiles_over_band"] == pytest.approx(1.5)


def test_a_program_without_the_kernels_reads_nothing(phi4_checkout):
    """What the parent gives: no `sel_scan_<i>`, `diff_attn_*` or
    `diff_swa_*` call in the trace and root spans without the scan's count.
    Each new reader returns None and raises nothing; so it does untraced,
    and where the ring does not hold the spans."""
    run = _recorded_run(phi4_checkout)
    reads = {name: cells.load_module("metrics", name).read
             for name in NEW_READERS}
    for call in run[("program_spans", "runner.transform", 2)]:
        for root, _steps_ in call:
            root.args = {"moe_picks_held": 3}
    assert reads["sel_scan.step_us"](run) is None
    assert reads["sel_scan.share"](run) is not None   # the kernel still ran
    run[("program_spans", "runner.transform", 2)] = None
    assert reads["sel_scan.step_us"](run) is None
    run = _recorded_run(phi4_checkout)
    run["trace"].device_ops = {"/device:TPU:0": [
        ev for ev in run["trace"].device_ops["/device:TPU:0"]
        if "sel_scan_" not in ev.name and "diff_" not in ev.name]}
    run["trace"]._own = None
    assert [reads[name](run) for name in NEW_READERS] == [None] * 5
    # another family's kernels are not these
    run["trace"].device_ops["/device:TPU:0"] += [
        Event(_pallas("ssd_scan_0.1", "f32[1,300,64]"), 1.0, 1.1),
        Event(_pallas("gqa_attn_1.1", "f32[1,300,64]"), 1.2, 1.3),
        Event(_pallas("swa_attn_w4096.1", "f32[1,300,64]"), 1.4, 1.5)]
    run["trace"]._own = None
    assert [reads[name](run) for name in NEW_READERS] == [None] * 5
    run["trace"] = None
    assert [reads[name](run) for name in NEW_READERS] == [None] * 5


def test_operations_parts_against_a_count_by_hand():
    ref = cells.load_module("reference", "decoder_hybrid_decoder")
    with open(os.path.join(BENCH_DIR, "configs",
                           "phi4_mini_flash.json")) as fh:
        config = json.load(fh)
    with open(os.path.join(BENCH_DIR, "traffic",
                           "score_long_traces.json")) as fh:
        traffic = json.load(fh)
    lengths = [(32768, 2), (4096, 10)]
    assert (traffic["adapter"], traffic["rows"], traffic["lengths"],
            traffic["mini_batch_size"], traffic["fetch_dict"],
            traffic["sample_rows"], traffic["trace_calls"],
            traffic["bfloat16"], traffic["fused_dispatch"]) == (
                "dnn_transform", 12, [[32768, 2], [4096, 10]], 1,
                {"logprob": "token_logprobs"}, 4, 2, False, False)
    need = ref.operations(config, lengths)
    parts = need["parts"]
    assert set(parts) == {"projections", "diff_attn", "selscan",
                          "convolution", "feed_forward", "head"}
    for key in ("ops", "bytes"):
        assert need[key] == pytest.approx(sum(p[key]
                                              for p in parts.values()))
    tokens = 2 * 32768 + 10 * 4096
    assert tokens == 106496
    triangle = 2 * 32768 * 32769 // 2 + 10 * 4096 * 4097 // 2
    band = 512 * 513 // 2
    banded = 2 * (band + (32768 - 512) * 512) + 10 * (
        band + (4096 - 512) * 512)
    # a full and three cross layers by the triangle, four sliding ones by
    # the band: 40 heads' scores 64 wide, 40 softmaxes over a value of 128
    assert parts["diff_attn"]["ops"] == pytest.approx(
        2.0 * (4 * triangle + 4 * banded) * 40 * (64 + 128))
    # 7680 T^2 a full layer a row, as the issue reckoned
    assert 2.0 * (32768 * 32769 / 2) * 40 * 192 == pytest.approx(
        7680 * 32768 ** 2, rel=1e-4)
    # q and o in 8 layers, k and v in the 5 that make them
    assert parts["diff_attn"]["bytes"] == pytest.approx(
        2.0 * tokens * (8 * 2 * 2560 + 5 * 2 * 1280))
    # 81,920 state cells a token a Mamba layer, six operations each; x and
    # y at two bytes, dt at four, B and C
    assert parts["selscan"]["ops"] == pytest.approx(
        6.0 * 5 * tokens * 5120 * 16)
    assert parts["selscan"]["bytes"] == pytest.approx(
        5 * tokens * (2 * 2 * 5120 + 4 * 5120 + 2 * 2 * 16))
    mamba = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    assert parts["projections"]["ops"] == pytest.approx(2.0 * tokens * (
        5 * mamba + 5 * (2560 * 5120 + 2560 * 2560) + 3 * 2 * 2560 * 2560
        + 3 * 2 * 2560 * 5120))
    assert parts["feed_forward"]["ops"] == pytest.approx(
        2.0 * tokens * 16 * 78643200)
    assert parts["head"]["ops"] == pytest.approx(
        2.0 * (tokens - 12) * 2560 * 200064)
    assert parts["convolution"]["ops"] == pytest.approx(
        2.0 * 5 * tokens * 5120 * 4)
    # about 4.4 GFLOP of products a token, as the issue reckoned
    products = sum(parts[k]["ops"] for k in ("projections", "feed_forward",
                                             "head"))
    assert 4.3e9 < products / tokens < 4.45e9
    # every published number is in the file; depth alone is reduced, and the
    # file says to what and from what
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "Phi-4-mini-flash-reasoning")
    for key, value in row["config"].items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert set(config["reduced"]) == {"num_hidden_layers"}
    assert (config["num_hidden_layers"],
            config["published_num_hidden_layers"]) == (16, 32)
    assert config["source"].startswith(row["source_url"])
    m = config["model"]
    assert (m["num_layers"], m["d_model"], m["num_heads"], m["num_kv_heads"],
            m["mamba_inner"], m["mamba_state"], m["mamba_dt_rank"],
            m["conv_taps"], m["window_size"], m["d_ff_dense"],
            m["vocab_size"], m["layer_norm_eps"]) == (
                16, 2560, 40, 20, 5120, 16, 160, 4, 512, 10240, 200064, 1e-5)
    # served at two bytes a parameter, over the benchmark's floor
    layer_kinds = {"mamba": 119895040, "self": 98322304, "gmu": 104867840,
                   "cross": 91766144}
    served = 5 * (layer_kinds["mamba"] + layer_kinds["self"]) + 3 * (
        layer_kinds["gmu"] + layer_kinds["cross"]) + 200064 * 2560 + 5120
    assert served == 2193157632
    assert f"{served:,} parameters" in config["layout"]
    assert 2 * served > 4294967296
    # ... and the program's own tree at these sizes has as many
    import jax
    from mmlspark_tpu.nn.models import make_model
    tree = jax.eval_shape(lambda: make_model(
        config["architecture"], **m).init(
            jax.random.PRNGKey(0), jax.numpy.zeros((1, 8), "int32")))
    assert sum(a.size for a in jax.tree.leaves(tree)) == served
