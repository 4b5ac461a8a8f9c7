"""A tiny cell of the `ssm_hybrid_decoder` family (a Mamba-2 state-space
scan and grouped-query attention side by side in every block, under fixed
multipliers), added AS FILES ONLY beside the benchmark's own, as
`test_looped_cell.py` does for its family, and run end to end on the CPU
through `run.py`: the lane scores it `correct` against
`reference/ssm_hybrid_decoder.py` (whose scan is the recurrence, a token at
a time), a reference whose decay is off by one token FAILS it, the control
through int8 fails it, the four new readers and the appended ones return a
number from a recorded trace and `None` from a program without the mixer,
and the parts of `operations` are what a count by hand gives."""

import json
import os
import shutil
import types

import pytest

from conftest import BENCH_DIR, REPO, run_cell, run_tool

from harness import cells
from harness.trace import Event, Trace
from harness.window import Call

CELL = "tiny_ssm.score_long_context"
REAL_CELL = "falcon_h1_34b.score_long_context"
SCORE_RATE = "transform_tokens_per_s"
LAYERS, SSM_HEADS = 2, 4
TINY_SSM = {
    "name": "tiny_ssm", "family": "ssm_hybrid_decoder",
    "reference": "ssm_hybrid_decoder", "architecture": "ssm_hybrid_decoder",
    "precision": "float32", "vocab_size": 40,
    "model": {"num_layers": LAYERS, "d_model": 64, "num_heads": 4,
              "num_kv_heads": 2, "head_dim": 16, "ssm_heads": SSM_HEADS,
              "ssm_head_dim": 16, "ssm_groups": 2, "ssm_state": 32,
              "conv_taps": 4, "d_ff_dense": 128, "rms_norm_eps": 1e-5,
              "rope_theta": 1e11, "embedding_multiplier": 5.656854249492381,
              "key_multiplier": 0.011048543456039804,
              "attention_in_multiplier": 1.0,
              "attention_out_multiplier": 0.0375, "ssm_in_multiplier": 0.25,
              "ssm_out_multiplier": 0.08838834764831845,
              "ssm_multipliers": [0.3535533905932738, 0.25,
                                  0.1767766952966369, 0.5,
                                  0.3535533905932738],
              "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
              "lm_head_multiplier": 0.0078125, "vocab_size": 40,
              "max_len": 512, "attention_impl": "flash",
              "head_chunk": 64},
}
# the real mix in small: one long row of three chunks, the last ragged, and
# short rows inside one chunk; batches of 1 as the real cell's
TINY_TRAFFIC = {
    "adapter": "dnn_transform", "rows": 5, "lengths": [[300, 1], [40, 4]],
    "mini_batch_size": 1, "bfloat16": False, "fused_dispatch": False,
    "fetch_dict": {"logprob": "token_logprobs"},
    "sample_rows": 5, "trace_calls": 1,
    # float32 against float32: the chunked algebra against the recurrence
    "limits": {"output_gap_p99": 1e-4, "output_gap_max": 1e-4,
               "pad_leak": 1e-4, "nonfinite": 0,
               "rows_or_positions_missing": 0, "call_mismatch": 0}}
NEW_READERS = ("ssd_scan_roofline", "ssd.share", "ssd.step_us")
MIXER = "ssm.share"     # the mixer whole, by its extents; the rest by name
APPENDED = ("runner.mfu", "gqa_attn_roofline", "loglik_head.share",
            "runner.h2d_share", "runner.host_s")


@pytest.fixture(scope="module")
def ssm_checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("ssm_checkout")
    shutil.copytree(BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    with open(root / "benchmark" / "configs" / "tiny_ssm.json", "w") as fh:
        json.dump(TINY_SSM, fh)
    with open(root / "benchmark" / "traffic" / "tiny_long_context.json",
              "w") as fh:
        json.dump(TINY_TRAFFIC, fh)
    bench["configs"].append({
        "name": "tiny_ssm", "source": "test", "reduced": [],
        "file": "benchmark/configs/tiny_ssm.json", "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny_ssm",
                               "traffic": "tiny_long_context",
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
        "gqa_attn_roofline", *NEW_READERS, MIXER, "setup.import_s",
        "setup.trace_s", "setup.lower_s", "setup.compile_s",
        "setup.first_run_s", "setup.traces", "setup.unspanned_s"}
    # found by name, not by place: the next cell is appended behind these
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in (*NEW_READERS, MIXER):
        assert by_name[name]["workloads"][0] == REAL_CELL
        assert by_name[name]["moves"] == SCORE_RATE
        assert by_name[name]["source"] == "device_trace"
    assert by_name["ssd.share"]["better"] == "lower"
    assert by_name[MIXER]["better"] == "lower"
    assert by_name[MIXER]["layer"] == by_name["ssd.share"]["layer"]
    assert by_name["ssd.step_us"]["unit"] == "us"
    work = {w["name"]: w for w in bench["workloads"]}[REAL_CELL]
    assert (work["config"], work["traffic"], work["chips"]) == (
        "falcon_h1_34b", "score_long_context", 1)
    entry = {c["name"]: c for c in bench["configs"]}["falcon_h1_34b"]
    assert entry["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert entry["file"] == "benchmark/configs/falcon_h1_34b.json"


def test_the_cell_runs_end_to_end_and_is_correct(ssm_checkout):
    out = _result(run_cell(ssm_checkout, CELL))
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {SCORE_RATE, "setup_s"}
    assert out["metrics"][SCORE_RATE]["value"] > 0
    for name in ("output_gap_p99", "output_gap_max"):
        assert 0 < out["checks"][name]["value"] < 1e-4


def test_an_untraced_device_reads_what_the_spans_give(ssm_checkout):
    """No device plane on the CPU: the device-trace readers, the new ones
    among them, leave their metrics out; the span readers report."""
    out = _result(run_cell(ssm_checkout, CELL, "--trace", "1"))
    assert out["correct"] is True
    assert out["metrics"]["runner.pad_share"]["value"] == pytest.approx(0.0)
    assert out["metrics"]["setup.traces"]["value"] > 0
    for name in (*NEW_READERS, *APPENDED):
        assert name not in out["metrics"]


def test_a_decay_off_by_one_token_is_not_correct(ssm_checkout):
    """The planted fault, on the reference's side: a token's own decay is
    left out of the state it reads (S_t = S_{t-1} + ..., decayed only for
    the NEXT token), so the program, which is right, is scored not correct
    by it."""
    proc = run_tool(ssm_checkout, [
        "benchmark/run.py", "--workload", CELL, "--seed", "5", "--seconds",
        "1"], prelude="""
import sys
sys.path.insert(0, "benchmark")
from harness import cells
_load = cells.load_module
def _moved(kind, name):
    module = _load(kind, name)
    if (kind, name) == ("reference", "ssm_hybrid_decoder"):
        sound = module.recurrence
        def late(xs, bm, cm, dt, a, d_skip, state):
            import jax.numpy as jnp
            shifted = jnp.concatenate([jnp.zeros_like(dt[:, :1]),
                                       dt[:, :-1]], 1)
            return sound(xs, bm, cm, shifted, a, d_skip, state)
        module.recurrence = late
    return module
cells.load_module = _moved
""")
    out = _result(proc)
    assert out["correct"] is False
    assert out["checks"]["output_gap_p99"]["value"] > 1e-2
    assert out["checks"]["nonfinite"]["value"] == 0
    assert out["checks"]["call_mismatch"]["value"] == 0


def test_correct_can_fail_a_control_through_int8(ssm_checkout):
    proc = run_tool(ssm_checkout, [
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
    return rows * SSM_HEADS * -(-length // 128) * LAYERS


def _recorded_run(root) -> dict:
    """One traced call of the tiny cell as a v5e shows it: every layer's
    scan and attention under their own names and the mixer's input
    projection by its width (2 x 64 + 2 x 2 x 32 + 4 = 260), for each of
    the two batch shapes; the times made up."""
    with open(root / "BENCHMARK.json") as fh:
        bench = json.load(fh)
    cell = cells.Cell(
        name=CELL, chips=1, config=TINY_SSM, traffic=TINY_TRAFFIC,
        per_layer=[m for m in bench["per_layer"]
                   if CELL in m.get("workloads", ())])
    ops, at = [], [0.0]

    def op(name: str, seconds: float):
        ops.append(Event(name, at[0], at[0] + seconds))
        at[0] += seconds + 1e-6

    for length, batches in ((300, 1), (40, 4)):
        for _batch in range(batches):
            for layer in range(LAYERS):
                op(f"%fusion.9 = f32[1,{length},260] fusion(f32[64,260] %w,"
                   f" f32[1,{length},64] %x), kind=kOutput", 3e-4)
                op(_pallas(f"ssd_scan_{layer}.1", f"f32[1,{length},64]"),
                   2e-4)
                op(_pallas(f"gqa_attn_{layer}.1", f"f32[1,{length},64]"),
                   1e-3)
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
            args={"ssd_steps": batches * _steps(1, length)})

    spans = [[(root_span(1, 300), steps[:1]), (root_span(4, 40), steps[1:])]]
    return {"cell": cell, "calls": [Call(0.0, 0.03, {"same": True})],
            "elapsed": 0.03, "work_per_call": 460.0, "setup_s": 1.0,
            "trace": trace, "annotation": "transform.call",
            "peaks": {"flops_per_s": 1e12, "bytes_per_s": 1e11},
            ("program_spans", "runner.transform", 2): spans}


def test_the_new_readers_and_the_appended_ones_return_a_number(ssm_checkout):
    run = _recorded_run(ssm_checkout)
    listed = [m["name"] for m in run["cell"].per_layer]
    assert {*NEW_READERS, *APPENDED} <= set(listed)
    values = {name: cells.load_module("metrics", name).read(run)
              for name in (*NEW_READERS, MIXER, *APPENDED)}
    assert all(isinstance(v, float) for v in values.values()), values
    busy = run["trace"].busy_seconds()
    scans = 5 * LAYERS * 2e-4
    assert busy == pytest.approx(5 * LAYERS * 2e-3 + 1e-3)
    assert values["ssd.share"] == pytest.approx(100 * scans / busy)
    # the scans by name and the projection by its width; the inner width,
    # 64, is the tiny model's hidden size too and tells nothing apart
    assert cells.load_module("metrics", MIXER).own_extents(
        TINY_SSM["model"]) == {"260"}
    assert values[MIXER] == pytest.approx(
        100 * (scans + 5 * LAYERS * 3e-4) / busy)
    # one row of three chunks and four of one, four heads, two layers
    steps = _steps(1, 300) + 4 * _steps(1, 40)
    assert steps == (3 + 4) * SSM_HEADS * LAYERS
    assert values["ssd.step_us"] == pytest.approx(1e6 * scans / steps)
    need = cells.load_module("reference", "ssm_hybrid_decoder").operations(
        TINY_SSM, [(300, 1), (40, 4)])
    ssd = need["parts"]["ssd"]
    assert values["ssd_scan_roofline"] == pytest.approx(100 * max(
        ssd["ops"] / 1e12, ssd["bytes"] / 1e11) / scans)
    attention = need["parts"]["attention"]
    assert values["gqa_attn_roofline"] == pytest.approx(100 * max(
        attention["ops"] / 1e12, attention["bytes"] / 1e11) / 10e-3)
    assert values["runner.mfu"] == pytest.approx(
        100 * need["ops"] / busy / 1e12)
    for name in ("ssd_scan_roofline", "gqa_attn_roofline", "runner.mfu"):
        assert 0 < values[name] < 100
    assert values["loglik_head.share"] == pytest.approx(100 * 1e-3 / busy)


def test_a_program_without_the_scan_reads_nothing(ssm_checkout):
    """What the parent gives: no `ssd_scan_<i>` call in the trace and root
    spans without the scan's count. Each new reader returns None and raises
    nothing; so it does untraced, and where the ring does not hold the
    spans."""
    run = _recorded_run(ssm_checkout)
    reads = {name: cells.load_module("metrics", name).read
             for name in NEW_READERS}
    for call in run[("program_spans", "runner.transform", 2)]:
        for root, _steps_ in call:
            root.args = {"moe_picks_held": 3}
    assert reads["ssd.step_us"](run) is None
    assert reads["ssd.share"](run) is not None       # the kernel still ran
    run[("program_spans", "runner.transform", 2)] = None
    assert reads["ssd.step_us"](run) is None
    run = _recorded_run(ssm_checkout)
    run["trace"].device_ops = {"/device:TPU:0": [
        ev for ev in run["trace"].device_ops["/device:TPU:0"]
        if "ssd_scan_" not in ev.name]}
    run["trace"]._own = None
    assert [reads[name](run) for name in NEW_READERS] == [None] * 3
    run["trace"] = None
    assert [reads[name](run) for name in NEW_READERS] == [None] * 3


def test_the_mixers_share_is_told_by_its_own_extents(ssm_checkout):
    """At the published widths both extents are the mixer's alone (9248
    and 4096); a program with neither the scan nor an array of such a
    width, a configuration of another family and an untraced run read
    nothing."""
    read = cells.load_module("metrics", MIXER)
    with open(os.path.join(BENCH_DIR, "configs", "falcon_h1_34b.json")) as fh:
        assert read.own_extents(json.load(fh)["model"]) == {"9248", "4096"}
    run = _recorded_run(ssm_checkout)
    run["trace"].device_ops = {"/device:TPU:0": [
        ev for ev in run["trace"].device_ops["/device:TPU:0"]
        if "ssd_scan_" not in ev.name and ",260]" not in ev.name]}
    run["trace"]._own = None
    assert read.read(run) is None
    run = _recorded_run(ssm_checkout)
    run["cell"].config = {"model": {"d_model": 64}}
    assert read.read(run) is None
    run["trace"] = None
    assert read.read(run) is None


def test_operations_parts_against_a_count_by_hand():
    ref = cells.load_module("reference", "ssm_hybrid_decoder")
    with open(os.path.join(BENCH_DIR, "configs", "falcon_h1_34b.json")) as fh:
        config = json.load(fh)
    with open(os.path.join(BENCH_DIR, "traffic",
                           "score_long_context.json")) as fh:
        traffic = json.load(fh)
    lengths = [(32768, 2), (2048, 14)]
    assert (traffic["rows"], traffic["lengths"], traffic["mini_batch_size"],
            traffic["fetch_dict"], traffic["sample_rows"],
            traffic["trace_calls"], traffic["bfloat16"],
            traffic["fused_dispatch"]) == (
                16, [[32768, 2], [2048, 14]], 1,
                {"logprob": "token_logprobs"}, 6, 2, False, False)
    need = ref.operations(config, lengths)
    parts = need["parts"]
    assert set(parts) == {"projections", "attention", "ssd", "convolution",
                          "feed_forward", "head"}
    for key in ("ops", "bytes"):
        assert need[key] == pytest.approx(sum(p[key]
                                              for p in parts.values()))
    layers = 5
    tokens = 2 * 32768 + 14 * 2048
    assert tokens == 94208
    triangle = 2 * 32768 * 32769 // 2 + 14 * 2048 * 2049 // 2
    # the causal triangle over 20 query heads of 128 channels, scores and
    # weighted values
    assert parts["attention"]["ops"] == pytest.approx(
        2.0 * layers * triangle * 20 * 256)
    assert parts["attention"]["bytes"] == pytest.approx(
        layers * 2.0 * tokens * 128 * (2 * 20 + 2 * 4))
    # a chunk of 128 tokens: three products a head (128 x 128 x 128, twice
    # 128 x 256 x 128) over 32 heads, C B^T (128 x 128 x 256) once a GROUP
    chunks = tokens // 128
    a_chunk = 32 * 2 * (128 * 128 * 128 + 2 * 128 * 256 * 128) \
        + 2 * 2 * 128 * 128 * 256
    assert parts["ssd"]["ops"] == pytest.approx(layers * chunks * a_chunk)
    # xs and y 4096 channels, B and C 512 each at two bytes, dt 32 at four
    assert parts["ssd"]["bytes"] == pytest.approx(
        layers * tokens * (2 * (4096 + 512 + 512 + 4096) + 4 * 32))
    assert parts["projections"]["ops"] == pytest.approx(
        2.0 * tokens * layers * (31457280 + 47349760 + 20971520))
    assert parts["feed_forward"]["ops"] == pytest.approx(
        2.0 * tokens * layers * 330301440)
    assert parts["head"]["ops"] == pytest.approx(
        2.0 * (tokens - 16) * 5120 * 32640)
    assert parts["convolution"]["ops"] == pytest.approx(
        2.0 * layers * tokens * 5120 * 4)
    # about 5.2 GFLOP a token, as the issue reckoned
    assert 5.0e9 < need["ops"] / tokens < 5.5e9
    # every published number is in the file; depth and vocabulary alone are
    # reduced, and the file says to what and from what
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "Falcon-H1-34B-Instruct")
    for key, value in row["config"].items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert set(config["reduced"]) == {"num_hidden_layers", "vocab_size"}
    assert (config["num_hidden_layers"], config["vocab_size"]) == (5, 32640)
    assert (config["published_num_hidden_layers"],
            config["published_vocab_size"]) == (72, 261120)
    assert config["source"].startswith(row["source_url"])
    m = config["model"]
    assert (m["num_layers"], m["d_model"], m["num_heads"], m["num_kv_heads"],
            m["head_dim"], m["ssm_heads"], m["ssm_head_dim"],
            m["ssm_groups"], m["ssm_state"], m["conv_taps"],
            m["d_ff_dense"], m["vocab_size"], m["rope_theta"]) == (
                5, 5120, 20, 4, 128, 32, 128, 2, 256, 4, 21504, 32640, 1e11)
    for name in ("embedding_multiplier", "key_multiplier",
                 "attention_in_multiplier", "attention_out_multiplier",
                 "ssm_in_multiplier", "ssm_out_multiplier",
                 "ssm_multipliers", "mlp_multipliers",
                 "lm_head_multiplier"):
        assert m[name] == row["config"][name], name
    # served at two bytes a parameter, over the benchmark's floor
    layer = 31457280 + 68351072 + 330301440 + 2 * 5120
    served = 2 * (layers * layer + 2 * 32640 * 5120 + 5120)
    assert f"{served // 2:,} parameters" in config["layout"]
    assert 4.96e9 < served < 4.98e9 and served > 4294967296
