"""A tiny cell of the `mla_moe_decoder` family (latent attention, routed
and shared experts of which this "chip" holds a share, a chunked
log-likelihood head), added AS FILES ONLY beside the benchmark's own, as
`conftest.py` does for the `transformer` family, and run end to end on the
CPU through `run.py`: the lane scores it `correct` against
`reference/mla_moe_decoder.py`, and with one expert's contribution left
out of the program it does not."""

import json
import os
import shutil

import pytest

from conftest import BENCH_DIR, REPO, run_cell, run_tool

CELL = "tiny_decoder.score_loglik"
SCORE_RATE = "transform_tokens_per_s"
TINY_DECODER = {
    "name": "tiny_decoder", "family": "mla_moe_decoder",
    "reference": "mla_moe_decoder", "architecture": "mla_moe_decoder",
    "precision": "float32", "vocab_size": 256,
    "model": {
        "num_layers": 3, "d_model": 64, "num_heads": 4, "kv_lora_rank": 32,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "d_ff_dense": 128, "first_k_dense": 1, "n_routed_experts": 8,
        # a share: experts 2 to 5 of the 8 routed over
        "experts_held": [2, 4], "num_experts_per_tok": 3, "d_ff_expert": 32,
        "n_shared_experts": 1, "routed_scaling_factor": 2.446,
        "norm_topk_prob": True, "rms_norm_eps": 1e-5, "rope_theta": 50000.0,
        "vocab_size": 256, "max_len": 64, "attention_impl": "flash",
        "head_chunk": 64},
}
# two lengths (19 rows of 24, 13 of 12), batches of 8: both end in a batch
# the program pads (3 rows, 5 rows)
TINY_TRAFFIC = {
    "adapter": "dnn_transform", "rows": 32, "lengths": [[24, 19], [12, 13]],
    "mini_batch_size": 8, "bfloat16": False, "fused_dispatch": False,
    "fetch_dict": {"logprob": "token_logprobs"}, "sample_rows": 32,
    "trace_calls": 1,
    # float32 against float32: only the order of the sums differs
    "limits": {"output_gap_p99": 1e-4, "output_gap_max": 1e-4,
               "pad_leak": 1e-4, "nonfinite": 0,
               "rows_or_positions_missing": 0, "call_mismatch": 0}}


@pytest.fixture(scope="module")
def decoder_checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("decoder_checkout")
    shutil.copytree(BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    with open(root / "benchmark" / "configs" / "tiny_decoder.json", "w") as fh:
        json.dump(TINY_DECODER, fh)
    with open(root / "benchmark" / "traffic" / "tiny_loglik.json", "w") as fh:
        json.dump(TINY_TRAFFIC, fh)
    bench["configs"].append({
        "name": "tiny_decoder", "source": "test", "reduced": [],
        "file": "benchmark/configs/tiny_decoder.json", "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny_decoder",
                               "traffic": "tiny_loglik", "chips": 1,
                               "why": "test"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if SCORE_RATE in (metric["name"], metric.get("moves")):
            metric["workloads"].append(CELL)
    with open(root / "BENCHMARK.json", "w") as fh:
        json.dump(bench, fh)
    return root


def _result(proc) -> dict:
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.splitlines()[-1])


def test_the_cell_runs_end_to_end_and_is_correct(decoder_checkout):
    out = _result(run_cell(decoder_checkout, CELL))
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {SCORE_RATE, "setup_s"}
    # 19 x 24 + 13 x 12 real tokens a call
    assert out["metrics"][SCORE_RATE]["value"] > 0
    for name in ("output_gap_p99", "output_gap_max", "pad_leak"):
        assert 0 <= out["checks"][name]["value"] < 1e-4


def test_a_traced_run_reports_what_the_host_can_see(decoder_checkout):
    """No device plane on the CPU: the device-trace readers find nothing
    and leave their metric out; the span readers report."""
    out = _result(run_cell(decoder_checkout, CELL, "--trace", "1"))
    assert out["correct"] is True
    # the tails of 3 and 5 rows are padded to 4 and 8 (the bucket ladder)
    assert out["metrics"]["runner.pad_share"]["value"] == pytest.approx(
        100.0 * (1 - 32 / 36))
    # experts 2 to 5 of 8, drawn evenly: near 1, never under it
    assert 1.0 <= out["metrics"]["moe.load_max_over_mean"]["value"] < 2.0
    for name in ("moe_expert_roofline", "mla_attn_roofline", "moe.share"):
        assert name not in out["metrics"]


def test_an_experts_contribution_left_out_is_not_correct(decoder_checkout):
    """The planted fault: the first held expert's down-projection is
    zeroed inside the program's expert layer."""
    proc = run_tool(decoder_checkout, [
        "benchmark/run.py", "--workload", CELL, "--seed", "5", "--seconds",
        "1"], prelude="""
from mmlspark_tpu.parallel import moe
_sound = moe.moe_ffn_dropless
def _broken(x, router, bias, gate, up, down, **kw):
    return _sound(x, router, bias, gate, up, down.at[0].set(0.0), **kw)
moe.moe_ffn_dropless = _broken
""")
    out = _result(proc)
    assert out["correct"] is False
    assert out["checks"]["output_gap_p99"]["value"] > 1e-3
    assert out["checks"]["nonfinite"]["value"] == 0
    assert out["checks"]["call_mismatch"]["value"] == 0
