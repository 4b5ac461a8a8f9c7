"""Tests of the harness itself, on the CPU at tiny sizes. Not part of the
repo's tier-1 suite: run `python -m pytest benchmark/tests -q`."""

import json
import os
import shutil
import subprocess
import sys

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)

TINY_SAR = {
    "name": "tiny_sar", "family": "sar", "reference": "sar",
    "num_users": 1500, "num_items": 200, "num_interactions": 30000,
    "similarity_function": "jaccard", "support_threshold": 4,
}
LIMITS = {"rating_gap_p90": 1e-5, "topk_regret": 1e-5, "seen_or_invalid": 0,
          "rows_or_ranks_missing": 0, "call_mismatch": 0}
TINY_TRAFFIC = {
    "tiny_recommend_all": {
        "adapter": "sar_recommend", "k": 10, "remove_seen": True,
        "user_block": 256, "sample_users": 64, "trace_calls": 2,
        "limits": LIMITS},
    # the same adapter at another block size and depth, as a traffic file
    "tiny_recommend_top3": {
        "adapter": "sar_recommend", "k": 3, "remove_seen": True,
        "user_block": None, "sample_users": 64, "trace_calls": 1,
        "limits": LIMITS},
}
TINY_ENCODER = {
    "name": "tiny_encoder", "family": "transformer",
    "reference": "transformer", "architecture": "transformer",
    "precision": "float32", "vocab_size": 50,
    "model": {"num_layers": 2, "d_model": 32, "num_heads": 4, "d_ff": 64,
              "num_outputs": 3, "vocab_size": 50, "max_len": 24,
              "attention_impl": "flash"},
}
SCORE_LIMITS = {"output_gap_p99": 1e-4, "output_gap_max": 1e-4,
                "pad_leak": 1e-4, "nonfinite": 0,
                "rows_or_positions_missing": 0, "call_mismatch": 0}
TINY_SCORE_TRAFFIC = {
    # one length, one dispatch for the whole table, 44 = 5 x 8 + 4 rows
    "tiny_score_fused": {
        "adapter": "dnn_transform", "rows": 44, "lengths": 16,
        "mini_batch_size": 8, "bfloat16": False, "fused_dispatch": True,
        "fetch_dict": {"pooled": "pooled_features"}, "sample_rows": 44,
        "trace_calls": 1, "limits": SCORE_LIMITS},
    # two lengths (37 rows of 24, 24 of 12), batch by batch; the longer
    # length's last batch holds 5 rows and is padded to 8; two fetched
    # columns
    "tiny_score_streamed": {
        "adapter": "dnn_transform", "rows": 61,
        "lengths": [[24, 0.6], [12, 0.4]], "mini_batch_size": 8,
        "bfloat16": False, "fused_dispatch": False,
        "fetch_dict": {"pooled": "pooled_features", "scores": "logits"},
        "sample_rows": 61, "trace_calls": 2, "limits": SCORE_LIMITS},
}
# (cell, configuration, traffic, chips); the first two report what
# `sar_recommend_all` does, the last two the neural scoring lane's metrics
TINY_CELLS = [("tiny_sar_all", "tiny_sar", "tiny_recommend_all", 1),
              ("tiny_sar_top3", "tiny_sar", "tiny_recommend_top3", 1),
              ("tiny_score_fused", "tiny_encoder", "tiny_score_fused", 1),
              ("tiny_score_streamed", "tiny_encoder", "tiny_score_streamed",
               1)]
SAR_CELLS = [cell for cell, config, *_rest in TINY_CELLS
             if config == "tiny_sar"]
SCORE_CELLS = [cell for cell, config, *_rest in TINY_CELLS
               if config == "tiny_encoder"]
SIBLING = "sar_recommend_all"
SCORE_RATE = "transform_tokens_per_s"


def _write(path, obj):
    with open(path, "w") as fh:
        json.dump(obj, fh)


@pytest.fixture(scope="session")
def tiny_checkout(tmp_path_factory):
    """A copy of the benchmark's files with four tiny cells added AS FILES
    ONLY (a configuration each, the neural one under the reference that
    `reference/transformer.py` already is; a traffic mix each; a `workloads` entry each; their names appended to the
    metrics' `workloads` lists): what a later PR does to add a cell."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    for config in (TINY_SAR, TINY_ENCODER):
        name = config["name"]
        _write(root / "benchmark" / "configs" / f"{name}.json", config)
        bench["configs"].append({
            "name": name, "source": "test", "reduced": [],
            "file": f"benchmark/configs/{name}.json", "why": "test"})
    for name, traffic in {**TINY_TRAFFIC, **TINY_SCORE_TRAFFIC}.items():
        _write(root / "benchmark" / "traffic" / f"{name}.json", traffic)
    for cell, config, traffic, chips in TINY_CELLS:
        bench["workloads"].append({"name": cell, "config": config,
                                   "traffic": traffic, "chips": chips,
                                   "why": "test"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if SIBLING in metric.get("workloads", ()):
            metric["workloads"] += SAR_CELLS
        if SCORE_RATE in (metric["name"], metric.get("moves")):
            metric["workloads"] += SCORE_CELLS
    _write(root / "BENCHMARK.json", bench)
    return root


def run_tool(root, argv, env=None, prelude=""):
    """A benchmark program from `root`, optionally after `prelude` (Python
    source that breaks the timed path underneath it)."""
    # at tiny sizes a program compiles in under JAX's one-second floor for
    # the persistent cache; lower the floor so that the cache serves
    # what it serves at real size
    full = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
                JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
                XLA_FLAGS="--xla_force_host_platform_device_count=4")
    full.update(env or {})
    code = (prelude + "\nimport runpy, sys\n"
            f"sys.argv = {argv!r}\n"
            "runpy.run_path(sys.argv[0], run_name='__main__')\n")
    return subprocess.run([sys.executable, "-c", code], cwd=root, env=full,
                          capture_output=True, text=True, timeout=900)


def run_cell(root, workload, *extra, env=None, seed=2**31 + 11):
    """One run of a cell, as the driver makes it."""
    return run_tool(root, ["benchmark/run.py", "--workload", workload,
                           "--seed", str(seed), "--seconds", "1", *extra],
                    env=env)
