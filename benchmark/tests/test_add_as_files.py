"""A later PR adds a cell, and a per-layer metric, as files only."""

import json
import os

from conftest import BENCH_DIR, REPO, SAR_CELLS, SCORE_CELLS, run_cell


def test_a_metric_added_as_one_file_is_found_and_reported(tiny_checkout):
    metrics = tiny_checkout / "benchmark" / "metrics"
    (metrics / "test.calls_per_window.py").write_text(
        '"""Calls a window held."""\n\n\n'
        "def read(run):\n    return float(len(run['calls']))\n")
    (metrics / "test.reads_nothing.py").write_text(
        "def read(run):\n    return None\n")
    bench = json.loads((tiny_checkout / "BENCHMARK.json").read_text())
    for name in ("test.calls_per_window", "test.reads_nothing"):
        bench["per_layer"].append({
            "name": name, "unit": "calls", "better": "higher",
            "source": "host_clock", "layer": "entry points",
            "moves": "recommend_users_per_s",
            "workloads": ["tiny_sar_all"]})
    (tiny_checkout / "BENCHMARK.json").write_text(json.dumps(bench))
    proc = run_cell(tiny_checkout, "tiny_sar_all", "--trace", "1")
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["metrics"]["test.calls_per_window"]["value"] == line[
        "attempted"]
    # a reader that finds nothing to read is left out of the line
    assert "test.reads_nothing" not in line["metrics"]
    # and the cells themselves were added by the fixture as files only:
    # no file of the benchmark that was there is changed
    real = json.loads((tiny_checkout / "BENCHMARK.json").read_text())
    assert {"tiny_sar_all", "sar_recommend_all"} <= {w["name"]
                                              for w in real["workloads"]}


def test_unknown_workload_is_refused(tiny_checkout):
    proc = run_cell(tiny_checkout, "no_such_cell", "--trace", "0")
    assert proc.returncode != 0 and "{" not in proc.stdout


def test_cells_were_added_with_no_byte_of_the_benchmark_changed(
        tiny_checkout):
    """The fixture's four cells, the neural lane's among them, came in as
    new files, new entries and names appended to `workloads` lists: every
    file the benchmark had is there byte for
    byte, and taking the additions out of `BENCHMARK.json` gives back the
    repo's."""
    had = added = 0
    for folder, _dirs, files in os.walk(tiny_checkout / "benchmark"):
        for name in files:
            path = os.path.join(folder, name)
            mine = os.path.join(BENCH_DIR, os.path.relpath(
                path, tiny_checkout / "benchmark"))
            if "__pycache__" in path or ".jax_cache" in path:
                continue
            if os.path.exists(mine):
                had += 1
                with open(path, "rb") as a, open(mine, "rb") as b:
                    assert a.read() == b.read(), path
            else:
                added += 1
    # two configurations, four traffic mixes (other tests add metric files
    # of their own)
    assert had > 30 and added >= 6
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        repo = json.load(fh)
    bench = json.loads((tiny_checkout / "BENCHMARK.json").read_text())
    tiny = set(SAR_CELLS + SCORE_CELLS)
    assert {w["name"] for w in bench["workloads"]} >= tiny
    bench["configs"] = [c for c in bench["configs"]
                        if not c["name"].startswith("tiny_")]
    bench["workloads"] = [w for w in bench["workloads"]
                          if w["name"] not in tiny]
    bench["per_layer"] = [m for m in bench["per_layer"]
                          if not m["name"].startswith("test.")]
    lane = [m for m in bench["end_to_end"] + bench["per_layer"]
            if set(SCORE_CELLS) <= set(m.get("workloads", ()))]
    assert [m["name"] for m in lane] == [
        "transform_tokens_per_s", "runner.call_s", "runner.host_s",
        "runner.mfu", "runner.h2d_share", "runner.pad_share"]
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = [w for w in metric["workloads"]
                                   if w not in tiny]
    assert bench == repo
