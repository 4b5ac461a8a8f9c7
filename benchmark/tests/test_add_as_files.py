"""A later PR adds a cell, and a per-layer metric, as files only."""

import json

from conftest import run_cell


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
