"""Every adapter end to end on the CPU, as the driver runs a cell."""

import json

import pytest

from conftest import TINY_CELLS, run_cell

CELLS = [cell for cell, *_rest in TINY_CELLS]


def last_line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reports_its_end_to_end_metrics(tiny_checkout, cell):
    proc = run_cell(tiny_checkout, cell, "--trace", "0")
    line = last_line(proc)
    assert line["correct"] is True, proc.stdout[-3000:]
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["device"]["platform"] == "cpu"
    names = set(line["metrics"])
    assert "setup_s" in names and len(names) == 2
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["compiles_in_window"] == 0
    # every number beside its limit: as it is read, as the last lines of
    # standard error and as the last key of the result
    assert "check " in proc.stdout
    assert list(line)[-1] == "checks" and line["checks"]
    tail = proc.stderr.strip().splitlines()[-len(line["checks"]):]
    assert [t.split()[1].rstrip(":") for t in tail] == list(line["checks"])
    assert all(c["value"] <= c["limit"] for c in line["checks"].values())


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_per_layer_metrics(tiny_checkout, cell):
    line = last_line(run_cell(tiny_checkout, cell, "--trace", "1"))
    assert line["correct"] is True
    assert "setup_s" not in line["metrics"] and line["metrics"]
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_no_accelerator_is_refused(tiny_checkout):
    # without the explicit JAX_PLATFORMS=cpu a machine with no TPU exits
    # non-zero and prints no result (here JAX is held to the CPU by config)
    proc = run_cell(tiny_checkout, "tiny_sar_all", "--trace", "0",
                    env={"JAX_PLATFORMS": "", "JAX_PLATFORM_NAME": "cpu"})
    assert proc.returncode != 0
    assert "{" not in proc.stdout


def test_bare_directory_is_refused(tiny_checkout):
    # only BENCHMARK.json and the benchmark's own files: no program
    proc = run_cell(tiny_checkout, "tiny_sar_all", "--trace", "0",
                    env={"PYTHONPATH": ""})
    assert proc.returncode != 0 and proc.stdout.strip() == ""
