"""The seven `setup.*` metrics (`harness/setup_spans.py`): where a start
goes, read from the tracer's ring. A ring recorded by hand (every span at a
time this file chooses, through `Tracer.record_span`), then a tiny cell end
to end on the CPU."""

import json
import shutil
import types

import pytest

from conftest import run_cell
from harness import setup_spans
from harness.cells import load_module
from harness.window import Call

SETUP = ["setup.import_s", "setup.trace_s", "setup.lower_s",
         "setup.compile_s", "setup.first_run_s", "setup.traces",
         "setup.unspanned_s"]
S = 1e6             # the ring is in microseconds


@pytest.fixture
def tracer():
    from mmlspark_tpu.observability import Tracer, set_default_tracer

    tr = Tracer()
    old = set_default_tracer(tr)
    yield tr
    set_default_tracer(old)


def _run(window_calls: int, setup_s: float, trace_calls: int = 1) -> dict:
    cell = types.SimpleNamespace(traffic={"trace_calls": trace_calls})
    return {"cell": cell, "trace": None, "setup_s": setup_s,
            "calls": [Call(float(i), i + 0.5) for i in range(window_calls)]}


def _read(run) -> dict:
    return {name: load_module("metrics", name).read(run) for name in SETUP}


def _record_a_start(tr, new_spans: bool = True, each: int = 2,
                    window_calls: int = 3, trace_calls: int = 1):
    """A start as the runner's lane leaves it, seconds: the package's
    import 0 to 10 with a sub-package's inside it (4 to 6) and another
    after it (10 to 11.5); the adapter's own jitted weight-making at 12
    (no program span above it); the warm-up call's two root spans 14 to 20
    and 20 to 23, the first tracing 14.5 to 17 with a nested trace inside
    (15 to 16), lowering 17 to 18, reading the cache 18 to 19, the second
    tracing 20 to 20.5 and compiling 20.5 to 22; then the window's calls
    from 30 on, one of which recompiles (not the start's)."""
    def span(name, start, seconds, parent=None, **args):
        return tr.record_span(name, start * S, seconds * S, parent=parent,
                              **args)

    if new_spans:
        span("package.import", 4.0, 2.0, module="mmlspark_tpu.core")
        span("package.import", 0.0, 10.0, module="mmlspark_tpu")
        span("package.import", 10.0, 1.5, module="mmlspark_tpu.nn")
        span("jax.trace", 12.0, 0.7, fun_name="weights")
        span("jax.compile", 12.7, 0.8, fun_name="jit(weights)",
             cache_hit=True, retrieval_s=0.6)
    first = span("runner.transform", 14.0, 6.0)
    step = span("runner.step", 14.2, 5.0, parent=first)
    second = span("runner.transform", 20.0, 3.0)
    if new_spans:
        span("jax.trace", 15.0, 1.0, parent=step, fun_name="rotary_c64")
        span("jax.trace", 14.5, 2.5, parent=step, fun_name="forward")
        span("jax.lower", 17.0, 1.0, parent=step, fun_name="jit(forward)")
        span("jax.compile", 18.0, 1.0, parent=step, fun_name="jit(forward)",
             cache_hit=True, retrieval_s=0.9)
        span("jax.trace", 20.0, 0.5, parent=second, fun_name="forward")
        span("jax.compile", 20.5, 1.5, parent=second,
             fun_name="jit(forward)", cache_hit=False, retrieval_s=0.0)
    t = 30.0
    for call in range(window_calls + trace_calls):
        for _ in range(each):
            root = span("runner.transform", t, 1.0)
            span("runner.step", t + 0.1, 0.8, parent=root)
            if new_spans and call == 1:
                span("jax.trace", t + 0.2, 0.3, parent=root,
                     fun_name="forward")
            t += 1.0


def test_the_seven_read_sums_union_and_count(tracer):
    _record_a_start(tracer)
    got = _read(_run(3, setup_s=30.0))
    assert got == pytest.approx({
        "setup.import_s": 11.5,         # 0..10 (4..6 inside it) and 10..11.5
        "setup.trace_s": 3.0,           # 14.5..17 (15..16 inside) + 20..20.5
        "setup.lower_s": 1.0,
        "setup.compile_s": 2.5,         # a cache read and a compile
        "setup.first_run_s": 2.5,       # 9 s of root spans less 6.5
        "setup.traces": 3.0,            # counted one by one
        "setup.unspanned_s": 9.5})      # 30 - 11.5 - 9
    # `setup.unspanned_s` closes the sum to `setup_s`
    parts = ["setup.import_s", "setup.trace_s", "setup.lower_s",
             "setup.compile_s", "setup.first_run_s", "setup.unspanned_s"]
    assert sum(got[name] for name in parts) == pytest.approx(30.0)
    # the adapter's weight-making (12 to 13.5) is in none of the program's
    # parts: it is inside what no span of the program covers
    assert got["setup.unspanned_s"] > 1.5


def test_one_root_a_call_reads_the_same_way(tracer):
    # SAR's lane: one root span a call, two traced calls
    _record_a_start(tracer, each=1, window_calls=4, trace_calls=2)
    # the warm-up "call" is then the first root alone; the second root of
    # the recorded start is the window's first call and sets the cut at 20
    got = _read(_run(5, setup_s=21.0, trace_calls=2))
    assert got == pytest.approx({
        "setup.import_s": 11.5, "setup.trace_s": 2.5, "setup.lower_s": 1.0,
        "setup.compile_s": 1.0, "setup.first_run_s": 1.5,
        "setup.traces": 2.0, "setup.unspanned_s": 3.5})


def test_a_ring_without_the_new_spans_reads_nothing(tracer, capsys):
    """A parent commit: the program's own spans, none of PR 37's."""
    _record_a_start(tracer, new_spans=False)
    assert _read(_run(3, setup_s=30.0)) == dict.fromkeys(SETUP)
    assert capsys.readouterr().err == ""
    tracer.clear()                           # and no span at all
    assert _read(_run(3, setup_s=30.0)) == dict.fromkeys(SETUP)


@pytest.mark.parametrize("window_calls", [2, 4])
def test_roots_that_do_not_add_up_read_nothing(tracer, capsys, window_calls):
    _record_a_start(tracer)                  # 1 + 3 + 1 calls were made
    assert _read(_run(window_calls, setup_s=30.0)) == dict.fromkeys(SETUP)
    assert "nothing read" in capsys.readouterr().err


def test_a_ring_that_dropped_spans_reads_nothing():
    from mmlspark_tpu.observability import Tracer, set_default_tracer

    tr = Tracer(max_spans=20)
    old = set_default_tracer(tr)
    try:
        _record_a_start(tr)
        assert tr.drop_count
        assert _read(_run(3, setup_s=30.0)) == dict.fromkeys(SETUP)
    finally:
        set_default_tracer(old)


def test_union_of_intervals():
    def span(start, seconds):
        return types.SimpleNamespace(start_us=start * S, dur_us=seconds * S)

    assert setup_spans.union_seconds([]) == 0.0
    assert setup_spans.union_seconds(
        [span(5, 1), span(0, 3), span(1, 1), span(2, 2), span(5.5, 1)]
    ) == pytest.approx(4.0 + 1.5)


@pytest.fixture(scope="module")
def start_checkout(tiny_checkout, tmp_path_factory):
    """The tiny checkout with the runner's tiny cell listed under the seven
    too (the shared one lists a metric for it by the rate it moves, and
    these move `setup_s`)."""
    root = tmp_path_factory.mktemp("start_checkout")
    shutil.copytree(tiny_checkout, root, dirs_exist_ok=True)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for metric in bench["per_layer"]:
        if metric["name"] in SETUP:
            metric["workloads"] = metric["workloads"] + ["tiny_score_streamed"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.mark.parametrize("cell", ["tiny_sar_all", "tiny_score_streamed"])
def test_a_tiny_cell_reports_all_seven(start_checkout, cell):
    """Both lanes on the CPU: the seven are numbers, the parts lie inside
    `setup_s` and close to it, and the window holds no compile."""
    proc = run_cell(start_checkout, cell, "--trace", "1")
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    got = {name: m["value"] for name, m in line["metrics"].items()}
    assert set(SETUP) <= set(got), proc.stderr[-3000:]
    assert line["compiles_in_window"] == 0
    assert got["setup.import_s"] > 0 and got["setup.traces"] >= 1
    assert all(got[name] >= 0 for name in SETUP)
    parts = ["setup.import_s", "setup.trace_s", "setup.lower_s",
             "setup.compile_s", "setup.first_run_s", "setup.unspanned_s"]
    (setup_line,) = [ln for ln in proc.stderr.splitlines()
                     if ln.startswith("run: set-up")]
    setup_s = float(setup_line.split()[2])
    # trace, lowering and compile may overlap one another (an operation run
    # eagerly while a function is traced), so the sum may pass `setup_s` by
    # that overlap; the line on stderr is rounded to a tenth
    assert setup_s - 0.06 <= sum(got[name] for name in parts) < 1.1 * setup_s
