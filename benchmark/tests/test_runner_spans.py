"""The eleven `runner.*` metrics of PR 51 (`harness/runner_spans.py`): the
runner's own phases, read from the tracer's ring and, one of them, from the
device trace. A ring recorded by hand (every span at a time this file
chooses, through `Tracer.record_span`), then the tiny streamed cell end to
end on the CPU."""

import json
import threading
import types

import pytest

from conftest import run_cell
from harness import runner_spans
from harness.cells import load_module
from harness.trace import Event, Trace
from harness.window import Call

RING = ["runner.stack_s", "runner.feed_wait_s", "runner.prepare_s",
        "runner.dispatch_s", "runner.readback_s", "runner.wait_s",
        "runner.call_self_s", "runner.longest_part_share",
        "runner.slowest_call_excess_s", "runner.slowest_call_host_excess_s"]
TRACED = ["runner.idle_named_share"]
ADD_UP = ["runner.stack_s", "runner.feed_wait_s", "runner.dispatch_s",
          "runner.wait_s", "runner.readback_s", "runner.call_self_s"]
S = 1e6             # the ring is in microseconds
WORKER = 7          # a thread that is not the calling one
TWO_LENGTHS = {"rows": 61, "lengths": [[24, 0.6], [12, 0.4]]}


@pytest.fixture
def tracer():
    from mmlspark_tpu.observability import Tracer, set_default_tracer

    tr = Tracer()
    old = set_default_tracer(tr)
    yield tr
    set_default_tracer(old)


def _run(window_calls: int, trace_calls: int = 1, **traffic) -> dict:
    cell = types.SimpleNamespace(
        traffic={"trace_calls": trace_calls, **(traffic or TWO_LENGTHS)})
    return {"cell": cell, "trace": None, "annotation": "transform.call",
            "calls": [Call(float(i), i + 0.5) for i in range(window_calls)]}


def _read(run, names=RING + TRACED) -> dict:
    return {name: load_module("metrics", name).read(run) for name in names}


def _whole(b, wait=1.0, readback=0.1):
    """A part of b batches: stack 0.2, waits for the prefetcher 0.3 +
    (b - 1) 0.01, b dispatches of 0.05, b waits, b readbacks, and of its
    own 0.04 a step and 0.1 at the end."""
    return (0.2 + 0.3 + (b - 1) * 0.01 + b * (0.05 + wait + readback)
            + b * 0.04 + 0.1)


def _part(tr, t, row_shape, batches, wait=1.0, readback=0.1, phases=True):
    """One `runner.transform` from `t` on, seconds: stack 0.2, then a batch
    a wait for the prefetcher (0.3 the first, 0.01 the others) and a step
    of dispatch 0.05, from the second on the wait and the readback of the
    batch before, and 0.04 of its own; the drain's wait and readback under
    the root, then 0.1 to join. The prefetcher's thread prepares a batch
    every 0.5 s from the stack's end on (an upload of 0.4 inside).
    -> the time the part ends."""
    def span(name, start, seconds, parent, tid=None, **args):
        made = tr.record_span(name, start * S, seconds * S, parent=parent,
                              **args)
        if tid is not None:
            made.tid = tid
        return made

    root = span("runner.transform", t, _whole(batches, wait, readback), None,
                rows=8 * batches, batch_size=8, row_shape=list(row_shape))
    at = t + 0.2
    if phases:
        span("runner.stack", t, 0.2, root, bytes=64)
    for b in range(batches):
        fed = 0.3 if b == 0 else 0.01
        behind = wait + readback if b else 0.0
        step = span("runner.step", at + fed, 0.05 + behind + 0.04, root,
                    rows=8, padded=8)
        if phases:
            span("runner.feed_wait", at, fed, root, item=b)
            made = span("runner.prepare", t + 0.2 + 0.5 * b, 0.5, root,
                        tid=WORKER, item=b, rows=8, padded=8, bytes=64)
            span("runner.upload", t + 0.25 + 0.5 * b, 0.4, made, tid=WORKER,
                 bytes=64)
            span("runner.dispatch", at + fed, 0.05, step, cache="hit")
            if b:
                span("runner.wait", at + fed + 0.05, wait, step, batch=b - 1)
                span("runner.readback", at + fed + 0.05 + wait, readback,
                     step, batch=b - 1, bytes=32)
        at += fed + 0.05 + behind + 0.04
    if phases:
        span("runner.wait", at, wait, root, batch=batches - 1)
        span("runner.readback", at + wait, readback, root,
             batch=batches - 1, bytes=32)
    return t + _whole(batches, wait, readback)


def _record(tr, window_calls, trace_calls=1, phases=True, stall=None):
    """Warm-up, window and traced calls of two parts each (three batches
    of rows of 24, two of rows of 12). `stall`: (call of the window, span
    name, seconds) lengthens every such span of that call's first part."""
    t = 10.0
    for call in range(-1, window_calls + trace_calls):
        extra = {}
        if stall is not None and call == stall[0]:
            extra = {{"runner.wait": "wait",
                      "runner.readback": "readback"}[stall[1]]: stall[2]}
        t = _part(tr, t, (24,), 3, phases=phases, **extra) + 0.5
        t = _part(tr, t, (12,), 2, phases=phases) + 0.5


def test_the_ring_metrics_read_sums_union_and_shares(tracer):
    _record(tracer, window_calls=3)
    got = _read(_run(3))
    assert got == pytest.approx({
        "runner.stack_s": 0.4,
        "runner.feed_wait_s": 0.63,             # 0.32 + 0.31
        "runner.prepare_s": 2.5,                # five batches, the worker's
        "runner.dispatch_s": 0.25,
        "runner.readback_s": 0.5,
        "runner.wait_s": 5.0,
        "runner.call_self_s": 0.4,              # 5 x 0.04 + 2 x 0.1
        "runner.longest_part_share": 100.0 * _whole(3) / (
            _whole(3) + _whole(2)),
        "runner.slowest_call_excess_s": 0.0,
        "runner.slowest_call_host_excess_s": 0.0,
        "runner.idle_named_share": None})       # no device trace
    # the six add up to the call's root spans; the worker's are in none
    calls = runner_spans.window_calls(_run(3))
    assert len(calls) == 3
    assert sum(got[name] for name in ADD_UP) == pytest.approx(
        calls[0]["runner.transform"])
    assert calls[0]["runner.transform"] == pytest.approx(
        _whole(3) + _whole(2))
    assert calls[0]["runner.upload"] == pytest.approx(2.0)


@pytest.mark.parametrize("phase,host", [
    ("runner.readback", 1.0),   # a stall in the host's own phase: all of it
    ("runner.wait", 0.0),       # spent waiting for the device: none of it
])
def test_a_stalled_call_says_where_it_stalled(tracer, phase, host):
    # the second of five calls holds three spans of that name in its first
    # part, each 0.7 s longer than in the other calls
    base = {"runner.wait": 1.0, "runner.readback": 0.1}[phase]
    _record(tracer, window_calls=5, stall=(1, phase, base + 0.7))
    got = _read(_run(5), RING)
    assert got["runner.slowest_call_excess_s"] == pytest.approx(2.1)
    assert got["runner.slowest_call_host_excess_s"] == pytest.approx(
        2.1 * host)
    # the medians do not see it
    assert got["runner.wait_s"] == pytest.approx(5.0)
    assert got["runner.readback_s"] == pytest.approx(0.5)


def test_the_host_excess_is_the_raw_difference(tracer):
    """A loop the device bounds hides a slow host phase: the call that was
    longest by a hair spent 0.3 s MORE outside its waits than the median
    call, its waits that much shorter. The reader says so, above the
    excess, and a longest call with faster host phases reads below 0."""
    _record(tracer, window_calls=3)
    roots = [s for s in tracer.spans() if s.name == "runner.transform"]
    first = roots[2]                        # the window's first call
    first.dur_us += 0.001 * S
    waits = [s for s in tracer.spans() if s.name == "runner.wait"
             and runner_spans._root_of(s) is first]
    for s in waits:
        s.dur_us -= 0.1 * S                 # 0.3 s less waiting in all
    excess, host = runner_spans.slowest_call_excess(_run(3))
    assert excess == pytest.approx(0.001) and host == pytest.approx(0.301)
    for s in waits:
        s.dur_us += 0.2 * S                 # now 0.3 s MORE waiting
    excess, host = runner_spans.slowest_call_excess(_run(3))
    assert excess == pytest.approx(0.001) and host == pytest.approx(-0.299)


def test_a_ring_without_the_new_spans_reads_nothing(tracer, capsys):
    """A parent commit: the root and `runner.step`, none of PR 51's."""
    _record(tracer, window_calls=3, phases=False)
    assert _read(_run(3)) == dict.fromkeys(RING + TRACED)
    assert capsys.readouterr().err == ""
    tracer.clear()                           # no span at all, SAR's lane,
    assert _read(_run(3)) == dict.fromkeys(RING + TRACED)   # the fused path
    assert _read(_run(3, k=10)) == dict.fromkeys(RING + TRACED)


def test_roots_that_do_not_add_up_read_nothing(tracer, capsys):
    _record(tracer, window_calls=3)          # 1 + 3 + 1 calls were made
    assert _read(_run(4)) == dict.fromkeys(RING + TRACED)
    assert "expected" in capsys.readouterr().err


def test_idle_time_is_laid_against_the_phases():
    # one traced call of one part; the device runs 3..6 and 6.5..9
    device = [("fusion", 3.0, 6.0), ("fusion", 6.5, 9.0)]
    host = [("transform.call", 0.0, 10.0),
            ("runner.transform", 0.5, 9.8),     # idle 2.5 + 0.5 + 0.8
            ("runner.stack", 0.5, 1.0),         # idle 0.5
            ("runner.feed_wait", 1.2, 2.0),     # idle 0.8
            ("runner.step", 2.0, 9.0),
            ("runner.dispatch", 2.0, 3.5),      # idle 1.0
            ("runner.wait", 3.5, 6.2),          # idle 0.2
            ("runner.readback", 6.2, 6.4)]      # idle 0.2
    trace = Trace({"/device:TPU:0": [Event(*e) for e in device]},
                  [Event(*e) for e in host], 0.0, 10.0)
    run = {"trace": trace, "annotation": "transform.call"}
    read = load_module("metrics", "runner.idle_named_share").read
    assert read(run) == pytest.approx(100.0 * 2.7 / 3.8)
    # a trace of a parent commit: the root span and no phase
    trace.host_events = [e for e in trace.host_events if e.name in (
        "transform.call", "runner.transform", "runner.step")]
    assert read(run) is None
    trace.device_ops = {}                       # a CPU run: no device plane
    assert read(run) is None and read({"trace": None}) is None


def test_the_tiny_streamed_cell_reports_the_ring_metrics(tiny_checkout):
    """Two lengths, batch by batch, on the CPU: the ten ring metrics are
    numbers, the six parts add up to the root spans, which lie inside the
    call as the benchmark times it from outside, and what the parent
    reported is still there. A CPU run has no device plane, so the one
    metric of the device trace is left out, as `runner.host_s` is."""
    proc = run_cell(tiny_checkout, "tiny_score_streamed", "--trace", "1")
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    got = {name: m["value"] for name, m in line["metrics"].items()}
    assert set(RING) <= set(got), proc.stderr[-3000:]
    assert not set(TRACED) & set(got) and "runner.host_s" not in got
    assert line["correct"] is True and line["compiles_in_window"] == 0
    assert got["runner.pad_share"] == pytest.approx(100.0 * 3 / 64)
    assert all(got[name] > 0 for name in ADD_UP + ["runner.prepare_s"])
    assert 50.0 < got["runner.longest_part_share"] < 100.0
    assert got["runner.slowest_call_excess_s"] >= 0.0
    assert 0.5 * got["runner.call_s"] < sum(
        got[name] for name in ADD_UP) < 1.02 * got["runner.call_s"]


def test_the_fused_cell_reports_none_of_them(tiny_checkout):
    """The one-dispatch path opens no `runner.*` span: the line holds the
    parent's metrics and none of the eleven, and nothing is raised."""
    proc = run_cell(tiny_checkout, "tiny_score_fused", "--trace", "1")
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert not set(RING + TRACED) & set(line["metrics"])
    assert "runner.call_s" in line["metrics"]


def test_the_worker_threads_spans_are_found(tiny_checkout):
    """`window_calls` walks the ring for every descendant of a root, on
    whichever thread: a span recorded on another thread with the root
    handed over as parent is summed under its name, and is in no part of
    the calling thread's sum."""
    from mmlspark_tpu.observability import Tracer, set_default_tracer

    tr = Tracer()
    old = set_default_tracer(tr)
    try:
        for _ in range(3):                   # warm-up, window, traced
            with tr.start_span("runner.transform", row_shape=[4]) as root:
                with tr.start_span("runner.stack"):
                    pass

                def work():
                    with tr.start_span("runner.prepare", parent=root):
                        with tr.start_span("runner.upload"):
                            pass

                worker = threading.Thread(target=work)
                worker.start()
                worker.join(timeout=10)
                assert not worker.is_alive()
        (call,) = runner_spans.window_calls(_run(1, rows=4, lengths=4))
        assert set(call) == {"runner.transform", "runner.stack",
                             "runner.prepare", "runner.upload", "self",
                             "longest"}
        assert call["self"] == pytest.approx(
            call["runner.transform"] - call["runner.stack"])
    finally:
        set_default_tracer(old)
