"""The per-layer metrics that read the program's own spans: from the
tracer's ring (the window's untraced calls) and from the device trace (the
traced calls). CPU, tiny sizes."""

import json
import os
import types

import numpy as np
import pytest

from conftest import run_cell
from harness import program_spans
from harness.cells import load_module
from harness.trace import Event, Trace, is_matmul_fusion
from harness.window import Call

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "sar_spans_v5e.xplane.pb")
RING = ["sar.dispatch_s", "sar.wait_s", "sar.readback_s", "sar.call_self_s"]
TRACED = ["sar.readback_idle_s", "sar.idle_named_share"]
TICK = 0.001


def test_tiny_cell_reports_the_ring_metrics(tiny_checkout):
    proc = run_cell(tiny_checkout, "tiny_sar_all", "--trace", "1")
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    got = {name: m["value"] for name, m in line["metrics"].items()}
    assert set(RING) <= set(got), proc.stderr[-3000:]
    assert all(got[name] > 0 for name in RING)
    # a CPU run has no device plane, so nothing says when the device was
    # idle: the two metrics of the device trace are left out, as
    # `sar.host_s` is
    assert not set(TRACED) & set(got) and "sar.host_s" not in got
    # the phases are the call: together they lie within the call as the
    # benchmark times it from outside (which holds the adapter's
    # comparison too)
    assert 0.5 * got["sar.call_s"] < sum(got[n] for n in RING) < 1.05 * got[
        "sar.call_s"]


class TickingClock:
    def __init__(self):
        self.readings = 0

    def monotonic(self):
        self.readings += 1
        return self.readings * TICK


@pytest.fixture
def ring():
    """The process-default tracer on a clock that ticks once a reading,
    and a tiny model to fill its ring with."""
    from mmlspark_tpu.observability import Tracer, set_default_tracer
    from mmlspark_tpu.recommendation import SARModel

    rng = np.random.default_rng(3)
    model = SARModel()
    model.user_affinity = rng.random((40, 12)).astype(np.float32)
    model.item_similarity = rng.random((12, 12)).astype(np.float32)
    model.seen = rng.random((40, 12)) < 0.3
    tracer = Tracer(clock=TickingClock())
    old = set_default_tracer(tracer)
    yield lambda: model.recommend_for_all_users(3, user_block=16)
    set_default_tracer(old)


def _run(window_calls: int, trace_calls: int = 2, **traffic) -> dict:
    cell = types.SimpleNamespace(
        traffic={"trace_calls": trace_calls, **traffic})
    return {"cell": cell, "trace": None, "annotation": "recommend.call",
            "calls": [Call(float(i), i + 0.5) for i in range(window_calls)]}


def test_ring_metrics_add_up_to_the_call(ring):
    for _ in range(1 + 5 + 2):         # warm-up, window, traced
        ring()
    run = _run(5)
    calls = program_spans.window_calls(run)
    assert len(calls) == 5
    # 3 blocks: a child span is one tick, the call 8 a block and one more
    assert calls[0] == pytest.approx({
        "sar.recommend_all": 25 * TICK, "sar.slice": 3 * TICK,
        "sar.dispatch": 3 * TICK, "sar.wait": 3 * TICK,
        "sar.readback": 3 * TICK, "self": 13 * TICK})
    got = {name: load_module("metrics", name).read(run) for name in RING}
    assert got == pytest.approx({
        "sar.dispatch_s": 3 * TICK, "sar.wait_s": 3 * TICK,
        "sar.readback_s": 3 * TICK, "sar.call_self_s": 13 * TICK})
    # with `sar.slice` (which no metric reads any more) they are the call
    assert sum(got.values()) + 3 * TICK == pytest.approx(
        program_spans.median_seconds(run, program_spans.SAR_ROOT))
    # the root span's arguments: 3 blocks, the 2nd and 3rd enqueued while
    # the one before was unread
    (root_args,) = program_spans.window_args(run, program_spans.SAR_ROOT)[0]
    assert (root_args["blocks"], root_args["dispatched_ahead"]) == (3, 2)
    # every block that can be ahead was: 2 of 3 - 1
    assert load_module("metrics", "sar.dispatched_ahead_share").read(
        run) == pytest.approx(100.0)
    assert [a["hi"] for a in program_spans.window_args(
        run, "sar.slice")[0]] == [16, 32, 40]


@pytest.mark.parametrize("made,window", [
    (7, 5),      # a call short: 1 + 5 + 2 expected
    (9, 5),      # a call over
    (0, 0),      # a program without the spans (a parent commit)
])
def test_nothing_is_read_when_the_roots_do_not_add_up(ring, capsys, made,
                                                      window):
    for _ in range(made):
        ring()
    run = _run(window)
    assert program_spans.window_calls(run) is None
    assert all(load_module("metrics", name).read(run) is None
               for name in RING + ["sar.dispatched_ahead_share"])
    assert "expected" in capsys.readouterr().err


def test_nothing_is_read_from_a_ring_that_dropped_spans():
    from mmlspark_tpu.observability import Tracer, set_default_tracer

    tracer = Tracer(clock=TickingClock(), max_spans=3)
    old = set_default_tracer(tracer)
    try:
        for _ in range(4):
            with tracer.start_span(program_spans.SAR_ROOT):
                pass
        assert tracer.drop_count == 1
        assert program_spans.window_calls(_run(0)) is None
    finally:
        set_default_tracer(old)


def _trace(device, host):
    events = [Event(*e) for e in host]
    return Trace({"/device:TPU:0": [Event(*e) for e in device]}, events,
                 0.0, 20.0)


def test_idle_time_is_laid_against_the_spans():
    # two traced calls of one block each; the device runs 2..6 and 12..16
    device = [("fusion", 2.0, 6.0), ("fusion", 12.0, 16.0)]
    host = [("recommend.call", 0.0, 10.0), ("recommend.call", 10.0, 20.0)]
    for t in (0.0, 10.0):
        host += [("sar.recommend_all", t + 0.5, t + 9.5),
                 ("sar.slice", t + 1.0, t + 1.5),       # idle 0.5
                 ("sar.dispatch", t + 1.5, t + 2.5),    # idle 0.5
                 ("sar.wait", t + 2.5, t + 6.0),        # idle 0
                 ("sar.readback", t + 6.0, t + 8.0)]    # idle 2
    run = {"trace": _trace(device, host), "annotation": "recommend.call",
           "cell": types.SimpleNamespace(traffic={"trace_calls": 2})}
    assert load_module("metrics", "sar.readback_idle_s").read(
        run) == pytest.approx(2.0)
    # idle inside a call 6 of 10, of which the phases name 3
    assert load_module("metrics", "sar.idle_named_share").read(
        run) == pytest.approx(50.0)


def test_recorded_v5e_trace_holds_the_spans_beside_the_device():
    """One whole `sar_recommend_all` pass recorded on a v5e (PR 24) in a
    session the script opened itself, no environment variable set: the
    program's spans are on the line of the thread that carried
    `recommend.call`, on the device operations' clock."""
    trace = Trace.from_file(RECORDED, annotations=("recommend.call",))
    (call,) = trace.spans("recommend.call")
    (root,) = trace.spans(program_spans.SAR_ROOT)
    assert call.start <= root.start and root.end <= call.end
    for name in program_spans.SAR_PHASES:
        spans = trace.spans(name)
        assert len(spans) == 18
        assert all(root.start <= s.start and s.end <= root.end
                   for s in spans)
    # the two clocks agree in this session: every product starts 0.40 to
    # 0.56 ms after the `sar.dispatch` span that launched it opens (in the
    # sessions `run.py` opens it reads 0.6 to 0.9 ms BEFORE: PERF.md)
    products = [e for e in trace.device_ops["/device:TPU:0"]
                if is_matmul_fusion(e.name)]
    lags = [op.start - span.start for op, span
            in zip(products, trace.spans("sar.dispatch"))]
    assert len(lags) == 18 and 0.39e-3 < min(lags) and max(lags) < 0.56e-3
    # read by hand: the device works 149.0 ms of the pass's 207.7; of the
    # 58.7 ms it idles, 17.0 lie in `sar.slice`, 5.6 in `sar.dispatch`,
    # 10.9 in `sar.wait` (after the last operation, before the host
    # returns) and 20.4 in `sar.readback`, where nothing is in flight
    idle = {name: program_spans.idle_seconds_inside(trace, (name,))
            for name in program_spans.SAR_PHASES}
    assert idle == pytest.approx({
        "sar.slice": 16.99e-3, "sar.dispatch": 5.55e-3,
        "sar.wait": 10.93e-3, "sar.readback": 20.42e-3}, rel=1e-3)
    assert idle["sar.readback"] == pytest.approx(
        sum(s.seconds for s in trace.spans("sar.readback")))
    run = {"trace": trace, "annotation": "recommend.call",
           "cell": types.SimpleNamespace(traffic={"trace_calls": 1})}
    assert load_module("metrics", "sar.readback_idle_s").read(
        run) == pytest.approx(20.42e-3, rel=1e-3)
    assert load_module("metrics", "sar.idle_named_share").read(
        run) == pytest.approx(100 * 53.89 / 58.71, rel=1e-3)


@pytest.mark.parametrize("device,host", [
    ([], [("recommend.call", 0.0, 10.0), ("sar.readback", 6.0, 8.0)]),
    ([("fusion", 2.0, 6.0)], [("recommend.call", 0.0, 10.0)]),
])
def test_no_device_plane_or_no_spans_reads_nothing(device, host):
    trace = _trace(device, host)
    if not device:
        trace.device_ops = {}
    run = {"trace": trace, "annotation": "recommend.call",
           "cell": types.SimpleNamespace(traffic={"trace_calls": 1})}
    assert all(load_module("metrics", name).read(run) is None
               for name in TRACED)
    assert all(load_module("metrics", name).read(dict(run, trace=None))
               is None for name in TRACED)


# ---- the runner's spans: several roots a call, arguments ---------------- #

@pytest.fixture
def runner_ring():
    """The process-default tracer, and `transform(rows, batch)` that opens
    the spans the streamed path of `DeepModelTransformer` opens, a full
    batch padded to itself and a ragged one to the next power of two."""
    from mmlspark_tpu.observability import Tracer, set_default_tracer

    tracer = Tracer(clock=TickingClock())
    old = set_default_tracer(tracer)

    def transform(rows, batch):
        with tracer.start_span("runner.transform", rows=rows,
                               batch_size=batch):
            for lo in range(0, rows, batch):
                m = min(batch, rows - lo)
                with tracer.start_span("runner.step", rows=m,
                                       padded=1 << (m - 1).bit_length()):
                    pass

    yield transform
    set_default_tracer(old)


TWO_LENGTHS = {"rows": 61, "lengths": [[24, 0.6], [12, 0.4]]}


def test_pad_share_reads_the_steps_arguments(runner_ring):
    # a call is two tables (two lengths): 37 rows and 24 rows in batches
    # of 8, so 61 real rows in 64 scored
    for _ in range(1 + 4 + 2):
        runner_ring(37, 8)
        runner_ring(24, 8)
    run = _run(4, **TWO_LENGTHS)
    assert load_module("metrics", "runner.pad_share").read(
        run) == pytest.approx(100.0 * 3 / 64)
    steps = program_spans.window_args(run, "runner.step",
                                      "runner.transform", 2)
    assert len(steps) == 4 and len(steps[0]) == 5 + 3
    sums = program_spans.window_calls(run, "runner.transform", 2)
    assert sums[0]["runner.step"] == pytest.approx(8 * TICK)


@pytest.mark.parametrize("tables", [
    0,      # the fused one-dispatch path opens no `runner.*` span
    10,     # roots that do not divide into 1 + 4 + 2 calls
    21,     # three a call where the traffic file's two lengths make two
])
def test_pad_share_reads_nothing_without_whole_calls(runner_ring, capsys,
                                                     tables):
    for _ in range(tables):
        runner_ring(37, 8)
    assert load_module("metrics", "runner.pad_share").read(
        _run(4, **TWO_LENGTHS)) is None
    assert "expected" in capsys.readouterr().err


def test_h2d_share_is_the_union_of_transfers_inside_the_calls():
    # two calls of 10 s; transfers 1..3 and 2..4 overlap (union 3 s), one
    # straddles the second call's end (1 s inside), one lies between calls
    trace = _trace([("fusion", 2.0, 6.0)],
                   [("transform.call", 0.0, 10.0),
                    ("transform.call", 12.0, 22.0)])
    trace.transfers_in = [Event("t", 1.0, 3.0), Event("t", 2.0, 4.0),
                          Event("t", 21.0, 23.0), Event("t", 10.5, 11.5)]
    run = {"trace": trace, "annotation": "transform.call"}
    read = load_module("metrics", "runner.h2d_share").read
    assert read(run) == pytest.approx(100.0 * 4.0 / 20.0)
    trace.transfers_in = []          # a trace without transfer events
    assert read(run) is None and read({"trace": None}) is None


def test_recorded_v5e_trace_pairs_each_transfer_with_its_completion():
    # 18 blocks, four small uploads each (PR 24's program sliced on the
    # host): 72 transfers, each issued on the calling side and reported
    # done on the runtime's worker 0.1 to 0.3 ms later
    trace = Trace.from_file(RECORDED, annotations=("recommend.call",))
    assert len(trace.transfers_in) == 72
    assert all(0.05e-3 < t.seconds < 0.5e-3 for t in trace.transfers_in)
    (call,) = trace.spans("recommend.call")
    assert all(call.start < t.start and t.end < call.end
               for t in trace.transfers_in)
