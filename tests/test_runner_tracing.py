"""Spans inside a streamed `DeepModelTransformer.transform`: one
`runner.transform` a call, opened at the entry, and under it `runner.stack`
and, a batch, the prefetcher's `runner.feed_wait` and `runner.prepare`
(`runner.upload` inside it, on whichever thread prepares), then
`runner.step` with `runner.dispatch` and the `runner.wait` and
`runner.readback` of the batch BEFORE; the last batch's two hang under the
root. A fake clock that ticks once a reading makes the calling thread's
durations exact where nothing else reads it (prefetch depth 0). And what
the traced loop returns: the untraced loop's table, bit for bit."""

from __future__ import annotations

import threading

import numpy as np
import pytest

from mmlspark_tpu.core.dataplane import Prefetcher
from mmlspark_tpu.core.schema import Table
from mmlspark_tpu.nn.models import ModelBundle
from mmlspark_tpu.nn.runner import DeepModelTransformer
from mmlspark_tpu.observability import Tracer, set_default_tracer

ROWS, BATCH, WIDTH = 11, 4, 6          # three batches, the last 3 padded to 4
TICK = 0.001
STEP = ["runner.dispatch", "runner.wait", "runner.readback"]


class TickingClock:
    """Every reading is one tick later than the last."""

    def __init__(self):
        self.readings = 0
        self._lock = threading.Lock()

    def monotonic(self) -> float:
        with self._lock:
            self.readings += 1
            return self.readings * TICK


def make_stage(depth: int = 2, **params) -> DeepModelTransformer:
    stage = DeepModelTransformer(
        input_col="x", fetch_dict={"out": "logits", "p": "probability"},
        mini_batch_size=BATCH, fused_dispatch=False, prefetch_depth=depth,
        **params)
    return stage.set_model(
        ModelBundle.init("mlp", (WIDTH,), seed=0, num_outputs=3))


def make_table(rows: int = ROWS) -> Table:
    rng = np.random.default_rng(7)
    return Table({"x": rng.normal(size=(rows, WIDTH)).astype(np.float32)})


@pytest.fixture
def tracer():
    tr = Tracer(clock=TickingClock(), id_seed=1)
    old = set_default_tracer(tr)
    yield tr
    set_default_tracer(old)


def traced_call(tracer, depth: int, table=None, **params):
    """One warm call's spans, completion order, and the stage."""
    stage = make_stage(depth, **params)
    table = make_table() if table is None else table
    stage.transform(table)              # compiles; its spans are dropped
    tracer.clear()
    out = stage.transform(table)
    return tracer.spans(), stage, out


def children(spans, parent):
    return sorted((s for s in spans if s.parent is parent),
                  key=lambda s: s.start_us)


@pytest.mark.parametrize("depth", [0, 2])
def test_a_call_records_its_phases(tracer, depth):
    spans, _stage, _out = traced_call(tracer, depth)
    (root,) = [s for s in spans if s.name == "runner.transform"]
    assert root.parent_id == 0 and spans[-1] is root    # completes last
    assert root.args == {"rows": ROWS, "batch_size": BATCH,
                         "row_shape": [WIDTH]}
    assert all(s.trace_id == root.trace_id for s in spans)
    under_root = children(spans, root)
    steps = [s for s in under_root if s.name == "runner.step"]
    assert [(s.args["rows"], s.args["padded"]) for s in steps] == [
        (4, 4), (4, 4), (3, 4)]
    # the stack first; a batch is fed, then stepped; the drain last
    on_caller = [s.name for s in under_root if s.tid == root.tid]
    if depth == 0:
        assert on_caller == (["runner.stack"]
                             + ["runner.feed_wait", "runner.step"] * 3
                             + ["runner.wait", "runner.readback"])
    else:       # the wait that finds the prefetcher's end mark is a span too
        assert on_caller == (["runner.stack"]
                             + ["runner.feed_wait", "runner.step"] * 3
                             + ["runner.feed_wait", "runner.wait",
                                "runner.readback"])
    (stack,) = [s for s in under_root if s.name == "runner.stack"]
    assert stack.args == {"bytes": ROWS * WIDTH * 4}
    # a step enqueues its batch BEFORE it waits for the batch before
    assert [[c.name for c in children(spans, s)] for s in steps] == [
        STEP[:1], STEP, STEP]
    waits = sorted((s for s in spans if s.name == "runner.wait"),
                   key=lambda s: s.start_us)
    dispatches = [children(spans, s)[0] for s in steps]
    assert [w.args["batch"] for w in waits] == [0, 1, 2]
    assert dispatches[1].start_us < waits[0].start_us
    assert dispatches[2].start_us < waits[1].start_us
    assert waits[2].parent is root                      # the drain's
    assert [d.args["cache"] for d in dispatches] == ["hit"] * 3
    readbacks = sorted((s for s in spans if s.name == "runner.readback"),
                       key=lambda s: s.start_us)
    # two fetched outputs of 3 float32 columns, the padded batch whole
    assert [(r.args["batch"], r.args["bytes"]) for r in readbacks] == [
        (b, 2 * BATCH * 3 * 4) for b in range(3)]
    # never more than two batches in flight, and the last one drained
    timeline = sorted(dispatches + readbacks, key=lambda s: s.start_us)
    in_flight = np.cumsum([1 if s.name == "runner.dispatch" else -1
                           for s in timeline])
    assert in_flight.max() == 2 and in_flight[-1] == 0
    # a batch is prepared once, its upload inside the preparing
    prepares = sorted((s for s in spans if s.name == "runner.prepare"),
                      key=lambda s: s.args["item"])
    assert [(p.args["item"], p.args["rows"], p.args["padded"],
             p.args["bytes"]) for p in prepares] == [
        (i, rows, 4, 4 * WIDTH * 4) for i, rows in enumerate((4, 4, 3))]
    for p in prepares:
        (upload,) = children(spans, p)
        assert upload.name == "runner.upload"
        assert upload.args == {"bytes": 4 * WIDTH * 4}
    feeds = [s for s in under_root if s.name == "runner.feed_wait"]
    assert [f.args["item"] for f in feeds] == list(range(len(feeds)))
    if depth == 0:      # serial: the wait IS the preparing, nested in it
        assert [p.parent for p in prepares] == feeds
        assert all(p.tid == root.tid for p in prepares)


def test_the_workers_spans_carry_the_root_and_no_span_is_an_orphan(tracer):
    spans, _stage, _out = traced_call(tracer, depth=2)
    (root,) = [s for s in spans if s.name == "runner.transform"]
    prepares = [s for s in spans if s.name == "runner.prepare"]
    uploads = [s for s in spans if s.name == "runner.upload"]
    assert len(prepares) == len(uploads) == 3
    # another thread's, handed the root as parent: never parentless
    assert all(p.parent is root and p.tid != root.tid for p in prepares)
    assert all(u.parent in prepares and u.tid == u.parent.tid
               for u in uploads)
    assert {s.name for s in spans if s.parent_id == 0} == {
        "runner.transform"}
    assert {s.tid for s in spans} == {root.tid, prepares[0].tid}


def test_the_callers_children_add_up_to_the_root(tracer):
    """Depth 0, so only the calling thread reads the clock: every span is
    one tick longer than what lies inside it, and between two children of
    the root lies one tick."""
    spans, _stage, _out = traced_call(tracer, depth=0)
    (root,) = [s for s in spans if s.name == "runner.transform"]
    assert all(s.tid == root.tid for s in spans)
    direct = children(spans, root)
    assert len(direct) == 1 + 2 * 3 + 2
    assert root.dur_us == pytest.approx(
        sum(s.dur_us for s in direct) + (len(direct) + 1) * TICK * 1e6)
    # a span of the innermost kind is one tick; a step holds three of them
    leaves = [s for s in spans if s.name in (
        "runner.stack", "runner.upload", *STEP)]
    assert all(s.dur_us == pytest.approx(TICK * 1e6) for s in leaves)
    # 23 spans (the root, the stack and seven a batch), two readings each,
    # and nothing else reads the clock
    assert len(spans) == 23
    assert root.dur_us == pytest.approx((2 * 23 - 1) * TICK * 1e6)


@pytest.mark.parametrize("depth", [0, 2])
def test_a_disabled_tracer_changes_nothing_and_records_nothing(tracer,
                                                               depth):
    spans, stage, traced = traced_call(tracer, depth)
    assert spans
    off = Tracer(enabled=False)
    set_default_tracer(off)
    plain = stage.transform(make_table())
    fresh = make_stage(depth).transform(make_table())
    assert off.spans() == []
    for name in ("out", "p"):
        for other in (plain, fresh):
            assert np.asarray(other[name]).dtype == np.asarray(
                traced[name]).dtype
            np.testing.assert_array_equal(np.asarray(other[name]),
                                          np.asarray(traced[name]))
    assert np.asarray(traced["out"]).shape == (ROWS, 3)


@pytest.mark.parametrize("depth", [0, 2])
def test_a_prefetcher_given_no_span_records_nothing(tracer, depth):
    """Whatever is active around it: `core/fusion.py`'s prefetchers run
    inside the pipeline's spans and hand none over."""
    with tracer.start_span("around") as around:
        assert list(Prefetcher(range(5), lambda i: i * i, depth=depth,
                               name="loose")) == [0, 1, 4, 9, 16]
    assert tracer.spans() == [around]
    tracer.clear()
    # handed one, it names its two spans after itself, under that span
    with tracer.start_span("around") as around:
        with tracer.start_span("elsewhere"):
            assert list(Prefetcher(range(2), str, depth=depth, name="loose",
                                   span=around, tracer=tracer)) == ["0", "1"]
    names = [s.name for s in tracer.spans()]
    assert names.count("loose.prepare") == 2
    assert names.count("loose.feed_wait") == (2 if depth == 0 else 3)
    feeds = [s for s in tracer.spans() if s.name == "loose.feed_wait"]
    assert all(f.parent is around for f in feeds)
    assert all(p.parent is (feeds[p.args["item"]] if depth == 0 else around)
               for p in tracer.spans() if p.name == "loose.prepare")
    assert {s.name for s in tracer.spans() if s.parent_id == 0} == {"around"}


def test_a_failed_prepare_still_reaches_the_consumer(tracer):
    def prepare(i):
        if i == 1:
            raise ValueError("item 1")
        return i

    with tracer.start_span("around") as around:
        got = []
        with pytest.raises(ValueError, match="item 1"):
            for item in Prefetcher(range(3), prepare, depth=2, name="loose",
                                   span=around, tracer=tracer):
                got.append(item)
    assert got == [0]
    failed = [s for s in tracer.spans()
              if s.name == "loose.prepare" and s.args["item"] == 1]
    assert len(failed) == 1                 # the span closed all the same


def test_a_streamed_fit_shows_the_prefetchers_spans_under_its_epochs():
    """`DNNLearner.fit`'s streamed epoch shares the prefetcher and hands
    it the epoch's span."""
    from mmlspark_tpu.nn.trainer import DNNLearner

    tr = Tracer()
    old = set_default_tracer(tr)
    try:
        rng = np.random.default_rng(0)
        x = rng.normal(size=(64, 6)).astype(np.float32)
        DNNLearner(architecture="mlp", model_config={"features": (8,)},
                   epochs=2, batch_size=16, features_col="features",
                   label_col="label", fused_epochs=False).fit(
            Table({"features": x, "label": (x[:, 0] > 0).astype(np.int64)}))
    finally:
        set_default_tracer(old)
    epochs = [s for s in tr.spans() if s.name == "trainer.epoch"]
    assert len(epochs) == 2 and not any(e.args["fused"] for e in epochs)
    for epoch in epochs:
        names = [s.name for s in tr.spans() if s.parent is epoch]
        assert names.count("trainer.prepare") == 4          # 64 rows of 16
        assert names.count("trainer.feed_wait") == 5
    assert not [s for s in tr.spans() if s.name.startswith("runner.")]


def test_the_fused_path_opens_no_runner_span(tracer):
    stage = DeepModelTransformer(
        input_col="x", fetch_dict={"out": "logits"},
        mini_batch_size=BATCH).set_model(
            ModelBundle.init("mlp", (WIDTH,), seed=0, num_outputs=3))
    out = stage.transform(make_table())
    assert np.asarray(out["out"]).shape == (ROWS, 3)
    assert tracer.spans() == [] and stage.last_pipeline_stats is None


def test_a_table_the_fused_budget_refuses_keeps_the_loops_own_root(tracer):
    """`fused_dispatch` on, a budget of nothing: the table goes to the
    streamed loop, whose root opens there as it always did, after the
    stacking, so the call records no `runner.stack`."""
    stage = DeepModelTransformer(
        input_col="x", fetch_dict={"out": "logits"}, mini_batch_size=BATCH,
        fused_dispatch_budget_mb=0).set_model(
            ModelBundle.init("mlp", (WIDTH,), seed=0, num_outputs=3))
    streamed = make_stage().transform(make_table())
    tracer.clear()
    out = stage.transform(make_table())
    np.testing.assert_array_equal(np.asarray(out["out"]),
                                  np.asarray(streamed["out"]))
    names = [s.name for s in tracer.spans()]
    assert names.count("runner.transform") == 1
    assert names.count("runner.step") == 3 and "runner.stack" not in names
    (root,) = [s for s in tracer.spans() if s.name == "runner.transform"]
    assert root.parent_id == 0 and root.args["row_shape"] == [WIDTH]
    assert {s.name for s in tracer.spans() if s.parent_id == 0} == {
        "runner.transform"}


def test_every_call_is_its_own_trace(tracer):
    stage = make_stage()
    for _ in range(3):
        stage.transform(make_table())
    roots = [s for s in tracer.spans() if s.name == "runner.transform"]
    assert len(roots) == 3 and len({s.trace_id for s in roots}) == 3
    assert {s.name for s in tracer.spans() if s.parent_id == 0} == {
        "runner.transform"}
