"""Unified telemetry: registry, tracer, /metrics scrape, soak.

Everything time-shaped runs on FakeClock (histogram timing asserts exact
bucket placement with zero real sleeps); the live pieces are a real
ServingServer scraped over HTTP and a supervised streaming query killed
and restarted whose restart counter and exported Perfetto trace survive
the query object's death.
"""

from __future__ import annotations

import glob
import json
import logging
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

from mmlspark_tpu.core.logging import JsonFormatter
from mmlspark_tpu.core.schema import Table
from mmlspark_tpu.core.table_io import write_csv
from mmlspark_tpu.observability import (
    CHROME_EVENT_KEYS,
    InstrumentedTransformer,
    MetricsRegistry,
    Tracer,
    get_registry,
    load_jsonl,
    set_default_registry,
    set_default_tracer,
)
from mmlspark_tpu.observability.metrics import METRIC_NAME_RE
from mmlspark_tpu.resilience import (
    FakeClock,
    QuerySupervisor,
    RestartPolicy,
    RetryPolicy,
)
from mmlspark_tpu.streaming import DirectorySource, MemorySink, StreamingQuery


def _wait_until(cond, timeout_s=10.0, interval_s=0.002):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval_s)
    return False


# --------------------------------------------------------------------- #
# MetricsRegistry
# --------------------------------------------------------------------- #


class TestRegistry:
    def test_counter_gauge_basics(self):
        reg = MetricsRegistry()
        c = reg.counter("mmlspark_tpu_test_events_total", "events")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        with pytest.raises(ValueError):
            c.inc(-1)
        g = reg.gauge("mmlspark_tpu_test_queue_depth", "depth")
        g.set(7)
        g.inc()
        g.dec(3)
        assert g.value == 5.0

    def test_labeled_children_are_distinct_and_cached(self):
        reg = MetricsRegistry()
        fam = reg.counter("mmlspark_tpu_test_hits_total", "", labels=("k",))
        a, b = fam.labels(k="a"), fam.labels(k="b")
        a.inc(3)
        b.inc(1)
        assert a.value == 3 and b.value == 1
        assert fam.labels(k="a") is a
        with pytest.raises(ValueError):
            fam.labels(wrong="x")
        with pytest.raises(ValueError):
            fam.inc()   # labeled family has no default child

    def test_redeclare_idempotent_mismatch_rejected(self):
        reg = MetricsRegistry()
        c1 = reg.counter("mmlspark_tpu_test_a_total", "doc")
        assert reg.counter("mmlspark_tpu_test_a_total") is c1
        with pytest.raises(ValueError):
            reg.gauge("mmlspark_tpu_test_a_total")           # kind mismatch
        with pytest.raises(ValueError):
            reg.counter("mmlspark_tpu_test_a_total", labels=("x",))
        with pytest.raises(ValueError):
            reg.counter("bad_name_total")                    # namespace

    def test_histogram_time_on_fake_clock(self):
        """Exact bucket placement with zero real sleeps: the injectable
        clock is the whole point of the registry's clock seam."""
        clk = FakeClock()
        reg = MetricsRegistry(clock=clk)
        h = reg.histogram("mmlspark_tpu_test_latency_seconds", "",
                          buckets=(0.01, 0.1, 1.0))
        with h.time():
            clk.advance(0.05)       # lands in the 0.1 bucket
        with h.time():
            clk.advance(0.5)        # lands in the 1.0 bucket
        with h.time():
            clk.advance(30.0)       # overflows to +Inf
        assert h.count == 3
        assert h.sum == pytest.approx(30.55)
        assert h.buckets() == {0.01: 0, 0.1: 1, 1.0: 2, float("inf"): 3}

    def test_disabled_registry_is_inert_and_reenables(self):
        reg = MetricsRegistry(enabled=False)
        c = reg.counter("mmlspark_tpu_test_n_total")
        h = reg.histogram("mmlspark_tpu_test_t_seconds")
        c.inc()
        h.observe(1.0)
        with h.time():
            pass
        assert c.value == 0 and h.count == 0
        reg.set_enabled(True)       # one store re-arms every child
        c.inc()
        assert c.value == 1

    def test_render_prometheus_format(self):
        clk = FakeClock()
        reg = MetricsRegistry(clock=clk)
        reg.counter("mmlspark_tpu_test_reqs_total", "requests",
                    labels=("server",)).labels(server="s0").inc(4)
        h = reg.histogram("mmlspark_tpu_test_lat_seconds", "latency",
                          buckets=(0.1, 1.0))
        h.observe(0.05)
        reg.register_callback("mmlspark_tpu_test_cache_hits_total",
                              "cache", lambda: 9, kind="counter")
        text = reg.render_prometheus()
        lines = text.strip().split("\n")
        # structural validity: every non-comment line is `name{labels} value`
        # with a registered, convention-conforming base name
        for line in lines:
            if line.startswith("# HELP ") or line.startswith("# TYPE "):
                continue
            name = line.split("{")[0].split(" ")[0]
            base = name
            for suffix in ("_bucket", "_sum", "_count"):
                if name.endswith(suffix) and reg.has(name[: -len(suffix)]):
                    base = name[: -len(suffix)]
            assert METRIC_NAME_RE.match(name), line
            assert reg.has(base), line
            float(line.rsplit(" ", 1)[1])            # value parses
        assert 'mmlspark_tpu_test_reqs_total{server="s0"} 4' in lines
        assert "# TYPE mmlspark_tpu_test_lat_seconds histogram" in text
        assert 'mmlspark_tpu_test_lat_seconds_bucket{le="0.1"} 1' in lines
        assert 'mmlspark_tpu_test_lat_seconds_bucket{le="+Inf"} 1' in lines
        assert "mmlspark_tpu_test_lat_seconds_count 1" in lines
        assert "mmlspark_tpu_test_cache_hits_total 9" in lines

    def test_broken_callback_never_breaks_the_scrape(self):
        reg = MetricsRegistry()

        def boom():
            raise RuntimeError("collector died")

        reg.register_callback("mmlspark_tpu_test_broken_total", "", boom,
                              kind="counter")
        reg.counter("mmlspark_tpu_test_ok_total").inc()
        assert "mmlspark_tpu_test_ok_total 1" in reg.render_prometheus()

    def test_snapshot_is_json_safe(self):
        reg = MetricsRegistry(clock=FakeClock())
        reg.counter("mmlspark_tpu_test_n_total").inc(2)
        reg.histogram("mmlspark_tpu_test_t_seconds",
                      buckets=(1.0,)).observe(0.5)
        snap = json.loads(json.dumps(reg.snapshot()))
        assert snap["mmlspark_tpu_test_n_total"]["samples"][0]["value"] == 2
        hist = snap["mmlspark_tpu_test_t_seconds"]["samples"][0]
        assert hist["count"] == 1 and hist["buckets"]["1.0"] == 1

    def test_concurrent_increments_do_not_drop(self):
        reg = MetricsRegistry()
        c = reg.counter("mmlspark_tpu_test_race_total")

        def work():
            for _ in range(2000):
                c.inc()

        threads = [threading.Thread(target=work) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value == 16000


# --------------------------------------------------------------------- #
# Tracer
# --------------------------------------------------------------------- #


class TestTracer:
    def test_parent_child_nesting(self):
        tr = Tracer(clock=FakeClock())
        with tr.start_span("outer", batch_id=7) as outer:
            with tr.start_span("inner") as inner:
                assert inner.parent is outer
                assert inner.trace_id == outer.trace_id
                assert inner.find_arg("batch_id") == 7
                assert tr.current_span() is inner
            assert tr.current_span() is outer
        assert tr.current_span() is None
        names = [s.name for s in tr.spans()]
        assert names == ["inner", "outer"]     # completion order

    def test_cross_thread_bind(self):
        tr = Tracer(clock=FakeClock())
        seen = {}

        def worker(parent):
            with tr.bind(parent):
                with tr.start_span("child") as c:
                    seen["parent_id"] = c.parent_id

        with tr.start_span("root") as root:
            t = threading.Thread(target=worker, args=(root,))
            t.start()
            t.join()
        assert seen["parent_id"] == root.span_id

    def test_span_durations_on_fake_clock(self):
        clk = FakeClock()
        tr = Tracer(clock=clk)
        with tr.start_span("work"):
            clk.advance(0.25)
        (span,) = tr.spans()
        assert span.dur_us == pytest.approx(250_000.0)

    def test_disabled_tracer_records_nothing(self):
        tr = Tracer(enabled=False)
        with tr.start_span("x") as span:
            span.set(k=1)           # null span absorbs everything
        assert tr.spans() == [] and tr.current_span() is None

    def test_export_jsonl_round_trip(self, tmp_path):
        clk = FakeClock()
        tr = Tracer(clock=clk)
        with tr.start_span("a", rows=4):
            clk.advance(0.1)
        path = str(tmp_path / "trace.jsonl")
        assert tr.export_jsonl(path) == 1
        events = load_jsonl(path)
        assert len(events) == 1
        ev = events[0]
        assert all(k in ev for k in CHROME_EVENT_KEYS)
        assert ev["name"] == "a" and ev["ph"] == "X"
        assert ev["args"]["rows"] == 4

    def test_load_jsonl_rejects_bad_schema(self, tmp_path):
        p = tmp_path / "bad.jsonl"
        p.write_text(json.dumps({"name": "x", "ph": "X"}) + "\n")
        with pytest.raises(ValueError):
            load_jsonl(str(p))

    def test_ring_buffer_bounds_retention(self):
        tr = Tracer(clock=FakeClock(), max_spans=4)
        for i in range(10):
            with tr.start_span(f"s{i}"):
                pass
        assert [s.name for s in tr.spans()] == ["s6", "s7", "s8", "s9"]

    @pytest.mark.parametrize("annotate_device,live,entered", [
        (None, True, True),      # the default follows a live session
        (None, False, False),
        (True, False, True),     # explicit overrides never ask
        (False, True, False),
    ])
    def test_span_enters_annotation_iff_session_live(
            self, monkeypatch, annotate_device, live, entered):
        from mmlspark_tpu.observability import tracing

        log = []

        class Annotation:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                log.append(("enter", self.name))

            def __exit__(self, *exc):
                log.append(("exit", self.name))

        monkeypatch.setattr(tracing, "_profiler_session_live", lambda: live)
        monkeypatch.setattr(tracing, "_device_annotation", Annotation)
        tr = Tracer(clock=FakeClock(), annotate_device=annotate_device)
        with tr.start_span("work"):
            pass
        assert log == ([("enter", "work"), ("exit", "work")]
                       if entered else [])
        assert [s.name for s in tr.spans()] == ["work"]

    def test_trace_dir_variable_alone_annotates_nothing(self, monkeypatch,
                                                        tmp_path):
        from mmlspark_tpu.observability import tracing

        monkeypatch.setenv("MMLSPARK_TPU_TRACE_DIR", str(tmp_path))
        monkeypatch.setattr(
            tracing, "_device_annotation",
            lambda name: pytest.fail("annotation entered with no session"))
        tr = Tracer(clock=FakeClock())
        assert tr.annotate_device is None
        with tr.start_span("work"):
            pass

    def test_default_spans_follow_a_real_profiler_session(self, tmp_path):
        """No patching: the liveness check is jaxlib's own, asked at span
        entry, so one tracer annotates inside a session and not around
        it."""
        import jax
        from jax.profiler import ProfileData
        from mmlspark_tpu.observability import tracing

        tr = Tracer()
        assert not tracing._profiler_session_live()
        with tr.start_span("before"):
            pass
        jax.profiler.start_trace(str(tmp_path))
        try:
            assert tracing._profiler_session_live()
            with tr.start_span("during"):
                pass
        finally:
            jax.profiler.stop_trace()
        assert not tracing._profiler_session_live()
        (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                                / "*.xplane.pb"))
        names = {e.name for plane in ProfileData.from_file(path).planes
                 for line in plane.lines for e in line.events}
        assert "during" in names and "before" not in names


# --------------------------------------------------------------------- #
# InstrumentedTransformer + logging + profiling
# --------------------------------------------------------------------- #


class _AddOne:
    def transform(self, table: Table) -> Table:
        return table.with_column("y", np.asarray(table["x"]) + 1)


class TestInstrumentation:
    def test_instrumented_transformer_emits(self):
        reg = MetricsRegistry(clock=FakeClock())
        tr = Tracer(clock=FakeClock())
        stage = InstrumentedTransformer(inner=_AddOne(), stage_name="addone")
        stage.metrics, stage.tracer = reg, tr
        out = stage.transform(Table({"x": np.arange(5.0)}))
        assert out["y"].tolist() == [1, 2, 3, 4, 5]
        hist = reg.histogram("mmlspark_tpu_pipeline_stage_seconds",
                             labels=("stage",)).labels(stage="addone")
        rows = reg.counter("mmlspark_tpu_pipeline_stage_rows_total",
                           labels=("stage",)).labels(stage="addone")
        assert hist.count == 1 and rows.value == 5
        assert [s.name for s in tr.spans()] == ["stage:addone"]
        assert stage.last_elapsed is not None

    def test_disable_param_bypasses_instruments(self):
        reg = MetricsRegistry()
        stage = InstrumentedTransformer(inner=_AddOne(), disable=True)
        stage.metrics = reg
        stage.transform(Table({"x": np.arange(3.0)}))
        assert not reg.has("mmlspark_tpu_pipeline_stage_rows_total")

    def test_json_formatter_stamps_trace_context(self):
        tr = Tracer(clock=FakeClock())
        old = set_default_tracer(tr)
        try:
            with tr.start_span("streaming.batch", batch_id=42) as span:
                record = logging.LogRecord(
                    "mmlspark_tpu.test", logging.INFO, __file__, 1,
                    "committed %d rows", (12,), None)
                doc = json.loads(JsonFormatter().format(record))
        finally:
            set_default_tracer(old)
        assert doc["message"] == "committed 12 rows"
        assert doc["level"] == "INFO"
        assert doc["trace_id"] == span.trace_id
        assert doc["span_id"] == span.span_id
        assert doc["batch_id"] == 42

    def test_profile_fn_emits_into_registry(self):
        from mmlspark_tpu.utils.profiling import profile_fn

        reg = MetricsRegistry()
        out, stats = profile_fn(lambda x: x * 2, 21, iters=2, registry=reg,
                                name="double")
        assert out == 42 and stats["iters"] == 2
        steady = reg.gauge("mmlspark_tpu_profile_steady_seconds",
                           labels=("fn",)).labels(fn="double")
        runs = reg.counter("mmlspark_tpu_profile_runs_total",
                           labels=("fn",)).labels(fn="double")
        assert steady.value == pytest.approx(stats["steady_s"])
        assert runs.value == 1


# --------------------------------------------------------------------- #
# live /metrics scrape
# --------------------------------------------------------------------- #


def _scrape(url: str) -> tuple[str, str]:
    with urllib.request.urlopen(url + "metrics", timeout=10) as r:
        return r.read().decode(), r.headers.get("Content-Type", "")


class TestMetricsEndpoint:
    def test_live_server_scrape(self):
        from mmlspark_tpu.io_http import make_reply, parse_request
        from mmlspark_tpu.io_http.serving import ServingServer

        def handler(table):
            t = parse_request(table)
            return make_reply(
                t.with_column("y", np.asarray(t["x"]) * 2), "y")

        reg = MetricsRegistry()
        srv = ServingServer(handler, metrics=reg).start()
        try:
            for i in range(3):
                req = urllib.request.Request(
                    srv.url, data=json.dumps({"x": float(i)}).encode(),
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=10) as r:
                    assert json.loads(r.read()) == {"y": 2.0 * i}
            text, ctype = _scrape(srv.url)
        finally:
            srv.stop()
        assert ctype.startswith("text/plain")
        assert "version=0.0.4" in ctype
        lbl = f'{{server="{srv.server_label}"}}'
        assert f"mmlspark_tpu_serving_requests_seen_total{lbl} 3" in text
        assert f"mmlspark_tpu_serving_requests_answered_total{lbl} 3" in text
        assert f"mmlspark_tpu_serving_latency_seconds_count{lbl} 3" in text
        # the declared-at-construction families render even before samples
        assert "# TYPE mmlspark_tpu_executable_cache_hits_total counter" \
            in text
        assert ("# TYPE mmlspark_tpu_resilience_breaker_transitions_total "
                "counter") in text
        # every sample line parses and carries the namespace
        for line in text.strip().split("\n"):
            if line.startswith("#"):
                continue
            assert line.startswith("mmlspark_tpu_"), line
            float(line.rsplit(" ", 1)[1])

    def test_scrape_reflects_counter_properties(self):
        from mmlspark_tpu.io_http import make_reply, parse_request
        from mmlspark_tpu.io_http.serving import ServingServer

        def handler(table):
            t = parse_request(table)
            return make_reply(t.with_column("y", np.asarray(t["x"])), "y")

        reg = MetricsRegistry()
        srv = ServingServer(handler, metrics=reg).start()
        try:
            req = urllib.request.Request(
                srv.url, data=json.dumps({"x": 1.0}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=10):
                pass
            assert srv.requests_seen == 1 == srv.requests_answered
            text, _ = _scrape(srv.url)
        finally:
            srv.stop()
        lbl = f'{{server="{srv.server_label}"}}'
        assert f"mmlspark_tpu_serving_requests_seen_total{lbl} 1" in text


# --------------------------------------------------------------------- #
# streaming kill-restart soak
# --------------------------------------------------------------------- #


class _FlakySink(MemorySink):
    """Fails enough consecutive calls to kill the query once."""

    def __init__(self, fail_calls=()):
        super().__init__()
        self.fail_calls = set(fail_calls)
        self.calls = 0

    def add_batch(self, batch_id, table):
        i = self.calls
        self.calls += 1
        if i in self.fail_calls:
            raise IOError(f"scripted failure on call {i}")
        super().add_batch(batch_id, table)


class TestStreamingSoak:
    def test_kill_restart_counts_and_trace_survive(self, tmp_path):
        """A supervised query dies (retry budget 0, sink fails twice),
        restarts, and completes. The restart counter lives in the
        registry, not the query, so it survives the death/rebirth; the
        tracer's exported JSONL is schema-valid Perfetto input covering
        batches from both lives."""
        d = str(tmp_path / "in")
        os.makedirs(d)
        for i in range(3):
            write_csv(Table({"x": np.arange(i * 10.0, i * 10.0 + 4)}),
                      os.path.join(d, f"f-{i:03d}.csv"))
        reg = MetricsRegistry()
        tr = Tracer()
        sink = _FlakySink(fail_calls=[1])
        q = StreamingQuery(
            DirectorySource(d, max_files_per_trigger=1), None, sink,
            checkpoint_dir=str(tmp_path / "ck"),
            trigger_interval_s=0.005,
            batch_retry_policy=RetryPolicy(max_retries=0, backoffs_ms=[0.0]),
            name="soak", metrics=reg, tracer=tr)
        sup = QuerySupervisor(
            q,
            RestartPolicy(max_restarts=5, window_s=1e6,
                          backoff=RetryPolicy(max_retries=5,
                                              backoffs_ms=[0.0])),
            poll_interval_s=0.002, metrics=reg)
        sup.start()
        assert _wait_until(lambda: q.batches_processed >= 3)
        sup.stop()

        assert sup.restarts >= 1
        restarts = reg.counter("mmlspark_tpu_streaming_restarts_total",
                               labels=("query",)).labels(query="soak")
        assert restarts.value == sup.restarts
        batches = reg.counter("mmlspark_tpu_streaming_batches_total",
                              labels=("query",)).labels(query="soak")
        assert batches.value == 3
        rows = reg.counter("mmlspark_tpu_streaming_rows_total",
                           labels=("query",)).labels(query="soak")
        assert rows.value == 12
        # exactly-once held across the restart
        assert sink.table()["x"].tolist() == pytest.approx(
            [0, 1, 2, 3, 10, 11, 12, 13, 20, 21, 22, 23])

        path = str(tmp_path / "soak.jsonl")
        n = tr.export_jsonl(path)
        events = load_jsonl(path)          # schema-validating load
        assert len(events) == n
        batch_events = [e for e in events
                        if e["name"] == "streaming.batch"
                        and e["args"].get("query") == "soak"]
        # 3 commits + at least one failed attempt, spanning both lives
        assert len(batch_events) >= 4
        assert {e["args"]["batch_id"] for e in batch_events} >= {0, 1, 2}
        # Perfetto's legacy-JSON importer accepts the wrapped form
        wrapped = json.dumps({"traceEvents": events})
        assert json.loads(wrapped)["traceEvents"][0]["ph"] == "X"

    def test_process_default_registry_swap(self):
        """set_default_registry is the test seam: swap in an isolated
        registry, confirm get_registry() serves it, restore."""
        mine = MetricsRegistry()
        old = set_default_registry(mine)
        try:
            assert get_registry() is mine
        finally:
            set_default_registry(old)
        assert get_registry() is not mine


# --------------------------------------------------------------------- #
# FlightRecorder: ring, triggers, dumps                                 #
# --------------------------------------------------------------------- #


class TestFlightRecorder:
    def _rec(self, tmp_path=None, **kw):
        from mmlspark_tpu.observability import FlightRecorder

        kw.setdefault("clock", FakeClock())
        if tmp_path is not None:
            kw.setdefault("dump_dir", str(tmp_path))
        return FlightRecorder(**kw)

    def test_ring_bounds_and_drop_count(self):
        rec = self._rec(capacity=4)
        for i in range(10):
            rec.record("e", i=i)
        evs = rec.events()
        assert [e["data"]["i"] for e in evs] == [6, 7, 8, 9]
        assert rec.drop_count == 6
        # seq stays monotone across evictions — the postmortem tiebreaker
        assert [e["seq"] for e in evs] == [7, 8, 9, 10]

    def test_disarmed_recorder_is_inert(self, tmp_path):
        rec = self._rec(tmp_path, enabled=False)
        rec.record("e")
        rec.record_request(trace_id="t", route="host")
        assert rec.events() == []
        assert rec.trigger_dump("anything", force=True) is None
        assert list(tmp_path.iterdir()) == []

    def test_dump_round_trips_through_schema_load(self, tmp_path):
        from mmlspark_tpu.observability import load_dump

        reg = MetricsRegistry()
        reg.counter("mmlspark_tpu_test_total", "t").inc(3)
        rec = self._rec(tmp_path, registry=reg, process="unit")
        rec.record_request(trace_id="ab" * 16, route="resident", bucket=8,
                           queue_depth=2, latency_s=0.004, status=200)
        rec.record_transition("breaker", "open", breaker="b0")
        path = rec.dump("manual", note="unit")
        meta, events = load_dump(path)
        assert meta["process"] == "unit" and meta["trigger"] == "manual"
        assert meta["detail"] == {"note": "unit"}
        assert meta["events"] == 2 and meta["events_dropped"] == 0
        kinds = [e["kind"] for e in events]
        # line 2 carries the registry snapshot, then the ring
        assert kinds == ["metrics.snapshot", "serving.request", "transition"]
        snap = events[0]["data"]["snapshot"]
        assert snap["mmlspark_tpu_test_total"]["samples"][0]["value"] == 3.0

    def test_dump_cooldown_and_force(self, tmp_path):
        clock = FakeClock()
        rec = self._rec(tmp_path, clock=clock, dump_cooldown_s=30.0)
        rec.record("e")
        assert rec.trigger_dump("slo_burn") is not None
        clock.advance(5.0)
        assert rec.trigger_dump("slo_burn") is None  # inside the cooldown
        assert rec.trigger_dump("sigterm", force=True) is not None
        clock.advance(31.0)
        assert rec.trigger_dump("slo_burn") is not None

    def test_shed_spike_trigger(self, tmp_path):
        clock = FakeClock()
        rec = self._rec(tmp_path, clock=clock, spike_window_s=1.0,
                        spike_threshold=3, dump_cooldown_s=0.0)
        assert rec.note_shed() is None
        clock.advance(2.0)  # the first shed ages out of the window
        assert rec.note_shed() is None
        assert rec.note_shed() is None
        path = rec.note_shed()  # 3 sheds inside 1s -> dump
        assert path is not None
        from mmlspark_tpu.observability import load_dump

        meta, events = load_dump(path)
        assert meta["trigger"] == "shed_spike"
        assert sum(1 for e in events if e["kind"] == "serving.shed") == 4

    def test_slo_transition_dumps_once_per_alert(self, tmp_path):
        rec = self._rec(tmp_path, dump_cooldown_s=0.0)
        assert rec.note_slo([]) is None
        first = rec.note_slo(["availability"])
        assert first is not None
        # still alerting: no new dump until a NEW name joins the set
        assert rec.note_slo(["availability"]) is None
        second = rec.note_slo(["availability", "latency"])
        assert second is not None and second != first

    def test_maybe_tick_records_counter_deltas(self):
        clock = FakeClock()
        reg = MetricsRegistry()
        c = reg.counter("mmlspark_tpu_tick_total", "t")
        rec = self._rec(clock=clock, tick_interval_s=5.0, registry=reg)
        c.inc(2)
        assert rec.maybe_tick()
        clock.advance(1.0)
        assert not rec.maybe_tick()  # between ticks: one clock compare
        clock.advance(5.0)
        c.inc(3)
        assert rec.maybe_tick()
        ticks = [e for e in rec.events() if e["kind"] == "metrics.tick"]
        assert ticks[0]["data"]["deltas"]["mmlspark_tpu_tick_total"] == 2.0
        assert ticks[1]["data"]["deltas"]["mmlspark_tpu_tick_total"] == 3.0

    def test_on_dump_callback_and_failure_isolation(self, tmp_path):
        rec = self._rec(tmp_path)
        calls = []
        rec.on_dump = lambda trigger, path: calls.append((trigger, path))
        p1 = rec.dump("manual")
        assert calls == [("manual", p1)]
        rec.on_dump = lambda trigger, path: 1 / 0  # a broken hook
        assert rec.dump("manual") is not None  # ...keeps the dump

    def test_dump_header_discloses_ring_and_span_loss(self, tmp_path):
        from mmlspark_tpu.observability import load_dump

        tr = Tracer(clock=FakeClock(), max_spans=2)
        old = set_default_tracer(tr)
        try:
            for i in range(5):
                with tr.start_span(f"s{i}"):
                    pass
            rec = self._rec(tmp_path, capacity=2)
            for i in range(5):
                rec.record("e", i=i)
            meta, _ = load_dump(rec.dump("manual"))
        finally:
            set_default_tracer(old)
        assert meta["events_dropped"] == 3
        assert meta["spans_lost"] == 3
        # disclosed loss resets once dumped (the next dump reports fresh)
        assert rec.drop_count == 0

    def test_load_dump_rejects_bad_schema(self, tmp_path):
        from mmlspark_tpu.observability import load_dump

        p = tmp_path / "flight-x.jsonl"
        p.write_text(json.dumps({"kind": "not-a-header"}) + "\n")
        with pytest.raises(ValueError, match="recorder.meta"):
            load_dump(str(p))
        p.write_text(json.dumps(
            {"kind": "recorder.meta", "schema": 99}) + "\n")
        with pytest.raises(ValueError, match="unknown dump schema"):
            load_dump(str(p))
        p.write_text(json.dumps(
            {"kind": "recorder.meta", "schema": 1}) + "\n"
            + json.dumps({"ts": 0.0, "kind": "e"}) + "\n")
        with pytest.raises(ValueError, match="missing keys"):
            load_dump(str(p))


# --------------------------------------------------------------------- #
# OpenMetrics exemplars + tracer loss disclosure                        #
# --------------------------------------------------------------------- #


class TestExemplars:
    def test_histogram_keeps_last_exemplar_per_bucket(self):
        reg = MetricsRegistry()
        h = reg.histogram("mmlspark_tpu_lat_seconds", "l", exemplars=True)
        h.observe(0.004, exemplar={"trace_id": "aa" * 16, "bucket": "8"})
        h.observe(0.004, exemplar={"trace_id": "bb" * 16, "bucket": "8"})
        text = reg.render_prometheus()
        assert "bb" * 16 in text and "aa" * 16 not in text  # last wins
        assert text.rstrip("\n").endswith("# EOF")

    def test_exemplar_lines_survive_fleet_round_trip(self):
        from mmlspark_tpu.observability.fleet import (parse_prometheus,
                                                      render_families)

        reg = MetricsRegistry()
        h = reg.histogram("mmlspark_tpu_lat_seconds", "l",
                          labels=("server",), exemplars=True)
        h.labels(server="s0").observe(
            0.004, exemplar={"trace_id": "cd" * 16, "route": "resident"})
        text = reg.render_prometheus()
        rendered = render_families(parse_prometheus(text))
        assert rendered.rstrip("\n") == text.rstrip("\n")  # byte-identical

    def test_exemplar_label_set_is_capped(self):
        from mmlspark_tpu.observability.metrics import EXEMPLAR_LABEL_SET_MAX

        reg = MetricsRegistry()
        h = reg.histogram("mmlspark_tpu_lat_seconds", "l", exemplars=True)
        h.observe(0.004, exemplar={"trace_id": "ab" * 16,
                                   "huge": "x" * 300, "route": "host"})
        text = reg.render_prometheus()
        ex_lines = [ln for ln in text.splitlines() if " # {" in ln]
        assert ex_lines
        for ln in ex_lines:
            body = ln.split(" # {", 1)[1].rsplit("}", 1)[0]
            pairs = [p.split("=", 1) for p in body.split(",") if p]
            total = sum(len(k) + len(v.strip('"')) for k, v in pairs)
            assert total <= EXEMPLAR_LABEL_SET_MAX
            assert "huge" not in body  # the oversized label was dropped

    def test_disabled_exemplars_render_plain(self):
        reg = MetricsRegistry()
        h = reg.histogram("mmlspark_tpu_lat_seconds", "l", exemplars=False)
        h.observe(0.004, exemplar={"trace_id": "ab" * 16})
        text = reg.render_prometheus()
        assert " # {" not in text
        assert not text.rstrip("\n").endswith("# EOF")

    def test_tracer_export_discloses_span_loss(self, tmp_path):
        tr = Tracer(clock=FakeClock(), max_spans=2)
        for i in range(5):
            with tr.start_span(f"s{i}"):
                pass
        assert tr.drop_count == 3
        p = str(tmp_path / "t.jsonl")
        tr.export_jsonl(p)
        events = load_jsonl(p)
        lost = [e for e in events if e["name"] == "tracer.spans_lost"]
        assert len(lost) == 1
        assert lost[0]["args"]["count"] == 3
