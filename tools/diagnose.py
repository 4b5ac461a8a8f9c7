#!/usr/bin/env python
"""One-shot fleet diagnosis: scrape, aggregate, and print a snapshot table.

Four entry modes:

  python tools/diagnose.py --rendezvous http://HOST:PORT
      Ask a running FleetRendezvous for /healthz + /metrics and print the
      per-replica table from the fleet exposition.

  python tools/diagnose.py --urls http://H1:P1/metrics http://H2:P2/metrics
      No rendezvous: scrape the replica /metrics endpoints directly
      through a local MetricsAggregator and print the same table.

  python tools/diagnose.py --gateway http://HOST:PORT
      Ask a running ServingGateway for /routes (+ /autoscaler when one is
      attached) and print the routing table — which replicas are live,
      which are ejected and why, in-flight depth and breaker state per
      replica — plus the autoscaler's control-loop state.

  python tools/diagnose.py --serving http://HOST:PORT
      Ask one ServingServer for its info JSON and print the hot-path
      snapshot: per-bucket crossover routes with their measured timings,
      path counters, readback lag, and host round-trips per request.

  python tools/diagnose.py --perf TARGET
      One-shot performance attribution. TARGET is a live ServingServer
      base URL (renders the armed profiler's phase table — host prepare,
      pad waste, h2d, dispatch, device compute, d2h, queue wait — next
      to the measured latency) or a MULTICHIP_*.json artifact (per-mesh
      phase table naming the slowest shard per segment with its row
      count and compute time). `--perf --selftest` runs a real resident
      server with the profiler armed and asserts the phase sum explains
      the measured RTT within 15%.

  python tools/diagnose.py --streaming CHECKPOINT_DIR
      Read a partition-parallel streaming query's checkpoint directory
      (commits.jsonl + status.json + per-partition snapshots) and print
      the partition table: rows, queue depths, lag, watermarks,
      state-backend spill bytes, and each partition's last snapshot
      batch. `--streaming --selftest` runs a real P=2 query in-process
      and asserts the snapshot against it.

  python tools/diagnose.py --checkpoints CKPT_DIR
      Read a training checkpoint directory (resilience/elastic.py
      layout: ckpt-*.bin + manifest.json, or a tune sweep tree nesting
      per-trial stores) and print the lineage/integrity table: every
      snapshot's seq, tag, parent, size and age, its verification
      verdict (ok / truncated / checksum-mismatch / ...), and which
      snapshot a restarted fit would actually resume from.
      `--checkpoints --selftest` exercises the whole surface against a
      real store plus a real checkpointed GBDT fit, including corruption
      fallback.

  python tools/diagnose.py --history SEGMENT_DIR
      Retrospective incident report from a telemetry timeline segment
      directory (observability/timeline.py): segment inventory, every
      recorded alert edge with its rule/severity/breaching series,
      flight-recorder dump timestamps, and the breaching series' values
      around the newest firing edge — all reconstructed from the
      checksummed segment files alone, no live process needed.
      `--history --selftest` drives a synthetic 3-segment incident and
      asserts the reconstruction end to end, byte-stably.

  python tools/diagnose.py --watch http://HOST:PORT
      Refreshing one-screen live dashboard: re-scrape the /metrics URL
      every --interval seconds, clear the screen, and reprint the fleet
      table plus the between-scrape request rate.

  python tools/diagnose.py --selftest
      Spin up a real 2-replica ServingFleet in-process, push traffic
      through it, diagnose it, then stand up a hot-path serve_model
      server and assert ≤1 host round-trip per resident request; exit
      nonzero unless every check holds — the CI smoke for the whole
      fleet-observability path (ci.sh).

The table is built ONLY from the exposition (never from side channels),
so what it prints is exactly what a Prometheus scrape would see.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import urllib.request

sys.path.insert(0, os.path.normpath(
    os.path.join(os.path.dirname(__file__), "..")))

from mmlspark_tpu.observability.fleet import (  # noqa: E402
    FLEET_REPLICA, MetricsAggregator, REPLICA_LABEL, parse_prometheus)
from mmlspark_tpu.observability.slo import SeriesReader  # noqa: E402

_SEEN = "mmlspark_tpu_serving_requests_seen_total"
_ANSWERED = "mmlspark_tpu_serving_requests_answered_total"
_FAILED = "mmlspark_tpu_serving_requests_failed_total"
_SHED = "mmlspark_tpu_serving_requests_shed_total"
_LATENCY = "mmlspark_tpu_serving_latency_seconds"
_UP = "mmlspark_tpu_fleet_replica_up_count"
_BREAKER = "mmlspark_tpu_resilience_breaker_state_count"
_BURN = "mmlspark_tpu_slo_burn_rate"
_BUDGET = "mmlspark_tpu_slo_budget_remaining_ratio"
_BREAKER_NAMES = {0: "closed", 1: "half_open", 2: "open"}


def _fetch(url: str, timeout_s: float = 5.0) -> str:
    with urllib.request.urlopen(url, timeout=timeout_s) as r:
        return r.read().decode("utf-8")


def _split_by_replica(families) -> dict[str, dict]:
    """Regroup a fleet exposition into per-replica snapshot-shaped dicts
    (the `replica` label partitions every sample)."""
    per: dict[str, list] = {}
    for fam in families:
        for s in fam.samples:
            rid = s.labels_dict().get(REPLICA_LABEL)
            if rid is None:
                rid = FLEET_REPLICA
            per.setdefault(rid, []).append((fam, s))
    out: dict[str, dict] = {}
    for rid, pairs in per.items():
        by_fam: dict[str, tuple] = {}
        for fam, s in pairs:
            by_fam.setdefault(fam.name, (fam, []))[1].append(s)
        out[rid] = {
            name: MetricsAggregator._snapshot_family(fam, samples)
            for name, (fam, samples) in by_fam.items()}
    return out


def _fmt(v: float, digits: int = 1) -> str:
    if v != v:  # nan
        return "-"
    if float(v).is_integer() and abs(v) < 1e15:
        return str(int(v))
    return f"{v:.{digits}f}"


def _render_table(rows: list[list[str]], header: list[str]) -> str:
    widths = [max(len(str(r[i])) for r in [header] + rows)
              for i in range(len(header))]
    def line(r):
        return "  ".join(str(c).ljust(w) for c, w in zip(r, widths)).rstrip()
    sep = "  ".join("-" * w for w in widths)
    return "\n".join([line(header), sep] + [line(r) for r in rows])


def diagnose_text(text: str, health: "dict | None" = None) -> str:
    """The full report from one fleet exposition (+ optional /healthz
    payload for alive/ready columns)."""
    families = parse_prometheus(text)
    per = _split_by_replica(families)
    fleet = per.pop(FLEET_REPLICA, {})
    hrep = (health or {}).get("replicas", {})

    header = ["replica", "up", "alive", "ready", "seen", "answered",
              "failed", "shed", "p50_ms", "p99_ms"]
    rows = []
    for rid in sorted(per, key=lambda r: (len(r), r)):
        reader = SeriesReader(per[rid])
        h = hrep.get(rid, {})
        p50 = reader.histogram_quantile(_LATENCY, 0.5) * 1e3
        p99 = reader.histogram_quantile(_LATENCY, 0.99) * 1e3
        rows.append([
            rid,
            _fmt(reader.gauge(_UP)),
            {True: "y", False: "n"}.get(h.get("alive"), "?"),
            {True: "y", False: "n"}.get(h.get("ready"), "?"),
            _fmt(reader.counter(_SEEN)), _fmt(reader.counter(_ANSWERED)),
            _fmt(reader.counter(_FAILED)), _fmt(reader.counter(_SHED)),
            _fmt(p50, 2), _fmt(p99, 2),
        ])
    out = [_render_table(rows, header)] if rows else ["(no replica series)"]

    freader = SeriesReader(fleet)
    out.append("")
    out.append(
        f"fleet: seen={_fmt(freader.counter(_SEEN))} "
        f"answered={_fmt(freader.counter(_ANSWERED))} "
        f"failed={_fmt(freader.counter(_FAILED))} "
        f"shed={_fmt(freader.counter(_SHED))} "
        f"p99_ms={_fmt(freader.histogram_quantile(_LATENCY, 0.99) * 1e3, 2)}")

    breakers = [(s["labels"].get("breaker", "?"), s["value"])
                for s in fleet.get(_BREAKER, {}).get("samples", [])]
    if breakers:
        worst = ", ".join(
            f"{n}={_BREAKER_NAMES.get(int(v), v)}" for n, v in breakers)
        out.append(f"breakers (worst across fleet): {worst}")

    slo_rows = []
    for s in fleet.get(_BURN, {}).get("samples", []):
        slo_rows.append([s["labels"].get("slo", "?"),
                         s["labels"].get("window", "?"), _fmt(s["value"], 3)])
    for s in fleet.get(_BUDGET, {}).get("samples", []):
        slo_rows.append([s["labels"].get("slo", "?"), "budget",
                         _fmt(s["value"], 3)])
    if slo_rows:
        out.append("")
        out.append(_render_table(sorted(slo_rows),
                                 ["slo", "window", "value"]))
    return "\n".join(out)


def diagnose_rendezvous(url: str) -> str:
    url = url.rstrip("/")
    text = _fetch(url + "/metrics")
    try:
        with urllib.request.urlopen(url + "/healthz", timeout=5) as r:
            health = json.loads(r.read())
    except urllib.error.HTTPError as e:  # 503 = not all ready, still JSON
        health = json.loads(e.read() or b"{}")
    except Exception:  # noqa: BLE001 — health is optional decoration
        health = None
    return diagnose_text(text, health)


def diagnose_urls(urls: list[str]) -> str:
    agg = MetricsAggregator(urls=list(urls))
    agg.scrape()
    return diagnose_text(agg.render())


def diagnose_gateway(url: str) -> str:
    """Routing table + autoscaler state from a running ServingGateway —
    or, pointed at a GatewayTier control endpoint, the worker tier table
    (shared port, per-worker pid/traffic/journal shard)."""
    url = url.rstrip("/")
    try:
        tier = json.loads(_fetch(url + "/workers"))
    except Exception:  # noqa: BLE001 — not a tier control endpoint
        tier = None
    if isinstance(tier, dict) and tier.get("tier"):
        out = [
            f"gateway tier: {tier.get('host')}:{tier.get('port')} "
            f"workers={tier.get('n_workers')} "
            f"members={len(tier.get('members') or [])}"
        ]
        rows = []
        for w in tier.get("workers", []):
            st = w.get("stats") or {}
            rows.append([
                st.get("worker") or f"w{w.get('index')}",
                "y" if w.get("alive") else "n",
                str(w.get("pid") or "-"),
                _fmt(st.get("requests", 0)),
                _fmt(st.get("n_live", 0)),
                w.get("journal_shard") or "-",
            ])
        out.append(_render_table(
            rows, ["worker", "alive", "pid", "requests", "live",
                   "journal_shard"]))
        return "\n".join(out)
    routes = json.loads(_fetch(url + "/routes"))
    out = [
        f"gateway: strategy={routes['strategy']} "
        f"hedge={'on' if routes['hedge'] else 'off'} "
        f"key_header={routes['routing_key_header']} "
        f"live={routes['n_live']}/{routes['n_targets']}"
    ]
    rows = []
    for target, st in sorted(routes.get("targets", {}).items()):
        rows.append([
            target,
            "y" if st.get("live") else "n",
            st.get("breaker", "?"),
            _fmt(st.get("inflight", 0)),
            (st.get("eject_reason") or "-") if st.get("ejected") else "-",
        ])
    if rows:
        out.append(_render_table(
            rows, ["replica", "live", "breaker", "inflight", "ejected"]))
    else:
        out.append("(no targets)")

    try:
        scaler = json.loads(_fetch(url + "/autoscaler"))
    except urllib.error.HTTPError:  # 404 = no autoscaler attached
        scaler = None
    except Exception:  # noqa: BLE001 — autoscaler view is optional
        scaler = None
    if scaler is not None:
        out.append("")
        out.append(
            f"autoscaler: n_live={scaler['n_live']} "
            f"range={scaler['min_replicas']}..{scaler['max_replicas']} "
            f"calm={scaler['calm_ticks']}/{scaler['hysteresis_ticks']} "
            f"cooldown_left={_fmt(scaler['cooldown_remaining_s'], 1)}s "
            f"last={scaler['last_action']}")
        if scaler.get("pressure"):
            out.append(f"pressure: {', '.join(scaler['pressure'])}")
        sig = scaler.get("signals") or {}
        if sig:
            out.append("signals: " + " ".join(
                f"{k}={_fmt(float(v), 3)}" for k, v in sorted(sig.items())
                if isinstance(v, (int, float))))
        for ev in scaler.get("events", []):
            out.append(
                f"  event t={_fmt(ev['t'], 1)} {ev['action']} "
                f"({ev['detail']}) n_live={ev['n_live']}")
    return "\n".join(out)


def diagnose_serving(url: str) -> str:
    """Hot-path snapshot from one ServingServer's info endpoint."""
    info = json.loads(_fetch(url.rstrip("/") + "/"))
    lat = info.get("latency") or {}
    out = [
        f"server: {info.get('host')}:{info.get('port')} "
        f"mode={info.get('mode')} "
        f"ready={'y' if info.get('ready') else 'n'} "
        f"seen={_fmt(info.get('seen', 0))} "
        f"answered={_fmt(info.get('answered', 0))} "
        f"p50_ms={_fmt(lat.get('p50_ms', float('nan')), 2)} "
        f"p99_ms={_fmt(lat.get('p99_ms', float('nan')), 2)}",
        f"executable cache: hits={_fmt(info.get('executable_cache_hits', 0))} "
        f"misses={_fmt(info.get('executable_cache_misses', 0))} "
        f"recompiles={_fmt(info.get('executable_cache_recompiles', 0))}",
    ]
    prot = info.get("protocols") or {}
    if prot:
        total = sum(prot.values()) or 1
        out.append("protocol mix: " + " ".join(
            f"{k}={_fmt(v)} ({100.0 * v / total:.1f}%)"
            for k, v in sorted(prot.items())))
    hp = info.get("hot_path")
    if not hp:
        out.append("hot path: none (handler-only server)")
        return "\n".join(out)
    state = ("enabled" if hp.get("enabled")
             else f"DISABLED ({hp.get('disabled_reason')})")
    # the resident lane's route label: "resident" for the GBDT walk,
    # "sar_resident" for the recommendation top-k path
    label = hp.get("resident_label") or "resident"
    out.append(f"hot path: {state} resident_label={label} "
               f"readback_lag={hp.get('readback_lag')}")
    timings = hp.get("timings_ms") or {}
    rows = []
    for bucket, route in sorted((hp.get("crossover") or {}).items(),
                                key=lambda kv: int(kv[0])):
        t = timings.get(bucket, {})
        rows.append([bucket, route,
                     _fmt(t.get("native", float("nan")), 3),
                     _fmt(t.get(label, float("nan")), 3)])
    if rows:
        out.append(_render_table(
            rows, ["bucket", "route", "native_ms", "resident_ms"]))
    else:
        out.append("(no crossover measured — server not warmed?)")
    by_route: dict = {}
    for t in timings.values():
        for route, ms in t.items():
            if isinstance(ms, (int, float)):
                by_route.setdefault(route, []).append(float(ms))
    if by_route:
        out.append("per-path rtt_ms: " + " ".join(
            f"{r}={_fmt(sum(v) / len(v), 3)}"
            for r, v in sorted(by_route.items())))
    paths = hp.get("paths") or {}
    out.append("paths: " + " ".join(
        f"{k}={_fmt(v)}" for k, v in sorted(paths.items())))
    out.append(
        f"round trips: total={_fmt(hp.get('round_trips', 0))} "
        f"resident_batches={_fmt(hp.get('resident_batches', 0))} "
        f"per_resident_request="
        f"{_fmt(hp.get('round_trips_per_resident_request', 0), 3)}")
    dec = hp.get("decoder") or {}
    out.append(f"decoder: hits={_fmt(dec.get('hits', 0))} "
               f"fallbacks={_fmt(dec.get('fallbacks', 0))} "
               f"binary={_fmt(dec.get('binary_hits', 0))}")
    return "\n".join(out)


# -- postmortem --------------------------------------------------------- #

# causal tiebreaker for FakeClock timelines: at an identical timestamp a
# request is observed gateway -> replica -> stage/executor, so the merge
# orders same-ts events by the dumping process's tier before pid/seq
_TIER_PREFIXES = (("gateway", 0), ("serving", 1), ("replica", 1),
                  ("stage", 2))


def _process_tier(process: str) -> int:
    for prefix, tier in _TIER_PREFIXES:
        if process.startswith(prefix):
            return tier
    return 3


def load_postmortem_dir(dump_dir: str) -> list[tuple[dict, list[dict]]]:
    """Every flight-recorder dump in `dump_dir` (schema-validated),
    sorted by filename so a process's dump_n sequence stays in order."""
    from mmlspark_tpu.observability.recorder import DUMP_PREFIX, load_dump

    out = []
    for name in sorted(os.listdir(dump_dir)):
        if name.startswith(DUMP_PREFIX) and name.endswith(".jsonl"):
            out.append(load_dump(os.path.join(dump_dir, name)))
    return out


def _merge_events(dumps) -> list[dict]:
    """One causally-ordered timeline from every process's dumps. A
    process that dumped more than once repeats its ring contents, so
    events dedup on (process, pid, seq); the sort key
    (ts, tier, pid, seq) is FakeClock-safe — simulated clocks produce
    ties, broken by causal tier then per-process monotone seq."""
    seen = set()
    merged = []
    for meta, events in dumps:
        process = meta.get("process", "proc")
        tier = _process_tier(process)
        for ev in events:
            key = (process, ev["pid"], ev["seq"])
            if key in seen:
                continue
            seen.add(key)
            merged.append({**ev, "process": process, "tier": tier})
    merged.sort(key=lambda e: (e["ts"], e["tier"], e["pid"], e["seq"]))
    return merged


def _event_summary(ev: dict) -> str:
    d = ev.get("data", {})
    kind = ev["kind"]
    if kind == "serving.request":
        parts = [f"trace={d.get('trace_id') or '-'}",
                 f"route={d.get('route') or '-'}"]
        if d.get("bucket") is not None:
            parts.append(f"bucket={d['bucket']}")
        if d.get("latency_s") is not None:
            parts.append(f"lat={d['latency_s'] * 1e3:.2f}ms")
        parts.append(f"status={d.get('status')}")
        if d.get("readback_lag") is not None:
            parts.append(f"readback_lag={d['readback_lag']}")
        return " ".join(parts)
    if kind == "transition":
        extra = {k: v for k, v in d.items()
                 if k not in ("component", "action") and v is not None}
        tail = " " + " ".join(
            f"{k}={v}" for k, v in sorted(extra.items())) if extra else ""
        return f"{d.get('component')}:{d.get('action')}{tail}"
    if kind == "metrics.tick":
        deltas = d.get("deltas", {})
        top = sorted(deltas.items(), key=lambda kv: -abs(kv[1]))[:4]
        return "deltas " + " ".join(
            f"{k.replace('mmlspark_tpu_', '')}+{_fmt(v)}" for k, v in top)
    if kind == "metrics.snapshot":
        return f"{len(d.get('snapshot', {}))} families"
    return " ".join(f"{k}={v}" for k, v in sorted(d.items())
                    if v is not None) or "-"


def _exemplar_traces(dumps) -> list[list[str]]:
    """The worst-p99 attribution table: highest-bucket latency exemplars
    from every dump's metrics snapshot, joined through trace_id to the
    processes whose rings saw that request — a fleet p99 bucket resolved
    to one exact cross-process trace."""
    # trace_id -> {process -> route} from the request events
    routes: dict[str, dict[str, str]] = {}
    for meta, events in dumps:
        process = meta.get("process", "proc")
        for ev in events:
            if ev["kind"] != "serving.request":
                continue
            tid = ev.get("data", {}).get("trace_id")
            if tid:
                routes.setdefault(tid, {})[process] = \
                    ev["data"].get("route") or "-"
    best: dict[str, tuple[float, str, str]] = {}
    for meta, events in dumps:
        process = meta.get("process", "proc")
        for ev in events:
            if ev["kind"] != "metrics.snapshot":
                continue
            snap = ev.get("data", {}).get("snapshot", {})
            for name, fam in snap.items():
                if fam.get("kind") != "histogram":
                    continue
                for sample in fam.get("samples", []):
                    for ex in (sample.get("exemplars") or {}).values():
                        tid = (ex.get("labels") or {}).get("trace_id")
                        if not tid:
                            continue
                        v = float(ex.get("value", 0.0))
                        if tid not in best or v > best[tid][0]:
                            best[tid] = (v, name, process)
    rows = []
    for tid, (v, name, process) in sorted(
            best.items(), key=lambda kv: -kv[1][0]):
        hops = routes.get(tid, {})
        chain = " -> ".join(
            f"{p}({r})" for p, r in sorted(
                hops.items(),
                key=lambda pr: (_process_tier(pr[0]), pr[0]))) or "-"
        rows.append([tid, f"{v * 1e3:.2f}", name.replace(
            "mmlspark_tpu_", ""), chain])
    return rows


def postmortem(dump_dir: str, tail: int = 200) -> str:
    """Merge every flight-recorder dump under `dump_dir` into one
    incident report: trigger matrix with the metric deltas around each
    trigger, the worst-latency exemplar traces, and the causally-ordered
    cross-process timeline."""
    dumps = load_postmortem_dir(dump_dir)
    if not dumps:
        return f"(no flight-recorder dumps under {dump_dir})"
    merged = _merge_events(dumps)
    processes = sorted({m.get("process", "proc") for m, _ in dumps})
    lost = sum(m.get("events_dropped", 0) for m, _ in dumps)
    spans_lost = sum(m.get("spans_lost", 0) for m, _ in dumps)
    out = [
        f"postmortem: {len(dumps)} dumps from {len(processes)} processes "
        f"({', '.join(processes)})",
        f"{len(merged)} unique events; {lost} ring events lost, "
        f"{spans_lost} spans lost (not captured below)",
        "",
        "triggers:",
    ]
    for meta, events in sorted(
            dumps, key=lambda d: (d[0].get("ts", 0.0),
                                  _process_tier(d[0].get("process", "")))):
        detail = meta.get("detail") or {}
        tail_s = " " + " ".join(
            f"{k}={v}" for k, v in sorted(detail.items())) if detail else ""
        rc = meta.get("route_counts") or {}
        routes_s = (" routes[" + " ".join(
            f"{k}={v}" for k, v in sorted(rc.items())) + "]") if rc else ""
        out.append(
            f"  ts={_fmt(meta.get('ts', 0.0), 3)} "
            f"process={meta.get('process')} "
            f"trigger={meta.get('trigger')} events={meta.get('events')}"
            + routes_s + tail_s)
        ticks = [e for e in events if e["kind"] == "metrics.tick"]
        if ticks:
            out.append(f"      deltas at trigger: "
                       f"{_event_summary(ticks[-1])}")
    ex_rows = _exemplar_traces(dumps)
    if ex_rows:
        out.append("")
        out.append("worst-latency exemplar traces:")
        out.append(_render_table(
            ex_rows[:8], ["trace_id", "value_ms", "metric", "path"]))
    out.append("")
    shown = merged[-tail:] if tail and len(merged) > tail else merged
    skipped = len(merged) - len(shown)
    head = "timeline (causally ordered"
    out.append(head + (f"; first {skipped} events elided):"
                       if skipped else "):"))
    for ev in shown:
        out.append(
            f"  {_fmt(ev['ts'], 4):>10}  {ev['process']:<14} "
            f"{ev['kind']:<18} {_event_summary(ev)}")
    return "\n".join(out)


def postmortem_selftest() -> int:
    """Synthesize a 3-process incident (gateway + 2 replicas on one
    FakeClock, one replica's final events only in its earlier burn dump),
    run the postmortem over it, and assert the merged report holds: one
    ordered timeline, dedup across double dumps, the exemplar trace
    crossing gateway -> replica, and schema-validating loads."""
    import tempfile

    from mmlspark_tpu.observability.metrics import MetricsRegistry
    from mmlspark_tpu.observability.recorder import (FlightRecorder,
                                                     load_dump)
    from mmlspark_tpu.resilience.policy import FakeClock

    checks: dict[str, bool] = {}
    with tempfile.TemporaryDirectory() as d:
        clock = FakeClock()
        tid = "cafe" * 8
        reg = MetricsRegistry()
        h = reg.histogram("mmlspark_tpu_serving_latency_seconds",
                          "latency", labels=("server",), exemplars=True)
        gw = FlightRecorder(dump_dir=d, process="gateway-gw0", clock=clock,
                            tick_interval_s=0.0, registry=reg)
        r0 = FlightRecorder(dump_dir=d, process="replica-0", clock=clock,
                            tick_interval_s=0.0, registry=reg)
        r1 = FlightRecorder(dump_dir=d, process="replica-1", clock=clock,
                            tick_interval_s=0.0, registry=reg)
        clock.advance(1.0)
        # one request crosses gateway -> replica-0 at the SAME fake ts
        gw.record_request(trace_id=tid, route="gateway", latency_s=0.2,
                          status=200)
        r0.record_request(trace_id=tid, route="resident", bucket=8,
                          latency_s=0.19, status=200, readback_lag=1)
        h.labels(server="srv0").observe(0.19, exemplar={"trace_id": tid})
        r1.record_request(trace_id="beef" * 8, route="host", bucket=1,
                          latency_s=0.01, status=200)
        for rec in (gw, r0, r1):
            rec.maybe_tick(reg)
        clock.advance(1.0)
        gw.record_transition("gateway", "eject", url="http://x:1/",
                             reason="connect")
        # burn-rate trigger: EVERY process dumps (the broadcast)
        for rec in (gw, r0, r1):
            rec.note_slo(["latency"])
        # replica-1 dies unannounced here (hard kill: no further dump);
        # its final events exist only in the burn dump above. The rest
        # drain-dump later, repeating ring contents the merge must dedup.
        clock.advance(2.0)
        gw.record_transition("gateway", "eject",
                             url="http://replica-1.dead/", reason="connect")
        gw.trigger_dump("drain", force=True)
        r0.trigger_dump("drain", force=True)

        dumps = load_postmortem_dir(d)
        checks["5 dumps load (schema-valid)"] = len(dumps) == 5
        for m, _ in dumps:
            load_dump(os.path.join(
                d, f"flight-{m['process']}-{m['pid']}-"
                   f"{m['dump_n']:03d}.jsonl"))
        report = postmortem(d)
        print(report)
        print()
        merged = _merge_events(dumps)
        ts_keys = [(e["ts"], e["tier"], e["pid"], e["seq"]) for e in merged]
        checks["timeline is ordered"] = ts_keys == sorted(ts_keys)
        reqs = [e for e in merged if e["kind"] == "serving.request"]
        checks["dedup across double dumps"] = (
            len(reqs) == 3 and len(merged) == len({
                (e["process"], e["pid"], e["seq"]) for e in merged}))
        gw_i = next(i for i, e in enumerate(merged)
                    if e["process"].startswith("gateway")
                    and e["kind"] == "serving.request")
        rep_i = next(i for i, e in enumerate(merged)
                     if e["process"] == "replica-0"
                     and e["kind"] == "serving.request")
        checks["same-ts gateway precedes replica"] = gw_i < rep_i
        checks["killed replica's final events present"] = any(
            e["process"] == "replica-1" for e in merged)
        checks["exemplar trace crosses gateway->replica"] = (
            f"gateway-gw0(gateway) -> replica-0(resident)" in report
            and tid in report)
        checks["burn trigger in report"] = "trigger=slo_burn" in report
    failed = [name for name, ok in checks.items() if not ok]
    if failed:
        print(f"postmortem selftest FAILED: {failed}", file=sys.stderr)
        return 1
    print(f"postmortem selftest OK ({len(checks)} checks)")
    return 0


# -- streaming ---------------------------------------------------------- #

def diagnose_streaming(ckpt_dir: str) -> str:
    """Partition table for one streaming checkpoint directory. Built only
    from what the query durably wrote (commits.jsonl, status.json, the
    per-partition snapshot files) — the same sources recovery reads, so
    what it prints is exactly what a restart would see."""
    from mmlspark_tpu.streaming.checkpoint import CommitLog

    if not os.path.isdir(ckpt_dir):
        return f"(no checkpoint directory at {ckpt_dir})"
    plans, commits = 0, []
    log_path = os.path.join(ckpt_dir, CommitLog.FILENAME)
    if os.path.exists(log_path):
        with open(log_path, encoding="utf-8") as fh:
            for line in fh:
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    break                       # torn tail
                if rec.get("t") == "plan":
                    plans += 1
                elif rec.get("t") == "commit":
                    commits.append(int(rec["batch_id"]))
    last = max(commits, default=-1)

    # newest snapshot per partition, straight off the filenames
    snap_bid: dict[int, int] = {}
    snap_bytes: dict[int, int] = {}
    for name in os.listdir(ckpt_dir):
        parsed = CommitLog._parse_pstate(name)
        if parsed is None:
            continue
        part, bid = parsed
        if bid >= snap_bid.get(part, -1):
            snap_bid[part] = bid
            snap_bytes[part] = os.path.getsize(
                os.path.join(ckpt_dir, name))

    status = {}
    try:
        with open(os.path.join(ckpt_dir, "status.json"),
                  encoding="utf-8") as fh:
            status = json.load(fh)
    except (OSError, json.JSONDecodeError):
        pass
    pstats = status.get("partitions", {})
    nparts = int(status.get("num_partitions") or 0)
    parts = sorted(set(snap_bid)
                   | {int(p) for p in pstats}
                   | set(range(nparts)))

    out = [
        f"query: {status.get('query', '?')} "
        f"mode={status.get('mode', '?')} "
        f"key_col={status.get('key_col', '?')} "
        f"partitions={nparts or len(parts)} "
        f"last_commit={last} wal_records={plans}+{len(commits)}"
    ]
    rows = []
    for p in parts:
        st = pstats.get(str(p), {})
        wm = st.get("watermark")
        lag = st.get("lag_s")
        rows.append([
            str(p),
            _fmt(st.get("rows_in", float("nan"))),
            _fmt(st.get("rows_out", float("nan"))),
            _fmt(st.get("queue_depth", float("nan"))),
            _fmt(lag * 1e3, 2) if lag is not None else "-",
            _fmt(wm, 3) if wm is not None else "-",
            _fmt(st.get("spilled_bytes", 0)),
            (str(snap_bid[p]) if p in snap_bid else "-"),
            _fmt(snap_bytes.get(p, float("nan"))),
        ])
    if rows:
        out.append(_render_table(rows, [
            "partition", "rows_in", "rows_out", "queue", "lag_ms",
            "watermark", "spill_bytes", "snapshot", "snap_bytes"]))
    else:
        out.append("(no partition snapshots or status)")
    return "\n".join(out)


def streaming_selftest() -> int:
    """Run a real P=2 partition-parallel query in-process (spilling state
    backend, incremental checkpoints), diagnose its checkpoint dir, and
    assert the snapshot against the query's own truth plus a P=1 oracle."""
    import tempfile

    import numpy as np

    from mmlspark_tpu.core.pipeline import pipeline_model
    from mmlspark_tpu.core.schema import Table
    from mmlspark_tpu.streaming import (
        GroupedAggregator, KeyedShuffle, MemorySink, MemorySource,
        ParallelStreamingQuery, StreamingQuery)

    checks: dict[str, bool] = {}
    rng = np.random.default_rng(7)
    data = [Table({"key": [f"k{int(i)}" for i in rng.integers(0, 6, 32)],
                   "value": np.round(rng.uniform(0, 10, 32), 3)})
            for _ in range(3)]
    # one batch whose keys all land in a single partition: the other
    # partition's state doc is unchanged and must NOT write a snapshot
    from mmlspark_tpu.streaming import partition_of
    k_one = next(f"s{i}" for i in range(100)
                 if partition_of(f"s{i}", 2) == 0)
    data.append(Table({"key": [k_one] * 8,
                       "value": np.ones(8, dtype=np.float64)}))

    def stage(spill_dir=None):
        kw = {}
        if spill_dir:
            kw = dict(state_backend="spill", spill_dir=spill_dir,
                      spill_hot_keys=2)
        return GroupedAggregator(group_col="key", value_col="value",
                                 agg="sum", output_col="total", **kw)

    src, sink = MemorySource(), MemorySink()
    oracle_q = StreamingQuery(src, stage(), sink, name="oracle")
    for b in data:
        src.add_rows(b)
        oracle_q.process_all_available()
    oracle_q.stop()
    oracle = sink.table()

    with tempfile.TemporaryDirectory() as d:
        ckpt = os.path.join(d, "ckpt")
        src, sink = MemorySource(), MemorySink()
        q = ParallelStreamingQuery(
            src,
            pipeline_model(KeyedShuffle(key_col="key", num_partitions=2),
                           stage(spill_dir=os.path.join(d, "spill"))),
            sink, name="diagq", checkpoint_dir=ckpt)
        incr = []
        for b in data:
            src.add_rows(b)
            q.process_all_available()
            incr.append(q.last_progress.get("partition_states_written"))
        q.stop()
        report = diagnose_streaming(ckpt)
        print(report)
        checks["P=2 output matches P=1 oracle"] = oracle.equals(
            sink.table())
        checks["status.json snapshot read"] = "mode=thread" in report
        checks["both partitions in table"] = all(
            f"\n{p} " in report for p in "01")
        from mmlspark_tpu.streaming.checkpoint import CommitLog

        checks["per-partition snapshots on disk"] = any(
            CommitLog._parse_pstate(n) for n in os.listdir(ckpt))
        checks["single-partition batch writes one snapshot"] = (
            incr[-1] == 1)
        checks["spill bytes surfaced"] = (
            q._pinfo[0].get("spilled_bytes", 0) > 0
            or q._pinfo[1].get("spilled_bytes", 0) > 0)
    failed = [name for name, ok in checks.items() if not ok]
    if failed:
        print(f"streaming selftest FAILED: {failed}", file=sys.stderr)
        return 1
    print(f"streaming selftest OK ({len(checks)} checks)")
    return 0


# -- training checkpoints ------------------------------------------------ #

def _checkpoint_store_dirs(root: str) -> list[str]:
    """Checkpoint stores at or under `root`: any directory holding a
    manifest.json or ckpt-*.bin files (a tune sweep nests per-trial
    stores as trial-NNNN/fold-N plus a _trials ledger)."""
    from mmlspark_tpu.resilience.elastic import _FILE_RE, _MANIFEST

    found = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames.sort()
        if _MANIFEST in filenames or any(
                _FILE_RE.match(f) for f in filenames):
            found.append(dirpath)
    return found


def diagnose_checkpoints(root: str) -> str:
    """Lineage/integrity table for every checkpoint store under `root`,
    built by verifying the snapshot files themselves (the same check a
    resumed fit runs), so the `resume` arrow marks exactly the snapshot
    `load_latest` would hand back."""
    import hashlib
    import time

    from mmlspark_tpu.resilience.elastic import (TrainingCheckpointer,
                                                 _DIGEST_SIZE)

    if not os.path.isdir(root):
        return f"(no checkpoint directory at {root})"
    stores = _checkpoint_store_dirs(root)
    if not stores:
        return f"(no checkpoint stores under {root})"
    out = []
    for d in stores:
        ckpt = TrainingCheckpointer(d)
        entries = ckpt.entries()
        verdicts: dict[int, tuple[bool, str]] = {}
        for e in entries:
            ok, detail, payload = TrainingCheckpointer.verify_file(
                os.path.join(d, e["file"]))
            if ok and e.get("blake2b") is not None and hashlib.blake2b(
                    payload, digest_size=_DIGEST_SIZE).hexdigest() \
                    != e["blake2b"]:
                ok, detail = False, "manifest-mismatch"
            verdicts[e["seq"]] = (ok, detail)
        resume_seq = next((e["seq"] for e in reversed(entries)
                           if verdicts[e["seq"]][0]), None)
        rel = os.path.relpath(d, root)
        out.append(f"store: {'.' if rel == os.curdir else rel}  "
                   f"snapshots={len(entries)}")
        rows = []
        for e in entries:
            ok, detail = verdicts[e["seq"]]
            age = (_fmt(max(time.time() - e["unix_ts"], 0.0), 1)
                   if e.get("unix_ts") else "-")
            rows.append([
                str(e["seq"]), e["tag"],
                _fmt(e["bytes"]) if e.get("bytes") is not None else "?",
                str(e["parent_seq"])
                if e.get("parent_seq") is not None else "-",
                age, detail,
                "<- resume" if e["seq"] == resume_seq else ""])
        if rows:
            out.append(_render_table(rows, [
                "seq", "tag", "bytes", "parent", "age_s", "integrity", ""]))
        else:
            out.append("(empty store)")
        if resume_seq is None and entries:
            out.append("  NO verifiable snapshot — a restart starts fresh")
        out.append("")
    return "\n".join(out).rstrip()


def checkpoints_selftest() -> int:
    """Exercise the whole --checkpoints surface against a real store:
    retention + lineage, every corruption mode the verifier names,
    resume fallback past a truncated snapshot, manifest-loss rebuild,
    and a real checkpointed GBDT fit whose store the table must read."""
    import tempfile

    import numpy as np

    from mmlspark_tpu.core.schema import Table
    from mmlspark_tpu.gbdt.estimators import GBDTRegressor
    from mmlspark_tpu.resilience.elastic import TrainingCheckpointer

    checks: dict[str, bool] = {}
    with tempfile.TemporaryDirectory() as d:
        store = os.path.join(d, "fit")
        ckpt = TrainingCheckpointer(store, keep=3)
        for i in range(4):
            ckpt.save(f"payload-{i}".encode(), tag=f"epoch-{i:04d}")
        entries = TrainingCheckpointer(store).entries()
        checks["retention keeps newest 3"] = (
            [e["seq"] for e in entries] == [1, 2, 3])
        checks["lineage chain intact"] = all(
            e["parent_seq"] == e["seq"] - 1 for e in entries)
        report = diagnose_checkpoints(store)
        print(report)
        checks["all snapshots verify"] = report.count(" ok") == 3
        checks["resume arrow on newest"] = (
            "epoch-0003" in report.splitlines()[
                next(i for i, ln in enumerate(report.splitlines())
                     if "<- resume" in ln)])

        # truncate the newest snapshot: the table must flag it and the
        # resume arrow must fall back to the next-newest verified one
        newest = os.path.join(store, entries[-1]["file"])
        with open(newest, "r+b") as fh:
            fh.truncate(os.path.getsize(newest) - 3)
        report = diagnose_checkpoints(store)
        print()
        print(report)
        checks["truncated snapshot flagged"] = "truncated" in report
        checks["resume falls back"] = any(
            "epoch-0002" in ln and "<- resume" in ln
            for ln in report.splitlines())
        loaded = TrainingCheckpointer(store).load_latest()
        checks["load_latest skips the torn file"] = (
            loaded is not None and loaded[0] == b"payload-2")

        # a bit-flip inside the payload: checksum catches it
        second = os.path.join(store, entries[-2]["file"])
        blob = bytearray(open(second, "rb").read())
        blob[-1] ^= 0xFF
        with open(second, "wb") as fh:
            fh.write(bytes(blob))
        checks["bit-flip named checksum-mismatch"] = (
            "checksum-mismatch" in diagnose_checkpoints(store))

        # kill the manifest: the store rebuilds its index from the
        # self-verifying files and the table still renders
        os.unlink(os.path.join(store, "manifest.json"))
        report = diagnose_checkpoints(store)
        checks["manifest loss rebuilds from files"] = (
            "epoch-0001" in report and "snapshots=3" in report)

        # real training loop: a checkpointed GBDT fit leaves a store the
        # table reads, and a refit resumes from it
        rng = np.random.default_rng(0)
        X = rng.normal(size=(160, 4))
        y = X @ rng.normal(size=4)
        t = Table({"features": X, "label": y})
        fit_dir = os.path.join(d, "gbdt")
        est = GBDTRegressor(num_iterations=4, num_leaves=7,
                            checkpoint_dir=fit_dir, checkpoint_every_n=2)
        ref = GBDTRegressor(num_iterations=4, num_leaves=7).fit(t)
        model = est.fit(t)
        report = diagnose_checkpoints(fit_dir)
        print()
        print(report)
        checks["gbdt fit writes round snapshots"] = "round-000004" in report
        checks["gbdt store fully verified"] = (
            "<- resume" in report and "mismatch" not in report)
        checks["checkpointed fit matches plain fit"] = (
            model.booster.to_text() == ref.booster.to_text())
    failed = [name for name, ok in checks.items() if not ok]
    if failed:
        print(f"checkpoints selftest FAILED: {failed}", file=sys.stderr)
        return 1
    print(f"checkpoints selftest OK ({len(checks)} checks)")
    return 0


# -- perf attribution --------------------------------------------------- #

def diagnose_perf(target: str) -> str:
    """One-shot performance attribution for a live server or a MULTICHIP
    artifact. `target` is either a ServingServer base URL (the info()
    `profiler` block is rendered as a phase table next to the measured
    latency) or a MULTICHIP_*.json path (per-mesh-size attribution with
    the slowest shard named per segment)."""
    from mmlspark_tpu.observability.profiler import render_attribution

    if target.startswith(("http://", "https://")):
        info = json.loads(_fetch(target if target.endswith("/")
                                 else target + "/"))
        lat = info.get("latency") or {}
        lines = [
            f"serving: {target}",
            f"  answered={info.get('answered')}  "
            f"p50_ms={lat.get('p50_ms')}  p99_ms={lat.get('p99_ms')}",
            f"  compile_seconds_total={info.get('compile_seconds_total')}",
        ]
        hp = info.get("hot_path") or {}
        if hp:
            # the donated/pipelined dispatch gauges: what fraction of
            # fetches found their batch already complete (compute fully
            # hidden behind pipeline work), and whether the resident
            # executable aliases its input buffers
            lines.append(
                f"  hot_path: donate_buffers={hp.get('donate_buffers')}  "
                f"dispatch_overlap_fraction="
                f"{hp.get('dispatch_overlap_fraction')}  "
                f"readback_lag={hp.get('readback_lag')}")
        for entry in (info.get("compile_ledger") or [])[:5]:
            lines.append(f"    compile {entry.get('seconds', 0.0):8.3f}s  "
                         f"{entry.get('shape', '')}")
        prof = info.get("profiler") or {}
        if not prof.get("enabled"):
            lines.append(
                "profiler: DISARMED — arm the process profiler "
                "(observability.profiler.get_profiler().arm()) and "
                "re-score to collect attribution")
            return "\n".join(lines)
        rows = prof.get("attribution") or []
        if not rows:
            lines.append("profiler: armed, no ledgers committed yet")
            return "\n".join(lines)
        lines.append(render_attribution(
            rows, title=f"phase attribution ({prof.get('ledgers')} "
                        "ledgers)"))
        return "\n".join(lines)

    with open(target) as fh:
        data = json.load(fh)
    ladder = data.get("fused_sharded_vs_single") or []
    lines = [f"multichip run: {target}  "
             f"n_devices={data.get('n_devices')}  ok={data.get('ok')}"]
    attr_rows = []
    for row in ladder:
        attr = row.get("attribution")
        mesh = row.get("mesh_shape", "?")
        if attr:
            # retitle by mesh size so the table separates ladder rungs
            attr = dict(attr)
            attr["segment"] = f"{attr.get('segment', 'seg?')}@{mesh}"
            attr_rows.append(attr)
            slowest = attr.get("slowest_shard")
            shards = {s.get("shard"): s for s in attr.get("shards") or []}
            if slowest and slowest in shards:
                sh = shards[slowest]
                lines.append(
                    f"  {attr['segment']}: slowest shard {slowest} — "
                    f"{sh.get('rows')} rows, "
                    f"{sh.get('seconds', 0.0) * 1e6:.1f} us compute "
                    f"(skew {attr.get('shard_skew'):.2f}x)")
        elif "shard_skew_ratio" in row:
            lines.append(
                f"  seg?@{mesh}: shard_skew_ratio="
                f"{row['shard_skew_ratio']:.2f}x (pre-profiler artifact: "
                "no per-shard attribution recorded)")
    if attr_rows:
        lines.append(render_attribution(
            attr_rows, title="per-mesh phase attribution"))
    elif not ladder:
        lines.append("  no fused_sharded_vs_single ladder in artifact")
    return "\n".join(lines)


def perf_selftest() -> int:
    """CI smoke for the attribution path: a real resident serve_model
    server with the process profiler armed, live traffic, then assert
    the phase ledger's sum covers its measured RTT within 15% and the
    --perf report renders the table. A synthetic MULTICHIP artifact
    checks the shard-attribution rendering without needing 8 devices."""
    import tempfile
    import time

    import numpy as np

    from mmlspark_tpu.core.schema import Table
    from mmlspark_tpu.gbdt.estimators import GBDTRegressor
    from mmlspark_tpu.io_http.schema import HTTPRequestData
    from mmlspark_tpu.io_http.serving import serve_model
    from mmlspark_tpu.observability.profiler import get_profiler

    checks: dict[str, bool] = {}
    prof = get_profiler()
    rng = np.random.default_rng(5)
    X = rng.normal(size=(256, 4)).astype(np.float32).astype(np.float64)
    y = X @ rng.normal(size=4)
    model = GBDTRegressor(num_iterations=5, num_leaves=7).fit(
        Table({"features": X, "label": y}))
    cols = [f"x{i}" for i in range(4)]
    warm = HTTPRequestData.from_json(
        "/", {c: float(np.float32(0.25 * i)) for i, c in enumerate(cols)})
    srv = serve_model(model, cols, max_batch_size=32, warmup_request=warm)
    try:
        deadline = time.monotonic() + 60
        while not srv.ready and time.monotonic() < deadline:
            time.sleep(0.05)
        checks["server warmed"] = srv.ready
        checks["hot path enabled"] = (
            srv.hot_path is not None and srv.hot_path.disabled is None)
        srv.hot_path.force_path = "resident"
        prof.reset()
        prof.arm()
        n = 8
        for _ in range(n):
            v = rng.normal(size=4).astype(np.float32)
            req = urllib.request.Request(
                srv.url, data=json.dumps(
                    {c: float(x) for c, x in zip(cols, v)}).encode(),
                headers={"Content-Type": "application/json"},
                method="POST")
            urllib.request.urlopen(req, timeout=10).read()
        report = diagnose_perf(srv.url)
        print(report)
        snap = prof.snapshot()
        rows = [r for r in snap["attribution"]
                if r["kind"] == "request"
                and r["segment"] == srv.hot_path.resident_label]
        checks["resident request ledgers committed"] = bool(rows)
        if rows:
            row = rows[0]
            checks["all resident requests attributed"] = row["count"] == n
            cov = row.get("coverage")
            # the ROADMAP bar: attributed phases explain the measured
            # server-side RTT to within 15%
            checks["phase sum within 15% of RTT"] = (
                cov is not None and 0.85 <= cov <= 1.15)
            checks["device phases present"] = all(
                row["phase_us"].get(p, 0.0) > 0.0
                for p in ("h2d", "dispatch", "compute", "d2h"))
            checks["queue wait attributed"] = (
                row["phase_us"].get("queue", 0.0) > 0.0)
        checks["report renders phase table"] = "dispatch/us" in report
        checks["report carries dispatch overlap"] = (
            "dispatch_overlap_fraction=" in report)
        info_blob = json.loads(_fetch(srv.url + "/"))
        checks["info carries profiler block"] = (
            info_blob.get("profiler", {}).get("enabled") is True)
        hp_snap = info_blob.get("hot_path") or {}
        checks["hot path reports dispatch overlap"] = isinstance(
            hp_snap.get("dispatch_overlap_fraction"), (int, float))
        checks["hot path reports donation"] = isinstance(
            hp_snap.get("donate_buffers"), bool)
    finally:
        prof.disarm()
        srv.stop()

    # synthetic MULTICHIP artifact: the shard-attribution rendering
    fake = {
        "n_devices": 2, "ok": True,
        "fused_sharded_vs_single": [{
            "n_devices": 2, "mesh_shape": "2x1",
            "shard_skew_ratio": 2.0,
            "attribution": {
                "kind": "fused", "segment": "seg0", "count": 1,
                "phase_us": {"prepare": 40.0, "pad": 5.0, "h2d": 100.0,
                             "dispatch": 220.0, "compute": 400.0,
                             "collective": 0.0, "d2h": 80.0,
                             "queue": 0.0},
                "phase_sum_us": 845.0, "rtt_us": 900.0,
                "coverage": 0.938, "rows_real": 4096, "rows_padded": 0,
                "pad_waste": 0.0, "gflops": 0.002,
                "achieved_gflops_per_s": 4.7,
                "slowest_shard": "cpu:1", "shard_skew": 2.0,
                "shards": [
                    {"shard": "cpu:1", "seconds": 0.0004, "rows": 2048,
                     "dispatches": 8, "mean_us": 50.0},
                    {"shard": "cpu:0", "seconds": 0.0002, "rows": 2048,
                     "dispatches": 8, "mean_us": 25.0},
                ],
            },
        }],
    }
    with tempfile.NamedTemporaryFile("w", suffix="_MULTICHIP.json",
                                     delete=False) as fh:
        json.dump(fake, fh)
        path = fh.name
    try:
        mc_report = diagnose_perf(path)
        print()
        print(mc_report)
        checks["multichip names slowest shard"] = (
            "slowest shard cpu:1" in mc_report
            and "2048 rows" in mc_report)
        checks["multichip renders shard table"] = "<- slowest" in mc_report
    finally:
        os.unlink(path)

    failed = [name for name, ok in checks.items() if not ok]
    if failed:
        print(f"perf selftest FAILED: {failed}", file=sys.stderr)
        return 1
    print(f"perf selftest OK ({len(checks)} checks)")
    return 0


# -- sweep -------------------------------------------------------------- #

def diagnose_sweep(ckpt_dir: str) -> str:
    """Trial ledger table for one AutoML sweep checkpoint directory.
    Built only from what the sweep durably wrote (spec.json + the
    `_sweep_ledger` TrainingCheckpointer snapshots) — exactly what a
    resumed `SweepScheduler.run` would see, so a live sweep can be
    watched from a second terminal with no coordination."""
    from mmlspark_tpu.resilience.elastic import TrainingCheckpointer

    if not os.path.isdir(ckpt_dir):
        return f"(no sweep checkpoint directory at {ckpt_dir})"
    spec = {}
    try:
        with open(os.path.join(ckpt_dir, "spec.json"),
                  encoding="utf-8") as fh:
            spec = json.load(fh)
    except (OSError, json.JSONDecodeError):
        pass
    doc = {}
    loaded = TrainingCheckpointer(
        os.path.join(ckpt_dir, "_sweep_ledger"), keep=2).load_latest()
    if loaded is not None:
        try:
            doc = json.loads(loaded[0].decode("utf-8"))
        except ValueError:
            doc = {}
    if doc.get("kind") != "sweep-ledger":
        doc = {}

    results = doc.get("results", {})
    pruned = doc.get("pruned", {})
    lineage = doc.get("lineage", {})
    budgets = [int(b) for b in (doc.get("budgets")
                                or spec.get("budgets") or [])]
    n_trials = int(doc.get("n_trials")
                   or len(spec.get("trials") or ()) or 0)
    pruned_at = {int(ti): rung for rung, tis in pruned.items()
                 for ti in tis}

    out = [
        f"sweep: {ckpt_dir} trials={n_trials} "
        f"metric={spec.get('metric', '?')} "
        f"rungs={budgets or '?'} workers={spec.get('n_workers', '?')} "
        f"resumed_trials={doc.get('resumed_trials', 0)} "
        f"scores={len(results)}"
    ]
    rows = []
    for ti in range(n_trials):
        events = lineage.get(str(ti), [])
        last = events[-1] if events else {}
        scores = {int(k.split(":")[1]): v for k, v in results.items()
                  if int(k.split(":")[0]) == ti}
        if ti in pruned_at:
            state = f"pruned@r{pruned_at[ti]}"
        elif budgets and len(budgets) - 1 in scores:
            state = "done"
        elif last.get("event") == "assigned":
            state = "running"
        elif last.get("event") == "failed":
            state = "failed"
        else:
            state = "pending" if not scores else "waiting"
        n_lost = sum(1 for e in events if e.get("event") == "lost")
        rows.append([
            str(ti), state,
            str(1 + max(scores, default=-1)) + f"/{len(budgets) or '?'}",
            " ".join(_fmt(scores[r], 4) for r in sorted(scores)) or "-",
            str(last.get("worker", "-") or "-"),
            str(n_lost) if n_lost else "-",
        ])
    if rows:
        out.append(_render_table(rows, [
            "trial", "state", "rungs", "scores", "last_worker", "lost"]))
    else:
        out.append("(no trials ledgered yet)")
    return "\n".join(out)


def sweep_selftest() -> int:
    """Build a known sweep ledger on disk (the same writer the scheduler
    uses), diagnose it, and assert every state the table can show:
    scored, pruned, resumed-after-loss, and still-pending trials."""
    import tempfile

    from mmlspark_tpu.resilience.elastic import TrainingCheckpointer

    checks: dict[str, bool] = {}
    with tempfile.TemporaryDirectory() as d:
        checks["empty dir reports cleanly"] = (
            "(no trials ledgered yet)" in diagnose_sweep(d))
        with open(os.path.join(d, "spec.json"), "w",
                  encoding="utf-8") as fh:
            json.dump({"kind": "sweep-spec", "metric": "accuracy",
                       "n_workers": 2, "budgets": [4, 8],
                       "trials": [[0, {}]] * 4}, fh)
        doc = {
            "kind": "sweep-ledger",
            "results": {"0:0": 0.9, "1:0": 0.5, "2:0": 0.7,
                        "0:1": 0.92, "2:1": 0.71},
            "pruned": {"0": [1]},
            "lineage": {
                "0": [{"event": "assigned", "rung": 0,
                       "worker": "http://w1/"},
                      {"event": "lost", "rung": 0, "worker": "http://w1/"},
                      {"event": "assigned", "rung": 1,
                       "worker": "http://w2/"}],
                "1": [{"event": "pruned", "rung": 0}],
            },
            "resumed_trials": 1, "n_trials": 4, "budgets": [4, 8],
        }
        TrainingCheckpointer(os.path.join(d, "_sweep_ledger"),
                             keep=2).save(
            json.dumps(doc).encode("utf-8"), tag="ledger-0005")
        report = diagnose_sweep(d)
        print(report)
        checks["header counts"] = ("trials=4" in report
                                   and "resumed_trials=1" in report
                                   and "scores=5" in report)
        lines = {ln.split()[0]: ln for ln in report.splitlines()
                 if ln and ln.split()[0].isdigit()}
        checks["winner done"] = "done" in lines["0"]
        checks["loss counted"] = lines["0"].rstrip().endswith("1")
        checks["pruned at rung"] = "pruned@r0" in lines["1"]
        checks["pending trial"] = "pending" in lines["3"]
        checks["scores render"] = "0.9000 0.9200" in lines["0"]
    failed = [name for name, ok in checks.items() if not ok]
    if failed:
        print(f"sweep selftest FAILED: {failed}", file=sys.stderr)
        return 1
    print(f"sweep selftest OK ({len(checks)} checks)")
    return 0


def diagnose_training(ckpt_dir: str) -> str:
    """Live table for one elastic training checkpoint directory: world
    epoch, member list with per-worker step lag, and the recent re-shard
    history. Built only from the driver's durably-written
    `elastic_status.json` (rewritten atomically every step), so a
    running fit can be watched from a second terminal."""
    if not os.path.isdir(ckpt_dir):
        return f"(no training checkpoint directory at {ckpt_dir})"
    try:
        with open(os.path.join(ckpt_dir, "elastic_status.json"),
                  encoding="utf-8") as fh:
            doc = json.load(fh)
    except (OSError, json.JSONDecodeError):
        return f"(no elastic_status.json under {ckpt_dir} yet)"

    last = doc.get("last_reshard") or {}
    out = [
        f"elastic {doc.get('kind', '?')} fit: {ckpt_dir} "
        f"world_epoch={doc.get('world_epoch', '?')} "
        f"P={doc.get('world_size', '?')} step={doc.get('step', '?')} "
        f"straggler_wait={_fmt(doc.get('straggler_wait_s'), 4)}s "
        f"last_reshard={last.get('cause', '-')}"
    ]
    rows = []
    for m in doc.get("members", ()):
        rows.append([
            str(m.get("rank", "?")), str(m.get("url", "?")),
            _fmt(m.get("step")) if m.get("step") is not None else "-",
            _fmt(m.get("lag")) if m.get("lag") is not None else "-",
            _fmt((m.get("rtt_s") or 0) * 1e3, 1)
            if m.get("rtt_s") is not None else "-",
        ])
    if rows:
        out.append(_render_table(
            rows, ["rank", "url", "step", "lag", "rtt_ms"]))
    else:
        out.append("(no members configured yet)")
    reshards = doc.get("reshards", ())
    if reshards:
        out.append("re-shards (most recent last):")
        out.append(_render_table(
            [[str(r.get("world_epoch", "?")), str(r.get("cause", "?")),
              _fmt(r.get("step")), _fmt(r.get("world_size")),
              _fmt(r.get("barrier_retries"))]
             for r in reshards],
            ["epoch", "cause", "step", "P", "barrier_retries"]))
    return "\n".join(out)


def training_selftest() -> int:
    """Run a REAL (in-process) elastic GBDT fit whose step hook kills a
    worker and adds another, then diagnose the directory the driver
    wrote and assert every fact the table must show: world epoch,
    members, step lag, and the re-shard causes."""
    import tempfile

    import numpy as np

    from mmlspark_tpu.core.schema import Table
    from mmlspark_tpu.io_http.schema import HTTPRequestData
    from mmlspark_tpu.resilience.elastic_fleet import (
        ElasticGBDTFit, ElasticWorkerFactory)

    class _LocalFleet:
        """In-process handler-per-URL stand-in for ServingFleet: the full
        driver protocol with none of the processes."""

        def __init__(self, checkpoint_dir):
            self.checkpoint_dir = checkpoint_dir
            self.handlers = {}
            self._n = 0

        def add(self):
            url = f"http://local/{self._n:03d}"
            self._n += 1
            self.handlers[url] = ElasticWorkerFactory(
                self.checkpoint_dir, guard=False)()
            return url

        urls = property(lambda self: list(self.handlers))
        n_live = property(lambda self: len(self.handlers))

        def watch(self, cb):
            pass

        def dump_all(self, trigger=""):
            return 0

        def stop(self):
            pass

    def _post(fleet):
        def post(url, body):
            handler = fleet.handlers.get(url)
            if handler is None:
                raise RuntimeError("dead member")
            out = handler(Table(
                {"request": [HTTPRequestData.from_json("/", body)]}))
            rep = out["reply"][0]
            doc = json.loads(bytes(rep.entity).decode("utf-8"))
            if rep.status_code != 200:
                raise RuntimeError(doc.get("error", "handler error"))
            return doc
        return post

    checks: dict[str, bool] = {}
    with tempfile.TemporaryDirectory() as d:
        checks["empty dir reports cleanly"] = (
            "(no training checkpoint directory" in diagnose_training(
                os.path.join(d, "missing")))
        fleet = _LocalFleet(d)
        seen = {"n": 0}

        def hook(fit):
            seen["n"] += 1
            if seen["n"] == 2 and fleet.n_live > 1:
                del fleet.handlers[fleet.urls[0]]
            elif seen["n"] == 4:
                fleet.add()

        fit = ElasticGBDTFit(
            d, objective="regression", num_iterations=6, num_leaves=7,
            max_bin=15, min_data_in_leaf=1, seed=0, n_workers=2,
            num_virtual=8, fleet=fleet, post=_post(fleet),
            step_hook=hook)
        fleet.add(), fleet.add()
        rng = np.random.default_rng(7)
        x = rng.normal(size=(80, 3))
        fit.fit(x, x[:, 0] * 2 + rng.normal(size=80) * 0.1)
        report = diagnose_training(d)
        print(report)
        checks["kind + dir header"] = "elastic gbdt fit" in report
        checks["final step"] = "step=6" in report
        checks["kill re-sharded"] = " death " in report
        checks["join re-sharded"] = " join " in report
        checks["members rendered"] = "http://local/" in report
        checks["epoch advanced"] = any(
            f"world_epoch={e}" in report for e in range(3, 10))
        checks["lag column"] = "lag" in report
    failed = [name for name, ok in checks.items() if not ok]
    if failed:
        print(f"training selftest FAILED: {failed}", file=sys.stderr)
        return 1
    print(f"training selftest OK ({len(checks)} checks)")
    return 0


# -- selftest ----------------------------------------------------------- #

def _selftest_handler(table):
    import numpy as np

    from mmlspark_tpu.io_http.schema import make_reply, parse_request

    t = parse_request(table)
    return make_reply(t.with_column(
        "doubled", np.asarray(t["x"], dtype=float) * 2), "doubled")


def _selftest_factory():
    return _selftest_handler


def _hot_path_selftest(checks: dict) -> None:
    """Stand up a hot-path serve_model server in-process, push traffic
    through every route, and assert the ≤1-host-round-trip-per-request
    serving bar on the resident path."""
    import time

    import numpy as np

    from mmlspark_tpu.core.schema import Table
    from mmlspark_tpu.gbdt.estimators import GBDTRegressor
    from mmlspark_tpu.io_http.schema import HTTPRequestData
    from mmlspark_tpu.io_http.serving import serve_model

    rng = np.random.default_rng(5)
    X = rng.normal(size=(256, 4)).astype(np.float32).astype(np.float64)
    y = X @ rng.normal(size=4)
    model = GBDTRegressor(num_iterations=5, num_leaves=7).fit(
        Table({"features": X, "label": y}))
    cols = [f"x{i}" for i in range(4)]
    warm = HTTPRequestData.from_json(
        "/", {c: float(np.float32(0.25 * i)) for i, c in enumerate(cols)})
    srv = serve_model(model, cols, max_batch_size=32, warmup_request=warm)
    try:
        deadline = time.monotonic() + 60
        while not srv.ready and time.monotonic() < deadline:
            time.sleep(0.05)
        checks["hot server warmed"] = srv.ready
        checks["hot path enabled"] = (
            srv.hot_path is not None and srv.hot_path.disabled is None)
        srv.hot_path.force_path = "resident"
        n = 6
        for i in range(n):
            v = rng.normal(size=4).astype(np.float32)
            req = urllib.request.Request(
                srv.url, data=json.dumps(
                    {c: float(x) for c, x in zip(cols, v)}).encode(),
                headers={"Content-Type": "application/json"}, method="POST")
            urllib.request.urlopen(req, timeout=10).read()
        report = diagnose_serving(srv.url)
        print()
        print(report)
        snap = srv.hot_path.snapshot()
        checks[f"{n} resident requests"] = snap["paths"]["resident"] == n
        checks["<=1 host round-trip per request"] = (
            0 < snap["round_trips_per_resident_request"] <= 1.0)
        checks["crossover measured"] = len(snap["crossover"]) > 0
        checks["report shows crossover"] = "resident_ms" in report
    finally:
        srv.stop()


def _sar_serving_selftest(checks: dict) -> None:
    """Stand up a resident SAR recommender and assert the --serving
    report carries the sar_resident route: its label on the hot-path
    line and its per-path request counter."""
    import time

    import numpy as np

    from mmlspark_tpu.core.schema import Table
    from mmlspark_tpu.recommendation import SAR, serve_recommender

    rng = np.random.default_rng(11)
    n = 400
    t = Table({"user": rng.integers(0, 40, n).astype(np.float64),
               "item": rng.integers(0, 30, n).astype(np.float64)})
    model = SAR(support_threshold=1).fit(t)
    srv = serve_recommender(model, k=5, max_batch_size=16)
    try:
        deadline = time.monotonic() + 60
        while not srv.ready and time.monotonic() < deadline:
            time.sleep(0.05)
        checks["sar server warmed"] = srv.ready
        checks["sar hot path enabled"] = (
            srv.hot_path is not None and srv.hot_path.disabled is None)
        for uid in range(6):
            req = urllib.request.Request(
                srv.url, data=json.dumps({"user": uid}).encode(),
                headers={"Content-Type": "application/json"}, method="POST")
            urllib.request.urlopen(req, timeout=10).read()
        report = diagnose_serving(srv.url)
        print()
        print(report)
        checks["report labels sar route"] = (
            "resident_label=sar_resident" in report)
        snap = srv.hot_path.snapshot()
        checks["sar resident requests counted"] = (
            snap["paths"].get("sar_resident", 0) >= 1)
    finally:
        srv.stop()


def selftest() -> int:
    from mmlspark_tpu.io_http.serving import ServingFleet

    fleet = ServingFleet(_selftest_factory, n_hosts=2,
                         device_workers=False).start()
    try:
        for i in range(8):
            req = urllib.request.Request(
                fleet.urls[i % 2],
                data=json.dumps({"x": float(i)}).encode(),
                headers={"Content-Type": "application/json"}, method="POST")
            urllib.request.urlopen(req, timeout=10).read()
        report = diagnose_rendezvous(fleet.rendezvous.url)
        print(report)
        info = fleet.info()
        checks = {
            "2 replicas registered": info["n_replicas"] == 2,
            "8 requests counted": info["totals"]["seen"] == 8,
            "totals match /metrics": int(fleet.rendezvous.aggregator.total(
                _SEEN)) == info["totals"]["seen"],
            "report mentions fleet": "fleet:" in report,
        }
    finally:
        fleet.stop()
    _hot_path_selftest(checks)
    _sar_serving_selftest(checks)
    failed = [name for name, ok in checks.items() if not ok]
    if failed:
        print(f"selftest FAILED: {failed}", file=sys.stderr)
        return 1
    print(f"selftest OK ({len(checks)} checks)")
    return 0


# --------------------------------------------------------------------- #
# --history: retrospective incident table from timeline segments        #
# --------------------------------------------------------------------- #

_ALERT_STATE = "mmlspark_tpu_timeline_alert_state_count"
_DUMP_TS = "mmlspark_tpu_timeline_dump_timestamp_seconds"
_STATE_NAMES = {0: "ok", 1: "pending", 2: "firing"}


def _history_scalar(v) -> float:
    if isinstance(v, dict):
        return float(v.get("count", 0.0))
    return float(v)


def diagnose_history(seg_dir: str, window_s: float = 60.0) -> str:
    """Reconstruct an incident from a timeline segment directory alone —
    no live process, no scrape. Prints the segment inventory, every
    alert edge the recorded alert-state series contains, the
    flight-recorder dump timestamps, and a table of the breaching
    series around the newest firing edge. Output is a pure function of
    the segment bytes (times are printed relative to the first sample),
    so two identical directories render byte-identical reports."""
    from mmlspark_tpu.observability.timeline import TimelineStore

    store = TimelineStore(seg_dir)
    segs = store.segments()
    out = [f"== timeline history: {os.path.basename(os.path.normpath(seg_dir))} =="]
    if not segs:
        out.append("  (no segment files)")
        return "\n".join(out)
    t0 = min((s["t_first"] for s in segs if s["intact"]
              and s["t_first"] is not None), default=0.0)

    def rel(t: "float | None") -> str:
        return "-" if t is None else f"{t - t0:+.1f}s"

    rows = [[f"{s['seq']:d}", str(s["samples"]),
             rel(s["t_first"]), rel(s["t_last"]),
             "ok" if s["intact"] else "CORRUPT"] for s in segs]
    out.append(_render_table(rows, ["seg", "samples", "first", "last",
                                    "integrity"]))
    # alert edges: every labelset of the recorded alert-state series
    alert_series = store.series(_ALERT_STATE)
    edges = []       # (t_edge, rule, severity, series, final_state)
    for lbl_json, pts in sorted(alert_series.items()):
        lbl = json.loads(lbl_json or "{}")
        prev = 0.0
        edge_t = None
        for t, v in pts:
            v = _history_scalar(v)
            if v >= 2.0 > prev:
                edge_t = t
            prev = v
        final = _STATE_NAMES.get(int(prev), str(prev))
        edges.append((edge_t, lbl.get("rule", "?"),
                      lbl.get("severity", "?"), lbl.get("series", "?"),
                      final))
    out.append("")
    if not edges:
        out.append("  (no alert-state series recorded)")
        return "\n".join(out)
    rows = [[rule, sev, series, final, rel(t)]
            for t, rule, sev, series, final in edges]
    out.append(_render_table(rows, ["rule", "severity", "series",
                                    "state", "firing_edge"]))
    # flight-recorder dumps, as recorded into the segments
    dump_pts = [(t, _history_scalar(v))
                for pts in store.series(_DUMP_TS).values()
                for t, v in pts if _history_scalar(v) > 0]
    dump_ts = sorted({v for _t, v in dump_pts})
    out.append("")
    if dump_ts:
        out.append("  dumps triggered at: "
                   + ", ".join(rel(v) for v in dump_ts))
    else:
        out.append("  dumps triggered at: (none recorded)")
    # the incident table: breaching series around the newest firing edge
    fired = [(t, rule, series) for t, rule, _sev, series, _f in edges
             if t is not None]
    if not fired:
        return "\n".join(out)
    edge_t, rule, breaching = max(fired)
    out.append("")
    out.append(f"== incident: {rule} (series {breaching}) "
               f"fired {rel(edge_t)} ==")
    series_pts = []
    for pts in store.series(breaching, since=edge_t - window_s,
                            until=edge_t + window_s).values():
        series_pts.extend((t, _history_scalar(v)) for t, v in pts)
    series_pts.sort()
    state_pts = []
    for lbl_json, pts in alert_series.items():
        if json.loads(lbl_json or "{}").get("rule") == rule:
            state_pts.extend((t, _history_scalar(v)) for t, v in pts)
    state_pts.sort()

    def state_at(t: float) -> str:
        cur = 0.0
        for ts, v in state_pts:
            if ts > t:
                break
            cur = v
        return _STATE_NAMES.get(int(cur), str(cur))

    rows = [[rel(t), _fmt(v, 3), state_at(t),
             "<-- edge" if t >= edge_t and (i == 0 or
                                            series_pts[i - 1][0] < edge_t)
             else ""]
            for i, (t, v) in enumerate(series_pts)]
    out.append(_render_table(rows, ["t", breaching, "alert", ""]))
    return "\n".join(out)


def history_selftest() -> int:
    """Synthetic 3-segment incident, asserted end to end: a gauge spike
    drives an AlertEngine rule through pending into firing on a
    FakeClock, the firing edge triggers a flight-recorder dump, and the
    retrospective table rebuilt from the segment files alone names the
    breaching series, the alert edge, and the dump timestamp —
    byte-identically across two independent runs."""
    import shutil
    import tempfile

    from mmlspark_tpu.observability.metrics import MetricsRegistry
    from mmlspark_tpu.observability.recorder import FlightRecorder
    from mmlspark_tpu.observability.timeline import (
        AlertEngine, AlertRule, TimelineRecorder, TimelineStore)
    from mmlspark_tpu.resilience.policy import FakeClock

    def run_once(root: str) -> "tuple[str, list[str]]":
        seg_dir = os.path.join(root, "segments")
        dump_dir = os.path.join(root, "dumps")
        clk = FakeClock()
        reg = MetricsRegistry()
        g = reg.gauge("mmlspark_tpu_serving_queue_depth", "t")
        store = TimelineStore(seg_dir, keep=8, segment_samples=6)
        fr = FlightRecorder(dump_dir=dump_dir, clock=clk, registry=reg,
                            process="selftest")
        engine = AlertEngine(store, [AlertRule(
            "queue_hot",
            "avg_over(mmlspark_tpu_serving_queue_depth[6s]) > 50",
            for_s=4.0, severity="page", dump=True)],
            clock=clk, recorder=fr)
        rec = TimelineRecorder(store, reg, clock=clk, alerts=engine)
        for i in range(16):
            g.set(3.0 if i < 8 else 100.0)
            rec.sample()
            clk.sleep(2.0)
        dumps = sorted(os.listdir(dump_dir)) if os.path.isdir(dump_dir) \
            else []
        n_segs = len([f for f in os.listdir(seg_dir)
                      if f.startswith("seg-")])
        return diagnose_history(seg_dir), dumps, n_segs

    root = tempfile.mkdtemp(prefix="mml_history_selftest_")
    try:
        report_a, dumps_a, segs_a = run_once(os.path.join(root, "a"))
        report_b, _dumps_b, _segs_b = run_once(os.path.join(root, "b"))
        checks = {
            "3 segments on disk": segs_a == 3,
            "breaching series named":
                "mmlspark_tpu_serving_queue_depth" in report_a,
            "alert edge found": "firing" in report_a
                                and "<-- edge" in report_a,
            "rule named": "queue_hot" in report_a,
            "dump landed on disk": len(dumps_a) == 1,
            "dump timestamp recorded":
                "dumps triggered at: +" in report_a,
            "byte-stable across runs": report_a == report_b,
        }
        failed = [name for name, ok in checks.items() if not ok]
        if failed:
            print(report_a)
            print(f"history selftest FAILED: {failed}", file=sys.stderr)
            return 1
        print(f"history selftest OK ({len(checks)} checks)")
        return 0
    finally:
        shutil.rmtree(root, ignore_errors=True)


# --------------------------------------------------------------------- #
# --watch: refreshing one-screen live dashboard                         #
# --------------------------------------------------------------------- #

def diagnose_watch(url: str, interval_s: float = 2.0,
                   iterations: "int | None" = None) -> int:
    """Refreshing one-screen dashboard off repeated scrapes: clears the
    terminal, reprints the fleet table, and shows the request rate
    measured BETWEEN scrapes (the live delta a single snapshot cannot
    show). Ctrl-C stops; `iterations` bounds the loop for tests."""
    import time as _time

    if not url.rstrip("/").endswith("/metrics"):
        url = url.rstrip("/") + "/metrics"
    n = 0
    prev_seen: "float | None" = None
    prev_t: "float | None" = None
    try:
        while iterations is None or n < iterations:
            text = _fetch(url)
            now = _time.monotonic()
            reader = SeriesReader(_snapshot_of_text(text))
            seen = reader.counter(_SEEN)
            rate = ""
            if prev_seen is not None and now > prev_t:
                rate = (f"  rate {((seen - prev_seen) / (now - prev_t)):.1f}"
                        " req/s")
            prev_seen, prev_t = seen, now
            n += 1
            body = diagnose_text(text)
            sys.stdout.write("\x1b[2J\x1b[H"
                             f"watch #{n}  {url}{rate}  (Ctrl-C stops)\n\n"
                             + body + "\n")
            sys.stdout.flush()
            if iterations is not None and n >= iterations:
                break
            _time.sleep(interval_s)
    except KeyboardInterrupt:
        pass
    return 0


def _snapshot_of_text(text: str) -> dict:
    """Fleet-merged snapshot from one exposition text (the --watch
    reader path: merge policies applied exactly as the aggregator
    would)."""
    agg = MetricsAggregator()
    agg.push("watch", text)
    return agg.snapshot()


def main(argv: "list[str] | None" = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    g = ap.add_mutually_exclusive_group()
    g.add_argument("--rendezvous", help="FleetRendezvous base URL")
    g.add_argument("--urls", nargs="+", help="replica /metrics URLs")
    g.add_argument("--gateway", help="ServingGateway base URL")
    g.add_argument("--serving", help="ServingServer base URL (hot-path "
                                     "snapshot)")
    # outside the group: `--postmortem --selftest` is the CI smoke for
    # the postmortem path, `--postmortem DIR` the incident report
    ap.add_argument("--postmortem", nargs="?", const="", metavar="DIR",
                    help="merge the flight-recorder dumps under DIR into "
                         "one incident timeline")
    ap.add_argument("--streaming", nargs="?", const="", metavar="DIR",
                    help="partition table for a streaming checkpoint "
                         "directory (with --selftest: run a real P=2 "
                         "query and assert the snapshot)")
    ap.add_argument("--perf", nargs="?", const="", metavar="TARGET",
                    help="phase-attribution table for a live server URL "
                         "or a MULTICHIP_*.json artifact (with "
                         "--selftest: armed resident server + 15% "
                         "phase-coverage assertion)")
    ap.add_argument("--checkpoints", nargs="?", const="", metavar="DIR",
                    help="lineage/integrity table for a training "
                         "checkpoint directory (with --selftest: real "
                         "store + checkpointed fit + corruption "
                         "fallback assertions)")
    ap.add_argument("--sweep", nargs="?", const="", metavar="DIR",
                    help="trial ledger table for an AutoML sweep "
                         "checkpoint directory (with --selftest: build "
                         "a known ledger and assert every table state)")
    ap.add_argument("--training", nargs="?", const="", metavar="DIR",
                    help="elastic training live table (world epoch, "
                         "members, step lag, re-shard causes) for a "
                         "training checkpoint directory (with "
                         "--selftest: real in-process elastic fit with "
                         "a kill + a join, then assert the table)")
    ap.add_argument("--history", nargs="?", const="", metavar="DIR",
                    help="retrospective incident table from a telemetry "
                         "timeline segment directory — alert edges, "
                         "breaching series, dump timestamps — no live "
                         "process needed (with --selftest: synthetic "
                         "3-segment incident asserted end to end)")
    ap.add_argument("--watch", metavar="URL",
                    help="refreshing one-screen live dashboard off "
                         "repeated scrapes of a /metrics URL "
                         "(Ctrl-C stops)")
    ap.add_argument("--interval", type=float, default=2.0,
                    help="--watch refresh cadence in seconds")
    ap.add_argument("--selftest", action="store_true",
                    help="run a 2-replica fleet and diagnose it (with "
                         "--postmortem/--streaming: the matching "
                         "selftest)")
    ap.add_argument("--tail", type=int, default=200,
                    help="timeline events shown by --postmortem DIR")
    args = ap.parse_args(argv)
    modes = [args.rendezvous, args.urls, args.gateway, args.serving,
             args.postmortem, args.streaming, args.perf, args.checkpoints,
             args.sweep, args.training, args.history, args.watch,
             args.selftest or None]
    if not any(m for m in modes):
        ap.error("pick a mode: --rendezvous/--urls/--gateway/--serving/"
                 "--postmortem/--streaming/--perf/--checkpoints/"
                 "--sweep/--training/--history/--watch/--selftest")
    if args.history is not None:
        if args.selftest:
            return history_selftest()
        if not args.history:
            ap.error("--history needs a timeline segment directory "
                     "(or --selftest)")
        print(diagnose_history(args.history))
        return 0
    if args.watch:
        return diagnose_watch(args.watch, interval_s=args.interval)
    if args.training is not None:
        if args.selftest:
            return training_selftest()
        if not args.training:
            ap.error("--training needs a training checkpoint directory "
                     "(or --selftest)")
        print(diagnose_training(args.training))
        return 0
    if args.sweep is not None:
        if args.selftest:
            return sweep_selftest()
        if not args.sweep:
            ap.error("--sweep needs a sweep checkpoint directory "
                     "(or --selftest)")
        print(diagnose_sweep(args.sweep))
        return 0
    if args.checkpoints is not None:
        if args.selftest:
            return checkpoints_selftest()
        if not args.checkpoints:
            ap.error("--checkpoints needs a checkpoint directory "
                     "(or --selftest)")
        print(diagnose_checkpoints(args.checkpoints))
        return 0
    if args.perf is not None:
        if args.selftest:
            return perf_selftest()
        if not args.perf:
            ap.error("--perf needs a server URL or MULTICHIP_*.json "
                     "path (or --selftest)")
        print(diagnose_perf(args.perf))
        return 0
    if args.streaming is not None:
        if args.selftest:
            return streaming_selftest()
        if not args.streaming:
            ap.error("--streaming needs a checkpoint directory "
                     "(or --selftest)")
        print(diagnose_streaming(args.streaming))
        return 0
    if args.postmortem is not None:
        if args.selftest:
            return postmortem_selftest()
        if not args.postmortem:
            ap.error("--postmortem needs a dump directory "
                     "(or --selftest)")
        print(postmortem(args.postmortem, tail=args.tail))
        return 0
    if args.selftest:
        return selftest()
    if args.rendezvous:
        print(diagnose_rendezvous(args.rendezvous))
    elif args.gateway:
        print(diagnose_gateway(args.gateway))
    elif args.serving:
        print(diagnose_serving(args.serving))
    else:
        print(diagnose_urls(args.urls))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
