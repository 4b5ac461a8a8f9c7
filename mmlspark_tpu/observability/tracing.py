"""Span tracing: Dapper-style host spans exported as Chrome-trace JSONL.

`Tracer.start_span` is a context manager; nested spans pick up the
active span as parent through a contextvar, and cross-thread work
propagates explicitly (`parent=span` or `bind(span)` in the worker).
Completed spans land in a bounded ring buffer and export as
Chrome-trace/Perfetto events — one JSON object per line (JSONL), each a
complete `"ph": "X"` duration event, so `chrome://tracing`, Perfetto's
legacy-JSON importer, or a five-line script can load them
(`export_jsonl` / `load_jsonl`).

Distributed propagation: span ids are PROCESS-SEEDED (pid mixed into the
high bits of the id counter), so per-replica JSONL exports merge into one
fleet trace with no id collisions (`merge_jsonl`). `inject()` renders the
active span as a W3C `traceparent` header (clients attach it via
`current_traceparent()`); `extract()` parses an incoming header into a
remote parent span, so a server-side span joins the caller's trace —
the Dapper pattern end to end.

Device correlation: while a JAX profiler session is live, however it was
started (`jax.profiler.start_trace`, a capture from TensorBoard,
`utils/profiling.device_trace`), every host span ALSO enters a
`jax.profiler.TraceAnnotation`. The span then sits on its thread's line of
the `.xplane.pb`, in the trace's own nanoseconds, next to the device's
operations. The check is made at span entry and costs well under a
microsecond; no option or environment variable is read.

Spans inside `SARModel.recommend_for_all_users` (process-default tracer):
`sar.recommend_all`, one a call and parent of the rest (arguments `users`,
`items`, `k`, `block`, `blocks`, `remove_seen` and, set at the end,
`bytes_read_back` and `dispatched_ahead`: the blocks that were enqueued
while an earlier block had not been read back yet, `blocks - 1` when the
look-ahead engages), and one a block of `sar.slice` (`lo`, `hi`: making the
start row the jitted program cuts the block's rows at; the rows themselves
are cut on the device, inside that program), `sar.dispatch` (the jitted
top-k call until it returns its futures, and asking for their copy to the
host), `sar.wait` (`block_until_ready` on them) and `sar.readback` (`bytes`:
both copies into the call's 64-bit result arrays and the marking of the
ranks a user has no unseen item for).
The order interleaves: `sar.slice` and `sar.dispatch` of block b+1 come
before `sar.wait` and `sar.readback` of block b, so at most two blocks are
in flight and the last is drained after the loop.

Spans inside a streamed `DeepModelTransformer.transform` (process-default
tracer; `nn/runner.py`, `core/dataplane.py` `Prefetcher`): `runner.transform`,
one a call, opened at the entry where the stage streams by its own setting
(`fused_dispatch` false) and parent of the rest (arguments `rows`,
`batch_size`, `row_shape` and what the model family reports of the call); a
table that the fused path's budget sends to the loop gets its root there,
after the stacking, and the fused one-dispatch path opens no `runner.*` span.
Under the root: `runner.stack` (`bytes`: the column made one host array and
the jitted forward looked up), and a batch `runner.feed_wait` (`item`: the
loop blocked on the prefetcher's queue, the `get` alone; the first of a call
is the start gap, the thread's own start lying before it in the root's self
time, the others should be microseconds, and at depth 2 one more finds the
end mark),
`runner.prepare` (`item`, `rows`, `padded`, `bytes`: slice, pad and upload
of batch N+1 under the device's work on N, on the PREFETCHER'S thread with
the root handed over as parent (`Prefetcher(..., span=root, tracer=...)`),
so it is never parentless; at depth 0 it
nests in the `runner.feed_wait` on the calling thread) with `runner.upload`
(`bytes`: `jnp.asarray`, the host-to-device copy as the host sees it)
inside it, and `runner.step` (`rows`, `padded`), which holds
`runner.dispatch` (`cache`: `hit` / `miss`; the executable cache's lookup
and the jitted call until it returns its futures; on a miss the `jax.*`
spans hang under it) and then, of the batch BEFORE, `runner.wait` (`batch`:
`block_until_ready` on its parked outputs, where the host should be in a
loop the device bounds) and `runner.readback` (`batch`, `bytes`: the copies
to the host and the slice of the padding). The last batch's wait and
readback hang under the root. On the calling thread `runner.stack`,
`runner.feed_wait`, `runner.dispatch`, `runner.wait` and `runner.readback`
never overlap, so with the root's self time they add up to the call. A
`Prefetcher` names its two spans after itself (`trainer.feed_wait` and
`trainer.prepare` under `trainer.epoch` in a streamed `DNNLearner.fit`,
which hands it the epoch's span) and records nothing when it is handed no
span, whatever is active around it.

Where a start, or a recompile, goes (end of this file): the package's
imports (`package.import`) and JAX's own trace, lowering and compile events
(`jax.trace`, `jax.lower`, `jax.compile`) are recorded as spans whose end
is already known (`Tracer.record_span`), each under the span that was
active on the thread that paid for it.

The disabled path is a no-op fast path: one attribute check, a shared
null context manager — no allocation, no locks, no contextvar writes.
"""

from __future__ import annotations

import contextvars
import itertools
import json
import os
import re
import threading
from .sanitizer import make_lock
import time
from collections import deque
from typing import Any

__all__ = ["Span", "Tracer", "get_tracer", "set_default_tracer",
           "load_jsonl", "merge_jsonl", "CHROME_EVENT_KEYS",
           "format_traceparent", "parse_traceparent",
           "current_traceparent", "PHASE_SPAN_PREFIX", "phase_children",
           "IMPORT_SPAN", "JAX_SPANS", "install_jax_bridge",
           "jax_compile_seconds", "record_import"]

# the profiler's phase child-spans are named `phase.<name>` under the
# dispatch/request span they decompose (observability.profiler)
PHASE_SPAN_PREFIX = "phase."

# the schema contract for exported events (load_jsonl verifies it)
CHROME_EVENT_KEYS = ("name", "cat", "ph", "ts", "dur", "pid", "tid")

# W3C Trace Context: version "00", 16-byte trace-id, 8-byte parent-id,
# flags — all lowercase hex, all-zero ids invalid
_TRACEPARENT_RE = re.compile(
    r"^([0-9a-f]{2})-([0-9a-f]{32})-([0-9a-f]{16})-([0-9a-f]{2})$")


def format_traceparent(trace_id: int, span_id: int) -> str:
    """Render ids as a W3C `traceparent` header value (sampled flag set)."""
    return f"00-{trace_id % (1 << 128):032x}-{span_id % (1 << 64):016x}-01"


def parse_traceparent(header: "str | None") -> "tuple[int, int] | None":
    """(trace_id, span_id) from a `traceparent` header; None when absent
    or malformed (a bad header must degrade to 'no trace', never error)."""
    if not header:
        return None
    m = _TRACEPARENT_RE.match(header.strip().lower())
    if m is None or m.group(1) == "ff":
        return None
    trace_id, span_id = int(m.group(2), 16), int(m.group(3), 16)
    if not trace_id or not span_id:
        return None
    return trace_id, span_id


class Span:
    """One timed region. `set(**args)` attaches arguments post-start
    (they export into the Chrome event's "args")."""

    __slots__ = ("name", "trace_id", "span_id", "parent_id", "parent",
                 "start_us", "dur_us", "args", "tid")

    def __init__(self, name: str, trace_id: int, span_id: int,
                 parent: "Span | None", start_us: float, args: dict):
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent = parent
        self.parent_id = parent.span_id if parent is not None else 0
        self.start_us = start_us
        self.dur_us = 0.0
        self.args = args
        self.tid = threading.get_ident()

    def set(self, **args: Any) -> None:
        self.args.update(args)

    def find_arg(self, key: str) -> Any:
        """Look up an argument on this span or the nearest ancestor that
        carries it (e.g. the batch id a streaming batch span stamped)."""
        node: "Span | None" = self
        while node is not None:
            if key in node.args:
                return node.args[key]
            node = node.parent
        return None


class _NullSpan:
    __slots__ = ()
    name = ""
    trace_id = 0
    span_id = 0
    parent_id = 0
    parent = None
    args: dict = {}

    def set(self, **args: Any) -> None:
        pass

    def find_arg(self, key: str) -> Any:
        return None


_NULL_SPAN = _NullSpan()


class _NullCtx:
    __slots__ = ()

    def __enter__(self):
        return _NULL_SPAN

    def __exit__(self, *exc) -> bool:
        return False


_NULL_CTX = _NullCtx()


def _no_session() -> bool:
    return False


_session_probe = None      # jaxlib's TraceMe.is_enabled, found at first use


def _profiler_session_live() -> bool:
    """Whether a JAX profiler session is recording right now. The import is
    lazy and fail-soft so the tracer stays dependency-free."""
    global _session_probe
    if _session_probe is None:
        try:
            from jax._src.lib import _profiler

            _session_probe = _profiler.TraceMe.is_enabled
        except Exception:
            _session_probe = _no_session
    return _session_probe()


def _device_annotation(name: str):
    """jax.profiler.TraceAnnotation, or None without JAX (lazy and
    fail-soft, as above)."""
    try:
        import jax

        return jax.profiler.TraceAnnotation(name)
    except Exception:
        return None


class _SpanCtx:
    __slots__ = ("_tracer", "_span", "_token", "_ann")

    def __init__(self, tracer: "Tracer", span: Span):
        self._tracer = tracer
        self._span = span
        self._token = None
        self._ann = None

    def __enter__(self) -> Span:
        self._token = self._tracer._current.set(self._span)
        annotate = self._tracer.annotate_device
        if annotate is None:
            annotate = _profiler_session_live()
        if annotate:
            self._ann = _device_annotation(self._span.name)
            if self._ann is not None:
                self._ann.__enter__()
        return self._span

    def __exit__(self, *exc) -> bool:
        if self._ann is not None:
            self._ann.__exit__(*exc)
        span = self._span
        span.dur_us = self._tracer._now_us() - span.start_us
        self._tracer._current.reset(self._token)
        self._tracer._record(span)
        return False


class Tracer:
    """Bounded-buffer span collector.

    clock            duck-typed `monotonic()` (resilience FakeClock fits);
                     span timestamps are microseconds on this clock
    max_spans        ring-buffer bound on retained completed spans
    annotate_device  also enter jax.profiler.TraceAnnotation per span.
                     None (the default): exactly while a profiler
                     session is live, decided at span entry, so host
                     spans appear in any device trace; True / False
                     override the check (tests)
    """

    def __init__(self, clock: Any = None, enabled: bool = True,
                 max_spans: int = 65536,
                 annotate_device: "bool | None" = None,
                 id_seed: "int | None" = None):
        self._clock = clock
        self.enabled = bool(enabled)
        self.annotate_device = (
            None if annotate_device is None else bool(annotate_device))
        self._spans: deque[Span] = deque(maxlen=int(max_spans))
        self._dropped = 0
        self._lock = make_lock("Tracer._lock")
        # Ids are PROCESS-SEEDED: the pid owns the top bits and random
        # bits scatter the counter base, so per-replica exports merge
        # into one fleet trace with no span-id collisions. Stays < 2^62
        # so span ids fit W3C traceparent's 8 bytes (and trace ids its
        # 16). id_seed=1 restores the legacy deterministic 1,2,3,...
        # numbering for tests that assert exact ids.
        if id_seed is None:
            rand = int.from_bytes(os.urandom(5), "big")  # 40 bits
            id_seed = ((os.getpid() & 0x3FFFFF) << 40) | rand | 1
        self._ids = itertools.count(int(id_seed))
        self._current: contextvars.ContextVar["Span | None"] = \
            contextvars.ContextVar(f"tracer_span_{id(self):x}",
                                   default=None)

    def _now_us(self) -> float:
        if self._clock is not None:
            return self._clock.monotonic() * 1e6
        return time.monotonic() * 1e6

    def _record(self, span: Span) -> None:
        with self._lock:
            if len(self._spans) == self._spans.maxlen:
                # the ring is about to evict its oldest span — count it,
                # so exports can say "N spans lost" instead of silently
                # truncating the incident's head
                self._dropped += 1
            self._spans.append(span)

    @property
    def drop_count(self) -> int:
        """Spans evicted from the ring since the last export (or clear) —
        the truncation an incident report must disclose."""
        return self._dropped

    # -- span API ------------------------------------------------------- #

    def start_span(self, name: str, parent: "Span | None" = None,
                   **args: Any):
        """Context manager yielding the Span. Parent resolution: explicit
        `parent=` (cross-thread propagation) beats the thread's active
        span. Disabled tracers return a shared null context: no locks, no
        allocation, no contextvar writes."""
        if not self.enabled:
            return _NULL_CTX
        if parent is None:
            parent = self._current.get()
        trace_id = parent.trace_id if parent is not None else next(self._ids)
        span = Span(name, trace_id, next(self._ids), parent,
                    self._now_us(), dict(args))
        return _SpanCtx(self, span)

    def record_span(self, name: str, start_us: float, dur_us: float,
                    parent: "Span | None" = None, **args: Any) -> "Span | None":
        """A span whose end is already known (JAX reports a trace, a
        lowering or a compile when it is over; an import is timed by two
        stamps) goes into the ring like any other: the same ids, the same
        parent rule as `start_span`. It was never active, so it enters no
        `TraceAnnotation`. Disabled tracers return at this check."""
        if not self.enabled:
            return None
        if parent is None:
            parent = self._current.get()
        trace_id = parent.trace_id if parent is not None else next(self._ids)
        span = Span(name, trace_id, next(self._ids), parent,
                    float(start_us), dict(args))
        span.dur_us = float(dur_us)
        self._record(span)
        return span

    def current_span(self) -> "Span | None":
        """The active span on this thread (None when outside any span)."""
        if not self.enabled:
            return None
        return self._current.get()

    def bind(self, span: "Span | None"):
        """Adopt `span` as the active parent on THIS thread — the worker
        half of cross-thread propagation (the submitting thread passes the
        span object, the worker binds it)."""
        if not self.enabled or span is None:
            return _NULL_CTX
        return _Bind(self, span)

    # -- distributed propagation ---------------------------------------- #

    def inject(self, span: "Span | None" = None) -> "str | None":
        """The active (or given) span as a `traceparent` header value;
        None when tracing is off or no span is active — callers skip the
        header rather than sending a broken one."""
        if not self.enabled:
            return None
        if span is None:
            span = self._current.get()
        if span is None or not getattr(span, "span_id", 0):
            return None
        return format_traceparent(span.trace_id, span.span_id)

    def extract(self, header: "str | None") -> "Span | None":
        """An incoming `traceparent` as a synthetic REMOTE parent span:
        pass it to `start_span(parent=...)` and the local span joins the
        caller's trace. The remote span is never recorded locally — the
        caller's own process exports it."""
        if not self.enabled:
            return None
        ids = parse_traceparent(header)
        if ids is None:
            return None
        trace_id, span_id = ids
        return Span("remote", trace_id, span_id, None, 0.0, {"remote": True})

    # -- export --------------------------------------------------------- #

    def spans(self) -> list[Span]:
        with self._lock:
            return list(self._spans)

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self._dropped = 0

    def chrome_events(self) -> list[dict]:
        """Completed spans as Chrome-trace duration events."""
        pid = os.getpid()
        out = []
        for s in self.spans():
            out.append({
                "name": s.name, "cat": "mmlspark_tpu", "ph": "X",
                "ts": s.start_us, "dur": s.dur_us,
                "pid": pid, "tid": s.tid,
                "args": {**s.args, "trace_id": s.trace_id,
                         "span_id": s.span_id, "parent_id": s.parent_id},
            })
        return out

    def export_jsonl(self, path: str) -> int:
        """Write one Chrome-trace event per line; returns the event count.
        Perfetto/chrome://tracing load the same events wrapped in a list —
        `json.dumps({"traceEvents": [json.loads(l) for l in open(p)]})`.

        When the ring evicted spans since the last export, the file leads
        with a synthetic zero-duration `tracer.spans_lost` event (schema-
        valid, args.count = N) so the truncation is stated in-band; the
        drop counter resets, scoping the disclosure to this export."""
        events = self.chrome_events()
        with self._lock:
            dropped, self._dropped = self._dropped, 0
        if dropped:
            first_ts = min((ev["ts"] for ev in events), default=0.0)
            events.insert(0, {
                "name": "tracer.spans_lost", "cat": "mmlspark_tpu",
                "ph": "X", "ts": first_ts, "dur": 0.0,
                "pid": os.getpid(), "tid": 0,
                "args": {"count": dropped},
            })
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with open(path, "w", encoding="utf-8") as fh:
            for ev in events:
                fh.write(json.dumps(ev) + "\n")
        return len(events)

    @staticmethod
    def merge_jsonl(paths: "list[str]", out_path: str) -> int:
        """Merge per-replica JSONL exports into one fleet trace file:
        each input is schema-validated (`load_jsonl`), events are sorted
        by timestamp, and the result is written as JSONL. Process-seeded
        ids keep cross-file span ids collision-free, so a client span in
        one file parents a server span in another purely through the
        propagated trace_id/parent_id args. Returns the event count."""
        events: list[dict] = []
        for p in paths:
            events.extend(load_jsonl(p))
        events.sort(key=lambda ev: ev["ts"])
        out_dir = os.path.dirname(os.path.abspath(out_path))
        os.makedirs(out_dir, exist_ok=True)
        with open(out_path, "w", encoding="utf-8") as fh:
            for ev in events:
                fh.write(json.dumps(ev) + "\n")
        return len(events)


class _Bind:
    __slots__ = ("_tracer", "_span", "_token")

    def __init__(self, tracer: Tracer, span: Span):
        self._tracer = tracer
        self._span = span
        self._token = None

    def __enter__(self) -> Span:
        self._token = self._tracer._current.set(self._span)
        return self._span

    def __exit__(self, *exc) -> bool:
        self._tracer._current.reset(self._token)
        return False


def load_jsonl(path: str) -> list[dict]:
    """Load an exported trace, verifying the Chrome-trace event schema
    (every line a JSON object with name/cat/ph/ts/dur/pid/tid)."""
    events = []
    with open(path, encoding="utf-8") as fh:
        for i, line in enumerate(fh):
            line = line.strip()
            if not line:
                continue
            ev = json.loads(line)
            missing = [k for k in CHROME_EVENT_KEYS if k not in ev]
            if missing:
                raise ValueError(
                    f"{path}:{i + 1}: event missing keys {missing}")
            if ev["ph"] != "X":
                raise ValueError(
                    f"{path}:{i + 1}: expected duration event, got "
                    f"ph={ev['ph']!r}")
            events.append(ev)
    return events


merge_jsonl = Tracer.merge_jsonl


def phase_children(events: "list[dict]",
                   parent_span_id: "int | None" = None) -> "dict[int, dict]":
    """Group the profiler's `phase.*` child events out of an exported
    Chrome-trace event list: {parent span_id: {phase name: dur_us}}.
    Pass `parent_span_id` to restrict to one dispatch/request span —
    what the Perfetto round-trip test and `diagnose.py --perf` use to
    re-read an attribution straight from a trace file."""
    out: dict[int, dict] = {}
    for ev in events:
        name = ev.get("name", "")
        if not name.startswith(PHASE_SPAN_PREFIX):
            continue
        args = ev.get("args", {})
        pid_ = args.get("parent_id", 0)
        if parent_span_id is not None and pid_ != parent_span_id:
            continue
        phases = out.setdefault(pid_, {})
        short = name[len(PHASE_SPAN_PREFIX):]
        phases[short] = phases.get(short, 0.0) + float(ev.get("dur", 0.0))
    return out


# --------------------------------------------------------------------- #
# process-default tracer                                                #
# --------------------------------------------------------------------- #

_DEFAULT: "Tracer | None" = None
_DEFAULT_LOCK = make_lock("tracing._DEFAULT_LOCK")


def get_tracer() -> Tracer:
    global _DEFAULT
    t = _DEFAULT
    if t is None:
        with _DEFAULT_LOCK:
            if _DEFAULT is None:
                _DEFAULT = Tracer()
            t = _DEFAULT
    return t


def set_default_tracer(tracer: "Tracer | None") -> "Tracer | None":
    """Swap the process-default tracer (tests); returns the previous one."""
    global _DEFAULT
    with _DEFAULT_LOCK:
        old, _DEFAULT = _DEFAULT, tracer
    return old


def current_traceparent() -> "str | None":
    """`traceparent` for the process-default tracer's active span — the
    one-liner HTTP clients call to propagate the trace downstream."""
    return get_tracer().inject()


# --------------------------------------------------------------------- #
# JAX's compile events and the package's imports, as spans              #
# --------------------------------------------------------------------- #

IMPORT_SPAN = "package.import"

# jax.monitoring's duration events (JAX 0.9.0, jax/_src/dispatch.py) and
# the span each one becomes
JAX_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.compile",
}
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"


class _ThreadCompiles(threading.local):
    seconds = 0.0         # the outermost events' durations, added up
    retrieval_s = None    # a cache read no `jax.compile` has taken yet

    def __init__(self):
        # one entry an event of JAX_SPANS begun and not ended on this
        # thread: the trace cache's misses as it began
        self.open: list = []


_compiles = _ThreadCompiles()


def _record_ended(name: str, seconds: float, args: dict) -> None:
    """A span of the process-default tracer that ends now and began
    `seconds` of wall time ago. A tracer on an injected clock (a test's
    fake one) takes none: seconds of the wall do not lie on its clock, and
    it is not read for them."""
    tracer = get_tracer()
    if tracer.enabled and tracer._clock is None:
        dur_us = seconds * 1e6
        tracer.record_span(name, tracer._now_us() - dur_us, dur_us, **args)


_bridge_installed = False
_trace_cache_info = None   # jax's `trace_to_jaxpr.cache_info`, at install


def _trace_misses() -> "int | None":
    return None if _trace_cache_info is None else _trace_cache_info().misses


def _on_jax_start(event: str, _value: float, **_kw: Any) -> None:
    if event in JAX_SPANS:
        _compiles.open.append(_trace_misses())


def _on_jax_duration(event: str, duration: float, **kw: Any) -> None:
    """Runs on the thread that traced, lowered or compiled, as that work
    ends: the span ends now on the tracer's clock and began `duration`
    earlier, under the thread's active span. A jit traced inside another
    reports inside the outer one's interval, so only an outermost event
    adds to the thread's running total. JAX 0.9.0 also reports a trace for
    every CALL of a jitted function inside a trace, `jnp.add` included,
    though the call finds its jaxpr in the cache (10 us): such a nested
    call traced nothing (the trace cache missed no more often than when it
    began) and leaves no span."""
    name = JAX_SPANS.get(event)
    state = _compiles
    if name is None:
        if event == _CACHE_RETRIEVAL:
            state.retrieval_s = duration
        return
    misses = state.open.pop() if state.open else None
    if not state.open:
        state.seconds += duration
    elif (name == "jax.trace" and misses is not None
          and misses == _trace_misses()):
        return
    args = {"fun_name": kw.get("fun_name", "")}
    if name == "jax.compile":
        retrieval, state.retrieval_s = state.retrieval_s, None
        args.update(cache_hit=retrieval is not None,
                    retrieval_s=retrieval or 0.0)
    _record_ended(name, duration, args)


def install_jax_bridge() -> bool:
    """Register the listener pair on `jax.monitoring`, once a process
    however often it is called; False without JAX (lazy and fail-soft, as
    `_device_annotation` is). From then on every trace, lowering and
    backend compile (or read of the persistent cache) is a `jax.trace`,
    `jax.lower` or `jax.compile` span of the process-default tracer, a
    child of the span that was active on the thread that paid for it. The
    listeners fire only when JAX traces, lowers or compiles: a warmed shape
    costs nothing."""
    global _bridge_installed
    if _bridge_installed:
        return True
    with _DEFAULT_LOCK:
        if not _bridge_installed:
            try:
                from jax import monitoring

                try:
                    from jax._src.interpreters.partial_eval import (
                        trace_to_jaxpr)

                    global _trace_cache_info
                    _trace_cache_info = trace_to_jaxpr.cache_info
                except Exception:        # every reported trace is a span
                    pass
                monitoring.register_scalar_listener(_on_jax_start)
                monitoring.register_event_duration_secs_listener(
                    _on_jax_duration)
            except Exception:
                return False
            _bridge_installed = True
    return True


def jax_compile_seconds() -> float:
    """Seconds the bridge has seen THIS thread trace, lower and compile,
    overlaps counted once: read it before and after a call to learn what
    that call paid (0.0 throughout without the bridge)."""
    return _compiles.seconds


def record_import(module: str, started: float) -> None:
    """A package's import as a `package.import` span: `started` is the
    `time.monotonic()` its `__init__` took on its first line, now is its
    last."""
    _record_ended(IMPORT_SPAN, time.monotonic() - started, {"module": module})
