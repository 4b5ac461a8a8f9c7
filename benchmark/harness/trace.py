"""Reduce a JAX profiler trace (`.xplane.pb`) to what the metrics read.

What a TPU trace holds (looked at by hand, PR 23): one plane per chip,
`/device:TPU:<n>`, whose line `XLA Ops` has one event per executed HLO
instruction, named by the instruction's whole text (`%fusion.5 = bf16[..]
fusion(...), kind=kOutput, calls=...`), with control flow (`while`,
`conditional`, `call`) as events that enclose their bodies' events; and
`/host:CPU`, whose line of the thread that made the calls holds the
benchmark's own `TraceAnnotation`s and JAX's host events. All times are
nanoseconds on one axis."""

from __future__ import annotations

import glob
import itertools
import os
import re
import sys
from dataclasses import dataclass

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
TRANSFER_IN = "tpu::System::TransferToDevice"
SHORT_GAP = 100e-6      # seconds
HEAD_EVENTS = 2000


@dataclass
class Event:
    name: str
    start: float        # seconds
    end: float

    @property
    def seconds(self) -> float:
        return self.end - self.start


def newest_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def union_seconds(intervals) -> float:
    """Total length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, t0: float, t1: float):
    """The (start, end) stretches of [t0, t1] no interval covers."""
    out, at = [], t0
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, t1)))
        at = max(at, e)
        if at >= t1:
            break
    if at < t1:
        out.append((at, t1))
    return [(s, e) for s, e in out if e > s]


def self_seconds(events) -> list:
    """[(event, seconds not covered by events nested inside it)] for
    properly nested events of one line."""
    out = []
    stack: list = []                      # [event, covered seconds]
    for ev in sorted(events, key=lambda e: (e.start, -e.end)):
        while stack and ev.start >= stack[-1][0].end:
            done, covered = stack.pop()
            out.append((done, max(done.seconds - covered, 0.0)))
        if stack:
            stack[-1][1] += min(ev.end, stack[-1][0].end) - ev.start
        stack.append([ev, 0.0])
    while stack:
        done, covered = stack.pop()
        out.append((done, max(done.seconds - covered, 0.0)))
    return out


def short_name(hlo_text: str) -> str:
    """`%fusion.5 = bf16[..] fusion(..)` -> `fusion.5`."""
    return hlo_text.split(" = ", 1)[0].lstrip("%").strip()


def is_pallas(name: str) -> bool:
    return 'custom_call_target="tpu_custom_call"' in name


def is_top_k(name: str) -> bool:
    return 'custom_call_target="TopK"' in name


def is_matmul_fusion(name: str) -> bool:
    """A fusion around a matrix product or convolution (XLA's TPU backend
    writes both as `convolution` and makes them `kOutput` fusions), or a
    bare one."""
    return "kind=kOutput" in name or " convolution(" in name


def transfers_to_device(host_plane) -> list:
    """[Event] per host-to-device transfer, from the runtime's call that
    issues it (on whichever thread uploads) to the event that reports it
    done (on the runtime's own worker): the two carry one flow id, `_p` on
    the issue and `_c` on the completion (host events: `run.py` traces at
    `host_tracer_level` 1)."""
    issued, done = {}, {}
    for line in host_plane.lines:
        for e in line.events:
            if e.name == TRANSFER_IN:
                issued[dict(e.stats).get("_p")] = e.start_ns * 1e-9
            elif e.name == TRANSFER_IN + "=>IssueEvent=>Done":
                done[dict(e.stats).get("_c")] = (
                    e.start_ns + e.duration_ns) * 1e-9
    return [Event(TRANSFER_IN, start, done[flow])
            for flow, start in issued.items()
            if flow is not None and done.get(flow, start) > start]


class Trace:
    """One traced window: device operations per chip, host events of the
    thread that carried the benchmark's annotations."""

    def __init__(self, device_ops: dict, host_events: list,
                 t0: float, t1: float, transfers_in=()):
        self.device_ops = device_ops          # plane name -> [Event]
        self.host_events = host_events        # [Event], one thread
        self.transfers_in = list(transfers_in)   # [Event], any thread
        self.t0, self.t1 = t0, t1
        self._own = None                      # [(event, self seconds)]

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    @staticmethod
    def from_file(path: str, annotations=()) -> "Trace":
        from jax.profiler import ProfileData

        data = ProfileData.from_file(path)
        device_ops, host_lines, transfers_in = {}, [], []
        for plane in data.planes:
            if DEVICE_PLANE.match(plane.name):
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        # an instruction's text is its name and runs to
                        # hundreds of bytes: keep one copy of each
                        device_ops[plane.name] = [
                            Event(sys.intern(e.name), e.start_ns * 1e-9,
                                  (e.start_ns + e.duration_ns) * 1e-9)
                            for e in line.events]
            elif plane.name == HOST_PLANE:
                transfers_in = transfers_to_device(plane)
                for line in plane.lines:
                    # a thread's line is sorted by start, and a traced call
                    # opens with its annotation: a line that shows none
                    # among its first events is another thread's (the
                    # runtime's worker threads log millions of events)
                    head = itertools.islice(line.events, HEAD_EVENTS)
                    if not any(e.name in annotations for e in head):
                        continue
                    host_lines.append(
                        [Event(sys.intern(e.name), e.start_ns * 1e-9,
                               (e.start_ns + e.duration_ns) * 1e-9)
                         for e in line.events])
        host = max(host_lines, key=len) if host_lines else []
        spans = [e for e in host if e.name in annotations]
        every = [e for ops in device_ops.values() for e in ops] + spans
        t0 = min((e.start for e in every), default=0.0)
        t1 = max((e.end for e in every), default=0.0)
        return Trace(device_ops, host, t0, t1, transfers_in)

    # -- device ------------------------------------------------------- #

    def busy_seconds(self, t0=None, t1=None) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        t0 = self.t0 if t0 is None else t0
        t1 = self.t1 if t1 is None else t1
        if not self.device_ops:
            return 0.0
        per_chip = [union_seconds(
            (max(e.start, t0), min(e.end, t1))
            for e in ops if e.end > t0 and e.start < t1)
            for ops in self.device_ops.values()]
        return sum(per_chip) / len(per_chip)

    def op_seconds(self, select=None) -> dict:
        """{instruction text: (count, self seconds)} over all chips."""
        if self._own is None:        # several metrics ask; reduce once
            self._own = [pair for ops in self.device_ops.values()
                         for pair in self_seconds(ops)]
        out: dict = {}
        for ev, own in self._own:
            if select is None or select(ev.name):
                c, s = out.get(ev.name, (0, 0.0))
                out[ev.name] = (c + 1, s + own)
        return out

    # -- host ---------------------------------------------------------- #

    def spans(self, name: str) -> list:
        return [e for e in self.host_events if e.name == name]

    def host_label(self, at: float, annotations, events=None) -> str:
        """What the host was doing at `at`: the benchmark's annotation
        around it and the innermost host event inside that."""
        outer, inner = None, None
        for e in self.host_events if events is None else events:
            if e.start <= at < e.end:
                if e.name in annotations:
                    outer = e
                elif inner is None or e.seconds < inner.seconds:
                    inner = e
        if outer is None:
            return "between_calls" if inner is None else inner.name
        if inner is None or inner.seconds >= outer.seconds:
            return outer.name
        return f"{outer.name}/{inner.name}"

    # -- the breakdown the ledger keeps --------------------------------- #

    def breakdown(self, annotations, top: int = 10) -> dict:
        ops = sorted(((short_name(n), s) for n, (_c, s)
                      in self.op_seconds().items()), key=lambda kv: -kv[1])
        first = next(iter(self.device_ops.values()), [])
        # a host event shorter than the gaps that get a name cannot explain
        # one; the many gaps between back-to-back operations go in one row
        host = [e for e in self.host_events if e.seconds >= SHORT_GAP]
        idle: dict = {}
        for s, e in gaps([(ev.start, ev.end) for ev in first],
                         self.t0, self.t1):
            label = (self.host_label((s + e) / 2, annotations, host)
                     if e - s >= SHORT_GAP else "gaps_under_100us")
            idle[label] = idle.get(label, 0.0) + (e - s)
        longest = sorted(idle.items(), key=lambda kv: -kv[1])
        return {"device_ops": [[n, s] for n, s in ops[:top]],
                "idle_gaps": [[n, s] for n, s in longest[:top]]}
