"""Arithmetic the per-layer metric files share. `run` is the dict run.py
hands to every reader: cell, calls, elapsed, work_per_call, trace, peaks,
annotation."""

from __future__ import annotations

import statistics


def good_calls(run) -> list:
    return [c for c in run["calls"] if c.error is None]


def median_call_seconds(run):
    calls = good_calls(run)
    return statistics.median(c.seconds for c in calls) if calls else None


def busy_seconds_per_traced_call(run):
    """Device busy time of one traced call: the traced window holds whole
    traced calls and nothing else (`trace_calls` of them). The device's
    work in a call is the same with the profiler on, the host's is not, so
    a call's length is always taken from the untraced window."""
    trace = run["trace"]
    if trace is None or not trace.device_ops:
        return None
    return trace.busy_seconds() / int(run["cell"].traffic["trace_calls"])


def median_host_seconds(run):
    """An untraced call's length minus the device's busy time in a call."""
    busy, call = busy_seconds_per_traced_call(run), median_call_seconds(run)
    return None if busy is None or call is None else call - busy
