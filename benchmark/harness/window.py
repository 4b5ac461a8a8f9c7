"""The timed window: whole calls, back to back.

A call starts only while the time already elapsed plus the longest call
seen so far still fits into `seconds`; the first call always starts. The
window closes at the end of the last call and every rate divides by the
time elapsed to that point, so no call is cut or thrown away, a run with
one call and a run with two read the same rate, and a run lasts at most
one call's jitter longer than `seconds` (a whole call longer only where a
single call is longer than `seconds`)."""

from __future__ import annotations

import time
import traceback
from dataclasses import dataclass


@dataclass
class Call:
    start: float            # seconds from the window's opening
    end: float
    out: object = None      # what the adapter's call returned
    error: str | None = None

    @property
    def seconds(self) -> float:
        return self.end - self.start


def run_window(call, seconds: float,
               clock=time.perf_counter) -> "tuple[list[Call], float]":
    """-> (calls, elapsed seconds). `call(i)` makes the i-th call and
    returns its record; an exception marks the call failed and the window
    goes on."""
    t0 = clock()
    calls: list[Call] = []
    longest = 0.0
    while True:
        start = clock() - t0
        out, error = None, None
        try:
            out = call(len(calls))
        except Exception:                      # a failed call is counted
            error = traceback.format_exc()
        end = clock() - t0
        calls.append(Call(start, end, out, error))
        longest = max(longest, end - start)
        if (clock() - t0) + longest > seconds:
            return calls, calls[-1].end


def rate(calls, elapsed: float, work_per_call: float) -> float:
    done = sum(1 for c in calls if c.error is None)
    return done * work_per_call / elapsed
