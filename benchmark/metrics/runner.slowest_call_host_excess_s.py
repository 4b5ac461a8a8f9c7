"""Beside `runner.slowest_call_excess_s`, what lay OUTSIDE `runner.wait`:
the longest call's seconds outside its waits less the median call's
(tracer's ring), the raw difference. A stall in the host's own phases
reads the excess; one spent waiting for the device or the runtime reads 0;
a slow host phase that a shorter wait absorbed reads above the excess, and
a longest call whose host phases ran faster than the median's below 0."""
from harness.runner_spans import slowest_call_excess


def read(run):
    found = slowest_call_excess(run)
    return None if found is None else found[1]
