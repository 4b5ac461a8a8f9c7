"""Over the window's untraced calls, the longest call's `runner.transform`
seconds less the median call's (tracer's ring): not a median, the one
number that says how large the window's worst stall was."""
from harness.runner_spans import slowest_call_excess


def read(run):
    found = slowest_call_excess(run)
    return None if found is None else found[0]
