"""Seconds of a traced call in which the device ran nothing while the host
was inside a `sar.readback` span (device trace): what dispatching the next
block before reading this one back would hide."""
from harness.program_spans import idle_seconds_inside


def read(run):
    idle = idle_seconds_inside(run["trace"], ("sar.readback",))
    if idle is None:
        return None
    return idle / int(run["cell"].traffic["trace_calls"])
