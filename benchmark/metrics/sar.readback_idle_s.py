"""Seconds of a traced call in which the device ran nothing while the host
was inside a `sar.readback` span (device trace). Every block but the last
is read back with the next one queued on the device, so this is the last
block's readback: what the look-ahead has left to hide."""
from harness.program_spans import idle_seconds_inside


def read(run):
    idle = idle_seconds_inside(run["trace"], ("sar.readback",))
    if idle is None:
        return None
    return idle / int(run["cell"].traffic["trace_calls"])
