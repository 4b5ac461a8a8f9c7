"""Of the device's idle time inside the benchmark's `recommend.call`
spans, the share that falls inside one of the program's four phase spans
(device trace), %. The rest is `sar.recommend_all`'s self time and what
the adapter does around the call. Better LOWER since PR 25: with a block
of look-ahead the phases run under the device's work (30 to 72% read
then, 91% before), so idle time inside a phase is look-ahead lost, and the
idle time that is left belongs to the self time and the adapter."""
from harness.program_spans import SAR_PHASES, idle_seconds_inside


def read(run):
    named = idle_seconds_inside(run["trace"], SAR_PHASES)
    idle = idle_seconds_inside(run["trace"], (run["annotation"],))
    return 100.0 * named / idle if named is not None and idle else None
