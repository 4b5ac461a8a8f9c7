"""Of the rows the device scored in a call, the share that were padding:
1 - rows / padded summed over the call's `runner.step` spans (their
arguments), %, median over the window's untraced calls (tracer's ring). A
call of the adapter enters `runner.transform` once a length of the traffic
file, so the ring has to hold exactly that many root spans a call. The
fused one-dispatch path opens no `runner.*` span: nothing is read there."""
import statistics

from harness.data import length_groups
from harness.program_spans import window_args

ROOT = "runner.transform"


def read(run):
    traffic = run["cell"].traffic
    tables = len(length_groups(int(traffic["rows"]), traffic["lengths"]))
    calls = window_args(run, "runner.step", ROOT, tables)
    shares = []
    for steps in calls or ():
        padded = sum(args["padded"] for args in steps)
        if padded:
            shares.append(
                100.0 * (1.0 - sum(args["rows"] for args in steps) / padded))
    return statistics.median(shares) if shares else None
