"""What one step of the channel-decay scan's grid costs the device: the
measured time of the Pallas calls `sel_scan_<i>` in a traced call over the
steps a call scans (rows x channel blocks x chunks, over the Mamba layers
and the batches, padding included), as `runner.transform` writes them on
its root span from the shapes of the batches it scored (`sel_scan_steps`;
summed over a call's tables, median over the window's untraced calls,
tracer's ring), in us. A step is one chunk of 128 tokens of one block of
channels: 128 passes of the recurrence over the block's (state, channel)
tile. A program that scans nothing writes no such count, and nothing is
read."""
import statistics

from harness.cells import load_module

STEPS = "sel_scan_steps"


def read(run):
    taken = load_module("metrics", "sel_scan_roofline").scan_seconds(run)
    calls = load_module("metrics", "moe_expert_roofline").root_args(run)
    steps = [sum(args[STEPS] for args in call) for call in calls or ()
             if call and all(STEPS in args for args in call)]
    a_call = statistics.median(steps) if steps else 0
    if not taken or not a_call:
        return None
    return 1e6 * taken / int(run["cell"].traffic["trace_calls"]) / a_call
