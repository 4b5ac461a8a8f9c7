"""What one step of the state-space scan's grid costs the device: the
measured time of the Pallas calls `ssd_scan_<i>` in a traced call over the
steps a call scans (rows x heads x chunks, over the layers and the
batches, padding included), as `runner.transform` writes them on its root
span from the shapes of the batches it scored (`ssd_steps`; summed over a
call's tables, median over the window's untraced calls, tracer's ring), in
us. A step is one chunk of 128 tokens of one head: four products
and the head's state carried on. A program that scans nothing writes no
such count, and nothing is read."""
import statistics

from harness.cells import load_module

STEPS = "ssd_steps"


def read(run):
    taken = load_module("metrics", "ssd_scan_roofline").scan_seconds(run)
    calls = load_module("metrics", "moe_expert_roofline").root_args(run)
    steps = [sum(args[STEPS] for args in call) for call in calls or ()
             if call and all(STEPS in args for args in call)]
    a_call = statistics.median(steps) if steps else 0
    if not taken or not a_call:
        return None
    return 1e6 * taken / int(run["cell"].traffic["trace_calls"]) / a_call
