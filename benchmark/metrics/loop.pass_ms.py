"""What one pass of a batch through one layer of a looped stack costs the
device: its busy time in a traced call over the layer passes a call runs
(batches x layers x steps), as `runner.transform` writes them on its root
span from the counters it read back with every batch (`loop_layer_passes`;
summed over a call's tables, median over the window's untraced calls,
tracer's ring), in ms. The embedding, the exit gate and the head are in
the busy time and in no pass: a stack run four times costs four times its
passes, and this reads whether a pass got dearer. A program whose stack
is not looped writes no such count, and nothing is read."""
import statistics

from harness.cells import load_module
from harness.readers import busy_seconds_per_traced_call

PASSES = "loop_layer_passes"


def read(run):
    busy = busy_seconds_per_traced_call(run)
    calls = load_module("metrics", "moe_expert_roofline").root_args(run)
    passes = [sum(args[PASSES] for args in call) for call in calls or ()
              if call and all(PASSES in args for args in call)]
    a_call = statistics.median(passes) if passes else 0
    return 1e3 * busy / a_call if busy and a_call else None
