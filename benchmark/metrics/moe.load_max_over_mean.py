"""How unevenly the picks fell on the experts held: the busiest held
expert of a layer over that layer's mean, the largest over the layers, as
`runner.transform` writes it on its root span from the counts it read back
with every batch (`moe_load_max_over_mean`); the larger of a call's tables,
median over the window's untraced calls (tracer's ring). 1.0 is an even
load; the grouped products wait for the busiest expert's rows."""
import statistics

from harness.cells import load_module


def read(run):
    calls = load_module("metrics", "moe_expert_roofline").root_args(run)
    worst = [max(args["moe_load_max_over_mean"] for args in call)
             for call in calls or ()
             if call and all("moe_load_max_over_mean" in args
                             for args in call)]
    return statistics.median(worst) if worst else None
