"""Of the expert layers' device time (`moe.share`'s numerator), what is
NOT the grouped products of the experts: the router's scores and top-k,
the sorts of the picks, the gather into expert order, the activation and
the weighted gather back, %."""
from harness.cells import load_module


def read(run):
    trace = run["trace"]
    if trace is None:
        return None
    taken = load_module("metrics", "moe.share").layer_seconds(run)
    if not taken:
        return None
    products = sum(s for _c, s in trace.op_seconds(select=load_module(
        "metrics", "moe_expert_roofline").is_grouped_product).values())
    return 100.0 * (1.0 - products / taken)
