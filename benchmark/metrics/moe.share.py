"""Of the device's busy time in the traced window, the expert layers'
routing, dispatch, grouped products and combine, %.

XLA gives the layer's fusions its generic names, so an operation is told
by the shapes in its instruction's text. The grouped products are the
`ragged-dot*` custom calls; their results' first extent is the dispatch
buffer's rows. An operation belongs to the layer if it is one of them, or
reads or writes an array with that many rows (the gather into expert
order, the activation, the weighing), or one with a batch's picks as an
extent (tokens of a batch x `num_experts_per_tok`: the sorts, the places,
the weighted gather back), or is shaped (k, tokens, ...), (tokens, k) or
(tokens, `n_routed_experts`) (the sum over a token's picks, the top-k, the
router's scores). The router's product carries the tokens' hidden states
as its other operand and is counted; the shared feed-forward, which every
token takes, is not part of this share."""
import re

from harness.cells import load_module
from harness.data import length_groups

SHAPE = re.compile(r"\[([\d,]+)\]")


def _shapes(name: str) -> list:
    return [tuple(int(d) for d in dims.split(","))
            for dims in SHAPE.findall(name)]


def layer_seconds(run) -> float:
    """Seconds, summed over the chips, of the expert layers' operations
    in the traced window; 0.0 where the trace shows no grouped product."""
    trace, cell = run["trace"], run["cell"]
    grouped = load_module("metrics",
                          "moe_expert_roofline").is_grouped_product
    rows = {_shapes(name.split(" = ", 1)[1])[0][0]
            for name in trace.op_seconds(select=grouped)}
    if not rows:
        return 0.0
    model = cell.config["model"]
    k, routed = int(model["num_experts_per_tok"]), int(
        model["n_routed_experts"])
    batch = int(cell.traffic["mini_batch_size"])
    tokens = {batch * length for length, _n in length_groups(
        int(cell.traffic["rows"]), cell.traffic["lengths"])}
    extents = rows | {t * k for t in tokens}
    whole = {(t, k) for t in tokens} | {(t, routed) for t in tokens}

    def of_the_layer(name: str) -> bool:
        if grouped(name):
            return True
        for shape in _shapes(name):
            if extents.intersection(shape) or shape in whole or (
                    shape[0] == k and len(shape) > 1 and shape[1] in tokens):
                return True
        return False

    return sum(s for _c, s in trace.op_seconds(select=of_the_layer).values())


def read(run):
    trace = run["trace"]
    if trace is None:
        return None
    busy = trace.busy_seconds() * max(len(trace.device_ops), 1)
    taken = layer_seconds(run)
    return 100.0 * taken / busy if taken and busy else None
