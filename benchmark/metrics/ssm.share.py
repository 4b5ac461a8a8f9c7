"""Of the device's busy time in the traced window, the state-space mixers
whole: the Pallas calls `ssd_scan_<i>` by name, and every operation that
reads or writes an array with one of the mixer's own extents, told by the
shapes in the instruction's text as `short_conv.share` tells its layer: the
input projection's width (z, x, B, C and dt side by side: the projection,
the convolution's taps and the step's softplus, which read that array) and
the inner width (z, the scan's output, the gate and its norm, the output
projection's input), %. An extent that another of the model's stated widths
equals is left out (the convolution's own output is as wide as the hidden
size in the published model, so what only touches that array is not in
it). `ssm.share` less `ssd.share` is the mixer around its scan."""
import re

from harness.cells import load_module

SHAPE = re.compile(r"\[([\d,]+)\]")


def own_extents(model: dict) -> set:
    """The mixer's extents no other stated width of `model` equals."""
    heads, groups = int(model["ssm_heads"]), int(model["ssm_groups"])
    inner = heads * int(model["ssm_head_dim"])
    projection = 2 * inner + 2 * groups * int(model["ssm_state"]) + heads
    head = int(model["head_dim"])
    stated = {model["d_model"], model["d_ff_dense"], model["vocab_size"],
              int(model["num_heads"]) * head,
              int(model["num_kv_heads"]) * head}
    return {str(e) for e in {projection, inner} - {int(w) for w in stated}}


def read(run):
    trace = run["trace"]
    model = run["cell"].config.get("model") or {}
    if trace is None or "ssm_heads" not in model:
        return None
    extents = own_extents(model)
    is_scan = load_module("metrics", "ssd_scan_roofline").is_state_space_scan

    def of_the_mixer(name: str) -> bool:
        return is_scan(name) or any(
            extents & set(dims.split(",")) for dims in SHAPE.findall(name))

    taken = sum(s for _c, s in trace.op_seconds(select=of_the_mixer).values())
    busy = trace.busy_seconds() * max(len(trace.device_ops), 1)
    return 100.0 * taken / busy if taken and busy else None
