"""Of the device's busy time in the traced window, the sliding-window
layers' attention: the Pallas calls `swa_attn_*` (the banded forward and,
for rows that fit the window, the sliding layers' plain causal calls), by
name, %. The projections, the rotary positions and the global layers'
attention (`gqa_attn_*`) are not in it."""
from harness.cells import load_module


def read(run):
    trace = run["trace"]
    if trace is None:
        return None
    taken = sum(s for _c, s in trace.op_seconds(select=load_module(
        "metrics", "swa_attn_roofline").is_window_attention).values())
    busy = trace.busy_seconds() * max(len(trace.device_ops), 1)
    return 100.0 * taken / busy if taken and busy else None
