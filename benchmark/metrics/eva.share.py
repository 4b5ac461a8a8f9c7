"""Of the device's busy time in the traced window, the windowed-and-
summarised attention: the Pallas calls `eva_attn_*` (the attention) and
`eva_pool_*` (the pass that pools 16 keys and values to one), by name, %.
The projections, the rotary positions and the layout copies around them
are not in it."""
from harness.trace import is_pallas, short_name


def is_eva_call(name: str) -> bool:
    return is_pallas(name) and short_name(name).startswith(
        ("eva_attn_", "eva_pool_"))


def read(run):
    trace = run["trace"]
    if trace is None:
        return None
    taken = sum(s for _c, s in trace.op_seconds(select=is_eva_call).values())
    busy = trace.busy_seconds() * max(len(trace.device_ops), 1)
    return 100.0 * taken / busy if taken and busy else None
