"""Of the device's busy time in the traced window, the gated short
convolutions: every operation that reads or writes an array with the
extent 3 x hidden (the input projection to [B, C, u], both gates and the
taps, which read that array; the output projection too where the compiler
fuses the output gate into its product, as it does on a v5e, and not where
it stays a plain product of the hidden width), told by the shapes in the
instruction's text, %."""
import re

SHAPE = re.compile(r"\[([\d,]+)\]")


def read(run):
    trace = run["trace"]
    hidden = run["cell"].config.get("hidden_size")
    if trace is None or not hidden:
        return None
    extent = str(3 * int(hidden))

    def has_the_projection(name: str) -> bool:
        return any(extent in dims.split(",") for dims in SHAPE.findall(name))

    taken = sum(s for _c, s in
                trace.op_seconds(select=has_the_projection).values())
    busy = trace.busy_seconds() * max(len(trace.device_ops), 1)
    return 100.0 * taken / busy if taken and busy else None
