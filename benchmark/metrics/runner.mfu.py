"""The operations scoring the cell's table needs (the configuration's
reference module, `operations`, from shapes alone: padding rows count for
nothing) over the device's busy time in a traced call, as a share of the
chip's bfloat16 peak, %."""
from harness import data
from harness.cells import load_module
from harness.readers import busy_seconds_per_traced_call


def read(run):
    busy = busy_seconds_per_traced_call(run)
    if not busy:
        return None
    cell = run["cell"]
    need = load_module("reference", cell.config["reference"]).operations(
        cell.config, data.length_groups(int(cell.traffic["rows"]),
                                        cell.traffic["lengths"]))
    return 100.0 * need["ops"] / busy / run["peaks"]["flops_per_s"]
