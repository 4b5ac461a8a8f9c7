"""The operations a call's scoring product needs (harness/counts.py) over
the device's busy time in a traced call, as a share of the chip's bfloat16
peak, %. Busy time holds the masking and the top k too, so this reads
under the product's own share of the peak."""
from harness import counts
from harness.readers import busy_seconds_per_traced_call


def read(run):
    busy = busy_seconds_per_traced_call(run)
    if not busy:
        return None
    config = run["cell"].config
    need = counts.sar_scores(int(config["num_users"]),
                             int(config["num_items"]))
    return 100.0 * need["ops"] / busy / run["peaks"]["flops_per_s"]
