#!/usr/bin/env python3
"""Read the numbers `correct` is decided on, for sound runs and for the
control, over several seeds in one process:

    python3 benchmark/controls.py --workload <name> --seeds 1,2,3 [--control bfloat16,int8]

Per seed: the cell's set-up (inputs, weights, the warm-up call at the
cell's own shape), `--calls` timed calls, the comparison with the plain
reference (`sound`), and for each precision named by `--control` the
reference through that precision put in the program's place
(`control.<precision>`). The benchmark's own runs never run the control.
One JSON line per seed; limits come from these readings (PERF.md
section 2)."""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)
sys.path.insert(1, ROOT)

from harness import cells  # noqa: E402


def read_seed(cell, seed: int, devices, calls: int, control: list) -> dict:
    adapter = cells.load_module("adapters", cell.traffic["adapter"]).Adapter(
        cell, seed, devices)
    adapter.setup()
    done = [adapter.call(i) for i in range(calls)]
    if not done and adapter.warm_out is not None:
        done = [adapter.warm_out]
    out = {"seed": seed,
           "sound": {n: v for n, v, _l in adapter.check(done)}}
    for through in control:
        out[f"control.{through}"] = {
            n: v for n, v, _l in adapter.control(done, through)}
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--calls", type=int, default=0)
    parser.add_argument("--control", default="")
    args = parser.parse_args(argv)
    cell = cells.load_cell(args.workload)
    cells.place_caches()
    import mmlspark_tpu  # noqa: F401

    from harness import device as device_gate

    devices = device_gate.require_devices(cell.chips)[: cell.chips]
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(read_seed(cell, seed, devices, args.calls,
                                   [p for p in args.control.split(",") if p])),
              flush=True)
        gc.collect()     # the seed's device state goes before the next's
    return 0


if __name__ == "__main__":
    sys.exit(main())
