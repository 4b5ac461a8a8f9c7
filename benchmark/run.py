#!/usr/bin/env python3
"""One run of one cell:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (inputs and weights from the seed, one warm-up call at the cell's own
shape) is timed from process start to the first timed call and reported as
`setup_s`. Then calls are made back to back (harness/window.py), then what
they produced is compared with the plain reference, each number beside its
limit (on standard output as they are read, and again as the last lines of
standard error and the last key, `checks`, of the result). The last line of
standard output is the result, one JSON object.
`--trace 0` reports the cell's end-to-end metrics; `--trace 1` makes a few
more whole calls under the profiler after the window and the comparison
(`trace_calls` in the traffic file) and reports the cell's per-layer
metrics, the device's busy time over those calls and the breakdown."""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)
sys.path.insert(1, ROOT)

from harness import cells, window  # noqa: E402


def trace_calls(adapter, directory: str, jax) -> None:
    """A few whole calls under the profiler (`trace_calls` in the traffic
    file), after the timed window, so that the profiler perturbs no timed
    call and no call is traced in part."""
    shutil.rmtree(directory, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0      # no per-Python-call events
    # the benchmark's annotations and JAX's own host events, which name the
    # idle gaps
    options.host_tracer_level = 1
    jax.profiler.start_trace(directory, profiler_options=options)
    try:
        for i in range(int(adapter.traffic["trace_calls"])):
            with jax.profiler.TraceAnnotation(adapter.annotation):
                adapter.call(i)
    finally:
        jax.profiler.stop_trace()


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    cell = cells.load_cell(args.workload)
    cells.place_caches()
    import mmlspark_tpu  # noqa: F401 — the system under test; absent -> exit 1
    import jax

    from harness import device as device_gate
    from harness.compiles import CompileCounter
    from harness.trace import Trace, newest_xplane

    devices = device_gate.require_devices(cell.chips)[: cell.chips]
    peaks = device_gate.peaks_for(devices[0])
    compiles = CompileCounter()
    adapter = cells.load_module("adapters", cell.traffic["adapter"]).Adapter(
        cell, args.seed, devices)
    adapter.setup()

    compiled_before = compiles.unserved
    setup_s = time.perf_counter() - T_START
    print(f"run: set-up {setup_s:.1f} s", file=sys.stderr, flush=True)

    def call(i):
        with jax.profiler.TraceAnnotation(adapter.annotation):
            return adapter.call(i)

    calls, elapsed = window.run_window(call, args.seconds)
    compiled_in_window = compiles.unserved - compiled_before
    print(f"run: window closed after {len(calls)} call(s), {elapsed:.1f} s",
          file=sys.stderr, flush=True)
    device = device_gate.describe(devices)   # the program's peak, before
    #                                          the reference touches the chip;
    #                                          the traced calls come last
    t_check = time.perf_counter()
    outs = [c.out for c in calls if c.error is None]
    checks = adapter.check(outs) if outs else []
    check_s = time.perf_counter() - t_check
    for name, value, limit in checks:
        print(f"check {name}: {value:.6g} (limit {limit:.6g})"
              f"{'' if value <= limit else '  <-- over'}", flush=True)
    failed = sum(1 for c in calls if c.error is not None)
    for c in calls:
        if c.error is not None:
            print(c.error, file=sys.stderr)
    correct = bool(outs) and failed == 0 and all(
        value <= limit and value == value for _n, value, limit in checks)

    trace = None
    if args.trace:
        directory = os.path.join(ROOT, ".bench_trace", cell.name)
        trace_calls(adapter, directory, jax)
        print("run: traced calls made", file=sys.stderr, flush=True)
        trace = Trace.from_file(newest_xplane(directory),
                                annotations=(adapter.annotation,))
        shutil.rmtree(directory, ignore_errors=True)
        print("run: trace read", file=sys.stderr, flush=True)

    run = {"cell": cell, "calls": calls, "elapsed": elapsed,
           "work_per_call": adapter.work_per_call, "setup_s": setup_s,
           "trace": trace, "peaks": peaks, "annotation": adapter.annotation}
    metrics = {}
    if args.trace:
        for entry in cell.per_layer:
            value = cells.load_module("metrics", entry["name"]).read(run)
            if value is not None:
                metrics[entry["name"]] = {"value": float(value),
                                          "unit": entry["unit"]}
        device["busy_s"] = trace.busy_seconds()
        device["window_s"] = trace.window_s
    else:
        rate = window.rate(calls, elapsed, adapter.work_per_call)
        for entry in cell.end_to_end:
            value = setup_s if entry["name"] == "setup_s" else rate
            metrics[entry["name"]] = {"value": float(value),
                                      "unit": entry["unit"]}
    result = {"correct": bool(correct), "attempted": len(calls),
              "failed": failed, "metrics": metrics, "device": device,
              "window_s": elapsed, "check_s": check_s,
              "calls_s": [round(c.seconds, 4) for c in calls],
              "compiles_in_window": compiled_in_window}
    if trace is not None:
        result["breakdown"] = trace.breakdown((adapter.annotation,))
    # each number compared beside its limit, once more: the last lines of
    # standard error and the last key of the result are what a record of a
    # run that was not correct keeps
    result["checks"] = {          # a NaN would not be JSON
        name: {"value": value if value == value else None, "limit": limit}
        for name, value, limit in checks}
    for name, value, limit in checks:
        print(f"check {name}: {value:.6g} (limit {limit:.6g})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
