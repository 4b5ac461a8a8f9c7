"""The device gate, the table of peaks and the memory reading."""

from __future__ import annotations

import os
import sys

# Published peaks per chip, keyed by `device_kind` as JAX reports it.
# Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s.
# (The profiler's own plane says 202.7 / 819.2 for the same part; the
# published figure is the yardstick.) A device not listed is an error.
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


def require_devices(chips: int):
    """The devices to measure on, or exit non-zero with no result: no TPU
    (unless JAX_PLATFORMS=cpu is explicit, the harness's own CPU tests) or
    fewer chips than the cell asks for."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    explicit_cpu = os.environ.get("JAX_PLATFORMS", "").strip() == "cpu"
    if platform != "tpu" and not explicit_cpu:
        print(f"benchmark: JAX found no TPU (platform={platform!r}); a "
              "measurement never falls back to the CPU", file=sys.stderr)
        raise SystemExit(3)
    if len(devices) < chips and not explicit_cpu:
        print(f"benchmark: the cell asks for {chips} chip(s), JAX sees "
              f"{len(devices)}", file=sys.stderr)
        raise SystemExit(3)
    return devices


def peaks_for(device) -> dict:
    if device.platform != "tpu":
        # the CPU tests of the harness: shares of a made-up peak are never
        # written anywhere as a device's
        return {"flops_per_s": 1e12, "bytes_per_s": 1e11, "source": "none"}
    if device.device_kind not in PEAKS:
        raise SystemExit(f"benchmark: no published peaks for device_kind "
                         f"{device.device_kind!r}; add it to PEAKS with its "
                         "source")
    return PEAKS[device.device_kind]


def memory_peak_bytes(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def describe(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": memory_peak_bytes(devices)}
