"""Kernel registry: resolve compute kernels per platform.

Reference analogue: `NativeLoader` (src/core/env/src/main/scala/
NativeLoader.java:47-105) picks the right native `.so` for the executing
platform and loads it before any native call; here the same role is played
by a registry that resolves a kernel NAME to the best implementation for
the active JAX backend — a hand-written Pallas TPU kernel on `tpu`, the
Pallas interpreter (for kernel-path testing) when forced, and a pure-XLA
composition everywhere else.

Resolution order for `resolve(name)`:
  1. `MMLSPARK_TPU_KERNELS` env var / `set_kernel_mode()`:
     "pallas" | "pallas_interpret" | "xla" | "xla_scatter" | "auto" (default)
  2. auto: "pallas" iff the default backend is a TPU and a pallas impl is
     registered; otherwise "xla_scatter" (XLA composition using native
     scatter — fast on CPU/GPU, pathological on TPU), else "xla" (the
     scatter-free composition that is safe everywhere).

Nothing here degrades silently: a backend that fails to initialise
propagates its error (a dead chip must not resolve as "not a TPU"), and a
mode whose variant is not registered raises instead of substituting
another implementation.
"""

from __future__ import annotations

import os
import threading
from typing import Callable

__all__ = ["register_kernel", "resolve", "set_kernel_mode", "kernel_mode",
           "registered_kernels"]

_REGISTRY: dict[str, dict[str, Callable]] = {}
_LOCK = threading.Lock()
_MODE_OVERRIDE: str | None = None

_VALID_MODES = ("auto", "pallas", "pallas_interpret", "xla", "xla_scatter")


def register_kernel(name: str, variant: str, fn: Callable) -> None:
    """variant: 'pallas' (compiled), 'pallas_interpret', 'xla', or
    'xla_scatter'."""
    if variant not in ("pallas", "pallas_interpret", "xla", "xla_scatter"):
        raise ValueError(f"unknown kernel variant {variant!r}")
    with _LOCK:
        _REGISTRY.setdefault(name, {})[variant] = fn


def registered_kernels() -> dict[str, tuple[str, ...]]:
    with _LOCK:
        return {k: tuple(v) for k, v in _REGISTRY.items()}


def set_kernel_mode(mode: str | None) -> None:
    """Process-wide override ('auto' / None resets to auto)."""
    global _MODE_OVERRIDE
    if mode is not None and mode not in _VALID_MODES:
        raise ValueError(f"kernel mode must be one of {_VALID_MODES}")
    _MODE_OVERRIDE = None if mode in (None, "auto") else mode


def kernel_mode() -> str:
    if _MODE_OVERRIDE:
        return _MODE_OVERRIDE
    env = os.environ.get("MMLSPARK_TPU_KERNELS", "").strip().lower()
    return env if env in _VALID_MODES else "auto"


def resolve(name: str) -> Callable:
    """Pick the implementation of `name` for the active mode/backend.
    Raises KeyError when the mode's variant is not registered and lets a
    backend-initialisation failure propagate."""
    with _LOCK:
        impls = dict(_REGISTRY.get(name, {}))
    if not impls:
        raise KeyError(f"no kernel registered under {name!r}")
    mode = kernel_mode()
    if mode == "auto":
        import jax

        on_tpu = jax.default_backend() == "tpu"
        if on_tpu and "pallas" in impls:
            mode = "pallas"
        elif not on_tpu and "xla_scatter" in impls:
            mode = "xla_scatter"
        else:
            mode = "xla"
    if mode not in impls:
        raise KeyError(
            f"kernel {name!r} has no {mode!r} variant "
            f"(registered: {sorted(impls)})")
    return impls[mode]
