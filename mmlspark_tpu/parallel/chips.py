"""One process for each chip: the environment a spawned worker starts in.

A TPU chip belongs to one process at a time. A parent that has touched JAX
holds every chip of its host, and a child that needs one then fails or
hangs in backend start-up. Every spawner in the library (ServingFleet and
the sweep, streaming and elastic fleets built on it, GatewayTier) asks
`worker_env` what its child's environment must say before the child
exists, and applies it with `spawn_env` around `Process.start()`:

- a host-only worker (gateway proxy, the `np.bincount` elastic GBDT
  grower) is pinned to the CPU backend, so it can never open a chip;
- a worker that scores or trains on the device gets ONE chip of a
  multi-chip host, named by index;
- where that is impossible — the parent already holds the chips, or there
  are more device workers than chips — the spawner fails at once with a
  message that says so, instead of waiting out its start-up timeout.

Counting chips and asking "is a backend up?" without bringing one up has no
public JAX API. This module leans on two private ones, as they are in the
pinned jax 0.9.0: `jax._src.hardware_utils.num_available_tpu_chips_and_device_id`
and `jax._src.xla_bridge.backends_are_initialized`. A JAX upgrade re-checks
both (tests/test_parallel.py::TestOneProcessPerChip calls them unfaked
once; the chip count itself can only be checked on a TPU host).
"""

from __future__ import annotations

import contextlib
import glob
import os
import threading

__all__ = ["worker_env", "spawn_env", "local_tpu_chips"]

_ENV_LOCK = threading.Lock()


def local_tpu_chips() -> int:
    """TPU chips this process could open, counted without initialising a
    backend (which would take them): the chips on the PCI bus, as JAX's
    own start-up counts them, bounded by the device nodes that are
    actually passed through (a one-chip slice of a four-chip host shows
    four PCI functions and one `/dev/vfio/N`)."""
    from jax._src import hardware_utils

    on_bus = hardware_utils.num_available_tpu_chips_and_device_id()[0]
    nodes = (glob.glob("/dev/accel[0-9]*")
             or glob.glob("/dev/vfio/[0-9]*"))
    return min(on_bus, len(nodes))


def _holds_tpu() -> bool:
    """True when this process has initialised a TPU backend (and so holds
    the host's chips). Never initialises one itself."""
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        return False
    import jax

    return jax.default_backend() == "tpu"


def worker_env(uses_device: bool, chip: int = 0) -> dict[str, str]:
    """Environment overrides for one worker process about to be spawned.

    `uses_device=False` pins the child to the CPU backend. Otherwise
    `chip` is the index of the chip the child is to own; the result is
    empty where there is nothing to partition (an explicit
    `JAX_PLATFORMS=cpu` run, which the child inherits, or a host with no
    TPU). Raises RuntimeError when the child could not reach its chip."""
    if not uses_device:
        return {"JAX_PLATFORMS": "cpu"}
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return {}
    n_chips = local_tpu_chips()
    if n_chips == 0:
        return {}
    if _holds_tpu():
        raise RuntimeError(
            f"this process has initialised JAX and holds the host's "
            f"{n_chips} TPU chip(s); a worker process that needs a chip "
            "would fail or hang in backend start-up. Start device workers "
            "from a process that has not touched JAX, or run them on the "
            "CPU by setting JAX_PLATFORMS=cpu")
    if chip >= n_chips:
        raise RuntimeError(
            f"device worker {chip} needs a chip of its own and this host "
            f"has {n_chips}: one process per chip. Use at most {n_chips} "
            "device worker(s) here, or run them on the CPU by setting "
            "JAX_PLATFORMS=cpu")
    return {"TPU_VISIBLE_DEVICES": str(chip),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1"}


@contextlib.contextmanager
def spawn_env(overrides: dict[str, str]):
    """`os.environ` patched for the duration of one `Process.start()`: a
    spawned child copies its parent's environment when it is created,
    before it imports anything, which is the only moment early enough for
    `JAX_PLATFORMS` and the libtpu variables."""
    with _ENV_LOCK:
        saved = {k: os.environ.get(k) for k in overrides}
        os.environ.update(overrides)
        try:
            yield
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
