"""Importing this module gives a ``JAX_PLATFORMS=cpu`` run eight devices.

JAX honours ``JAX_PLATFORMS=cpu`` by itself; the multi-device examples
also need virtual host devices, and that flag must be in ``XLA_FLAGS``
BEFORE the backend initialises. One shared bootstrap keeps the flag logic
in one place: CI (which exports ``JAX_PLATFORMS=cpu``) runs every example
on an 8-device CPU mesh, while a direct ``python examples/...`` run uses
whatever devices JAX finds.
"""

import os

if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
    _flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8").strip()
