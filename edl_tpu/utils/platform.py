"""Virtual-CPU platform forcing for hardware-free multi-chip validation.

Where a TPU is attached JAX picks it by default, ``JAX_PLATFORMS=cpu``
set after ``import jax`` is not read again, and backend choice is
immutable once any device query has run — so both the env vars *and*
``jax.config`` must be set before the first query. Used by
tests/conftest.py and __graft_entry__.dryrun_multichip (SURVEY §4:
multi-node testing without a cluster).
"""

from __future__ import annotations

import os
import re

_FLAG = "--xla_force_host_platform_device_count"


def prepare_virtual_cpu(n_devices: int = 8) -> None:
    """Arrange for an ``n_devices``-device virtual CPU platform WITHOUT
    touching the backend (no device query — callers that still need to
    run ``jax.distributed.initialize`` must not initialize XLA yet)."""
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(re.escape(_FLAG) + r"=(\d+)", flags)
    if m is None:
        flags = f"{flags} {_FLAG}={n_devices}".strip()
    elif int(m.group(1)) < n_devices:
        flags = flags.replace(m.group(0), f"{_FLAG}={n_devices}")
    os.environ["XLA_FLAGS"] = flags
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("JAX_ENABLE_X64", "0")

    import jax

    jax.config.update("jax_platforms", "cpu")


def force_virtual_cpu(n_devices: int = 8) -> None:
    """Force an ``n_devices``-device virtual CPU platform.

    Must run before the first backend query in the process. Raises
    RuntimeError if a non-CPU backend already won or fewer devices than
    requested materialized.
    """
    prepare_virtual_cpu(n_devices)

    import jax

    devs = jax.devices()
    if devs[0].platform != "cpu" or len(devs) < n_devices:
        raise RuntimeError(
            f"force_virtual_cpu({n_devices}): got {len(devs)} "
            f"{devs[0].platform} device(s) — a non-CPU backend was already "
            "initialized in this process, or XLA_FLAGS was locked in"
        )
