"""Test harness: force an 8-device virtual CPU platform.

Stands in for a multi-chip TPU slice (SURVEY §4: multi-node testing
without a cluster). The driver separately dry-runs the multi-chip path
via __graft_entry__.dryrun_multichip; the real chip is chip_smoke.py's
(and bench.py's), never a test's.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# hermetic compiles: entry points under test turn the persistent compile
# cache on (edl_tpu/utils/jaxcache.py), and XLA:CPU's loader logs a
# machine-feature warning on every hit. JAX's own switch, set before
# jax is imported and inherited by every worker subprocess.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

from edl_tpu.utils.platform import force_virtual_cpu  # noqa: E402

force_virtual_cpu(8)

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def cpu_devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 virtual devices, got {len(devs)}"
    return devs
