"""Test harness setup.

Tests run on the CPU backend with 8 virtual devices so multi-chip
sharding (Mesh/shard_map) is testable without an accelerator, and so a
test run never takes the chip from a process that is measuring on it.
The chip path is driven by chip_smoke.py, not by pytest.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from horaedb_tpu.utils.cpu_mesh import force_cpu_devices  # noqa: E402

force_cpu_devices(8)

import jax  # noqa: E402


def pytest_sessionstart(session):
    devices = jax.devices()
    assert devices[0].platform == "cpu", f"tests must run on CPU, got {devices}"
    assert len(devices) == 8, f"expected 8 virtual CPU devices, got {len(devices)}"
