"""Force the JAX CPU backend with N virtual devices.

Shared by tests/conftest.py, __graft_entry__.dryrun_multichip and the
CPU-rung tools: multi-chip sharding (Mesh/shard_map) is testable on a
host with no accelerator by splitting the host platform into virtual
devices.  `JAX_PLATFORMS=cpu` plus `--xla_force_host_platform_device_count`
in XLA_FLAGS, both set before the first backend use, is all it takes.
"""

from __future__ import annotations

import os
import re

_FLAG = "--xla_force_host_platform_device_count"


def force_cpu_devices(n_devices: int) -> None:
    """Make `jax.devices()` return >= n_devices virtual CPU devices.

    Must run before the process first uses a JAX backend: XLA parses
    XLA_FLAGS once per process, so a backend that already exists keeps
    its device count (callers assert the count they need).
    """
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(rf"{_FLAG}=(\d+)", flags)
    if m is None or int(m.group(1)) < n_devices:
        want = f"{_FLAG}={n_devices}"
        flags = flags.replace(m.group(0), want) if m else f"{flags} {want}"
        os.environ["XLA_FLAGS"] = flags.strip()
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    # jax reads JAX_PLATFORMS at import; the config update covers a
    # caller that imported jax before calling here
    jax.config.update("jax_platforms", "cpu")
