"""Multi-host (multi-process) mesh plumbing — the DCN tier.

The reference's sharding RFC scales out with HoraeMeta + gRPC
forwarding (docs/rfcs/20240827-metric-engine.md:20-76); the engine's
own data plane has no cross-node compute.  The TPU-native design
instead runs ONE SPMD program over a global device mesh spanning
processes/hosts: each process contributes its local segment windows,
`jax.lax` collectives (psum/pmin/pmax — the same ops that ride ICI
within a pod) combine partial grids ACROSS hosts over DCN, and every
process receives the replicated result.  On real TPU pods
`jax.distributed.initialize()` auto-detects topology; the CPU Gloo
backend runs the identical program across local processes, which is
how the tests exercise true cross-process collectives without TPU
hardware (see tests/test_multihost.py).

The segment axis stays the ONE mesh axis (parallel/mesh.py): segments
partition time, so cross-host combination is the same psum tree the
single-host mesh path uses — no new program shapes, just more devices
under the same axis name.
"""

from __future__ import annotations

import numpy as np

from horaedb_tpu.common.error import ensure
# shared with the single-host mesh programs — importing mesh.py does NOT
# initialize the XLA backend (module imports only)
from horaedb_tpu.parallel.mesh import SEGMENT_AXIS


def initialize(coordinator_address: str | None = None,
               num_processes: int | None = None,
               process_id: int | None = None,
               local_device_count: int | None = None) -> None:
    """Join (or form) a multi-process JAX runtime.

    On TPU pods call with no arguments — topology is auto-detected.
    For CPU-backed tests/dev, pass the coordinator plus this process's
    rank, and optionally force `local_device_count` virtual CPU devices
    (must happen before first backend use; utils/cpu_mesh.py)."""
    if local_device_count is not None:
        from horaedb_tpu.utils.cpu_mesh import force_cpu_devices

        force_cpu_devices(local_device_count)
    import jax

    kwargs = {}
    if coordinator_address is not None:
        kwargs = dict(coordinator_address=coordinator_address,
                      num_processes=num_processes,
                      process_id=process_id)
    jax.distributed.initialize(**kwargs)


def global_segment_mesh():
    """A 1-D mesh over EVERY device of EVERY process, on the same
    segment axis the single-host mesh uses — collectives cross hosts
    transparently."""
    import jax
    from jax.sharding import Mesh

    devices = np.asarray(jax.devices())
    ensure(devices.size > 0, "no devices for the global mesh")
    return Mesh(devices, (SEGMENT_AXIS,))


def host_local_rows_to_global(mesh, arr: np.ndarray):
    """Lift this process's (n_local, ...) segment rows into the global
    (n_global, ...) sharded array the SPMD query consumes.  Every
    process must contribute the same n_local (pad with empty windows —
    n_valid 0 rows aggregate to nothing)."""
    from jax.experimental import multihost_utils
    from jax.sharding import PartitionSpec as P

    spec = P(SEGMENT_AXIS, *([None] * (np.ndim(arr) - 1)))
    return multihost_utils.host_local_array_to_global_array(
        np.asarray(arr), mesh, spec)


def downsample_query_global(mesh, *, num_groups: int, num_buckets: int,
                            k: int):
    """The multi-chip downsample+topk program (parallel.scan) compiled
    over a GLOBAL mesh: per-shard partial grids, cross-host
    psum/pmin/pmax combine, replicated finalized output on every
    process.  Inputs must be global arrays (host_local_rows_to_global);
    the replicated outputs are addressable on every process via
    `np.asarray(out.addressable_data(0))`."""
    from horaedb_tpu.parallel.scan import sharded_downsample_query

    return sharded_downsample_query(mesh, num_groups=num_groups,
                                    num_buckets=num_buckets, k=k)


def process_info() -> tuple[int, int]:
    """(process_index, process_count) of the joined runtime."""
    import jax

    return jax.process_index(), jax.process_count()
