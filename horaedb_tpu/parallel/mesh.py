"""Device mesh construction — THE module that declares mesh topology.

scan_mesh is the 2-D (time, series) mesh of the in-region scan
([scan.mesh]): plan segments shard along the `time` axis (one merge
window per time slot, plan order), group/tsid blocks along the `series`
axis.  The time axis carries the segmented-reduction combine
(parallel/scan.py mesh_run_partials); the series axis divides the
resident grid state and the per-chip combine egress by its size.

tools/lint.py enforces that Mesh/shard_map/NamedSharding construction
happens only under horaedb_tpu/parallel/ — mesh topology stays declared
in one place.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import Mesh

from horaedb_tpu.common.error import ensure

# the 2-D scan mesh's axis names ([scan.mesh]; docs/parallel.md)
TIME_AXIS = "time"
SERIES_AXIS = "series"


def default_scan_shape(n_devices: int) -> tuple[int, int]:
    """Auto (time, series) factorization for `n` local devices: series
    gets 2 when it divides evenly past a 2x2 mesh, else 1 — the time
    axis (window parallelism) is where scan throughput scales, while
    the series axis only divides grid state and combine egress.
    Operators with huge-cardinality workloads raise [scan.mesh] series
    explicitly."""
    ensure(n_devices >= 1, "mesh needs at least one device")
    series = 2 if n_devices >= 4 and n_devices % 2 == 0 else 1
    return n_devices // series, series


def scan_mesh(time: int = 0, series: int = 0,
              devices: Optional[Sequence] = None) -> Mesh:
    """The 2-D (time, series) scan mesh ([scan.mesh]).

    `time`/`series` of 0 mean auto: use every local device under
    default_scan_shape's factorization (one axis given → the other is
    derived).  `series` must be a power of two — group spaces are
    padded to powers of two (read.py g_pad) and the series axis must
    divide them exactly."""
    import numpy as np

    devs = list(devices) if devices is not None else jax.devices()
    n = len(devs)
    if time == 0 and series == 0:
        time, series = default_scan_shape(n)
    elif time == 0:
        ensure(series > 0 and n % series == 0,
               f"[scan.mesh] series = {series} does not divide the "
               f"{n} local devices")
        time = n // series
    elif series == 0:
        ensure(time > 0 and n % time == 0,
               f"[scan.mesh] time = {time} does not divide the "
               f"{n} local devices")
        series = n // time
    ensure(time * series <= n,
           f"[scan.mesh] {time}x{series} mesh needs {time * series} "
           f"devices but only {n} are available")
    ensure(series & (series - 1) == 0,
           f"[scan.mesh] series = {series} must be a power of two "
           "(group spaces are padded to powers of two and the series "
           "axis must divide them)")
    devs = devs[: time * series]
    return Mesh(np.array(devs).reshape(time, series),
                axis_names=(TIME_AXIS, SERIES_AXIS))
