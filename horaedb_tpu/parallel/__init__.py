"""Multi-chip execution: the 2-D (time, series) scan mesh + shard_map
scan programs ([scan.mesh]; docs/parallel.md).

The TPU-native replacement for the reference's cross-partition merge
(SortPreservingMergeExec under UnionExec, SURVEY.md section 2.5 P2/P3):
time segments are independent by construction (storage.rs:342-368 builds
one plan per segment), so segments shard along the time axis.  Each chip
aggregates its own windows; only the small dense (group, bucket) grids
cross chips, in the segmented time-axis combine over ICI — never row
data.
"""

# Lazy exports (PEP 562): importing this package must not initialize
# the XLA backend (scan.py builds jnp constants at import).
_EXPORTS = {
    "scan_mesh": "horaedb_tpu.parallel.mesh",
    "default_scan_shape": "horaedb_tpu.parallel.mesh",
    "mesh_run_partials": "horaedb_tpu.parallel.scan",
}

__all__ = list(_EXPORTS)


def __getattr__(name: str):
    if name not in _EXPORTS:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}")
    import importlib

    val = getattr(importlib.import_module(_EXPORTS[name]), name)
    globals()[name] = val  # cache: next access skips __getattr__
    return val
