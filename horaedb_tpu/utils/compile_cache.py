"""Persistent XLA compilation cache.

The reference pays zero compile cost (native code); the scan is built of
many small compiled programs (aggregation rounds, the fused accumulator,
the fused decode dispatch, mesh rounds) whose compiles sum to seconds or
minutes on an accelerator.  JAX's persistent cache keys each program by
its HLO + backend fingerprint + the cache directory's own settings, so a
SECOND process on the same machine skips XLA entirely — provided the
directory does not move between processes.

Placement:

- `JAX_COMPILATION_CACHE_DIR` set: JAX reads it itself and this module
  never touches `jax_compilation_cache_dir` — whoever runs the process
  (an operator, a benchmark driver) owns the location.
- unset: one fixed directory inside the checkout, `CACHE_DIR`
  (git-ignored; `make clean` removes it).  Never `~`, a temp name, a pid
  or a time: a directory that moves never hits.

On the CPU backend the cache stays off unless HORAEDB_COMPILE_CACHE=1
(XLA:CPU AOT cache loads log machine-feature-mismatch errors and its
compiles are fast); HORAEDB_COMPILE_CACHE=0 disables it everywhere.

Call site: MetricEngine.open().  A failure to set the cache up raises —
a server that silently compiles everything, every start, is a defect on
the accelerator path, not a degraded mode.
"""

from __future__ import annotations

import os
import pathlib
from typing import Optional

# <checkout>/.jax_cache — fixed relative to this file, so two processes
# started from different working directories still share it
CACHE_DIR = str(pathlib.Path(__file__).resolve().parents[2] / ".jax_cache")

_enabled: Optional[str] = None


def enable_compile_cache() -> Optional[str]:
    """Idempotently enable JAX's persistent compilation cache and
    return its directory, or None when it is off (HORAEDB_COMPILE_CACHE=0,
    or the CPU backend without HORAEDB_COMPILE_CACHE=1).  Initializes
    the JAX backend (the CPU opt-out reads the real platform, not an
    environment string)."""
    global _enabled
    force = os.environ.get("HORAEDB_COMPILE_CACHE", "")
    if force == "0":
        return None
    if _enabled is not None:
        return _enabled
    import jax

    if force != "1" and jax.default_backend() == "cpu":
        return None
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CACHE_DIR
        pathlib.Path(path).mkdir(parents=True, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    # default thresholds skip small/fast programs — but the scan is
    # built of MANY small programs whose compiles sum to seconds, so
    # cache everything
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    _enabled = path
    return path


def cache_dir() -> Optional[str]:
    """Where this process keeps its persistent cache (None = off) — a
    read-only twin of enable_compile_cache() for GET /debug/device."""
    return _enabled
