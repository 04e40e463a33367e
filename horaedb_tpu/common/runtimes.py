"""Named worker pools — the reference's dedicated runtimes.

The reference serves queries on the main tokio runtime and pins
manifest folds and SST compaction onto separate runtimes so a long
compaction cannot starve serving (ref: src/storage/src/storage.rs:91-104,
src/server/src/main.rs:104-109 builds them from
threads.manifest_thread_num / threads.sst_thread_num).

The asyncio analogue: the event loop stays an I/O scheduler only, and
every CPU-heavy step — parquet encode/decode, host merge, numpy window
prep, device dispatch + blocking syncs — runs on one of these pools via
run_in_executor.  Pools:

  sst      — serving reads/writes (parquet decode/encode, merge prep)
  compact  — compaction rewrites (so they queue behind each other, not
             in front of serving work)
  manifest — manifest codec/folds

Every hop through `run` is timed where the work waits
(docs/observability.md, scan phases and waits): submit to the first
instruction on the worker (`runtime_pool_wait_seconds{pool}`: the
pool's queue) and the worker's return to the coroutine's resumption on
the loop (`runtime_pool_resume_seconds{pool}`: the loop's lag as this
job feels it); with the time on the worker they are, in a traced
request, the fields of the hop's `pool_hop` span (submit to
resumption).  What the workers' CPU comes to is the stall sampler's
account (`process_thread_cpu_seconds_total{role}`, common/loops.py),
which tells these pools' threads by `thread_name_prefix`.
"""

from __future__ import annotations

import asyncio
import contextvars
import time
import weakref
from concurrent.futures import ThreadPoolExecutor
from typing import Callable

from horaedb_tpu.utils.metrics import registry
from horaedb_tpu.utils.tracing import record_hop

POOLS = ("sst", "compact", "manifest")
THREAD_NAME_PREFIX = "horaedb-"   # a pool's threads: horaedb-<pool>_<n>
# waits are short when all is well: the default buckets start at 0.5 ms
_WAIT_BUCKETS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0)


def _per_pool(name: str, help_: str) -> dict:
    family = registry.histogram(name, help_, buckets=_WAIT_BUCKETS)
    return {p: family.labels(pool=p) for p in POOLS}


_WAIT = _per_pool(
    "runtime_pool_wait_seconds",
    "time a job waited in a named pool's queue: submit to its first "
    "instruction on a worker thread")
_RESUME = _per_pool(
    "runtime_pool_resume_seconds",
    "time from a job's return on the worker to the resumption of the "
    "coroutine that awaited it: the event loop's lag as the job feels "
    "it")


_LIVE: "weakref.WeakSet[Runtimes]" = weakref.WeakSet()


def queue_depths() -> dict:
    """Jobs waiting (not running) in each named pool, summed over the
    process's open Runtimes: what the stall line reports."""
    out = dict.fromkeys(POOLS, 0)
    for rt in list(_LIVE):
        for name, pool in rt._pools.items():
            out[name] += pool._work_queue.qsize()
    return out


class Runtimes:
    """Owner of the named pools.  `close()` only shuts down pools this
    instance created (a sharing parent keeps ownership)."""

    def __init__(self, sst_threads: int = 4, compact_threads: int = 2,
                 manifest_threads: int = 1):
        _LIVE.add(self)
        self._pools = {
            pool: ThreadPoolExecutor(
                n, thread_name_prefix=THREAD_NAME_PREFIX + pool)
            for pool, n in zip(POOLS, (sst_threads, compact_threads,
                                       manifest_threads))}

    async def run(self, pool: str, fn: Callable, *args, **kwargs):
        """Run fn(*args, **kwargs) on the named pool; await the result.
        The caller's contextvars context rides along (run_in_executor,
        unlike asyncio.to_thread, does not copy it) so request-scoped
        state — the ambient trace, deadline — stays visible to stage
        attribution inside pool work."""
        loop = asyncio.get_running_loop()
        ctx = contextvars.copy_context()
        on_worker = [0.0, 0.0]

        def job():
            on_worker[0] = time.perf_counter()
            try:
                return fn(*args, **kwargs)
            finally:
                on_worker[1] = time.perf_counter()

        submitted = time.perf_counter()
        try:
            return await loop.run_in_executor(self._pools[pool], ctx.run,
                                              job)
        finally:
            started, returned = on_worker
            if returned:  # a job cancelled in the queue never ran
                resumed = time.perf_counter()
                _WAIT[pool].observe(started - submitted)
                _RESUME[pool].observe(resumed - returned)
                record_hop(pool, submitted, started, returned, resumed)

    def close(self) -> None:
        # wait=True is load-bearing: shutdown(wait=False) leaves an
        # in-flight parquet encode/merge running on the worker thread
        # AFTER the owner tears down the engine — the job then races
        # object teardown and corrupts the heap (observed as later
        # segfaults/aborts inside pyarrow).  Queued-but-unstarted jobs
        # are cancelled; the bounded in-flight ones finish first.
        _LIVE.discard(self)
        for pool in self._pools.values():
            pool.shutdown(wait=True, cancel_futures=True)


def from_config(threads, sst_override: int = 0) -> Runtimes:
    """Build pools from a ThreadsConfig (storage.config).
    `sst_override` > 0 widens/narrows the serving decode pool — the
    [scan] decode_workers knob for cold-path tuning."""
    return Runtimes(sst_threads=sst_override or threads.sst_thread_num,
                    compact_threads=threads.compact_thread_num,
                    manifest_threads=threads.manifest_thread_num)
