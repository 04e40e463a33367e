"""horaedb-tpu: a TPU-native time-series storage & query framework.

A from-scratch rebuild of Apache HoraeDB's metric-engine architecture
(reference: /root/reference, surveyed in SURVEY.md) designed TPU-first:

- Host engine (Python/asyncio + C++ hot paths): manifest, SST lifecycle,
  time-window compaction, object-store I/O, Arrow ingestion.
- Compute core (JAX/XLA): the columnar scan path -- predicate
  filtering, primary-key merge/dedup, time-bucketed downsampling -- runs as
  compiled kernels over HBM-resident columnar batches, sharded across chips
  by time segment with ICI collectives.

Layout mirrors the reference's crate graph (SURVEY.md section 1):
  common/        errors, human-readable durations/sizes   (ref: src/common)
  objstore/      object-storage abstraction               (ref: object_store crate)
  storage/       TimeMergeStorage engine                  (ref: src/storage)
  ops/           JAX physical operators                   (ref: DataFusion layer)
  parallel/      mesh / shard_map multi-chip execution    (new, TPU-native)
  metric_engine/ Prometheus-style metric layer            (ref: src/metric_engine + RFC)
  server/        HTTP server + config                     (ref: src/server)
"""

__version__ = "0.1.0"
