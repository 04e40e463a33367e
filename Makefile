.PHONY: test native clean verify lint chaos trace-demo chip-smoke

# mirrors the tier-1 invocation (fast variants of the slow suites stay
# in-tier; `make chaos` runs the full slow schedules)
test:
	python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors \
	-p no:cacheprovider -p no:xdist -p no:randomly

# seeded fault-injection + crash-consistency torture suites (see
# docs/robustness.md); override TORTURE_SEED / TORTURE_SCHEDULES (and
# the WAL replay twins WAL_TORTURE_SEED / WAL_TORTURE_SCHEDULES) to
# reproduce a failure or dial intensity
TORTURE_SEED ?= 1337
TORTURE_SCHEDULES ?= 200
WAL_TORTURE_SEED ?= 1337
WAL_TORTURE_SCHEDULES ?= 120

SCANCACHE_SEED ?= 1337
SCANCACHE_SCHEDULES ?= 40

ROLLUP_SEED ?= 1337
ROLLUP_SCHEDULES ?= 24

PIPELINE_SEED ?= 1337
PIPELINE_SCHEDULES ?= 10

COMBINE_SEED ?= 1337
COMBINE_SCHEDULES ?= 25

TENANT_SEED ?= 1337
TENANT_SCHEDULES ?= 20

DECODE_SEED ?= 1337
DECODE_SCHEDULES ?= 20

SCANAGENT_SEED ?= 1337
SCANAGENT_SCHEDULES ?= 15

MESH_SEED ?= 1337
MESH_SCHEDULES ?= 12

MESHDECODE_SEED ?= 1337
MESHDECODE_SCHEDULES ?= 10

REPL_SEED ?= 1337
REPL_SCHEDULES ?= 10

FAILOVER_SEED ?= 1337
FAILOVER_SCHEDULES ?= 5

chaos:
	TORTURE_SEED=$(TORTURE_SEED) TORTURE_SCHEDULES=$(TORTURE_SCHEDULES) \
	WAL_TORTURE_SEED=$(WAL_TORTURE_SEED) \
	WAL_TORTURE_SCHEDULES=$(WAL_TORTURE_SCHEDULES) \
	SCANCACHE_SEED=$(SCANCACHE_SEED) \
	SCANCACHE_SCHEDULES=$(SCANCACHE_SCHEDULES) \
	ROLLUP_SEED=$(ROLLUP_SEED) \
	ROLLUP_SCHEDULES=$(ROLLUP_SCHEDULES) \
	PIPELINE_SEED=$(PIPELINE_SEED) \
	PIPELINE_SCHEDULES=$(PIPELINE_SCHEDULES) \
	COMBINE_SEED=$(COMBINE_SEED) \
	COMBINE_SCHEDULES=$(COMBINE_SCHEDULES) \
	TENANT_SEED=$(TENANT_SEED) \
	TENANT_SCHEDULES=$(TENANT_SCHEDULES) \
	DECODE_SEED=$(DECODE_SEED) \
	DECODE_SCHEDULES=$(DECODE_SCHEDULES) \
	SCANAGENT_SEED=$(SCANAGENT_SEED) \
	SCANAGENT_SCHEDULES=$(SCANAGENT_SCHEDULES) \
	MESH_SEED=$(MESH_SEED) \
	MESH_SCHEDULES=$(MESH_SCHEDULES) \
	MESHDECODE_SEED=$(MESHDECODE_SEED) \
	MESHDECODE_SCHEDULES=$(MESHDECODE_SCHEDULES) \
	REPL_SEED=$(REPL_SEED) \
	REPL_SCHEDULES=$(REPL_SCHEDULES) \
	FAILOVER_SEED=$(FAILOVER_SEED) \
	FAILOVER_SCHEDULES=$(FAILOVER_SCHEDULES) \
	python -m pytest tests/test_fault_injection.py tests/test_torture.py \
	tests/test_objstore_middleware.py tests/test_wal.py \
	tests/test_scan_cache.py tests/test_rollup.py \
	tests/test_pipeline.py tests/test_combine.py \
	tests/test_tenant.py tests/test_device_decode.py \
	tests/test_scanagent.py tests/test_mesh_scan.py \
	tests/test_mesh_decode.py tests/test_replication.py -q

# stdlib AST lint gate (the reference CI runs fmt+clippy -D warnings;
# this image ships no ruff/flake8, so the gate is tools/lint.py)
lint:
	python tools/lint.py

# end-to-end tracing demo (docs/observability.md): run a query against
# a throwaway local server and pretty-print its span tree + counters,
# then (--ops) provoke a compaction + roll pass and print their op
# traces and the /debug/tasks background-loop table
trace-demo:
	JAX_PLATFORMS=cpu python tools/trace_demo.py --ops

# device-plane demo (docs/observability.md, device plane): a cold
# fused mesh-decode round then the identical warm repeat, attributed —
# compile ledger, dispatch/exec split, transfer totals, round timeline
trace-demo-device:
	JAX_PLATFORMS=cpu python tools/trace_demo.py --device

# the served scan path on the accelerator, end to end (chip_smoke.py's
# docstring): exits non-zero wherever JAX finds no TPU.  Run it on the
# chip through the chip tool: `chiprun -- python chip_smoke.py`, and
# `chiprun --chips 4 -- python chip_smoke.py --chips 4` for the mesh.
# A dry run of the plumbing on this box:
#   python chip_smoke.py --platform cpu --rows 200000
chip-smoke:
	python chip_smoke.py

# the driver-facing deliverables, end to end: lint + full suite + the
# fixed-seed chaos gate + the multi-chip dryrun on the virtual CPU mesh
verify: lint test chaos
	python -c "import __graft_entry__; __graft_entry__.dryrun_multichip(8); print('dryrun OK')"

native:
	$(MAKE) -C native

clean:
	$(MAKE) -C native clean
	find . -name __pycache__ -type d -exec rm -rf {} +
	rm -rf .jax_cache
