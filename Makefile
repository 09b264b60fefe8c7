# Developer entry points. Everything runs with PYTHONPATH=src (the tier-1
# contract in ROADMAP.md).
PY ?= python
PYTHONPATH := src

.PHONY: test regen-goldens check-goldens check-autotune bench-regression sharded-eval-sim distributed-smoke

# tier-1 suite
test:
	PYTHONPATH=$(PYTHONPATH) $(PY) -m pytest -x -q

# Regenerate BOTH derived fixture sets together — the conformance golden
# (tests/conformance/fixtures/) and the pinned synthetic-data checksums
# (tests/fixtures/data_checksums.json). Run ONLY on an intentional
# numerics/data change, then commit both. The golden-regen CI job runs
# check-goldens and fails on any half-updated state.
regen-goldens:
	PYTHONPATH=$(PYTHONPATH) $(PY) scripts/regen_goldens.py

check-goldens:
	PYTHONPATH=$(PYTHONPATH) $(PY) scripts/regen_goldens.py --check

# The committed autotune cache must COVER every fused layer shape of the
# benchmarked configs (default 24x32 + the large-input 96x128) — lookups
# for uncovered shapes silently fall back to the untuned default, which
# is bit-identical but forfeits the tuned tiling. Fails on a stale
# (version-bumped) cache too, since that loads as empty. Regenerate with:
#   PYTHONPATH=src python -m repro.kernels.autotune --input-hw 96x128
check-autotune:
	JAX_PLATFORMS=cpu PYTHONPATH=$(PYTHONPATH) \
		$(PY) -m repro.kernels.autotune --check --input-hw 96x128

# Compare fresh BENCH_*.json against baselines (default: the checked-in
# copies snapshotted by CI before the benchmark run); fails on >20%
# throughput regression. BASELINE_DIR must hold the baseline copies.
BASELINE_DIR ?= .bench-baseline
bench-regression:
	$(PY) scripts/bench_regression.py --baseline-dir $(BASELINE_DIR)

# The sharded-evaluation CI lane, runnable locally: 8 simulated CPU
# devices, the shard-reduction tests, and the 4-shard vs single-host
# bit-identical parity gate.
sharded-eval-sim:
	JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
		PYTHONPATH=$(PYTHONPATH) $(PY) -m pytest tests/test_sharded_eval.py -q
	JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
		PYTHONPATH=$(PYTHONPATH) $(PY) -m benchmarks.eval_map --fast --shards 4

# The multi-CONTROLLER lane, runnable locally: each test spawns a REAL
# 2-process jax.distributed job (local coordinator, gloo CPU collectives,
# one device per process) and gates eval-mAP bit-parity, data-parallel
# train-loss parity, and the 2-host-save -> 1-host-restore checkpoint
# round-trip against single-host references.
distributed-smoke:
	JAX_PLATFORMS=cpu PYTHONPATH=$(PYTHONPATH) \
		$(PY) -m pytest tests/test_multihost.py -q
