# Test entry points.
#
# Tests run on a virtual 8-device CPU mesh (the JAX analog of Spark
# local[8]); they never need the chip. `python chip_smoke.py` is the
# on-chip check (one process per chip); `python3 benchmark/run.py` is the
# benchmark (BENCHMARK.json, PERF.md).

TEST_ENV = JAX_PLATFORMS=cpu \
	XLA_FLAGS=--xla_force_host_platform_device_count=8 KERAS_BACKEND=jax

.PHONY: test test-fast test-chaos test-perf test-spec test-streaming \
	test-fleet test-elastic test-paged test-soak

test:
	$(TEST_ENV) bash scripts/run_tests.sh -x -q

test-fast:
	$(TEST_ENV) bash scripts/run_tests.sh -x -q -m "not slow"

# Pinned deterministic chaos scenarios only (quorum commit under dead
# workers, straggler backup exactly-once, hot-standby PS failover).
test-chaos:
	ELEPHAS_TEST_GROUP=chaos $(TEST_ENV) bash scripts/run_tests.sh -x -q

# Slow loss-trajectory parity sweeps for the train-step hot-path knobs
# (overlap_grads / fused_apply / remat) — kept out of tier-1 by marker.
test-perf:
	ELEPHAS_TEST_GROUP=perf $(TEST_ENV) bash scripts/run_tests.sh -x -q

# Speculative-decoding pins only (draft/verify token identity across
# dense/paged/mesh/adapters + the metrics schema).
test-spec:
	ELEPHAS_TEST_GROUP=spec $(TEST_ENV) bash scripts/run_tests.sh -x -q

# Streaming train-to-serve pins only (hot weight rollover replay identity,
# publication cadence/eval-gate/rollback, version piggyback parity,
# supervised stream crash-resume determinism).
test-streaming:
	ELEPHAS_TEST_GROUP=streaming $(TEST_ENV) bash scripts/run_tests.sh -x -q

# Serving-fleet pins only (trace determinism, DRR fairness, router
# migration identity, autoscaler scale-up/down, the pinned fleet chaos
# scenario with kill + join mid-trace).
test-fleet:
	ELEPHAS_TEST_GROUP=fleet $(TEST_ENV) bash scripts/run_tests.sh -x -q

# Elastic multi-host control-plane pins only (subprocess host emulation:
# epoch fencing, mesh re-formation on SIGKILL/partition/late-join, and
# the pinned 2→4→3-host SparkModel.fit chaos scenario).
test-elastic:
	ELEPHAS_TEST_GROUP=elastic $(TEST_ENV) bash scripts/run_tests.sh -x -q

# Paged-KV pins only (fused paged-attention kernel oracles, dense-vs-paged
# token-identity fuzz over the knob cross-product, page-boundary
# speculative accepts, and the PagesExhausted-mid-window chaos).
test-paged:
	ELEPHAS_TEST_GROUP=paged $(TEST_ENV) bash scripts/run_tests.sh -x -q

# Randomized cross-stack chaos soak, including the slow >=20-schedule
# acceptance run (seeded fault schedules over ALL sites — wire corruption
# + logical drops/kills — applied to sync/async/hogwild fit, fit_stream,
# and a fleet replay, with the global invariant checker after every run).
# The fast smoke + harness pins also carry the marker and run in tier-1.
test-soak:
	ELEPHAS_TEST_GROUP=soak $(TEST_ENV) bash scripts/run_tests.sh -x -q
