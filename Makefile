# Developer entry points.
#
# check-fast is the MANDATORY pre-snapshot gate: the distributed learners,
# wave-vs-exact parity, and an engine smoke — the tests that have caught
# every shipped regression so far (the round-2 data-parallel breakage
# shipped precisely because these didn't run before the snapshot).

# Timing on the 1-core CI box: full `check` is ~9 min after grower/kernel
# changes (XLA recompiles dominate) and ~5 min warm via the persistent
# compile cache in .jax_cache; `check-fast` is ~4 min cold.
PYTEST := python -m pytest -q

# Static JAX/TPU hygiene, both tiers (docs/Static-Analysis.md):
#   1. AST tier  — rules R001-R013 over the package source with the
#      whole-package call graph; findings gate unless covered by
#      tpu_lint_baseline.json.
#   2. trace tier — contracts T001+ over the SHIPPED entry points' jaxprs
#      and optimized HLO (one sort a compacted wave, gather-free bundle routing,
#      collective set vs the cost model, f64 discipline, donation
#      aliasing, no host transfers in loop bodies); gates unless covered
#      by trace_lint_baseline.json.
lint:
	python -m lightgbm_tpu.analysis lightgbm_tpu/
	python -m lightgbm_tpu.analysis --trace

# CI gate: lint + tier-1 tests + the recompile guard on a 5-iter smoke run
# (which also asserts checkpoint save/resume stays recompile-free, that the
# watchdog + checkpoint-checksum path adds 0 recompiles / 0 host syncs, and
# pins the fused step's FLOPs/bytes to golden values) + the out-of-core
# stream smoke (small N, forced budget -> tpu_residency=stream; asserts 0
# recompiles and bit-identity with the resident output) + the serving
# smoke (protobuf -> ServingEngine bit-identity, 0 recompiles across the
# bucket ladder under load) + the serving-resilience chaos matrix (make
# serve-chaos: overload shed / breaker degrade-recover / deadline hang /
# mid-load reload, all typed + bit-identical) + the perf-ledger diff. The
# FAST chaos-matrix arms (corrupt-latest lineage fallback across
# serial/data8/stream, watchdog fake-clock boundaries, shard-CRC
# detection, supervisor policy) ride inside the tier-1 line — only the
# slow supervised kill -9 / hang / shard-restart arms are deferred to
# `make chaos`.
verify: lint
	env JAX_PLATFORMS=cpu $(PYTEST) tests/ -m 'not slow'
	python bench.py --smoke
	$(MAKE) stream
	$(MAKE) ingest
	$(MAKE) linear
	$(MAKE) serve
	$(MAKE) serve-chaos
	env JAX_PLATFORMS=cpu LGBM_TPU_CHAOS_DIST_FAST=1 \
	    LGBM_TPU_CHAOS_SEED=1234 python bench.py --chaos-dist
	$(MAKE) bench-diff

# Out-of-core streaming smoke (docs/TPU-Performance.md "Out-of-core
# streaming"): hermetic-CPU train of a dataset >= 4x an artificial HBM
# budget with tpu_residency auto-falling back to stream — asserts the
# streamed run is bit-identical to device residency, steady-state waves
# add 0 recompiles, and reports throughput + prefetch-stall fraction vs
# the resident arm. Bigger N: LGBM_TPU_STREAM_ROWS=500000 make stream.
stream:
	env LGBM_TPU_STREAM_ROWS=20000 LGBM_TPU_STREAM_ITERS=5 \
	    python bench.py --stream

# Device-side ingest phase (docs/TPU-Performance.md "Device-side ingest"):
# hermetic-CPU raw-rows-to-codes A/B — the jitted chunked bin+pack kernel
# (tpu_ingest=device, ops/ingest.py) vs the host bin_dense_host oracle.
# Asserts BIT identity (real region, padding zeros, packed bytes), one
# compile for every chunk shape class, a >= 3x device rows/s floor, and
# measures the prefetch overlap vs a forced no-prefetch arm. Bank with
# LGBM_TPU_INGEST_OUT=INGEST_r<N>.json; `bench.py --compare` judges the
# newest banked file under the |ingest= comparability key. Bigger N:
# LGBM_TPU_INGEST_ROWS=2000000 make ingest.
ingest:
	env LGBM_TPU_INGEST_ROWS=200000 python bench.py --ingest

# Wide-sparse (Bosch-shaped) EFB phase, three arms: bundlespace (native
# bundle-space scan/routing — the default), efb_unpack (legacy
# tpu_efb_unpack=true A/B arm that measured the round-5 3.5x loss), noefb
# (enable_bundle=false). The bundlespace arm must at least match noefb
# throughput with a lower peak (docs/TPU-Performance.md "EFB on TPU").
# Bank with LGBM_TPU_SPARSE_OUT=SPARSE_r<N>.json; `bench.py --compare`
# judges the newest banked file under the |bundle= comparability key.
# Full Bosch scale: LGBM_TPU_BENCH_SPARSE_ROWS=1000000 \
#   LGBM_TPU_BENCH_SPARSE_FEATS=968 python bench.py --sparse, on the chip.
sparse:
	env JAX_PLATFORMS=cpu LGBM_TPU_BENCH_SPARSE_ROWS=60000 \
	    LGBM_TPU_BENCH_SPARSE_FEATS=256 python bench.py --sparse

# Piecewise-linear leaves phase (docs/Linear-Trees.md): hermetic-CPU A/B
# of linear_tree=true vs constant leaves at fixed tree count on a
# piecewise-linear synthetic — asserts the linear arm wins on holdout L2,
# trains real linear leaves, stays 0-recompile with the solve leg on, and
# serves bit-identically through a proto -> ServingEngine round trip.
# Bank with LGBM_TPU_LINEAR_OUT=LINEAR_r<N>.json; `bench.py --compare`
# judges the newest banked file under the |linear= comparability key.
# Bigger N: LGBM_TPU_LINEAR_ROWS=500000 make linear.
linear:
	env LGBM_TPU_LINEAR_ROWS=20000 LGBM_TPU_LINEAR_ITERS=5 \
	    python bench.py --linear

# Serving smoke (docs/Serving.md): hermetic-CPU train -> protobuf ->
# ServingEngine round trip asserting bit-identity with the training
# booster's predict(), zero jit cache misses across closed + open
# (Poisson/MicroBatcher) load after the AOT bucket warmup
# (RecompileGuard), and reporting p50/p99 latency + rows/s per
# concurrency x batch-size shape. Bank with
# LGBM_TPU_SERVE_OUT=SERVE_r<N>.json.
serve:
	env LGBM_TPU_SERVE_ROWS=20000 python bench.py --serve

# Serving-resilience chaos matrix (docs/Serving.md "Resilience"): overload
# burst against the bounded queue (typed sheds, never a hang or OOM),
# injected dispatch failures (circuit breaker -> degraded host serving ->
# probe recovery to ready), a slow-dispatch hang under per-request
# deadlines (callers unblock at THEIR deadline; expired requests never
# cost a dispatch), a mid-load hot reload (atomic, verified, rolled back
# on a corrupted candidate), and a final 0-recompile steady-state pin —
# every arm asserting bit-identity wherever a result is produced. Bank
# with LGBM_TPU_SERVE_CHAOS_OUT=SERVE_CHAOS_r<N>.json.
serve-chaos:
	env LGBM_TPU_SERVE_CHAOS_ROWS=8000 python bench.py --serve-chaos

# Ledger gate (docs/TPU-Performance.md): assert the committed
# PERF_LEDGER.json matches the checked-in *_r<N>.json result files (no
# drift), then judge the newest of each kind against best-known values —
# exits nonzero on a throughput/recompile/host-sync/HBM/cost regression.
# These files are the builders' CPU correctness drives; chip numbers are
# the driver's record (PERF_LEDGER.jsonl) and PERF.md.
bench-diff:
	python -m lightgbm_tpu.observability.ledger --check
	python bench.py --compare

# One-shot ledger rebuild from the checked-in result files; commit the
# regenerated PERF_LEDGER.json alongside any new *_r<N>.json file.
ledger:
	python -m lightgbm_tpu.observability.ledger --rebuild

# Measured multi-chip story (docs/TPU-Performance.md "Multi-chip"): the
# 8-device parity suite + the weak/strong-scaling bench on SIMULATED CPU
# devices (bench.py --multichip re-execs one child per device count with
# --xla_force_host_platform_device_count). On real chips run
# LGBM_TPU_MULTICHIP_PLATFORM=tpu python bench.py --multichip instead.
multichip:
	env JAX_PLATFORMS=cpu $(PYTEST) tests/test_multichip_parity.py tests/test_parallel.py
	env LGBM_TPU_MULTICHIP_OUT=$(CURDIR)/MULTICHIP_latest.json python bench.py --multichip

# Fault-injection suite (docs/Fault-Tolerance.md): KV delay/drop/corruption
# through the chaos harness, all three nan_policy branches, kill-and-resume,
# and the self-healing matrix — corrupt-latest lineage fallback, SUPERVISED
# kill -9 / injected-hang / shard-corruption recovery (real child
# processes, slow arms included here), each asserting the recovered model
# is bit-identical to a fault-free run. The pinned seed makes a failing
# run replayable bit-for-bit.
chaos:
	env JAX_PLATFORMS=cpu LGBM_TPU_CHAOS_SEED=1234 \
	    LGBM_TPU_COMM_JITTER_SEED=1234 \
	    $(PYTEST) tests/ -m chaos
	env JAX_PLATFORMS=cpu LGBM_TPU_CHAOS_SEED=1234 $(PYTEST) \
	    tests/test_watchdog.py tests/test_supervisor.py

# Measured recovery bench (docs/Fault-Tolerance.md): supervised kill -9 +
# corrupt-latest against a fault-free baseline — reports MTTR, restart
# count, total disruption, bit-identity, and the robustness layer's
# steady-state overhead. Bank with LGBM_TPU_CHAOS_OUT=CHAOS_r<N>.json.
bench-chaos:
	python bench.py --chaos

# Distributed fault-tolerance matrix (docs/Fault-Tolerance.md "Distributed
# fault tolerance"): heartbeat-lease expiry (detection latency p50/p99),
# KV flap during init_distributed (reset + rejoin on attempt 2),
# manifest-vs-shard mismatch (whole-gang one-epoch fallback, --verify exit
# 2), kill -9 of one rank in a REAL 2-process jax.distributed gang
# (survivor exits 145 naming the peer, FleetSupervisor relaunches,
# bit-identical model, measured fleet MTTR), and the elastic 8->4 shrink
# (loud refusal without tpu_reshard_on_resume; bit-identical to a fresh
# 4-device resume with it). The FAST subset (first three arms) rides
# `make verify`. Bank with LGBM_TPU_CHAOS_DIST_OUT=CHAOS_DIST_r<N>.json.
chaos-dist:
	env JAX_PLATFORMS=cpu LGBM_TPU_CHAOS_SEED=1234 \
	    LGBM_TPU_COMM_JITTER_SEED=1234 python bench.py --chaos-dist

check-fast:
	$(PYTEST) tests/test_parallel.py tests/test_wave_parity.py \
	          tests/test_engine.py::test_binary tests/test_engine.py::test_regression \
	          tests/test_multihost.py
	python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

check:
	$(PYTEST) tests/

capi:
	$(MAKE) -C capi

# On the chip (one process holds it; both exit non-zero without a TPU):
# the end-to-end proof, then the throughput benchmark.
chip-smoke:
	python chip_smoke.py

bench:
	python bench.py

# Perfetto-loadable trace from the hermetic smoke run (docs/Observability.md):
# open the printed trace_*.json at https://ui.perfetto.dev. The smoke run
# also enforces the telemetry overhead contract (zero recompiles / zero new
# host syncs in the fused step with spans on).
trace:
	env LGBM_TPU_TELEMETRY_DIR=$(CURDIR)/.telemetry python bench.py --smoke
	@echo "trace: $$(ls -1t .telemetry/trace_*.json | head -1)"

.PHONY: lint verify check-fast check capi chip-smoke bench chaos bench-chaos \
        chaos-dist trace bench-diff ledger multichip stream serve \
        serve-chaos sparse linear ingest
