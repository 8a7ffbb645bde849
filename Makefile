# Convenience entry points. Everything here is reproducible by hand —
# the targets just spell the one-liners out.

.PHONY: test test-serving test-precision test-fleet test-paged \
	test-procfleet dryrun bench smoke serving-smoke bench-precision \
	bench-fleet bench-procfleet test-obs bench-obs \
	obs-smoke evidence lint test-lint test-elastic bench-elastic \
	test-spec bench-spec test-disagg bench-disagg test-pressure \
	bench-pressure test-tenancy bench-tenants test-zero bench-zero \
	test-paged-kernel test-hibernate \
	bench-hibernate chip-smoke

# lint first: the four-pass static sweep is ~1s and fails fast on a
# race/host-sync/recompile-hazard/broad-except finding before the
# (much slower) runtime suite spins up.
test: lint
	python -m pytest tests/ -x -q

# Serving subsystem only (micro-batcher, bucket ladder, continuous LM).
test-serving:
	python -m pytest tests/ -q -m serving

# Serving-fleet only (failover router, health ejection/re-admission,
# rolling weight swaps, fleet chaos).
test-fleet:
	python -m pytest tests/ -q -m fleet

# Fleet bench row: concurrency-32 storm with a replica killed mid-storm
# (requests/s, p99, failed must be 0) + the shared-prefix LM leg.
bench-fleet:
	BENCH_ONLY=servingfleet python bench.py

# Process-supervision only (crash detection/classification, backoff
# restart, crash-loop quarantine, cross-host attach, launcher
# spawn/reap/log hygiene — real processes via the stdlib stub worker).
test-procfleet:
	python -m pytest tests/ -q -m procfleet

# Process-supervision bench row: 3 REAL `dl4j serve` worker processes,
# one SIGKILL'd mid-storm — failed must be 0, restart latency reported.
bench-procfleet:
	BENCH_ONLY=procfleet python bench.py

# Paged-KV tests only (block-table pool parity, radix prefix reuse +
# copy-on-write, chunked prefill, page refcount ledger under chaos,
# zero-recompile guard).
test-paged:
	python -m pytest tests/ -q -m paged

# Paged-attention KERNEL plane only (fused block-table-walk flash
# attention: kernel-vs-gather-oracle parity incl. C>1 chunks, page
# straddles, null lanes, bf16/fp16 finite masks, serving byte-parity,
# zero-recompile guard — docs/performance.md "The paged-attention
# kernel cost model").
test-paged-kernel:
	python -m pytest tests/ -q -m paged_kernel

# Speculative-decode tests only (drafter plane: n-gram property suite +
# small-model drafter, wide verify with in-jit accept/rollback, greedy
# byte-parity vs generate() incl. adversarial drafters, rollback page
# hygiene, unsupported-combo admission, zero-recompile guard).
test-spec:
	python -m pytest tests/ -q -m spec

# Speculative-decode bench row: shared-prefix greedy storm, n-gram
# drafter vs the PR-7 paged baseline — gates tokens_per_dispatch > 1.5,
# a tokens/s win, byte-parity sentinel, balanced page ledger, zero
# off-ladder compiles (docs/performance.md "The speculative decode
# cost model").
bench-spec:
	BENCH_ONLY=speculative python bench.py

# Disaggregated-serving tests only (KV page shipping wire format +
# integrity, shipped-lane byte parity, role routing with the recompute
# failure ladder, sticky sessions, SSE streaming incl. disconnect
# hygiene).
test-disagg:
	python -m pytest tests/ -q -m disagg

# Disaggregated-serving bench row: mixed long-prompt + short-chat storm,
# 1 prefill + 2 decode workers vs 3 undifferentiated — gates decode-side
# p99 TTFT improvement and failed == 0 with a prefill worker killed
# mid-storm (docs/architecture.md "Disaggregated serving").
bench-disagg:
	BENCH_ONLY=disagg python bench.py

# Overload-survival tests only (priority admission ordering, KV lane
# preemption + host swap-out byte parity, swap eviction/corruption
# recompute fallback, brownout ladder hysteresis, pool-exhaustion
# chaos regression, role-aware autoscale signals).
test-pressure:
	python -m pytest tests/ -q -m pressure

# Tenancy-plane tests only (registry/quotas/WFQ, per-tenant 429s,
# burn-rate victim selection, fleet ledger reconciliation).
test-tenancy:
	python -m pytest tests/ -q -m tenancy

# Tiered KV state hierarchy tests only (host/disk store economy,
# quantized frames at rest, hibernate -> resume byte parity incl. a
# full process restart over the same disk dir, the disk chaos ladder;
# docs/robustness.md "The state hierarchy").
test-hibernate:
	python -m pytest tests/ -q -m hibernate

# Hibernation bench row: N idle sessions hibernated int8 to the disk
# tier under a deliberately tight host cap, then resumed COLD — gates
# at-rest bytes <= 0.3x exact, zero failed resumes, byte parity,
# balanced ledger, zero off-ladder compiles.
bench-hibernate:
	BENCH_ONLY=hibernate python bench.py

# Multi-tenant isolation bench row: tenant-B best_effort flood at 5x
# its token quota vs tenant-A's interactive wave on the same pool.
bench-tenants:
	BENCH_ONLY=tenants python bench.py

# Overload-survival bench row: a mixed-priority storm sized to >2x the
# paged pool's capacity, survival plane (priorities + preemption +
# brownout) vs the all-FIFO baseline — gates zero failed interactive
# requests, interactive p99 under the FIFO baseline, ladder
# transitions counted, pool ledger + swap byte-cap honored.
bench-pressure:
	BENCH_ONLY=pressure python bench.py

# Observability-plane tests only (metrics registry + exposition,
# request tracing across the fleet, compile watcher, training
# telemetry; docs/observability.md).
test-obs:
	python -m pytest tests/ -q -m obs

# Observability-overhead bench row: serving storm with the full
# observability plane on vs off (gate: >= 0.97x baseline requests/s).
bench-obs:
	BENCH_ONLY=obs python bench.py

# The obs CI gate: tests + the overhead row.
obs-smoke: test-obs bench-obs

# First-party static analysis (docs/static-analysis.md): lock-discipline
# race detector (LCK), jit-purity/host-sync (JIT), recompile hazards
# (RCP), broad excepts (BLE).  Fails on any finding not frozen in
# tools/dl4jlint/lint_baseline.json; < 10s budget asserted in tier-1.
lint:
	python -m tools.dl4jlint

# Lint-framework tests only (per-pass fixtures, baseline workflow, the
# zero-new-findings sweep + <10s budget gate).
test-lint:
	python -m pytest tests/ -q -m lint

# Elastic checkpoint plane only (sharded snapshots + SHA-256 integrity,
# kill-at-every-commit-boundary atomicity, N→M topology-elastic restore,
# corruption fallback, real-process kill-mid-save resume acceptance).
test-elastic:
	python -m pytest tests/ -q -m elastic

# Elastic bench row: save sharded on 4 replicas, verified restore on 2 —
# restore latency + bitwise gate + corruption-detected gate.
bench-elastic:
	BENCH_ONLY=elastic python bench.py

# Multichip dryrun on 8 virtual CPU devices + committed evidence log in
# EVIDENCE/.  The platform and device count are stated here, not inferred:
# without them the dryrun takes the host's real devices (on a four-chip
# host: `python -m deeplearning4j_tpu.dryrun 4`).
CPU_MESH = JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8
dryrun:
	$(CPU_MESH) python -m deeplearning4j_tpu.dryrun 8

# The quickest proof that the system still starts on a TPU chip: serve and
# train GPT-2-small at full width, kernels checked against their references.
# Needs a chip (exit 2 without one); `python chip_smoke.py --tiny` rehearses
# the phases on the CPU.
chip-smoke:
	python chip_smoke.py

bench:
	python bench.py

smoke:
	BENCH_ONLY=lenet,transformer python bench.py

# Serving throughput rows only (micro-batched classifier + continuous LM
# + the overload/admission-control row + the fleet mid-storm-kill row).
serving-smoke:
	BENCH_ONLY=serving,servinglm,servingoverload,servingfleet,speculative,disagg,pressure,tenants python bench.py

# Precision-plane tests only (bf16-mixed parity/determinism, loss-scaler
# overflow recovery, int8 serving agreement, dtype round-trips).
test-precision:
	python -m pytest tests/ -q -m precision

# Precision-plane bench row: bf16-mixed train-state reduction, int8
# param-bytes reduction, parity guards (docs/performance.md).
bench-precision:
	BENCH_ONLY=precision python bench.py

# ZeRO-1 weight-update sharding plane only (sharded-vs-replicated fp32
# bitwise parity, loss-scale lockstep, chunked/local-SGD/clip-norm
# composition, hybrid+pipeline DP-axis moments, elastic N->M resume,
# zero-recompile guard).
test-zero:
	python -m pytest tests/ -q -m zero

# The ZeRO leg rides the precision row (composed per-replica
# train-state-bytes columns + the >=3.5x composed-reduction gate).
bench-zero: bench-precision

# Regenerate every committed EVIDENCE/ artifact (see EVIDENCE/README.md)
# on the 8-virtual-CPU-device mesh the runners are written for.
evidence: dryrun
	cd tools/evidence && export $(CPU_MESH) \
	  && python longctx.py && python ui_server.py \
	  && python scaleout.py && python runtime.py && python nlp.py \
	  && python analysis.py && python profiling.py && python hybrid_training.py && python moe.py && python lm_cli.py
