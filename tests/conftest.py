"""Test configuration: force an 8-device virtual CPU mesh before jax imports.

Mirrors the reference's "distributed tests without a real cluster" strategy
(SURVEY §4): the same SPMD code that targets a v5e-8 ICI mesh runs here on
8 virtual CPU devices via XLA_FLAGS.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running quality gates (deselect with "
        "-m 'not slow')")
    config.addinivalue_line(
        "markers", "chaos: fault-injection recovery tests (CPU-only, "
        "fast; run in tier-1)")
    config.addinivalue_line(
        "markers", "serving: serving-engine tests — micro-batcher, bucket "
        "ladder, continuous LM decode (fast; run in tier-1)")
    config.addinivalue_line(
        "markers", "precision: precision-plane invariants — bf16 mixed "
        "parity/determinism, loss-scaler overflow recovery, int8 serving "
        "agreement, dtype round-trips (fast; run in tier-1)")
    config.addinivalue_line(
        "markers", "fleet: serving-fleet tests — failover router, health "
        "ejection/re-admission, rolling weight swaps, fleet chaos (fast; "
        "run in tier-1)")
    config.addinivalue_line(
        "markers", "paged: paged-KV tests — block-table pool parity, "
        "radix prefix reuse + copy-on-write, chunked prefill, page "
        "refcount ledger under chaos, compile-count guard (fast; run "
        "in tier-1)")
    config.addinivalue_line(
        "markers", "obs: observability-plane tests — metrics registry "
        "+ Prometheus exposition, request tracing across the fleet, "
        "compile watcher, training telemetry (fast; run in tier-1)")
    config.addinivalue_line(
        "markers", "procfleet: process-supervision tests — crash "
        "detection/classification, backoff restart, crash-loop "
        "quarantine, cross-host attach, launcher spawn/reap/log "
        "hygiene (real processes via the stdlib stub worker; fast, "
        "run in tier-1 — full `dl4j serve` worker spawns are `slow`)")
    config.addinivalue_line(
        "markers", "zero: ZeRO-1 weight-update sharding plane — "
        "sharded-vs-replicated fp32 bitwise parity, mixed-precision "
        "loss-scale lockstep under the scatter, chunked-fit/local-SGD/"
        "clip-norm/lr-multiplier composition, hybrid+pipeline DP-axis "
        "moment sharding, elastic N-to-M resume, zero-recompile guard "
        "(fast; run in tier-1)")
    config.addinivalue_line(
        "markers", "lint: dl4jlint static-analysis gates — per-pass "
        "fixtures, baseline workflow, the zero-new-findings sweep over "
        "the real tree (pure AST, no jax; fast, run in tier-1)")
    config.addinivalue_line(
        "markers", "spec: speculative-decode tests — drafter plane "
        "(n-gram/prompt-lookup properties, small-model drafter), wide "
        "verify with in-jit accept/rollback, greedy byte-parity vs "
        "generate() across page sizes/chunk widths/adversarial "
        "drafts, page-ledger hygiene under rollback-heavy storms, "
        "unsupported-combo admission (fast; run in tier-1)")
    config.addinivalue_line(
        "markers", "disagg: disaggregated prefill/decode serving — KV "
        "page shipping wire format + integrity, shipped-lane byte "
        "parity vs generate(), role-based fleet routing with the "
        "recompute failure ladder, sticky sessions, SSE token "
        "streaming incl. mid-stream disconnect hygiene (fast; run in "
        "tier-1)")
    config.addinivalue_line(
        "markers", "pressure: overload-survival plane — priority "
        "admission ordering, KV lane preemption with host swap-out "
        "byte-parity, swap eviction/corruption recompute fallback, "
        "brownout degradation ladder incl. hysteresis, pool-exhaustion "
        "chaos regression, role-aware autoscale signals (fast; run in "
        "tier-1)")
    config.addinivalue_line(
        "markers", "tenancy: multi-tenant traffic shaping — tenant "
        "registry/quota token buckets, WFQ ordering composed with "
        "priority classes (one tenant == historic FIFO, pinned), "
        "per-tenant 429s with honest Retry-After, burn-rate-driven "
        "brownout victim selection, fleet ledger reconciliation "
        "(fast; run in tier-1)")
    config.addinivalue_line(
        "markers", "elastic: elastic checkpoint plane — sharded "
        "snapshots with SHA-256 integrity, two-phase atomic commit "
        "(kill -9 at every boundary), N→M topology-elastic restore, "
        "corruption fallback, crash-safe resume incl. a real training "
        "process killed mid-save (fast; run in tier-1)")
    config.addinivalue_line(
        "markers", "hibernate: tiered KV state hierarchy — host/disk "
        "TieredStateStore economy, int8 quantized frames at rest, "
        "idle-session hibernate → resume byte-parity (greedy/seeded, "
        "composed with speculation/radix/chunked prefill), full "
        "process-restart resume over the same disk dir, disk chaos "
        "ladder (torn/truncated/corrupt/missing/ENOSPC/kill -9) with "
        "typed per-victim errors and recompute fallback (fast; run in "
        "tier-1)")
    config.addinivalue_line(
        "markers", "paged_kernel: Pallas paged-attention decode kernel "
        "— fused block-table walk vs. the gather oracle (ragged "
        "n_feed, page straddles, C>1 chunk/verify widths, null lanes, "
        "random-shape sweep), dtype-aware mask constants, and the "
        "serving-ladder zero-new-compiles guard (fast; run in tier-1)")


@pytest.fixture
def rng_key():
    import jax

    return jax.random.PRNGKey(0)
