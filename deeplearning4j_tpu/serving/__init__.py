"""High-throughput serving subsystem.

The inference-side counterpart of the fused training driver
(`runtime/fused.py`): where training amortizes dispatch overhead by
scanning K optimizer steps per XLA call, serving amortizes it by
coalescing K concurrent *requests* per device dispatch.

- `MicroBatcher` — request queue coalescing concurrent requests within a
  `max_wait_ms` window into one padded dispatch (`batcher.py`);
- `BucketLadder` — fixed batch/length shape ladder so any traffic
  pattern compiles a bounded, pre-warmable program set (`bucketing.py`);
- `ServingEngine` — a MultiLayerNetwork behind batcher + ladder with an
  explicit `warmup()` and a compile-count guard (`engine.py`);
- `ContinuousLMServer` — slot-based continuous LM decode: finished
  sequences free their slot and queued prompts join mid-flight
  (`lm.py`).  KV state is block-table PAGED (ISSUE-7):
  a fixed pool of `[pages, page_size]` KV pages addressed through
  per-slot page lists, pages allocated on admission and refcount-freed
  on completion (`PagePool`), shared prompt prefixes prefilled once and
  radix-cached (`RadixPrefixCache`, copy-on-write at the divergence
  page), long prompts fed up to `prefill_chunk` tokens per dispatch; with
  `speculate="ngram"`/`"model"` (ISSUE-13) a cheap drafter
  (`draft.py`: prompt-lookup `NgramDrafter`, small-model
  `ModelDrafter`) proposes up to `draft_len` tokens per greedy lane
  per round and the target verifies the whole chunk in ONE wide
  dispatch with in-jit accept/rollback — ~2-4 committed tokens per
  dispatch at byte-identical greedy output, rollback a block-table
  pointer move (docs/performance.md "The speculative decode cost
  model");
- `ServingMetrics` — queue depth, batch occupancy, p50/p95/p99 latency,
  requests/s and tokens/s, plus the resilience ledger (`rejected`,
  `shed`, `deadline_missed`, `poison_isolated`, `breaker_state`)
  (`metrics.py`), surfaced via the UI server's `GET /serving/stats`.
  Since ISSUE-8 the cells are `obs.registry` metric objects: the same
  values render as Prometheus text at `GET /metrics`, end-to-end
  latency is split into queue-wait vs dispatch-compute histograms,
  every request is traced (`GET /trace/recent`, X-Request-Id
  propagated across the fleet), and XLA compiles are first-class
  (`compiles_total{program_key=...}`) — docs/observability.md;
- serving-plane resilience (`resilience.py`, ISSUE-4): typed failures
  (`ServingOverloadError` -> 503 + Retry-After, `DeadlineExceededError`
  -> 504, `ServingUnavailableError` -> 503, `CircuitOpenError`,
  `UnservableShapeError` -> 400) and the `CircuitBreaker`; bounded
  admission, deadline shedding, poison-request bisection and graceful
  drain are enforced in `batcher.py`/`lm.py`;
- the serving fleet (`fleet.py`, ISSUE-6): `FleetRouter` over N replica
  endpoints — least-loaded + prefix-affinity dispatch, `/readyz`-driven
  health ejection with half-open re-admission (one `CircuitBreaker` per
  replica), failover resubmission with an excluded-replica set, rolling
  weight swaps, queue-depth autoscale through graceful drain — plus the
  `FleetServer` HTTP front (`/fleet/stats`) and `spawn_local_replica`
  for thread-hosted replicas (process-per-replica launching lives in
  `runtime.launcher.FleetProcessLauncher`);
- disaggregated prefill/decode serving (`transfer.py` + role routing
  in `fleet.py`, ISSUE-14): `PageExport`/`serialize_export`/
  `deserialize_export` — the SHA-256-checked KV page shipping wire
  format; `ContinuousLMServer(ship=True)` grows
  `prefill_export`/`admit_with_pages` so prefill-role workers chew
  long prompts and ship the finished pages to the decode worker the
  router picked up front (failure ladder: dead prefill worker ->
  resubmit to a peer; corrupt/rejected shipment -> recompute locally;
  zero failed requests); sticky `session_id` rendezvous affinity keeps
  multi-turn chats on the replica holding their pages with spill-over
  served by shipping; SSE token streaming on `/lm/generate`
  (`"stream": true`) makes time-to-first-token a first-class
  measurement (docs/architecture.md "Disaggregated serving");
- overload survival (`pressure.py`, ISSUE-15): per-request `priority`
  (`interactive` > `batch` > `best_effort`) accepted on every front,
  with the LM pool's admission queue priority-ordered; KV lane
  PREEMPTION with host swap-out (`ContinuousLMServer(preempt=True)`) —
  a higher-priority request that would wait on a dry `PagePool`
  preempts the lowest-priority lane, gathers its pages through the
  shipping wire frame into a byte-capped LRU `SwapStore`, and the lane
  resumes BYTE-IDENTICALLY on re-admission (evicted/corrupt swap state
  is a typed `SwapEvictedError`/SHA-256 failure and the lane recomputes
  from its prompt — still byte-identical); and the `BrownoutLadder`
  degradation automaton (`brownout=True`) that degrades speculation,
  prefill width, then best_effort lanes before shedding anything,
  hysteresis both directions, every transition counted
  (docs/robustness.md "The degradation ladder");
- multi-tenant traffic shaping (`tenancy.py`, ISSUE-16): a
  `TenantRegistry` of named `TenantSpec`s (WFQ weight, token-rate
  quota + burst, SLO target), accepted on every front via the
  `tenant` field or `X-Tenant` header (the built-in `default` tenant
  keeps pre-tenancy behavior byte-for-byte); a `TokenBucketMeter`
  whose 429s carry a Retry-After derived from the bucket's own refill
  (floored at the brownout ladder's exit timescale while it is up); a
  `FairQueueClock` stamping virtual finish times so the admission
  queue orders by (priority rank, vft, arrival) — weighted fair
  sharing WITHIN a class, classes still dominate, one tenant == the
  historic FIFO; an `SLOTracker` whose burn rate picks brownout
  victims (a compliant tenant's best_effort admits through L4 while
  an offender exists); per-tenant ledgers that must re-add to the
  plane totals (`check_fleet_ledger` reports drift as a typed
  failure) — docs/robustness.md "Tenancy & SLOs";
- tiered KV state hierarchy (`hibernate.py`, ISSUE-19): device pages →
  host LRU tier → disk tier of checksummed, atomically-written blobs
  behind a `MANIFEST.json`; `TieredStateStore` is the `SwapStore`
  surface with a durable bottom, so preempted-lane swap state spills
  to disk instead of vanishing, and idle sticky sessions HIBERNATE
  (`ContinuousLMServer(hibernate_idle_s=..., state_dir=...)`): their
  pages leave the device entirely, keyed by a digest of the token
  prefix (`prefix_key`), and a later request — even from a FRESH
  process over the same directory — resumes them byte-identically.
  KV travels and rests per-page int8-quantized by default
  (`quantize_export`, ~4x smaller; `swap_quantize=False` keeps exact
  bytes); torn/truncated/corrupt/missing blobs surface as typed
  errors on the victim alone and the session recomputes from its
  prompt (docs/robustness.md "The state hierarchy");
- process supervision (`procfleet.py`, ISSUE-10): `FleetSupervisor`
  owns spawned worker processes end-to-end — exit-status + `/readyz`
  crash detection with clean/crash/wedged classification, exponential
  jittered backoff restarts re-admitted through warm-then-attach,
  crash-loop quarantine behind a typed `CrashLoopError`, cross-host
  attach by URL with restart delegated to a pluggable `RestartPolicy`,
  rotating per-worker log capture with tails on crash reports, and
  `fleet_process_*` obs counters (docs/robustness.md "Process
  supervision").

See docs/performance.md (serving cost model), docs/architecture.md and
docs/robustness.md ("serving plane", "serving fleet").
"""

from deeplearning4j_tpu.serving.batcher import MicroBatcher
from deeplearning4j_tpu.serving.bucketing import (
    BucketLadder,
    DEFAULT_BATCH_BUCKETS,
    pow2_length_buckets,
)
from deeplearning4j_tpu.serving.draft import (
    Drafter,
    ModelDrafter,
    NgramDrafter,
)
from deeplearning4j_tpu.serving.engine import ServingEngine
from deeplearning4j_tpu.serving.fleet import (
    FleetClientError,
    FleetRouter,
    FleetServer,
    ROLE_BOTH,
    ROLE_DECODE,
    ROLE_PREFILL,
    Replica,
    check_fleet_ledger,
    spawn_local_replica,
)
from deeplearning4j_tpu.serving.hibernate import (
    DiskTier,
    TieredStateStore,
    prefix_key,
)
from deeplearning4j_tpu.serving.lm import ContinuousLMServer
from deeplearning4j_tpu.serving.metrics import ServingMetrics
from deeplearning4j_tpu.serving.paged import (
    PageLeakError,
    PagePool,
    RadixPrefixCache,
)
from deeplearning4j_tpu.serving.pressure import (
    BrownoutLadder,
    PRIORITY_CLASSES,
    PressureConfig,
    SwapEvictedError,
    SwapStore,
    normalize_priority,
)
from deeplearning4j_tpu.serving.procfleet import (
    CrashLoopError,
    FleetSupervisor,
    RestartPolicy,
    WorkerSpec,
)
from deeplearning4j_tpu.serving.resilience import (
    CircuitBreaker,
    CircuitOpenError,
    DeadlineExceededError,
    ServingError,
    ServingOverloadError,
    ServingUnavailableError,
    TenantQuotaError,
    UnservableShapeError,
)
from deeplearning4j_tpu.serving.tenancy import (
    DEFAULT_TENANT,
    FairQueueClock,
    SLOTracker,
    TenantRegistry,
    TenantSpec,
    TokenBucketMeter,
)
from deeplearning4j_tpu.serving.transfer import (
    PageExport,
    PageShipError,
    check_compatible,
    deserialize_export,
    quantize_export,
    serialize_export,
)

__all__ = [
    "BrownoutLadder",
    "BucketLadder",
    "CircuitBreaker",
    "CircuitOpenError",
    "ContinuousLMServer",
    "CrashLoopError",
    "DEFAULT_BATCH_BUCKETS",
    "DEFAULT_TENANT",
    "DeadlineExceededError",
    "DiskTier",
    "Drafter",
    "FairQueueClock",
    "FleetClientError",
    "FleetRouter",
    "FleetServer",
    "FleetSupervisor",
    "MicroBatcher",
    "ModelDrafter",
    "NgramDrafter",
    "PageExport",
    "PageShipError",
    "PRIORITY_CLASSES",
    "PressureConfig",
    "RestartPolicy",
    "ROLE_BOTH",
    "ROLE_DECODE",
    "ROLE_PREFILL",
    "PageLeakError",
    "PagePool",
    "RadixPrefixCache",
    "Replica",
    "ServingEngine",
    "ServingError",
    "ServingMetrics",
    "SLOTracker",
    "ServingOverloadError",
    "ServingUnavailableError",
    "SwapEvictedError",
    "SwapStore",
    "TenantQuotaError",
    "TenantRegistry",
    "TenantSpec",
    "TieredStateStore",
    "TokenBucketMeter",
    "UnservableShapeError",
    "WorkerSpec",
    "check_compatible",
    "check_fleet_ledger",
    "deserialize_export",
    "normalize_priority",
    "pow2_length_buckets",
    "prefix_key",
    "quantize_export",
    "serialize_export",
    "spawn_local_replica",
]
