"""Continuous batching for LM generation.

`generate()` decodes one request (or one fixed batch) to completion:
requests arriving mid-decode wait for the whole previous decode.  The
continuous server instead keeps a fixed pool of `slots` decode lanes
and advances every active lane each device step:

- a finished sequence frees its slot immediately;
- a queued prompt joins mid-flight — prefill rides the same per-token
  step (prefill-as-decode), so admission never interrupts other lanes;
- every dispatch shape is fixed (`slots` lanes, whatever is inactive
  rides as masked padding), so the WHOLE serving lifetime runs a fixed,
  pre-compilable program set per config.

KV state is block-table paged (ISSUE-7): one fixed pool
`[L, pages, page_size, H*K]` (lane-dense rows, updated in place by the
donating step), per-slot page lists carried as a `[slots, max_pages]`
int32 block table inside the jitted step
(`parallel.generation.make_paged_step`).  Pages are allocated on
admission and refcount-freed on completion (`serving/paged.py`), so
device capacity is sum-of-actual-lengths instead of slots * max_len.
On top of it:

- **radix prefix reuse** — a host-side radix tree over prompt token
  prefixes maps to refcounted page runs; a request whose prompt
  shares a cached prefix skips prefill for those tokens entirely
  (copy-on-write at the divergence page), which is what the fleet's
  prefix-affinity router (ISSUE-6) was set up to feed;
- **chunked prefill** — a long prompt feeds up to `prefill_chunk`
  tokens per dispatch instead of one, so admission latency shrinks
  by ~chunk× while active decode lanes keep advancing every step.

The compile-count discipline holds: one program per
(config, pages, page_size, chunk) — a decode-step (chunk 1), one
prefill-chunk step when `prefill_chunk > 1`, and the copy-on-write
page copy; `warmup()` compiles all of them before traffic (after it,
no request can trigger an XLA compile), otherwise each compiles on
its first dispatch like every other serving program.  On a TPU the
steps attend through the fused block-table kernel, elsewhere through
the gather oracle (`parallel.paged_kernel.paged_kernel_enabled`).

Greedy and plain-temperature sampling run in the slot pool (sampling is
seeded per request: `fold_in(PRNGKey(seed), tokens_generated)`, so a
request's output does not depend on what shared its dispatches).
top-k/top-p/beam requests take the legacy whole-sequence path in
`ui/server.py` — their filters are static program variants, not per-slot
switches.

**Speculative multi-token decode** (ISSUE-13, `speculate="ngram"` or
`"model"`): a cheap drafter (`serving/draft.py`)
proposes up to `draft_len` continuation tokens per greedy decode lane
per round; the target model scores `[last_committed, d_1..d_k]` in ONE
wide dispatch through the SAME chunked-feed program ladder chunked
prefill rides, and the accept rule runs in-jit
(`parallel.generation.make_spec_step`): the longest draft prefix the
target's argmax agrees with is committed, plus the target's own bonus
token at the divergence point.  Greedy output is byte-identical to
1-token decode by construction.  Rollback is a pointer move on the
paged pool — rejected columns wrote k/v into the lane's own future
pages (or the null page), positions the causal mask hides, so the host
just advances `pos` by 1 + accepted; pages were allocated at admission
for the whole request and flow back through the normal `PagePool`
refcount discipline at completion, never per round.  SAMPLING lanes
(temperature > 0) are never drafted for — verifying a sampled draft
greedily would mis-sample — and fall back to 1-token decode per round
while riding the same dispatches.
Accounting: accept-rate / tokens-per-round counters in
`ServingMetrics`, a `speculate` section in `stats()`, and
drafted/accepted attrs on each request's decode trace span.

**Disaggregated serving hooks** (ISSUE-14, `ship=True`):
the pool speaks the KV page-shipping wire plane (`serving/transfer.py`)
so a fleet can split worker roles — prefill workers chew long prompts
and ship the finished pages to decode workers:

- `prefill_export(...)` admits a request normally (radix reuse +
  chunked prefill included), but at prefill completion — after the
  first token is sampled and the prompt pages enter the radix tree —
  the lane's pages are gathered OUT of the pool in one fixed-shape
  dispatch (`parallel.generation.make_page_gather`) and the request
  resolves to a `PageExport` instead of decoding further.  The radix
  tree keeps the prefix, so repeated shared-prefix prefills stay
  nearly free on the prefill worker.
- `admit_with_pages(export)` allocates the lane's full page budget
  from the local pool, installs the shipped pages in ONE batched
  dispatch (`make_page_install`, the pending-install plane riding the
  same pre-feed window as pending CoW copies), registers the prompt's
  full pages in the local radix tree, and joins the lane mid-flight
  exactly like a chunked-prefill completion: pos/fed/committed state
  arrives with the shipment, decode continues through the normal step.
  KV at position t is a pure function of tokens[0..t] and the weights,
  so a shipped lane's output is byte-identical to a locally-prefilled
  one, greedy or seeded sampling.

**Token streaming + TTFT**: `generate_stream(...)` yields each
committed token as it lands (speculative rounds can commit several at
once — each is yielded individually), backing the SSE leg of
`/lm/generate`; a consumer that goes away mid-stream abandons the
request, freeing its slot and pages at the next admit round.  Every
request stamps time-to-first-token into the `ttft` histogram — the
latency the prefill/decode split exists to protect.  Per-request
`session_id`s feed sticky-session accounting (`session_affinity_hits`)
whether or not a fleet router is in front.

**Overload survival** (ISSUE-15, `serving/pressure.py`): every request
carries a `priority` (`interactive` > `batch` > `best_effort`, default
interactive) and the admission queue is kept ordered by
(priority, arrival) — one class degenerates to the historic FIFO.
With `preempt=True` (paged KV), a higher-priority request that would
otherwise wait on a dry `PagePool` PREEMPTS the lowest-priority active
lane: its pages are gathered in one fixed-shape dispatch, serialized
through the shipping wire frame (SHA-256 over the payload) into a
bounded host-side `SwapStore` (LRU, byte-capped), its slot and pages
freed, and the request requeued with its original arrival stamp.  On
re-admission the lane restores through the same pending-install plane
a shipped lane uses and resumes BYTE-IDENTICALLY — greedy and seeded
sampling alike, because the `fold_in(seed, count)` automaton sees
identical inputs — composing with speculation, radix prefix reuse and
chunked prefill.  A victim whose swap state was evicted (typed
`SwapEvictedError`) or corrupted (the SHA-256 check) recomputes from
its prompt: deterministic decode makes even that path byte-identical,
so the loss is visible only in the ledger and the trace.  With
`brownout` on, a pool-pressure automaton (`BrownoutLadder`:
pages-free + queue-depth signals, hysteresis both directions) degrades
gracefully before shedding — 1: speculation off, 2: prefill ride-along
width shrunk, 3: best_effort lanes preempted proactively, 4:
best_effort admissions shed with Retry-After — never touching
interactive until the ladder is exhausted; every transition is
counted, traced and exposed (docs/robustness.md "The degradation
ladder").

**Multi-tenant traffic shaping** (ISSUE-16, `serving/tenancy.py`):
with a `TenantRegistry` installed every request carries a `tenant`
(default: the built-in unmetered ``default`` tenant, so registry-less
deployments and pre-tenancy clients keep exact behavior).  Admission
charges the request's token cost against the tenant's token bucket
BEFORE the shared gate — an over-quota tenant gets a typed 429 whose
Retry-After derives from its own bucket refill, and its refusals never
consume the queue bound other tenants share.  The queue order becomes
(priority rank, WFQ virtual finish time, arrival): priority still
dominates absolutely; weighted-fair queuing only interleaves tenants
WITHIN a class, and one tenant degenerates to the historic FIFO.  The
brownout ladder's L3 preemption and L4 shed become tenant-aware: while
any tenant is over quota or burning SLO budget, victims are taken from
the worst offender first and a compliant tenant is never touched;
without an offender the rungs keep their PR-15 global behavior.
Per-tenant ledgers (tokens in/out, throttles, SLO burn rate) ride
``/serving/stats`` under ``tenancy`` and Prometheus under the
``serving_lm_tenant_*`` families (docs/robustness.md "Tenancy &
SLOs").

**The block round** (ISSUE-42): a block-diffusion model
(`cfg.block_length` B > 1: causal between blocks of B positions dealt by
absolute position, bidirectional inside one) does not decode "one lane,
one new token".  A lane in its decode phase holds its CURRENT BLOCK: B
token ids and a `known` flag a column (a flag, never a comparison with
the mask id, which traffic may send as an ordinary token).  A **denoise
round** feeds the block at `pos .. pos + B - 1` (masked columns as
`cfg.mask_token`), writes its provisional K/V into the lane's own next
rows (as a rejected draft's are: `pos` does not move, so nothing reads
them later), and gets back from the step program
(`parallel.generation.make_block_step`) the block after this round's
unmasking: `denoise_steps` S gives the static schedule (the `B / S`
masked columns of highest confidence a round, ties to the lower
position), `unmask="dynamic"` every masked column whose confidence
passes `tau` and the single best one if none does.  When no column is
masked the lane's next round is a **commit pass**: the same feed with
every column known, after which `pos += B`, the block's tokens are
committed (`_commit_tokens`, B at once: a stream yields in bursts of at
most B and never a token that could still change) and the next block
starts all masked.  Prefill feeds whole blocks (`page_size` and
`prefill_chunk` are multiples of B); the prompt's last `len % B` tokens
ride the first denoise round as known columns; the last block of an
answer is denoised whole and what lies past `max_new` is dropped.  The
narrow program is `[slots, B]`.  THE RULE, written once: **a lane's
durable state is its committed blocks.**  Preemption, swap-out,
hibernation and every radix insert act at the last committed block
(`pos`), and a block in flight is denoised again after a restore
(`serving_lm_block_redone_total`).  **One round ahead of the host**:
under the static schedule which lanes denoise, commit, begin a block or
end follows from counts the host has (a step unmasks `B / S` columns),
and only the token ids come from the device; so round N + 1 is
dispatched BEFORE round N is read, a lane whose block round N is still
unmasking feeds it from round N's result left on the device (the step
program's `held` and `carry`), and the device does not wait while the
host reads, folds, streams and marshals (`_block_round`; the dynamic
schedule's counts come from the device, so its round is read before the
next is built).  The host's account of a lane is then one round behind
the device's: `pos` and `fed` move at dispatch, tokens at the read, a
lane that ends is freed at the read of its last round and idles for the
round between, and whoever moves a lane's durable state reads the round
in flight first (`_settle_block_round`).  Refused where the server is
built: speculation (a draft has no meaning inside a block) and page shipping
(`prefill_export` resolves a lane at prefill completion, which for a
block model is mid-block); sampling at a temperature is refused a
request.

Resilience contract (ISSUE-4, mirrors `batcher.MicroBatcher`): bounded
admission (`max_queue_depth` -> `ServingOverloadError`), per-request
deadlines shed at the admitter before a prompt ever occupies a slot
(`DeadlineExceededError`), an abandoned request's slot (and its pages)
is freed so a timed-out client stops costing decode steps, an optional
circuit breaker fast-fails admission after consecutive step failures,
and `begin_drain()`/`drain()` implement the SIGTERM grace window.  A
failed dispatch consumed its donated KV buffers AND invalidated the
page contents, so the recovery path rebuilds the device pool and resets
the allocator + radix tree together — a stale tree entry pointing into
a zeroed pool would serve silent garbage.
"""

from __future__ import annotations

import collections
import queue as _queue
import threading
import time
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np

from deeplearning4j_tpu.obs.compilewatch import (
    STAGES,
    compile_scope,
    compile_watcher,
    over_keys,
)
from deeplearning4j_tpu.obs.registry import MetricsRegistry
from deeplearning4j_tpu.obs.trace import (
    PhaseClock,
    TraceRecorder,
    new_request_id,
    span,
    trace,
)
from deeplearning4j_tpu.serving.hibernate import (
    TieredStateStore,
    prefix_key,
)
from deeplearning4j_tpu.serving.metrics import ServingMetrics
from deeplearning4j_tpu.serving.paged import (
    PagePool,
    RadixPrefixCache,
    StatePool,
)
from deeplearning4j_tpu.serving.pressure import (
    BrownoutLadder,
    PRIORITY_RANK,
    PressureConfig,
    RANK_BEST_EFFORT,
    SwapEvictedError,
    normalize_priority,
)
from deeplearning4j_tpu.serving.resilience import (
    CircuitBreaker,
    CircuitOpenError,
    DeadlineExceededError,
    ServingError,
    ServingOverloadError,
    ServingUnavailableError,
    check_admission,
)
from deeplearning4j_tpu.serving.tenancy import (
    DEFAULT_TENANT,
    TenantQuotaError,
    TenantRegistry,
)
from deeplearning4j_tpu.serving.transfer import (
    PageExport,
    PageShipError,
    check_compatible,
    deserialize_export,
    model_signature,
    quantize_export,
    serialize_export,
)


def validate_request(cfg, prompt_ids, max_new_tokens: int) -> List[int]:
    """THE serving-request contract, shared by the HTTP endpoint (as
    400s) and `ContinuousLMServer` (as ValueErrors): non-empty prompt of
    in-vocab tokens, positive budget, and prompt + new tokens within the
    model's fixed max_len cache.  A bad request must fail HERE, before
    it reaches a decode worker — an error raised mid-drain fails every
    co-travelling request in the slot pool."""
    ids = [int(t) for t in prompt_ids]
    if not ids:
        raise ValueError("prompt_ids must contain at least one token")
    bad = [t for t in ids if not 0 <= t < cfg.vocab_size]
    if bad:
        raise ValueError(f"prompt_ids outside vocab "
                         f"[0, {cfg.vocab_size}): {bad[:5]}")
    max_new = int(max_new_tokens)
    if max_new < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new}")
    if len(ids) + max_new > cfg.max_len:
        raise ValueError(
            f"prompt ({len(ids)} tokens) + max_new_tokens ({max_new}) "
            f"exceeds max_len ({cfg.max_len}); shorten one of them")
    return ids


class _LMRequest:
    __slots__ = ("prompt", "max_new", "temperature", "seed", "event",
                 "result", "error", "enqueued", "deadline", "abandoned",
                 "request_id", "t_installed", "t_done", "prefix_matched",
                 "drafted", "accepted", "export", "export_result",
                 "import_pages", "stream", "session_id", "t_first",
                 "priority", "rank", "swap_key", "swap_restore",
                 "swap_error", "stream_pushed", "preempted",
                 "tenant", "vft", "cost", "prefill_rounds",
                 "prefill_wide_rounds", "snapshot_matched",
                 "prefill_len", "unmask_steps", "surplus", "surplus_steps",
                 "blocks", "denoise_rounds")

    def __init__(self, prompt: List[int], max_new: int, temperature: float,
                 seed: int, deadline: Optional[float] = None,
                 request_id: Optional[str] = None):
        self.prompt = list(prompt)
        self.max_new = int(max_new)
        self.temperature = float(temperature)
        self.seed = int(seed)
        self.event = threading.Event()
        self.result: Optional[List[int]] = None
        self.error: Optional[BaseException] = None
        self.enqueued = time.perf_counter()
        self.deadline = deadline   # absolute perf_counter time, or None
        self.abandoned = False     # client gave up waiting
        self.request_id = request_id       # X-Request-Id (ISSUE-8)
        self.t_installed: Optional[float] = None  # slot-install stamp
        self.t_done: Optional[float] = None       # decode-complete stamp
        self.prefix_matched = 0            # radix-cache tokens reused
        self.drafted = 0                   # speculative tokens proposed
        self.accepted = 0                  # speculative tokens accepted
        # disaggregated serving (ISSUE-14)
        self.export = False                # resolve at prefill completion
        self.export_result: Optional[PageExport] = None
        self.import_pages: Optional[PageExport] = None  # shipped-in lane
        self.stream = None                 # per-token queue (SSE leg)
        self.session_id: Optional[str] = None
        self.t_first: Optional[float] = None  # first-committed-token stamp
        # overload survival (ISSUE-15)
        self.priority = "interactive"      # admission class
        self.rank = 0                      # PRIORITY_RANK[priority]
        self.swap_key: Optional[str] = None   # SwapStore key while queued
        self.swap_restore = False          # import_pages came from swap
        self.swap_error: Optional[str] = None  # typed restore failure
        self.stream_pushed = 0             # tokens already streamed
        self.preempted = 0                 # times this lane was preempted
        # multi-tenant traffic shaping (ISSUE-16)
        self.tenant = DEFAULT_TENANT       # normalized tenant name
        self.vft = 0.0                     # WFQ virtual finish time
        self.cost = self.max_new + len(self.prompt)  # token cost charged
        # rounds this request's lane rode while prefilling, and how many
        # of them dispatched the wide program (the `prefill` span's attrs)
        self.prefill_rounds = 0
        self.prefill_wide_rounds = 0
        # prompt tokens whose state came from a snapshot (recurrent models)
        self.snapshot_matched = 0
        # prompt tokens a prefill feeds: all of them, or a block model's
        # whole blocks (the tail rides the first denoise round)
        self.prefill_len = len(self.prompt)
        # the block round's account (block models): the denoise step,
        # within its block, at which each committed token was unmasked;
        # what the last block held past the answer's end, and its steps;
        # blocks committed (a commit pass each) and denoise lane-rounds
        self.unmask_steps: List[int] = []
        self.surplus: List[int] = []
        self.surplus_steps: List[int] = []
        self.blocks = 0
        self.denoise_rounds = 0


class _Block:
    """A block in flight at `first .. first + B - 1`: its ids (the mask id
    where a column is masked), a column's known flag, the denoise step that
    unmasked it (-1: a prompt token) and the denoise steps the block has
    had, all as of the last round the host has read."""
    __slots__ = ("first", "tokens", "known", "steps", "step")

    def __init__(self, first: int, tail: List[int], width: int,
                 mask_token: int):
        rest = width - len(tail)
        self.first = first
        self.tokens = list(tail) + [mask_token] * rest
        self.known = [True] * len(tail) + [False] * rest
        self.steps = [-1] * width
        self.step = 0


class _Slot:
    __slots__ = ("req", "pos", "fed", "generated",
                 "table", "owned", "shared", "inserted",
                 "row", "trail", "trail_pos", "block")

    def __init__(self):
        self.req: Optional[_LMRequest] = None
        self.pos = 0          # next cache position to write
        self.fed = 0          # prompt tokens already fed (prefill cursor)
        self.generated: List[int] = []
        # paged-KV bookkeeping
        self.table: Optional[np.ndarray] = None   # [max_pages] int32 row
        self.owned: List[int] = []    # pages this lane allocated
        self.shared: List[int] = []   # prefix pages reused from the tree
        self.inserted = False         # prompt pages registered in the tree
        # a recurrent model's lane: its live state row, the trailing row
        # (the state at the last page boundary it landed on) and that
        # boundary's position (None: the trailing row holds nothing yet)
        self.row = 0
        self.trail = 0
        self.trail_pos: Optional[int] = None
        # a block model's lane in its decode phase: the block it denoises
        # at `pos .. pos + B - 1` (None: not begun, or its commit pass is
        # dispatched)
        self.block: Optional[_Block] = None

    @property
    def active(self) -> bool:
        return self.req is not None


class ContinuousLMServer:
    """Slot-based continuous decode over one TransformerLM.

    `generate(prompt_ids, max_new_tokens)` is thread-safe and blocks
    until the request's sequence is complete; any number of requests
    share the device via the slot pool, served from the block-table
    paged pool with radix prefix reuse and chunked prefill.
    """

    def __init__(self, cfg, params, slots: int = 4,
                 metrics: Optional[ServingMetrics] = None,
                 max_queue_depth: Optional[int] = None,
                 default_deadline_s: Optional[float] = None,
                 breaker: Optional[CircuitBreaker] = None,
                 page_size: int = 16,
                 pages: Optional[int] = None, prefill_chunk: int = 8,
                 speculate: str = "off", draft_len: int = 4,
                 drafter=None, draft_model=None, ship: bool = False,
                 preempt: bool = False, swap_bytes: int = 64 << 20,
                 brownout=None, tenants=None,
                 hibernate_idle_s: Optional[float] = None,
                 state_dir: Optional[str] = None,
                 state_disk_bytes: int = 1 << 30,
                 swap_quantize: bool = True,
                 state_rows: Optional[int] = None,
                 snapshot_every: Optional[int] = None,
                 denoise_steps: Optional[int] = None,
                 unmask: Optional[str] = None,
                 tau: Optional[float] = None,
                 tracer: Optional[TraceRecorder] = None,
                 registry: Optional[MetricsRegistry] = None):
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        if max_queue_depth is not None and max_queue_depth < 1:
            raise ValueError(f"max_queue_depth must be >= 1 or None, got "
                             f"{max_queue_depth}")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {prefill_chunk}")
        if speculate not in ("off", "ngram", "model"):
            raise ValueError(f"speculate must be 'off', 'ngram' or "
                             f"'model', got {speculate!r}")
        if drafter is not None and speculate == "off":
            speculate = "custom"           # injected Drafter instance
        if speculate != "off" and draft_len < 1:
            raise ValueError(f"draft_len must be >= 1, got {draft_len}")
        if hibernate_idle_s is not None and float(hibernate_idle_s) < 0:
            raise ValueError(
                f"hibernate_idle_s must be >= 0, got {hibernate_idle_s}")
        if state_dir is not None and not (preempt
                                          or hibernate_idle_s is not None):
            raise ValueError(
                "state_dir names a disk tier nothing would write: it "
                "requires preempt=True or hibernate_idle_s (serve with "
                "-lm-preempt or -lm-hibernate-idle-s)")
        # a recurrent model (state rows beside the pages, snapshots under
        # the radix tree): what of the serving plane it is refused
        self.recurrent = bool(getattr(cfg, "recurrent", False))
        if not self.recurrent and (state_rows is not None
                                   or snapshot_every is not None):
            raise ValueError(
                "state_rows / snapshot_every size the state pool of a "
                "model with recurrent layers; this one has none")
        if self.recurrent:
            from deeplearning4j_tpu.parallel.transformer import (
                UnsupportedLayerKind,
            )

            for on, what in ((speculate != "off", "speculative decoding"),
                             (ship, "page shipping (ship=True)"),
                             (preempt, "preemption with host swap-out"),
                             (hibernate_idle_s is not None,
                              "session hibernation")):
                if on:
                    raise UnsupportedLayerKind(
                        f"{what} moves or rewinds a lane by its K/V pages; "
                        f"this model's recurrent layers keep a state row "
                        f"a lane that it would leave behind")
            state_rows = (4 * int(slots) if state_rows is None
                          else int(state_rows))
            if state_rows < 2 * int(slots):
                raise ValueError(
                    f"state_rows must be at least 2 a lane "
                    f"({2 * int(slots)}), got {state_rows}")
            if snapshot_every is None:
                snapshot_every = 16 * max(int(page_size),
                                          int(prefill_chunk))
            if (snapshot_every % int(page_size)
                    or snapshot_every % int(prefill_chunk)):
                raise ValueError(
                    f"snapshot_every ({snapshot_every}) must be a multiple "
                    f"of page_size ({page_size}) and prefill_chunk "
                    f"({prefill_chunk})")
        self.state_rows = state_rows
        self.snapshot_every = snapshot_every
        # the block round (module docstring): B = 1 is every causal model
        self.block = int(getattr(cfg, "block_length", 1))
        if self.block == 1:
            if not (denoise_steps is None and unmask is None
                    and tau is None):
                raise ValueError(
                    "denoise_steps / unmask / tau set the unmasking "
                    "schedule of a block-diffusion model "
                    "(cfg.block_length > 1); this one is causal")
        else:
            from deeplearning4j_tpu.parallel.transformer import (
                UnsupportedLayerKind,
            )

            for on, what in ((speculate != "off" or drafter is not None,
                              "speculative decoding: a draft has no "
                              "meaning inside a block"),
                             (ship, "page shipping (ship=True): a lane "
                              "would leave at prefill completion, which "
                              "is mid-block")):
                if on:
                    raise UnsupportedLayerKind(
                        f"a block-diffusion model (block_length "
                        f"{self.block}) is refused {what}")
            if int(page_size) % self.block or (
                    int(prefill_chunk) % self.block):
                raise ValueError(
                    f"page_size ({page_size}) and prefill_chunk "
                    f"({prefill_chunk}) must hold whole blocks of "
                    f"{self.block}: a cached page is valid for any "
                    f"request that shares its prefix only because its "
                    f"K/V depend on tokens up to the page's end")
            denoise_steps = (self.block if denoise_steps is None
                             else int(denoise_steps))
            if denoise_steps < 1 or self.block % denoise_steps:
                raise ValueError(
                    f"denoise_steps ({denoise_steps}) must divide the "
                    f"block length ({self.block})")
            unmask = "static" if unmask is None else unmask
            if unmask not in ("static", "dynamic"):
                raise ValueError(f"unmask must be 'static' or 'dynamic', "
                                 f"got {unmask!r}")
            tau = 0.9 if tau is None else float(tau)
            if not 0.0 <= tau <= 1.0:
                raise ValueError(f"tau must be in [0, 1], got {tau}")
        self.denoise_steps = denoise_steps
        self.unmask = unmask
        self.tau = tau
        self.cfg = cfg
        self.params = params
        self.n_slots = int(slots)
        self.max_queue_depth = max_queue_depth
        self.default_deadline_s = default_deadline_s
        self.breaker = breaker
        self.page_size = int(page_size)
        from deeplearning4j_tpu.parallel.generation import pages_per_seq

        self.max_pages = pages_per_seq(cfg, self.page_size)
        # `pages` = usable KV pages in the pool (the reserved null page
        # is on top).  Default: full worst-case capacity — every slot
        # can hold max_len, and prefix sharing turns into extra
        # effective capacity rather than a correctness question.
        self.kv_pages = (int(pages) if pages is not None
                         else self.n_slots * self.max_pages)
        if self.kv_pages < 1:
            raise ValueError(f"pages must be >= 1, got {self.kv_pages}")
        self.prefill_chunk = int(prefill_chunk)
        # what the platform rule gives the step programs (reported in
        # `stats()`; the makers apply the same rule themselves)
        from deeplearning4j_tpu.parallel import paged_kernel

        self.paged_kernel = paged_kernel.paged_kernel_enabled()
        self._walk_plans: Dict[int, Tuple[int, int]] = {}
        self.speculate = speculate
        self.draft_len = int(draft_len)
        self._drafter = drafter            # built in _start_locked if None
        self._draft_model = draft_model    # optional (cfg, params) pair
        # the ONE wide program width: chunked prefill and speculative
        # verify share it ([last, d_1..d_k] needs draft_len+1 columns)
        if speculate != "off":
            self.spec_width = max(self.prefill_chunk, self.draft_len + 1)
        else:
            self.spec_width = 0
        self.metrics = metrics if metrics is not None else ServingMetrics()
        # observability plane (ISSUE-8): publish the LM pool's cells on
        # the server registry, trace every request, and install the
        # compile watcher before any program compiles
        self.tracer = tracer
        if registry is not None:
            self.metrics.register_into(registry, plane="lm")
        self._compile_watch = compile_watcher()
        if breaker is not None:
            breaker.add_listener(self.metrics.set_breaker_state)
            self.metrics.set_breaker_state(breaker.state)
        self._queue = collections.deque()
        self._cond = threading.Condition()
        self._running = False
        self._accepting = True
        self._thread: Optional[threading.Thread] = None
        self._cache = None    # lazy: (k, v) device buffers
        self._step = None     # ONE dispatch entry point (tests stub it)
        self._write_path: Dict[str, str] = {}   # set with the programs
        self._decode_step = None
        self._chunk_step = None
        self._copy = None
        self._pool: Optional[PagePool] = None
        self._tree: Optional[RadixPrefixCache] = None
        self._pending_cow: List[Dict] = []
        # recurrent models: the state rows' allocator, the row-copy
        # program, restores awaiting their copy, and what the tree had
        # evicted at the last count
        self._states: Optional[StatePool] = None
        self._state_copy = None
        # copies a dispatch of the row-copy program: a round seldom has
        # more than a lane or two at a boundary, and every entry, spare or
        # not, moves a whole row (12.6 MB for Solar-Open2's three layers)
        self._copy_batch = max(2, self.n_slots // 2)
        self._pending_state: List[Dict] = []
        self._snap_evicted_seen = 0
        # disaggregation plane (ISSUE-14): page export/import programs,
        # shipments awaiting their device install, and the sticky-session
        # LRU (session_id -> last-seen tick) behind session_affinity_hits
        self.ship = bool(ship)
        self._gather = None
        self._install = None
        self._pending_install: List[Dict] = []
        # overload-survival plane (ISSUE-15): priority preemption with
        # host swap-out, and the brownout degradation ladder.  All of
        # it is worker-thread state mutated under self._cond (the same
        # single-mutator discipline as the page pool).
        self.preempt = bool(preempt)
        # tiered state hierarchy (ISSUE-19): ONE store serves both the
        # preemption swap plane (process-local "swap-<n>" keys) and the
        # hibernation plane (content-addressed "hib-<digest>" keys).
        # With a state_dir the host LRU tier spills to a checksummed
        # disk tier, so idle-session capacity is bounded by disk.
        self.hibernate_idle_s = (float(hibernate_idle_s)
                                 if hibernate_idle_s is not None else None)
        self.hibernate = self.hibernate_idle_s is not None
        self.swap_quantize = bool(swap_quantize)
        self.state_dir = str(state_dir) if state_dir is not None else None
        if self.preempt or self.hibernate:
            self._swap = TieredStateStore(
                int(swap_bytes), disk_dir=self.state_dir,
                disk_bytes=int(state_disk_bytes))
            if self.state_dir is not None:
                # a crashed predecessor's process-local swap keys can
                # never restore in THIS process — GC them (counted);
                # hibernated prefixes are content-addressed and stay
                # valid across restarts, so they survive untouched
                self._swap.gc("swap-")
        else:
            self._swap = None
        self._swap_seq = 0
        # idle-session tracking for hibernation: session_id -> the full
        # committed token sequence + last-activity stamp, LRU-bounded.
        # Worker-thread state like the slots (finish-fold writes it,
        # the admit-round sweep drains it).
        self._hib_sessions: "collections.OrderedDict[str, Dict]" = (
            collections.OrderedDict())
        if brownout is None or brownout is False:
            self._pressure = None
        elif isinstance(brownout, PressureConfig):
            self._pressure = BrownoutLadder(brownout)
        else:
            self._pressure = BrownoutLadder()
        # multi-tenant traffic shaping (ISSUE-16): None = tenancy off
        # (zero behavioral change); a registry/dict/JSON turns on the
        # quota meter, WFQ queue ordering and SLO-aware victim
        # selection.  Meter charges and WFQ stamps happen under
        # self._cond like every other admission mutation.
        self.tenants = TenantRegistry.coerce(tenants)
        # observed cadence of pressure-ladder updates (EWMA seconds):
        # the Retry-After base for the L4 shed and the quota 429 —
        # down_dwell calm updates at this cadence is the ladder's real
        # exit timescale (ISSUE-16 satellite fix)
        self._pressure_tick_s = 0.05
        self._pressure_stamp: Optional[float] = None
        self._sessions: "collections.OrderedDict[str, int]" = (
            collections.OrderedDict())
        self._session_capacity = 1024
        self._warm_req: Optional[threading.Event] = None
        self._warming: Optional[threading.Event] = None  # warm in flight
        self._warm_error: Optional[BaseException] = None
        self._slots = [_Slot() for _ in range(self.n_slots)]
        self._steps = 0
        # a block model's round that is dispatched and not read yet (the
        # static schedule runs one round ahead of the host: `_block_round`)
        self._ahead: Optional[Dict] = None
        # the worker's wall time by phase of the round (its thread only)
        self._clock = PhaseClock("lm:")
        self._warmup_stats: Optional[Dict] = None

    # ---- client side ------------------------------------------------------

    def _required_pages(self, plen: int, max_new: int) -> int:
        """Pages one lane needs: positions written = plen + max_new - 1
        (the final sampled token is returned, never fed); a block model
        writes whole blocks through its answer's last one."""
        rows = plen + max_new - 1
        if self.block > 1:
            rows = -(-(plen + max_new) // self.block) * self.block
        return -(-rows // self.page_size)

    def _kv_rows(self, n_tokens: int) -> int:
        """How many leading positions of a finished sequence of
        `n_tokens` hold K/V made from its own tokens alone: all but the
        last sampled one, or a block model's whole blocks (the last
        block's rows also saw what was dropped past the answer's end)."""
        if self.block > 1:
            return n_tokens // self.block * self.block
        return n_tokens - 1

    def validate(self, prompt_ids, max_new_tokens: int) -> List[int]:
        """`validate_request` against this server's config, plus the
        paged pool's hard capacity: a request that could never fit the
        whole pool is the client's error, not an overload."""
        ids = validate_request(self.cfg, prompt_ids, max_new_tokens)
        need = self._required_pages(len(ids), int(max_new_tokens))
        if need > self.kv_pages:
            raise ValueError(
                f"request needs {need} KV pages "
                f"({len(ids)} prompt + {int(max_new_tokens)} new, "
                f"page_size {self.page_size}) but the pool holds "
                f"{self.kv_pages}; raise -lm-pages or shorten it")
        return ids

    def _retry_after_locked(self) -> float:
        lat = self.metrics.latency.summary()
        per_req = (lat.get("p50_ms", 100.0) or 100.0) / 1e3
        return max(0.1, per_req * (1 + len(self._queue) / self.n_slots))

    def _ladder_retry_after_locked(self) -> float:
        """Retry-After for pressure-driven refusals (the L4 shed, and
        the floor under a quota 429 while the ladder is up).  ISSUE-16
        satellite fix: derived from the ladder's REAL exit timescale —
        `down_dwell` consecutive calm updates at the observed update
        cadence (EWMA, stamped by `_update_pressure_locked`) — instead
        of the backlog constant, so clients back off proportionally to
        how long the ladder actually needs to step down.  Falls back to
        the backlog estimate when no ladder is installed."""
        if self._pressure is None:
            return self._retry_after_locked()
        dwell = self._pressure.config.down_dwell * self._pressure_tick_s
        return max(0.1, dwell)

    def _build_request(self, prompt_ids, max_new_tokens: int,
                       temperature: float, seed: int,
                       deadline_s: Optional[float],
                       request_id: Optional[str],
                       session_id: Optional[str] = None,
                       export: bool = False,
                       priority: Optional[str] = None,
                       tenant: Optional[str] = None) -> _LMRequest:
        """Validate + construct one queue item — THE shared front half of
        `generate`/`generate_stream`/`prefill_export`/`admit_with_pages`.
        Export lanes are budgeted for their prefill pages only (they
        never decode here); everything else pays the full page budget
        via the ONE shared `validate()` contract."""
        if export:
            ids = validate_request(self.cfg, prompt_ids, max_new_tokens)
            if -(-len(ids) // self.page_size) > self.kv_pages:
                raise ValueError(
                    f"prompt needs {-(-len(ids) // self.page_size)} "
                    f"prefill pages (page_size {self.page_size}) but "
                    f"the pool holds {self.kv_pages}; raise -lm-pages "
                    f"or shorten it")
        else:
            ids = self.validate(prompt_ids, max_new_tokens)
        if temperature < 0:
            raise ValueError(f"temperature must be >= 0, got {temperature}")
        if self.block > 1 and temperature > 0:
            raise ValueError(
                "a block-diffusion model is served greedily: the "
                "unmasking schedule ranks positions by the confidence of "
                "their best token (temperature must be 0)")
        # fold into int32 range (the device-side PRNGKey seed dtype) so a
        # huge client seed cannot overflow the worker's seed vector
        seed = int(seed) & 0x7FFFFFFF
        if deadline_s is None:
            deadline_s = self.default_deadline_s
        if request_id is None and self.tracer is not None:
            request_id = new_request_id()
        req = _LMRequest(ids, int(max_new_tokens), float(temperature),
                         seed, request_id=request_id)
        req.prefill_len = len(ids) // self.block * self.block
        if deadline_s is not None:
            req.deadline = req.enqueued + float(deadline_s)
        req.session_id = (str(session_id) if session_id is not None
                          else None)
        req.export = bool(export)
        req.priority = normalize_priority(priority)
        req.rank = PRIORITY_RANK[req.priority]
        # tenant validation mirrors the priority gate: None -> the
        # built-in default tenant, unknown -> ValueError (the front's
        # 400).  Without a registry any explicit non-default tenant is
        # unknown by definition.
        if self.tenants is not None:
            req.tenant = self.tenants.normalize(tenant)
        elif tenant is not None and str(tenant) != DEFAULT_TENANT:
            raise ValueError(
                f"unknown tenant {str(tenant)!r}: no tenant registry "
                f"is installed (serve -tenants, or "
                f"ContinuousLMServer(tenants=...))")
        return req

    def _enqueue(self, req: _LMRequest) -> None:
        """Admission under the pool lock: the shared gate, worker start,
        priority-ordered queue insert, and sticky-session accounting.
        The brownout ladder's last rung fires here: at level 4 a
        best_effort admission is refused with 503 + Retry-After BEFORE
        the shared gate's queue bound, so interactive (and batch)
        traffic keeps the whole queue bound to itself while the pool
        recovers.  A draining/stopped server is NOT accepting at all —
        that outranks the shed, so clients get the typed
        draining/unavailable error and fail over instead of retrying a
        pool that will never admit again.

        The tenant quota gate (ISSUE-16) fires FIRST among the
        accepting-state refusals: an over-quota tenant's 429s are the
        CLIENT's budget, evaluated before the server-capacity shed and
        the shared gate, so a flooding tenant's refusals never consume
        the queue bound (and never dodge the meter by arriving while
        the ladder is shedding)."""
        with self._cond:
            if self._accepting and self.tenants is not None:
                try:
                    self.tenants.meter.charge(req.tenant, req.cost)
                except TenantQuotaError as e:
                    self.metrics.record_rejected()
                    self.metrics.record_class("rejected", req.priority)
                    self.metrics.record_tenant("rejected", req.tenant)
                    self.metrics.record_tenant("throttled", req.tenant)
                    # while the ladder is up, the bucket-refill retry is
                    # floored at the ladder's exit timescale: tokens
                    # refilling sooner than the pool recovers would
                    # invite the flood straight back (satellite fix)
                    if (self._pressure is not None
                            and self._pressure.level > 0):
                        e.retry_after_s = max(
                            e.retry_after_s,
                            self._ladder_retry_after_locked())
                    raise
            if (self._accepting
                    and self._pressure is not None
                    and self._pressure.level >= 4
                    and req.rank >= RANK_BEST_EFFORT
                    and not (self.tenants is not None
                             and self.tenants.compliant(req.tenant)
                             and self.tenants.any_offender())):
                # tenant-aware shed (ISSUE-16): while a non-compliant
                # tenant exists, a COMPLIANT tenant's best_effort still
                # admits — the rung takes from the offender, never from
                # a tenant inside its quota and SLO.  Without tenancy
                # (or without an offender) the PR-15 global shed holds.
                self.metrics.record_rejected()
                self.metrics.record_class("rejected", req.priority)
                if self.tenants is not None:
                    self.metrics.record_tenant("rejected", req.tenant)
                self.metrics.record_brownout_shed()
                raise ServingOverloadError(
                    "brownout level 4: best_effort admission shed "
                    "while the KV pool recovers",
                    retry_after_s=self._ladder_retry_after_locked())
            try:
                check_admission(
                    accepting=self._accepting, breaker=self.breaker,
                    queue_depth=len(self._queue),
                    max_queue_depth=self.max_queue_depth,
                    metrics=self.metrics,
                    retry_after_s=self._retry_after_locked, what="LM")
            except ServingError:
                # the shared gate already counted the rejection; the
                # per-class ledger rides along (ISSUE-15)
                self.metrics.record_class("rejected", req.priority)
                if self.tenants is not None:
                    self.metrics.record_tenant("rejected", req.tenant)
                raise
            if not self._running:
                self._start_locked()
            if req.session_id is not None:
                self._note_session_locked(req.session_id)
            if self.tenants is not None:
                # WFQ stamp at admission: virtual finish time within
                # the tenant's weighted share (ISSUE-16).  Stamped once
                # — a preempted request re-inserts with its ORIGINAL
                # vft, the WFQ analog of keeping the enqueue stamp.
                req.vft = self.tenants.wfq.stamp(req.tenant, req.cost)
            self._queue_insert_locked(req)
            self.metrics.set_queue_depth(len(self._queue))
            self._cond.notify_all()

    def _queue_insert_locked(self, req: _LMRequest) -> None:
        """Priority-ordered insert: the queue is kept sorted by
        (rank, vft, enqueued) so `popleft` always yields the most
        important request, weighted-fairly across tenants within a
        class (ISSUE-16), oldest-first as the tie-break.  Without a
        tenant registry every vft is 0.0 and the key degenerates to the
        PR-15 (rank, enqueued) sort; with ONE tenant the WFQ virtual
        finish times are strictly increasing in arrival order, so one
        class × one tenant is exactly the historic FIFO (pinned by
        test).  A preempted request re-inserts with its ORIGINAL
        enqueue stamp AND original vft, so it lands ahead of later
        arrivals of its own class/tenant instead of restarting at the
        back.  O(queue) insert; the queue is bounded by
        `max_queue_depth`."""
        key = (req.rank, req.vft, req.enqueued)
        i = len(self._queue)
        while i > 0:
            prev = self._queue[i - 1]
            if (prev.rank, prev.vft, prev.enqueued) <= key:
                break
            i -= 1
        if i == len(self._queue):
            self._queue.append(req)
        else:
            self._queue.insert(i, req)

    def _note_session_locked(self, session_id: str) -> None:
        """Sticky-session accounting (ISSUE-14 satellite): a session_id
        this pool has served before is an affinity HIT — the router's
        session rendezvous (or a client pinning one replica) landed the
        conversation back on the pool holding its radix pages.  Bounded
        LRU; works identically behind a fleet front or a bare `serve`
        so clients write one payload shape against both."""
        hit = session_id in self._sessions
        if hit:
            self._sessions.move_to_end(session_id)
        else:
            self._sessions[session_id] = 1
            while len(self._sessions) > self._session_capacity:
                self._sessions.popitem(last=False)
        self.metrics.record_session(hit)

    def _cancel_request(self, req: _LMRequest, status: str) -> None:
        """Give up on an unresolved request (client timeout or stream
        disconnect).  Cancel rather than abandon (mirror of
        MicroBatcher.submit): a still-queued request is removed so
        retry-on-timeout clients cannot fill the pool with zombie
        decodes; one already in a slot is MARKED abandoned and the
        worker frees the slot at its next admit round (slot state is
        written by the worker thread ONLY — freeing it here would race
        the lock-free step-input build in `_drain_step`)."""
        now = time.perf_counter()
        with self._cond:
            try:
                self._queue.remove(req)
                self.metrics.set_queue_depth(len(self._queue))
                self.metrics.record_shed()
                self.metrics.record_class("shed", req.priority)
                if self.tenants is not None:
                    self.metrics.record_tenant("shed", req.tenant)
                self._drop_swap_locked(req)
            except ValueError:
                req.abandoned = True
                # a request the worker already RESOLVED needs no shed
                # here: a completed result was counted as a served
                # request at fold time, and a worker-shed error was
                # counted when it was shed; an in-slot request is
                # shed by the admitter when it frees the slot
            resolved_with_error = (req.event.is_set()
                                   and req.error is not None)
        if (req.deadline is not None and now >= req.deadline
                and not resolved_with_error):
            # count a deadline miss only when the server-side
            # deadline actually expired and the worker has not
            # already accounted it (mirror of MicroBatcher.submit)
            self.metrics.record_deadline_missed()
            self.metrics.record_class("deadline_missed", req.priority)
            if self.tenants is not None:
                self.metrics.record_tenant("deadline_missed", req.tenant)
        self._trace_request(req, time.perf_counter(), status)

    def _wait(self, req: _LMRequest,
              timeout: Optional[float]) -> List[int]:
        """Block until the request resolves; raises its error or the
        timeout as typed failures.  Returns `req.result`."""
        if not req.event.wait(timeout):
            self._cancel_request(req, "timeout")
            raise DeadlineExceededError(
                f"LM request timed out after {timeout}s")
        done = time.perf_counter()
        if req.error is not None:
            self._trace_request(req, done, "error")
            raise req.error
        self._trace_request(req, done, "ok")
        return req.result

    def generate(self, prompt_ids, max_new_tokens: int,
                 temperature: float = 0.0, seed: int = 0,
                 timeout: Optional[float] = None,
                 deadline_s: Optional[float] = None,
                 request_id: Optional[str] = None,
                 session_id: Optional[str] = None,
                 priority: Optional[str] = None,
                 tenant: Optional[str] = None) -> List[int]:
        """prompt ids -> full sequence (prompt + generated), blocking.

        `timeout` bounds the client's wait; `deadline_s` (default
        `default_deadline_s`) rides the queue item so the admitter sheds
        the request once it expires instead of spending decode steps on
        a client that already gave up.  `request_id` names the request's
        trace (``X-Request-Id``); `session_id` feeds sticky-session
        affinity accounting.  `priority` (interactive/batch/best_effort,
        default interactive) orders admission and marks the lane's
        preemption class (docs/robustness.md "The degradation
        ladder").  `tenant` (default "default") names the registered
        tenant charged for the request — quota 429s, WFQ ordering, and
        SLO burn accounting key on it (ISSUE-16)."""
        req = self._build_request(prompt_ids, max_new_tokens, temperature,
                                  seed, deadline_s, request_id,
                                  session_id=session_id,
                                  priority=priority, tenant=tenant)
        self._enqueue(req)
        return self._wait(req, timeout)

    def generate_stream(self, prompt_ids, max_new_tokens: int,
                        temperature: float = 0.0, seed: int = 0,
                        timeout: Optional[float] = None,
                        deadline_s: Optional[float] = None,
                        request_id: Optional[str] = None,
                        session_id: Optional[str] = None,
                        priority: Optional[str] = None,
                        tenant: Optional[str] = None
                        ) -> Iterator[int]:
        """Streaming `generate`: admission happens HERE (typed errors
        raise before a single byte of response is committed), then the
        returned iterator yields each committed token as the worker
        folds it — a speculative round's multi-token commit is yielded
        token by token.  Closing the iterator mid-stream (the SSE
        client disconnected) abandons the request so its slot and pages
        free at the worker's next admit round instead of decoding for
        nobody.  The full sequence is `prompt + every yielded token`."""
        req = self._build_request(prompt_ids, max_new_tokens, temperature,
                                  seed, deadline_s, request_id,
                                  session_id=session_id,
                                  priority=priority, tenant=tenant)
        req.stream = _queue.SimpleQueue()
        self._enqueue(req)
        return self._stream_tokens(req, timeout)

    def _stream_tokens(self, req: _LMRequest,
                       timeout: Optional[float]) -> Iterator[int]:
        deadline = (None if timeout is None
                    else time.perf_counter() + float(timeout))
        cancelled = False
        try:
            while True:
                wait = 0.05
                if deadline is not None:
                    remaining = deadline - time.perf_counter()
                    if remaining <= 0:
                        cancelled = True
                        self._cancel_request(req, "timeout")
                        raise DeadlineExceededError(
                            f"LM stream timed out after {timeout}s")
                    wait = min(wait, remaining)
                try:
                    yield int(req.stream.get(timeout=wait))
                    continue
                except _queue.Empty:
                    pass
                if req.event.is_set():
                    # the worker resolved the request; tokens are pushed
                    # BEFORE the event is set (same thread), so one final
                    # drain empties the queue in order
                    while True:
                        try:
                            yield int(req.stream.get_nowait())
                        except _queue.Empty:
                            break
                    if req.error is not None:
                        self._trace_request(req, time.perf_counter(),
                                            "error")
                        raise req.error
                    self._trace_request(req, time.perf_counter(), "ok")
                    return
        finally:
            if not cancelled and not req.event.is_set():
                # consumer went away mid-stream (GeneratorExit from the
                # SSE handler, or an error in the client loop): abandon
                # so the slot and its pages stop decoding for nobody.
                # The timeout branch above already cancelled — a second
                # cancel would double-count the deadline miss and
                # record two traces for one request.
                self._cancel_request(req, "disconnect")

    # ---- disaggregation: KV page export / import (ISSUE-14) ---------------

    def _require_ship(self, what: str) -> None:
        if not self.ship:
            raise ValueError(
                f"page {what} requested but the pool was started with "
                f"ship=False (serve with -lm-ship, or "
                f"ContinuousLMServer(ship=True))")

    def prefill_export(self, prompt_ids, max_new_tokens: int,
                       temperature: float = 0.0, seed: int = 0,
                       timeout: Optional[float] = None,
                       deadline_s: Optional[float] = None,
                       request_id: Optional[str] = None,
                       session_id: Optional[str] = None,
                       priority: Optional[str] = None,
                       tenant: Optional[str] = None) -> PageExport:
        """Prefill-worker half of disaggregation: run the prompt through
        normal admission (radix reuse, chunked prefill, CoW) but resolve
        at prefill completion with the lane's shippable state — prompt
        pages, block-table metadata, and the FIRST committed token (the
        last prompt token's logits produce it, so shipping without it
        would cost the decode worker a redundant dispatch).  The request
        contract (max_new within max_len etc.) is validated here so a
        doomed request fails on the prefill worker, before any bytes
        move."""
        self._require_ship("export")
        req = self._build_request(prompt_ids, max_new_tokens, temperature,
                                  seed, deadline_s, request_id,
                                  session_id=session_id, export=True,
                                  priority=priority, tenant=tenant)
        self._enqueue(req)
        self._wait(req, timeout)
        return req.export_result

    def admit_with_pages(self, export: PageExport,
                         timeout: Optional[float] = None,
                         deadline_s: Optional[float] = None,
                         request_id: Optional[str] = None) -> List[int]:
        """Decode-worker half: verify the shipment's geometry against
        this pool (`PageShipError` on any mismatch — the caller's
        recompute ladder), allocate the lane's full page budget, install
        the shipped pages in one batched dispatch, and join mid-flight
        exactly like a chunked-prefill completion.  Returns the full
        sequence, byte-identical to a locally-prefilled lane."""
        self._require_ship("import")
        check_compatible(export, self.cfg, self.page_size)
        if export.quantized and not self.swap_quantize:
            raise PageShipError(
                "shipment is int8-quantized but this pool runs "
                "swap_quantize=off: refusing a lossy install on an "
                "exact-bytes pool (recompute locally instead)")
        if len(export.committed) >= export.max_new:
            # the prefill worker's first sample already filled the whole
            # budget (max_new == 1): nothing to decode — answer without
            # occupying a slot or installing a page.  Still a served
            # request in EVERY ledger (plane, class, tenant) — the
            # fleet reconciliation asserts they agree (ISSUE-16)
            priority = normalize_priority(export.priority)
            tenant = (self.tenants.normalize(export.tenant)
                      if self.tenants is not None else DEFAULT_TENANT)
            with self._cond:
                if export.session_id is not None:
                    self._note_session_locked(export.session_id)
            self.metrics.record_request(0.0)
            self.metrics.record_first_token(0.0)
            self.metrics.record_class("requests", priority)
            if self.tenants is not None:
                self.metrics.record_tenant("requests", tenant)
            return (list(export.prompt)
                    + list(export.committed[:export.max_new]))
        req = self._build_request(export.prompt, export.max_new,
                                  export.temperature, export.seed,
                                  deadline_s, request_id,
                                  session_id=export.session_id,
                                  priority=export.priority,
                                  tenant=export.tenant)
        req.import_pages = export
        self._enqueue(req)
        return self._wait(req, timeout)

    def _trace_request(self, req: _LMRequest, done: float,
                       status: str) -> None:
        """The LM request's lifecycle trace: queue_wait (admission to
        slot install) then decode (install to completion) with prefill
        (install to first committed token) inside it, plus any XLA
        compiles that landed inside the decode window."""
        if self.tracer is None:
            return
        spans = []
        t_in = req.t_installed if req.t_installed is not None else done
        spans.append(span("queue_wait", req.enqueued, t_in))
        if req.t_installed is not None:
            t_done = req.t_done if req.t_done is not None else done
            spans.append(span(
                "decode", req.t_installed, t_done,
                prompt_tokens=len(req.prompt),
                generated=(len(req.result) - len(req.prompt)
                           if req.result else 0),
                prefix_matched=req.prefix_matched or None,
                snapshot_matched=req.snapshot_matched or None,
                drafted=req.drafted or None,
                accepted=(req.accepted if req.drafted else None),
                preempted=req.preempted or None,
                swap_error=req.swap_error,
                **({"blocks": req.blocks,
                    "denoise_rounds": req.denoise_rounds,
                    "commit_rounds": req.blocks,
                    "unmask_steps": list(req.unmask_steps),
                    "surplus": list(req.surplus),
                    "surplus_steps": list(req.surplus_steps)}
                   if self.block > 1 else {})))
            if (req.t_first is not None
                    and req.t_first >= req.t_installed):
                # a preempted lane's first token predates its last
                # install: its prefill is not this residency's
                spans.append(span(
                    "prefill", req.t_installed, req.t_first,
                    fed_tokens=req.prefill_len - req.prefix_matched,
                    rounds=req.prefill_rounds,
                    wide_rounds=req.prefill_wide_rounds))
            if self._compile_watch.any_since(req.t_installed):
                for c_end, c_dur, key in (self._compile_watch
                                          .events_between(req.t_installed,
                                                          t_done)):
                    spans.append(span("xla_compile", c_end - c_dur,
                                      c_end, program_key=key))
        self.tracer.record(trace(
            req.request_id or new_request_id(), "lm", spans,
            status=status, prompt_tokens=len(req.prompt),
            error=(str(req.error) if req.error is not None else None)))

    def warmup(self, timeout: Optional[float] = 600.0) -> int:
        """Start the worker and pre-compile every device program before
        traffic; returns the compiled-program count.  Without warmup
        each program compiles on its first dispatch (the decode step on
        the first request, the prefill-chunk step on the first
        full-chunk prompt, the CoW copy on the first mid-page prefix
        split) — the same lazy-until-warmup contract as
        `ServingEngine.warmup()`: after warmup, NO request can trigger
        an XLA compile, which is what the zero-recompile storm tests
        pin via jax.monitoring.

        The warm dispatches run on the WORKER's live cache (inactive
        lanes write only the reserved null page), not a throwaway copy:
        a pool sized to fill device memory must not transiently double
        during startup or a rolling swap.

        A warm dispatch that raises (the compiler refusing a program)
        is re-raised HERE: a server whose programs do not compile must
        not report a warm count and stay up to fail request by
        request."""
        with self._cond:
            if not self._running:
                self._start_locked()
            ev = self._warm_req
            if ev is None:
                ev = self._warm_req = threading.Event()
            self._cond.notify_all()
        if not ev.wait(timeout):
            # the warm never ran (the device is wedged): report 0, not
            # a count the zero-compile contract would falsely promise
            return 0
        with self._cond:
            err, self._warm_error = self._warm_error, None
        if err is not None:
            raise err
        return self.compiled_programs()

    def _warm_programs(self) -> None:
        """Worker-side warm: one dispatch per program against the live
        cache.  The paged step with n_feed=0 writes nothing but the null
        page, so cache contents stay serviceable beside live lanes and
        no second pool is ever allocated."""
        import jax

        if self._cache is None:
            self._reset_cache()
        t_start = time.perf_counter()
        compiles = self._compile_watch.total()
        programs: Dict[str, float] = {}
        stages: Dict[str, Dict[str, float]] = {}

        def warm(key, call):
            """One program under its key, waited for: what it costs to
            trace, lower, compile or load, and to run once."""
            t0 = time.perf_counter()
            with compile_scope(key):
                out = jax.block_until_ready(call())
            t1 = time.perf_counter()
            programs[key] = t1 - t0
            # whatever was built in the key's interval (a drafter builds
            # under a key of its own); cache_load lies inside backend
            split = {**dict.fromkeys(STAGES, 0.0), **over_keys(
                self._compile_watch.stage_seconds(t0, t1))}
            cache = over_keys(self._compile_watch.cache_results(t0, t1))
            stages[key] = {
                **split, "hits": cache.get("hit", 0),
                "misses": cache.get("miss", 0),
                "run": t1 - t0 - sum(split[stage] for stage in
                                     ("trace", "lower", "backend"))}
            return out

        try:
            zi = np.zeros((self.n_slots,), np.int32)
            zf = np.zeros((self.n_slots,), np.float32)
            table = np.zeros((self.n_slots, self.max_pages), np.int32)
            if self.speculate != "off":
                widths = [1, self.spec_width]
                for w in widths:
                    tok = np.zeros((self.n_slots, w), np.int32)
                    out = warm(f"lm:paged[w{w}]", lambda: self._step(
                        self.params, *self._cache, table, zi, zi, zi, tok,
                        zf, zi, zi))
                    self._cache = tuple(out[2:])
                if hasattr(self._drafter, "warmup"):
                    warm("lm:drafter", self._drafter.warmup)
            else:
                widths = [self.block] + ([self.prefill_chunk]
                                         if self.prefill_chunk > self.block
                                         else [])
                # a recurrent model's lanes name their state rows last
                # (all the null row here); a block model's feed carries
                # its columns' known flags, the quota and the threshold
                if self.block > 1:
                    tail = (np.ones((self.n_slots, self.block), np.int32),
                            zi, zf, self._no_block_held(), zi)
                else:
                    tail = (zf, zi, zi) + ((zi,) if self.recurrent else ())
                for w in widths:
                    tok = np.zeros((self.n_slots, w), np.int32)
                    out = warm(f"lm:paged[w{w}]", lambda: self._step(
                        self.params, *self._cache, table, zi, zi, tok,
                        *tail))
                    self._cache = tuple(out[1:])
            if self.recurrent:
                # the null row onto itself; no match ends mid-page, so
                # there is no copy-on-write page copy to warm
                warm("lm:state_copy", lambda: self._copy_state_rows([]))
            else:
                self._cache = tuple(warm(
                    "lm:page_copy", lambda: self._copy(
                        *self._cache, np.int32(0), np.int32(0))))
            if self.ship or self.preempt or self.hibernate:
                # the shipping/swap/hibernate pair: a gather out of the live
                # pool (not donated — the row of nulls reads only the null
                # page) and an n=0 install whose every row lands on the
                # null page
                zrow = np.zeros((self.max_pages,), np.int32)
                warm("lm:page_gather",
                     lambda: self._gather(*self._cache, zrow))
                zp = self._padded_stacks(None, 0)
                self._cache = tuple(warm(
                    "lm:page_install", lambda: self._install(
                        *self._cache, *zp, zrow, np.int32(0))))
        finally:
            self._warmup_stats = {
                "programs": programs,
                "stages": stages,
                "compiles": self._compile_watch.total() - compiles,
                "total_s": time.perf_counter() - t_start}

    def compiled_programs(self) -> int:
        # page gather + batched install serve the shipping wire plane,
        # preemption swap-out/restore AND hibernate/resume — one
        # compiled pair for all three
        ship = 2 if (self.ship or self.preempt or self.hibernate) else 0
        if self.speculate != "off":
            # 1-wide decode + the shared prefill/verify wide program +
            # page copy, plus whatever the drafter runs on device
            drafter = (self._drafter.compiled_programs()
                       if self._drafter is not None
                       and hasattr(self._drafter, "compiled_programs")
                       else 0)
            return 3 + drafter + ship
        return 2 + (1 if self.prefill_chunk > self.block else 0) + ship

    def stop(self) -> None:
        with self._cond:
            self._running = False
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None
        with self._cond:
            leftovers = list(self._queue)
            self._queue.clear()
            self.metrics.set_queue_depth(0)
        for req in leftovers:
            self.metrics.record_shed()
            self.metrics.record_class("shed", req.priority)
            if self.tenants is not None:
                self.metrics.record_tenant("shed", req.tenant)
            req.error = ServingUnavailableError("LM server stopped")
            req.event.set()

    # ---- drain lifecycle --------------------------------------------------

    @property
    def accepting(self) -> bool:
        """False once draining — the /readyz signal."""
        with self._cond:
            return self._accepting

    def ready(self) -> bool:
        """Readiness for traffic: accepting admissions and the circuit
        breaker is not open (docs/robustness.md serving lifecycle)."""
        if not self.accepting:
            return False
        return self.breaker is None or self.breaker.state != "open"

    def begin_drain(self) -> None:
        """Stop admission: subsequent generates raise
        `ServingUnavailableError`; queued + in-slot work still decodes."""
        with self._cond:
            self._accepting = False
            self._cond.notify_all()

    def drain(self, grace_s: float = 5.0) -> bool:
        """Stop admission, wait up to `grace_s` for queued + in-slot
        requests to finish, then stop the worker.  Returns True when
        everything drained within the grace window."""
        self.begin_drain()
        deadline = time.perf_counter() + max(0.0, grace_s)
        while True:
            with self._cond:
                busy = bool(self._queue) or any(
                    s.active for s in self._slots)
            if not busy:
                break
            if time.perf_counter() >= deadline:
                break
            time.sleep(0.01)
        with self._cond:
            drained = not self._queue and not any(
                s.active for s in self._slots)
        self.stop()
        return drained

    def _kv_bytes(self) -> Dict:
        """Actual vs provisioned KV bytes: the pool's bytes are paid
        whether or not any lane fills them; the active bytes follow the
        refcounted pages, radix-shared prefixes counted once."""
        from deeplearning4j_tpu.parallel.generation import pool_token_bytes

        per_tok = pool_token_bytes(self.cfg)
        provisioned = self.kv_pages * self.page_size * per_tok
        in_use = self._pool.in_use if self._pool is not None else 0
        active = in_use * self.page_size * per_tok
        return {"provisioned": int(provisioned), "active": int(active),
                "per_token": int(per_tok)}

    def stats(self) -> Dict:
        out = self.metrics.snapshot()
        with self._cond:
            out["slots"] = self.n_slots
            out["active_slots"] = sum(s.active for s in self._slots)
            out["queue_depth"] = len(self._queue)
            out["decode_steps"] = self._steps
            out["accepting"] = self._accepting
            out["kv_bytes"] = self._kv_bytes()
            out["kv"] = {
                "mode": "paged",
                "page_size": self.page_size,
                "pages": self.kv_pages,
                "max_pages_per_seq": self.max_pages,
                "prefill_chunk": self.prefill_chunk,
                "pages_in_use": (self._pool.in_use
                                 if self._pool is not None else 0),
                "pages_free": (self._pool.free
                               if self._pool is not None
                               else self.kv_pages),
                "radix_nodes": (self._tree.nodes
                                if self._tree is not None else 0),
                "ship": self.ship,
                "paged_kernel": self.paged_kernel,
                "write_path": dict(self._write_path)}
            if self.recurrent:
                from deeplearning4j_tpu.parallel.generation import (
                    state_row_bytes,
                )

                st = out.setdefault("state", {})
                st.update({
                    "rows": self.state_rows,
                    "rows_in_use": (self._states.in_use
                                    if self._states is not None else 0),
                    "snapshots_held": (self._tree.snapshots
                                       if self._tree is not None else 0),
                    "snapshot_every": self.snapshot_every,
                    "row_bytes": state_row_bytes(self.cfg)})
            if self.block > 1:
                out.setdefault("blocks", {}).update({
                    "block_length": self.block,
                    "denoise_steps": self.denoise_steps,
                    "unmask": self.unmask, "tau": self.tau,
                    "mask_token": self.cfg.mask_token})
            if self._sessions:
                out["sessions_tracked"] = len(self._sessions)
            if self.preempt or self._pressure is not None:
                pres: Dict = {"preempt": self.preempt}
                if self._swap is not None:
                    pres["swap"] = self._swap.stats()
                if self._pressure is not None:
                    pres["brownout"] = self._pressure.stats()
                out["pressure"] = pres
            if self.hibernate and self._swap is not None:
                out["hibernation"] = {
                    "idle_s": self.hibernate_idle_s,
                    "quantize": self.swap_quantize,
                    "disk": self.state_dir,
                    "tracked_sessions": len(self._hib_sessions),
                    "store": self._swap.stats()}
            if self.tenants is not None:
                out["tenancy"] = self.tenants.stats()
            if self.speculate != "off":
                spec = {"mode": self.speculate,
                        "draft_len": self.draft_len,
                        "verify_width": self.spec_width}
                drafted = out.get("spec_drafted", 0)
                if drafted:
                    spec.update({
                        "drafted": drafted,
                        "accepted": out.get("spec_accepted", 0),
                        "accept_rate": out.get("spec_accept_rate", 0.0)})
                if out.get("decode_rounds"):
                    spec["tokens_per_decode_round"] = out.get(
                        "tokens_per_decode_round", 0.0)
                out["speculate"] = spec
        out["max_len"] = self.cfg.max_len
        out["compiled_programs"] = self.compiled_programs()
        # first-class compile accounting (ISSUE-8): XLA compiles the
        # watcher attributed to the LM pool's dispatch scopes
        out["compiles_total"] = compile_watcher().total(prefix="lm:")
        with self._cond:
            if self._warmup_stats is not None:
                # what warmup() cost, by program key (seconds, waited)
                out["warmup"] = dict(self._warmup_stats)
        return out

    # ---- worker side ------------------------------------------------------

    def _reset_cache(self) -> None:
        """(Re)allocate the device KV buffers.  Needed after a FAILED
        dispatch too: the step donates the k/v buffers, so an exception
        mid-step leaves `self._cache` pointing at deleted buffers —
        without a rebuild the keep-serving path would fail every later
        request.  Host-side page state is reset separately
        (`_reset_pool_locked`) because it must happen BEFORE the next admit
        round, while the device rebuild may be deferred to dispatch."""
        from deeplearning4j_tpu.parallel.generation import init_paged_cache

        # k and v, or the one latent pool (`generation.pool_layout`), and
        # after them a recurrent model's state and tail rows
        with compile_scope("kv:pool"):
            pools = tuple(init_paged_cache(
                self.cfg, self.kv_pages + 1, self.page_size).values())
            if self.recurrent:
                from deeplearning4j_tpu.parallel.generation import (
                    init_state_pool,
                )

                pools += tuple(init_state_pool(
                    self.cfg, self.state_rows + 1).values())
        self._cache = pools

    def _reset_pool_locked(self) -> None:
        """Fresh allocator + radix tree + slot page bookkeeping.  Called
        at start and whenever the device pool's CONTENTS died (failed
        dispatch, worker stop): a radix entry pointing into a rebuilt
        pool would serve zeros as a cached prefix.  Caller holds
        ``self._cond`` (the ``*_locked`` contract — admission reads the
        pool/tree/CoW list under the same lock)."""
        self._pool = PagePool(self.kv_pages + 1, self.page_size)
        self._states = (StatePool(self.state_rows + 1)
                        if self.recurrent else None)
        self._tree = RadixPrefixCache(self._pool, self._states)
        self._pending_cow = []
        self._pending_state = []
        self._snap_evicted_seen = 0
        # shipments awaiting device install referenced pages (and
        # content) that died with the pool — their lanes restart or fail
        # with it, so the pending plane resets wholesale too
        self._pending_install = []
        self._ahead = None      # its lanes restart or fail with the pool
        for s in self._slots:
            s.table = None
            s.owned = []
            s.shared = []
            s.inserted = False
            s.row = s.trail = 0
            s.trail_pos = None
        if self._drafter is not None:
            # the drafter's lane state tracked lanes that no longer
            # exist; its own cache self-heals via the common-prefix
            # rewind, but the bookkeeping must not outlive the pool
            self._drafter.reset()
        if self._swap is not None:
            # swapped blobs are self-contained host copies and would
            # stay VALID across a device pool rebuild, but the reset
            # paths either fail every request that could restore them
            # (stop) or want one coherent story (failed dispatch):
            # clear, and let any surviving queued victim take the
            # recompute-from-prompt path — byte-identical either way.
            # HIBERNATED entries ("hib-") survive the reset: they are
            # content-addressed by prompt tokens and the KV they carry
            # is deterministic from those tokens, so they stay valid no
            # matter what happened to the device pool.
            self._swap.clear("swap-")
        self._hib_sessions.clear()
        self.metrics.set_pages(0, self.kv_pages, self.kv_pages)

    def _start_locked(self) -> None:
        if self._step is None:
            from deeplearning4j_tpu.parallel.generation import (
                kv_write_path,
                make_block_step,
                make_page_copy,
                make_paged_step,
                make_spec_step,
                pool_names,
            )

            total = self.kv_pages + 1
            # how each step program writes its fed K/V rows, by width
            wide = (self.spec_width if self.speculate != "off"
                    else self.prefill_chunk)
            path = kv_write_path(self.cfg, self.page_size,
                                 self.paged_kernel)
            self._write_path = {f"w{w}": path
                                for w in sorted({self.block, wide})}
            # the narrow program is `[slots, B]` (B = 1: a causal model's
            # `[slots, 1]`), the wide one the prefill chunk, whole blocks
            step_of = make_block_step if self.block > 1 else make_paged_step
            self._decode_step = step_of(
                self.cfg, total, self.page_size, self.block)
            if self.speculate != "off":
                # ONE wide program serves chunked prefill AND the
                # speculative verify — the same chunked-feed ladder,
                # widened to fit [last, d_1..d_draft_len]
                self._chunk_step = make_spec_step(
                    self.cfg, total, self.page_size, self.spec_width)
            else:
                self._chunk_step = (step_of(
                    self.cfg, total, self.page_size, self.prefill_chunk)
                    if self.prefill_chunk > self.block else None)
            if self.recurrent:
                from deeplearning4j_tpu.parallel.generation import (
                    make_state_copy,
                )

                self._state_copy = make_state_copy(self.cfg,
                                                   self._copy_batch)
            else:
                self._copy = make_page_copy(self.cfg, total,
                                            self.page_size)
            if self.ship or self.preempt or self.hibernate:
                from deeplearning4j_tpu.parallel.generation import (
                    make_page_gather,
                    make_page_install,
                )

                self._gather = make_page_gather(self.cfg, total,
                                                self.page_size)
                self._install = make_page_install(self.cfg, total,
                                                  self.page_size)
            if self.speculate != "off" and self._drafter is None:
                from deeplearning4j_tpu.serving.draft import make_drafter

                self._drafter = make_drafter(
                    self.speculate, self.cfg, self.params,
                    self.n_slots, draft_model=self._draft_model)

            # the pool arrays lead every call (k and v, or the one
            # latent pool: `generation.pool_layout`)
            n_pools = len(pool_names(self.cfg))
            if self.speculate != "off":
                def dispatch(params, *args):
                    # speculative signature: every dispatch carries
                    # n_draft and returns per-lane accepted counts
                    # (zeros on the 1-wide plain-decode program)
                    pools = args[:n_pools]
                    (table, pos, n_feed, n_draft, tokens, temperature,
                     seeds, counts) = args[n_pools:]
                    if tokens.shape[1] == 1:
                        out = self._decode_step(
                            params, *pools, table, pos, n_feed,
                            tokens, temperature, seeds, counts)
                        return (out[0], np.zeros(
                            (self.n_slots,), np.int32)) + tuple(out[1:])
                    return self._chunk_step(
                        params, *pools, table, pos, n_feed, n_draft,
                        tokens, temperature, seeds, counts)
            else:
                def dispatch(params, *args):
                    # ONE entry point for every paged dispatch
                    # (decode and prefill-chunk widths) so
                    # fault-injection tests that stub `self._step`
                    # intercept them all
                    tokens = args[n_pools + 3]
                    fn = (self._decode_step
                          if tokens.shape[1] == self.block
                          else self._chunk_step)
                    return fn(params, *args)

            self._step = dispatch
            self._reset_pool_locked()
            self._reset_cache()
        self._running = True
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="lm-decode")
        self._thread.start()

    # ---- paged admission --------------------------------------------------

    def _free_slot_pages(self, slot: _Slot) -> None:
        """Refcount-release everything a lane held: its own pages drop
        to 0 and return to the free list unless the radix tree kept
        them; shared prefix pages drop back to their other holders."""
        if self._pool is None:
            return
        if slot.owned:
            self._pool.release(slot.owned)
        if slot.shared:
            self._pool.release(slot.shared)
        slot.owned = []
        slot.shared = []
        slot.table = None
        slot.inserted = False
        if self._states is not None:
            self._states.release([r for r in (slot.row, slot.trail) if r])
            slot.row = slot.trail = 0
            slot.trail_pos = None

    def _resolve_swap_locked(self, req: _LMRequest) -> None:
        """Turn a requeued victim's swap key into an installable
        shipment.  A key whose blob was evicted (`SwapEvictedError`) or
        fails the wire frame's SHA-256/geometry checks (`PageShipError`)
        is the typed swap-loss path: the loss is counted, stamped on
        the victim request's trace, and the lane falls back to
        recomputing from its prompt — deterministic decode makes the
        recomputed tokens byte-identical, so the CLIENT never sees the
        error, only the accounting and the trace do."""
        key, req.swap_key = req.swap_key, None
        try:
            blob = self._swap.take(key)
        except SwapEvictedError as e:
            self.metrics.record_swap_lost("evicted")
            req.swap_error = f"{type(e).__name__}: {e}"
            return
        try:
            ex = deserialize_export(blob)
            check_compatible(ex, self.cfg, self.page_size,
                             mid_decode=True)
            if ex.quantized and not self.swap_quantize:
                raise PageShipError(
                    "swapped frame is int8-quantized but this pool "
                    "runs swap_quantize=off: refusing a lossy restore "
                    "on an exact-bytes pool")
        except PageShipError as e:
            self.metrics.record_swap_lost("corrupt")
            req.swap_error = f"{type(e).__name__}: {e}"
            return
        req.import_pages = ex
        req.swap_restore = True

    def _plan_admission_paged(self, req: _LMRequest):
        """Radix-match + allocate for one queued request.  Returns the
        install plan, or None when the pool (after eviction) cannot
        supply the fresh pages — the request stays queued, FIFO.  Every
        page the plan references is already retained."""
        plen = len(req.prompt)
        if self.recurrent:
            return self._plan_admission_state(req)
        if req.swap_key is not None and self._swap is not None:
            # a preempted lane coming back: resolve its host swap into
            # the same install plane a shipped lane uses (or fall back
            # to recompute-from-prompt when the state is gone/corrupt)
            self._resolve_swap_locked(req)
        if req.import_pages is not None:
            # shipped-in lane (ISSUE-14): FULL prefix pages this pool's
            # radix tree already holds are reused instead of installing
            # duplicate shipped copies — a sticky session's next turn
            # re-ships its growing prompt, and without this the decode
            # pool would pay O(turns x prompt) duplicate pages for a
            # prefix it already caches.  No plen-1 cap (unlike normal
            # admission): the first token arrived committed, nothing
            # re-feeds.  Partial (CoW) matches are skipped — the
            # shipped copy of a mid-page divergence is cheaper than a
            # device copy + overwrite.
            total_pages = self._required_pages(plen, req.max_new)
            full, partial = self._tree.match(req.prompt)
            if partial is not None:
                self._pool.release([partial[0]])
            need = total_pages - len(full)
            if self._pool.free < need:
                if self._pool.free + self._tree.evictable() >= need:
                    self._tree.evict(need)
            fresh = self._pool.alloc(need)
            if fresh is None:
                if full:
                    self._pool.release(full)
                return None
            return {"full": full, "partial": None, "fresh": fresh,
                    "matched": len(full) * self.page_size,
                    "total_pages": total_pages}
        # export lanes (prefill-only) budget just their prompt pages —
        # decode happens on whatever pool the shipment lands in
        total_pages = (-(-plen // self.page_size) if req.export
                       else self._required_pages(plen, req.max_new))
        # cap reuse at plen-1: the LAST prompt token is always re-fed —
        # its logits are what the first sampled token comes from.  A
        # block model reuses whole blocks (a row's K/V saw its whole
        # block): its prompt's tail rides the first denoise round, and a
        # match that ends inside a page ends on a block's boundary
        full, partial = self._tree.match(
            req.prompt[:self._kv_rows(plen)])
        if partial is not None and partial[1] % self.block:
            cut = partial[1] // self.block * self.block
            if not cut:
                self._pool.release([partial[0]])
            partial = (partial[0], cut) if cut else None
        if len(full) > total_pages:     # cannot happen (cap above), but
            raise AssertionError("radix match exceeded the page budget")
        resume = None
        if self.hibernate and self._swap is not None:
            # a hibernated session's prompt prefix may cover MORE pages
            # than the tree still holds: probe the tiered store for the
            # longest stored whole-page prefix beyond the radix match
            resume = self._probe_hibernated_locked(req, len(full))
        if resume is not None and partial is not None:
            # the resumed frame extends past the divergence page: the
            # CoW copy would duplicate content the frame carries exactly
            self._pool.release([partial[0]])
            partial = None
        need = total_pages - len(full)
        if self._pool.free < need:
            # evict ONLY when eviction can actually cover the shortfall:
            # wiping cached prefixes while still admitting nothing would
            # destroy the hit rate for zero capacity gained (the pages
            # this plan already retained are pinned, so they never count
            # as evictable against themselves)
            if self._pool.free + self._tree.evictable() >= need:
                self._tree.evict(need)
        fresh = self._pool.alloc(need)
        if fresh is None:
            if full:
                self._pool.release(full)
            if partial is not None:
                self._pool.release([partial[0]])
            if resume is not None:
                # un-consume the blob: the session's state must survive
                # until the pool can actually seat the lane
                self._swap.put(resume["key"], resume["blob"])
            return None
        if resume is not None:
            return {"full": full, "partial": None, "fresh": fresh,
                    "matched": int(resume["n_hib"]) * self.page_size,
                    "total_pages": total_pages, "resume": resume}
        matched = len(full) * self.page_size + (partial[1]
                                                if partial else 0)
        return {"full": full, "partial": partial, "fresh": fresh,
                "matched": matched, "total_pages": total_pages}

    def _plan_admission_state(self, req: _LMRequest):
        """`_plan_admission_paged` for a recurrent model: the match ends
        at the deepest SNAPSHOT on the prompt's path (a page boundary, so
        no copy-on-write), and the lane needs two state rows, its live
        one and its trailing one, beside its pages.  Eviction may drop
        pages (with their snapshots) and then snapshots alone; it is run
        only where it can cover the shortfall."""
        plen = len(req.prompt)
        total_pages = self._required_pages(plen, req.max_new)
        full, snap = self._tree.match_snapshot(req.prompt[:plen - 1])
        need = total_pages - len(full)
        if (self._pool.free < need
                and self._pool.free + self._tree.evictable() >= need):
            self._tree.evict(need)
        if (self._states.free < 2 and self._states.free
                + self._tree.snapshots_evictable() >= 2):
            self._tree.evict_snapshots(2)
        fresh = self._pool.alloc(need)
        rows = self._states.alloc(2) if fresh is not None else None
        if rows is None:
            if fresh:
                self._pool.release(fresh)
            if full:
                self._pool.release(full)
                self._states.release([snap])
            return None
        return {"full": full, "partial": None, "fresh": fresh,
                "matched": len(full) * self.page_size,
                "total_pages": total_pages, "rows": rows, "snap": snap}

    def _probe_hibernated_locked(self, req: _LMRequest,
                                 have: int) -> Optional[Dict]:
        """Longest hibernated whole-page prompt prefix beyond the
        `have` pages the radix tree already matched -> an exact
        (dequantized) `PageExport` ready for the pending-install plane,
        or None.  Probes deepest-first by content digest, so the cost
        on a miss is one digest per candidate depth, no I/O.  A stored
        blob that is gone or fails its integrity/geometry/quantization
        checks is the typed resume-loss path: counted on the hibernate
        ledger, stamped on THIS request's trace, and the probe keeps
        descending — shallower prefixes may still be intact."""
        plen = len(req.prompt)
        for k in range(self._kv_rows(plen) // self.page_size, have, -1):
            covered = [int(t) for t in req.prompt[:k * self.page_size]]
            key = prefix_key(covered)
            if key not in self._swap:
                continue
            try:
                blob = self._swap.take(key)
            except SwapEvictedError as e:
                self.metrics.record_hibernate_lost("evicted")
                req.swap_error = f"{type(e).__name__}: {e}"
                continue
            except PageShipError as e:
                self.metrics.record_hibernate_lost("corrupt")
                req.swap_error = f"{type(e).__name__}: {e}"
                continue
            try:
                ex = deserialize_export(blob)
                check_compatible(ex, self.cfg, self.page_size,
                                 prefix=True)
                if ex.quantized and not self.swap_quantize:
                    raise PageShipError(
                        "hibernated frame is int8-quantized but this "
                        "pool runs swap_quantize=off: refusing a lossy "
                        "resume on an exact-bytes pool")
                if ex.prompt != covered:
                    raise PageShipError(
                        "hibernated frame's tokens diverge from its "
                        "digest key: refusing to install foreign KV")
            except PageShipError as e:
                self.metrics.record_hibernate_lost("corrupt")
                req.swap_error = f"{type(e).__name__}: {e}"
                continue
            nbytes = ex.nbytes()
            exact = ex.exact_nbytes()
            return {"ex": ex.dequantized(), "n_hib": k, "nbytes": nbytes,
                    "exact_nbytes": exact, "key": key, "blob": blob}
        return None

    def _install_paged_locked(self, slot: _Slot, req: _LMRequest,
                              plan) -> None:
        """Bind one admitted request to a lane.  Caller holds
        ``self._cond`` (the ``*_locked`` contract): the pending-CoW
        append below races the worker's swap in `_drain_step`
        otherwise."""
        slot.req = req
        req.t_installed = time.perf_counter()
        req.prefix_matched = plan["matched"]
        slot.generated = []
        slot.block = None
        slot.fed = plan["matched"]
        slot.pos = plan["matched"]
        slot.shared = list(plan["full"])
        slot.owned = list(plan["fresh"])
        slot.inserted = False
        row = np.zeros((self.max_pages,), np.int32)
        n_full = len(plan["full"])
        row[:n_full] = plan["full"]
        row[n_full:plan["total_pages"]] = plan["fresh"]
        slot.table = row
        if "rows" in plan:
            # a recurrent lane starts from the matched snapshot's state,
            # or from nothing: one row copy ahead of its first feed.  The
            # snapshot stays retained until that copy is dispatched.
            slot.row, slot.trail = plan["rows"]
            slot.trail_pos = None
            snap = plan["snap"]
            req.snapshot_matched = plan["matched"] if snap else 0
            self._pending_state.append(
                {"src": -1 if snap is None else int(snap),
                 "dst": int(slot.row), "held": snap})
        if req.import_pages is not None:
            # shipped-in lane: arrive mid-flight exactly where the
            # prefill worker left it — prompt fully fed, first token(s)
            # committed, next write lands at pos (possibly mid-page,
            # overwriting shipped garbage past the divergence).  The
            # device install rides the pending plane below, executed
            # BEFORE any feed of this round; the prompt's full pages
            # enter the local radix tree now so the next shared-prefix
            # admission (this session's next turn) reuses them.
            ex = req.import_pages
            # at-rest/wire bytes BEFORE dequantizing — the ledger must
            # read what actually moved through the store or the wire
            wire_nbytes = ex.nbytes()
            ex = ex.dequantized()   # identity on exact frames
            slot.fed = len(req.prompt)
            slot.pos = int(ex.pos)
            slot.generated = list(ex.committed)
            del req.unmask_steps[len(slot.generated):]
            n_ship = ex.n_pages
            stacks = self._padded_stacks(ex, n_ship)
            # radix-matched prefix pages are NOT re-installed: their
            # rows in the install target the null page, so the shared
            # pages (other lanes may be reading them) are never
            # rewritten — shipped content for them is byte-identical
            # by the radix invariant anyway
            irow = row.copy()
            irow[:len(plan["full"])] = 0
            self._pending_install.append(
                {"stacks": stacks, "row": irow, "n": n_ship,
                 "nbytes": wire_nbytes, "swap": req.swap_restore})
            self.metrics.record_prefix_query(plan["matched"])
            n_full_prompt = len(req.prompt) // self.page_size
            if n_full_prompt:
                slot.inserted = True
                self._tree.insert(
                    req.prompt[:n_full_prompt * self.page_size],
                    [int(p) for p in row[:n_full_prompt]])
            # the shipment's committed tokens ARE this lane's first
            # tokens: stamp TTFT at install (the prefill worker already
            # paid the first-token latency; this pool's number says how
            # long the shipment sat in its queue).  A PREEMPTED lane
            # restoring from swap already stamped its true first token
            # before the preemption — never re-stamp it.
            if req.t_first is None:
                req.t_first = req.t_installed
                self.metrics.record_first_token(
                    req.t_first - req.enqueued)
            return
        res = plan.get("resume")
        if res is not None:
            # hibernated-session resume (ISSUE-19): the store held KV
            # for a longer prompt prefix than the radix tree — install
            # the resumed pages through the same pending plane a
            # shipment uses, then register them in the tree so the
            # session's NEXT turn (or a concurrent shared-prefix
            # admission) reuses them without touching disk.  Rows the
            # tree already served stay zeroed (null page): shared pages
            # other lanes may be reading are never rewritten.
            ex = res["ex"]
            n_hib = int(res["n_hib"])
            stacks = self._padded_stacks(ex, n_hib)
            irow = row.copy()
            irow[:n_full] = 0
            irow[n_hib:] = 0
            self._pending_install.append(
                {"stacks": stacks, "row": irow, "n": n_hib,
                 "nbytes": res["nbytes"],
                 "exact_nbytes": res["exact_nbytes"],
                 "pages": n_hib, "hibernate": True})
            self._tree.insert(req.prompt[:n_hib * self.page_size],
                              [int(p) for p in row[:n_hib]])
        elif plan["partial"] is not None:
            # copy-on-write: the divergence page's matched tokens are
            # valid KV; copy it into this lane's first fresh page and
            # overwrite from the divergence offset.  The source stays
            # retained until the device copy lands (eviction must not
            # recycle it first); _drain_step executes and releases.
            src, _ = plan["partial"]
            self._pending_cow.append({"src": int(src),
                                      "dst": int(plan["fresh"][0])})
        self.metrics.record_prefix_query(plan["matched"])
        del req.unmask_steps[:]     # a lane that restarts from its prompt

    def _admit_locked(self) -> None:
        """Queued prompts join free slots.  Doomed work is shed first:
        an abandoned request's slot (and pages) is freed, and an expired
        or abandoned queue item must never occupy a slot.  The queue
        sweep is one rebuild pass — per-item `deque.remove` would be
        O(n^2) under exactly the overload storm it exists for.

        Paged admission is priority-then-FIFO (ISSUE-15): the queue is
        kept sorted by (rank, enqueued), so the head is the most
        important oldest request.  When the head's pages cannot be
        supplied even after eviction, admission PREEMPTS the
        lowest-priority active lane (strictly outranked by the head;
        its state swaps out to the host store) before giving up and
        waiting — so a latency class never starves behind a long
        low-value lane.  With preemption off (or no outranked victim)
        the historic head-of-line wait is unchanged: admission stops
        rather than letting smaller later requests starve the head."""
        for slot in self._slots:
            if slot.active and slot.req.abandoned:
                self.metrics.record_shed()
                self.metrics.record_class("shed", slot.req.priority)
                if self.tenants is not None:
                    self.metrics.record_tenant("shed", slot.req.tenant)
                self._free_slot_pages(slot)
                slot.req = None
        now = time.perf_counter()
        kept, shed = collections.deque(), 0
        for req in self._queue:
            if req.abandoned:
                shed += 1
                self.metrics.record_class("shed", req.priority)
                if self.tenants is not None:
                    self.metrics.record_tenant("shed", req.tenant)
                self._drop_swap_locked(req)
            elif req.deadline is not None and now >= req.deadline:
                shed += 1
                self.metrics.record_deadline_missed()
                self.metrics.record_class("shed", req.priority)
                self.metrics.record_class("deadline_missed",
                                          req.priority)
                if self.tenants is not None:
                    self.metrics.record_tenant("shed", req.tenant)
                    self.metrics.record_tenant("deadline_missed",
                                               req.tenant)
                self._drop_swap_locked(req)
                req.error = DeadlineExceededError(
                    f"deadline exceeded after {now - req.enqueued:.3f}s "
                    f"in LM queue; shed before decode")
                req.event.set()
            else:
                kept.append(req)
        if shed:
            self._queue = kept
            self.metrics.record_shed(shed)
        self._update_pressure_locked()
        self._hibernate_idle_locked(now)
        for slot in self._slots:
            if not self._queue:
                break
            if slot.active:
                continue
            head = self._queue[0]
            plan = self._plan_admission_paged(head)
            while plan is None and self._preempt_one_locked(head):
                plan = self._plan_admission_paged(head)
            if plan is None:
                break              # head-of-line waits for pages
            req = self._queue.popleft()
            if self.tenants is not None:
                self.tenants.wfq.advance(req.vft)
            self._install_paged_locked(slot, req, plan)
        self.metrics.set_queue_depth(len(self._queue))
        if self._pool is not None:
            self.metrics.set_pages(self._pool.in_use, self._pool.free,
                                   self.kv_pages)
        self._record_state()

    def _record_state(self, taken: int = 0, hit: int = 0,
                      copied: int = 0) -> None:
        """The state rows held now, and what the tree evicted since the
        last count (eviction happens inside admission and snapshotting)."""
        if self._states is None:
            return
        evicted = self._tree.snapshots_evicted - self._snap_evicted_seen
        self._snap_evicted_seen = self._tree.snapshots_evicted
        self.metrics.record_state(self._states.in_use, taken=taken, hit=hit,
                                  evicted=evicted, copied=copied)

    def _copy_state_rows(self, copies) -> tuple:
        """Dispatch `(src, dst)` state-row copies through the ONE row-copy
        program, `_copy_batch` a dispatch (spare entries copy the null row
        onto itself); `src < 0` zeroes `dst`.  With nothing to copy one
        dispatch still runs: the warm-up's, which waits on the `(state,
        tail)` returned."""
        n = self._copy_batch
        # the worker thread owns `_cache` between dispatches (as in
        # `_dispatch_paged`); every caller is the worker
        pools = self._cache  # noqa: LCK101
        paged, rows = pools[:-2], pools[-2:]
        for i in range(0, max(len(copies), 1), n):
            src = np.zeros((n,), np.int32)
            dst = np.zeros((n,), np.int32)
            for j, (a, b) in enumerate(copies[i:i + n]):
                src[j], dst[j] = a, b
            with compile_scope("lm:state_copy"):
                rows = tuple(self._state_copy(*rows, src, dst))
        self._cache = paged + rows  # noqa: LCK101
        return rows

    def _give_snapshot(self, slot: _Slot, tokens) -> bool:
        """Hand the lane's trailing row, the state after `tokens` (whole
        pages, all written), to the radix tree with the pages that lead to
        it.  False where the tree has that snapshot already."""
        n = len(tokens) // self.page_size
        self._tree.insert(tokens, [int(p) for p in slot.table[:n]])
        if not self._tree.attach(tokens, slot.trail):
            return False
        slot.trail, slot.trail_pos = 0, None
        return True

    def _hibernate_idle_locked(self, now: float) -> None:
        """Park idle sticky sessions' cached pages on the tiered state
        store (ISSUE-19).  A session is idle once `hibernate_idle_s`
        has passed since its last completion; its radix-cached chain is
        gathered in one fixed-shape dispatch, (optionally) quantized,
        serialized through the integrity-checked wire frame, stored
        under its content digest, and the tree's hold on the pages is
        dropped — device capacity frees while the session's KV rests
        on host or disk, resumable hours later byte-identically (the
        store outlives pool resets AND, with a state_dir, the
        process)."""
        if (not self.hibernate or self._swap is None
                or self._gather is None or self._cache is None
                or self._tree is None or not self._hib_sessions):
            return
        idle = [sid for sid, meta in self._hib_sessions.items()
                if now - meta["t"] >= self.hibernate_idle_s]
        for sid in idle:
            meta = self._hib_sessions.pop(sid)
            tokens = meta["tokens"]
            # only positions BEFORE the final sampled token have KV
            # (the last sample is returned, never fed) — park exactly
            # the fully-written pages
            n_full = self._kv_rows(len(tokens)) // self.page_size
            if n_full == 0:
                continue
            covered = [int(t) for t in tokens[:n_full * self.page_size]]
            full, partial = self._tree.match(covered)
            if partial is not None:
                self._pool.release([partial[0]])
            if len(full) != n_full:
                # the tree already evicted part of the chain under
                # pressure: nothing complete to park — whatever prefix
                # remains keeps serving radix hits
                if full:
                    self._pool.release(full)
                continue
            row = np.zeros((self.max_pages,), np.int32)
            row[:n_full] = full
            ex = PageExport(
                **self._gathered(row, n_full),
                prompt=covered, max_new=1, temperature=0.0, seed=0,
                committed=[], pos=n_full * self.page_size,
                page_size=self.page_size,
                model=model_signature(self.cfg, self.page_size),
                session_id=sid)
            exact = ex.exact_nbytes()
            if self.swap_quantize:
                ex = quantize_export(ex)
            blob = serialize_export(ex)
            stored = self._swap.put(prefix_key(covered), blob)
            self._pool.release(full)
            if stored is None:
                # the blob alone exceeds the host cap: nothing was
                # parked and nothing was lost — the pages stay in the
                # radix tree and keep serving hits from device
                continue
            for lost in stored:
                # hibernated prefixes pushed off the capped tiers are
                # counted NOW — a resume probe treats a missing key as
                # a plain miss, so eviction time is the only chance
                # (swap-keyed victims stay counted at restore, as ever)
                if lost.startswith("hib-"):
                    self.metrics.record_hibernate_lost("evicted")
            self.metrics.record_hibernate("out", n_full, ex.nbytes(),
                                          exact)
            self._tree.forget(covered)

    def _drop_swap_locked(self, req: _LMRequest) -> None:
        """A shed/abandoned queue item releases its host swap bytes."""
        if req.swap_key is not None and self._swap is not None:
            self._swap.discard(req.swap_key)
            req.swap_key = None

    def _update_pressure_locked(self) -> None:
        """One brownout-ladder reading per admission round: pool
        pages-free + queue depth in, level out; every transition is
        counted and published (ISSUE-15).  Ladder level 3 additionally
        preempts best_effort lanes PROACTIVELY — before the pool is
        fully dry — whenever strictly higher-class work is waiting;
        with a tenant registry installed the rung takes lanes from
        non-compliant (over-quota / SLO-burning) tenants FIRST and
        leaves a compliant tenant's lanes alone whenever an offender
        holds one (ISSUE-16)."""
        if self._pressure is None or self._pool is None:
            return
        # observed update cadence (EWMA), the real timescale behind
        # `down_dwell` exits — feeds `_ladder_retry_after_locked` so
        # Retry-After tracks how fast this pool ACTUALLY re-evaluates
        # pressure, not a constant (ISSUE-16 satellite fix)
        now = time.perf_counter()
        if self._pressure_stamp is not None:
            dt = now - self._pressure_stamp
            if 0.0 < dt < 5.0:
                self._pressure_tick_s = (0.8 * self._pressure_tick_s
                                         + 0.2 * dt)
        self._pressure_stamp = now
        # pages-free counts evictable radix-cached pages too: a warm
        # prefix cache is reclaimable capacity, not pressure — without
        # this an idle pool with a full cache would sit degraded forever
        cfg = self._pressure.config
        avail = self._pool.free
        if (self._tree is not None
                and avail / max(1, self.kv_pages)
                <= cfg.enter_free_frac[0] + cfg.exit_free_margin):
            # evictable() is an O(cache) tree walk under the pool lock,
            # once per admission round: skip it when free pages alone
            # clear the shallowest enter threshold plus the exit margin
            # — adding reclaimable capacity on top cannot change the
            # ladder's reading there (every enter_free_frac[k] and
            # every calm bound is <= this line)
            avail += self._tree.evictable()
        moves = self._pressure.update(avail, self.kv_pages,
                                      len(self._queue), self.n_slots)
        self.metrics.record_brownout(self._pressure.level, len(moves))
        if (self._pressure.level >= 3 and self.preempt and self._queue
                and self._queue[0].rank < RANK_BEST_EFFORT):
            head_rank = self._queue[0].rank
            victims = [s for s in self._slots
                       if (s.active and not s.req.abandoned
                           and s.req.rank >= RANK_BEST_EFFORT
                           and s.req.rank > head_rank)]
            if (self.tenants is not None
                    and any(not self.tenants.compliant(s.req.tenant)
                            for s in victims)):
                # offender-first rung (ISSUE-16): while a non-compliant
                # tenant holds a candidate lane, preempt ONLY its lanes
                # — a compliant tenant's best_effort survives L3
                victims = [s for s in victims
                           if not self.tenants.compliant(s.req.tenant)]
            for slot in victims:
                self._preempt_slot_locked(slot)

    def _preempt_one_locked(self, head: _LMRequest) -> bool:
        """Pick and preempt ONE victim so `head` can admit: the active
        lane with the worst (highest) rank strictly above the head's,
        ties broken newest-first so older work of the same class keeps
        its progress.  With a tenant registry the WORST-BEHAVED tenant
        pays first: victims sort by (over-quota, SLO burn rate) ahead
        of the PR-15 (rank, enqueued) key, so an offender's lane swaps
        out before a compliant tenant's ever does (ISSUE-16).  Returns
        False when preemption is off, no program pair exists yet, or
        nothing outranked is running."""
        if not self.preempt or self._gather is None or self._cache is None:
            return False
        victims = [s for s in self._slots
                   if s.active and not s.req.abandoned
                   and s.req.rank > head.rank]
        if not victims:
            return False
        if self.tenants is not None:
            victim = max(victims,
                         key=lambda s: (self.tenants.badness(s.req.tenant),
                                        s.req.rank, s.req.enqueued))
        else:
            victim = max(victims,
                         key=lambda s: (s.req.rank, s.req.enqueued))
        self._preempt_slot_locked(victim)
        return True

    def _preempt_slot_locked(self, slot: _Slot) -> None:
        """Evict one active lane in favor of higher-priority work.

        A lane mid-decode swaps its KV state out to the host: one
        fixed-shape gather dispatch, then the same serialized wire
        frame the shipping plane uses (SHA-256 over the payload), into
        the byte-capped LRU `SwapStore`.  On re-admission it restores
        through the pending-install plane and resumes byte-identically
        — decode is deterministic (greedy and `fold_in(seed, count)`
        sampling), so even a lane whose swap is later lost recomputes
        the SAME tokens from its prompt.  A lane still mid-prefill (or
        an export lane) has nothing worth shipping: it just requeues
        and re-prefills (radix-cached pages make that cheap).  Either
        way the request keeps its original enqueue stamp, so it
        re-enters AHEAD of later arrivals of its own class."""
        # a lane swaps out what the host has read of it: read the round
        # in flight first, which may have been this lane's last
        self._settle_block_round()
        if not slot.active:
            return
        req = slot.req
        mid_decode = (slot.fed >= req.prefill_len and slot.generated
                      and not req.export)
        if slot.block is not None and slot.block.step:
            # the durable state is the committed blocks: the block in
            # flight is denoised again wherever the lane comes back
            self.metrics.record_block_redone()
        slot.block = None
        if (mid_decode and self._swap is not None
                and self._gather is not None and self._cache is not None):
            n = -(-slot.pos // self.page_size)
            ex = PageExport(
                **self._gathered(slot.table, n),
                prompt=list(req.prompt), max_new=req.max_new,
                temperature=req.temperature, seed=req.seed,
                committed=list(slot.generated), pos=int(slot.pos),
                page_size=self.page_size,
                model=model_signature(self.cfg, self.page_size),
                session_id=req.session_id, priority=req.priority,
                tenant=req.tenant)
            if self.swap_quantize:
                # per-page int8 in transit and at rest (ISSUE-19):
                # ~4x fewer bytes through the tiers; the deterministic
                # resume-parity tests pin that dequantized restore
                # still reproduces the exact token stream
                ex = quantize_export(ex)
            blob = serialize_export(ex)
            key = f"swap-{self._swap_seq}"
            self._swap_seq += 1
            evicted = self._swap.put(key, blob)
            if evicted is None:
                # the blob alone exceeds the cap: recompute-from-prompt
                # on re-admission instead of wiping every other victim
                self.metrics.record_swap_lost("evicted")
            else:
                req.swap_key = key
                # raw array bytes, matching the swap-in site and the
                # ship ledger — a lossless round trip reads out == in
                self.metrics.record_swap("out", n, ex.nbytes())
                # LRU victims whose state just got dropped recompute
                # from their prompts at restore time — where the loss
                # is counted (once), by _resolve_swap_locked
        req.preempted += 1
        self.metrics.record_preemption(req.priority)
        if self.tenants is not None:
            self.metrics.record_tenant("preempted", req.tenant)
        self._free_slot_pages(slot)
        slot.req = None
        slot.generated = []
        self._queue_insert_locked(req)
        self.metrics.set_queue_depth(len(self._queue))

    def _finish_slot(self, slot: _Slot) -> None:
        """Completion fold: resolve the client, free the lane + pages."""
        if slot.req.abandoned:
            # the client timed out mid-decode and already got
            # DeadlineExceededError: the finished sequence is
            # discarded work, not a served request
            self.metrics.record_shed()
            self.metrics.record_class("shed", slot.req.priority)
            if self.tenants is not None:
                self.metrics.record_tenant("shed", slot.req.tenant)
        else:
            self.metrics.record_class("requests", slot.req.priority)
            slot.req.result = slot.req.prompt + slot.generated
            now = time.perf_counter()
            slot.req.t_done = now
            t_in = slot.req.t_installed or now
            # queue-wait vs decode-compute split (ISSUE-8 satellite)
            self.metrics.record_request(
                now - slot.req.enqueued,
                queue_wait_s=t_in - slot.req.enqueued,
                compute_s=now - t_in)
            if self.tenants is not None:
                # the tenant's completion ledger: served count, tokens
                # actually generated (tokens_out), and the SLO window
                # sample that drives the burn-rate gauge (ISSUE-16)
                tn = slot.req.tenant
                self.metrics.record_tenant("requests", tn)
                self.tenants.meter.record_out(tn, len(slot.generated))
                self.tenants.slo.record(tn, now - slot.req.enqueued)
                self.metrics.set_tenant_burn(
                    tn, self.tenants.slo.burn_rate(tn))
            if (self.hibernate and slot.req.session_id is not None
                    and self._tree is not None
                    and slot.table is not None):
                # sticky-session hibernation tracking (ISSUE-19): the
                # FULL committed sequence's whole pages enter the radix
                # tree (prompt pages alone would forget the generated
                # turn), and the session is stamped for the idle sweep.
                # Only fully-WRITTEN pages insert — the final sampled
                # token is returned, never fed, so its position has no
                # KV yet.
                seq = slot.req.result
                n_full = self._kv_rows(len(seq)) // self.page_size
                if n_full:
                    self._tree.insert(
                        seq[:n_full * self.page_size],
                        [int(p) for p in slot.table[:n_full]])
                sid = slot.req.session_id
                self._hib_sessions[sid] = {"tokens": list(seq),
                                           "t": now}
                self._hib_sessions.move_to_end(sid)
                while len(self._hib_sessions) > self._session_capacity:
                    self._hib_sessions.popitem(last=False)
            slot.req.event.set()
        if (self.recurrent and slot.trail_pos is not None
                and slot.trail_pos > slot.req.prefix_matched):
            # the request's end: its trailing row, the state at the last
            # page boundary it landed on, goes to the tree, so that the
            # session's next turn prefills at most a page and its new text
            seq = slot.req.prompt + slot.generated
            if self._give_snapshot(slot, seq[:slot.trail_pos]):
                self._record_state(taken=1)
        self._free_slot_pages(slot)
        slot.req = None

    def _insert_prompt_pages(self, slot: _Slot) -> None:
        """Prefill just completed: register this prompt's FULL pages in
        the radix tree so the next shared-prefix request skips them.
        Page-granular — a prompt shorter than one page caches nothing."""
        if slot.inserted or self.recurrent:
            # a recurrent model's pages serve a later prompt only up to a
            # snapshot: they enter the tree with one (`_give_snapshot`)
            return
        slot.inserted = True
        n_full = slot.req.prefill_len // self.page_size
        if n_full:
            self._tree.insert(slot.req.prompt[:n_full * self.page_size],
                              [int(p) for p in slot.table[:n_full]])

    def _drain_step(self) -> bool:
        """One scheduling round: admit, build the step inputs, dispatch,
        fold the sampled tokens back into each lane.  Returns False when
        idle (nothing active, nothing queued)."""
        clock = self._clock
        clock.to("admit")                   # the wait for the lock too
        with self._cond:
            # a pending warmup runs on the worker's own cache, inside
            # this protected loop (a failing warm dispatch rides the
            # same fault path as a failing decode).  The paged step
            # with n_feed=0 touches only the null page, so it is safe
            # even alongside live lanes
            warm = self._warm_req
            if warm is not None:
                self._warm_req = None
                # a warm dispatch that raises lands in the worker's
                # fault arm (`_run`), which rebuilds the donated pool
                # and hands the exception to the waiting warmup()
                # through `_warming`
                self._warming = warm
        if warm is not None:
            # the warm-up is `yield` time, but no round's: counted at
            # once, so the next round's host time does not carry it
            clock.to("yield")
            self._warm_programs()
            with self._cond:
                self._warming = None
            warm.set()
            clock.to("yield")
            self.metrics.record_phase_seconds(clock.take())
            return True
        with self._cond:
            self._admit_locked()
            active = [s for s in self._slots if s.active]
            if not active:
                return False
            cow, self._pending_cow = self._pending_cow, []
            installs, self._pending_install = self._pending_install, []
            restores, self._pending_state = self._pending_state, []
            # the brownout level this round dispatches under — read
            # once with the lock held; the ladder only moves inside
            # _admit_locked, so the level cannot change mid-dispatch
            level = (self._pressure.level if self._pressure is not None
                     else 0)
        if self.breaker is not None and not self.breaker.allow_dispatch():
            # open breaker: fast-fail whatever is in flight rather than
            # burning decode steps on a failing device
            err = CircuitOpenError(
                "circuit breaker open: decode fast-failed",
                retry_after_s=self.breaker.retry_after_s())
            with self._cond:
                for item in cow:
                    # un-executed CoW copies hold a retention on their
                    # source page; the lane that wanted them is failing
                    self._pool.release([item["src"]])
                for item in restores:
                    if item["held"] is not None:
                        self._states.release([item["held"]])
                for s in self._slots:
                    if s.active:
                        self.metrics.record_shed()
                        self.metrics.record_class("shed",
                                                  s.req.priority)
                        if self.tenants is not None:
                            self.metrics.record_tenant("shed",
                                                       s.req.tenant)
                        s.req.error = err
                        s.req.event.set()
                        self._free_slot_pages(s)
                        s.req = None
            return True
        clock.to("pages")
        if self._cache is None:
            # a failed step consumed its donated k/v buffers and set the
            # cache aside; rebuild INSIDE the protected loop so a failing
            # rebuild fails this round's requests instead of killing the
            # worker thread (page/radix state was already reset by the
            # fault handler — slots restart at pos 0, nothing to keep)
            self._reset_cache()
        return self._dispatch_paged(active, cow, installs, level, restores)

    def _draft_proposals(self) -> Dict[int, List[int]]:
        """One drafting round: collect per-lane proposals for GREEDY
        decode-phase lanes with budget left.  Sampling lanes
        (temperature > 0) are never drafted for — a greedy accept rule
        over a sampled lane would mis-sample — and ride the round as
        plain 1-token decode; so do lanes mid-prefill and lanes within
        one token of their budget.  Out-of-vocab draft tokens (a
        misbehaving custom Drafter) are truncated at the first offender
        so the verify feed stays a valid token chunk."""
        histories: List[Optional[List[int]]] = [None] * self.n_slots
        budgets = [0] * self.n_slots
        for i, slot in enumerate(self._slots):
            if not slot.active:
                continue
            req = slot.req
            remaining = req.max_new - len(slot.generated)
            if (slot.fed >= len(req.prompt) and req.temperature == 0
                    and remaining >= 2 and slot.generated):
                histories[i] = req.prompt + slot.generated
                budgets[i] = min(self.draft_len, remaining - 1)
        if not any(budgets):
            return {}
        proposals = self._drafter.propose(histories, budgets)
        out: Dict[int, List[int]] = {}
        for i, prop in enumerate(proposals):
            if not budgets[i] or not prop:
                continue
            clean: List[int] = []
            for t in prop[:budgets[i]]:
                t = int(t)
                if not 0 <= t < self.cfg.vocab_size:
                    break
                clean.append(t)
            if clean:
                out[i] = clean
        return out

    def _commit_tokens(self, slot: _Slot, toks: List[int]) -> None:
        """Fold newly committed tokens into a lane: first-token TTFT
        stamp, the lane's generated list, and the request's stream (one
        push per token — a speculative round's multi-token commit
        streams as individual events).  The stream cursor is the
        COUNT of tokens already pushed, not "everything new": a
        preempted lane whose swap state was lost recomputes its early
        tokens from the prompt, and those regenerated (byte-identical)
        tokens must not stream twice."""
        req = slot.req
        if req.t_first is None:
            req.t_first = time.perf_counter()
            self.metrics.record_first_token(req.t_first - req.enqueued)
        slot.generated.extend(toks)
        if req.stream is not None and not req.abandoned:
            # monotonic cursor: a recompute rebuilding the early tokens
            # stays BELOW the cursor until it passes where the stream
            # left off — never rewind it, or the rebuilt (identical)
            # tokens would stream again
            for t in slot.generated[req.stream_pushed:]:
                req.stream.put(int(t))
            req.stream_pushed = max(req.stream_pushed,
                                    len(slot.generated))

    def _gathered(self, row, n: int) -> Dict:
        """A block-table row's first `n` pages out of every pool (one
        fixed-shape dispatch, one host sync), as `PageExport`'s page
        fields."""
        with compile_scope("lm:page_gather"):
            # the worker thread owns `_cache` between dispatches (as in
            # `_dispatch_paged`); the sweep and the preemption call
            # this under the lock, the export lane from the worker
            stacks = self._gather(*self._cache, row)  # noqa: LCK101
        stacks = [np.asarray(st)[:, :n] for st in stacks]
        return {"pages_k": stacks[0],
                "pages_v": stacks[1] if len(stacks) > 1 else None}

    def _padded_stacks(self, ex, n: int):
        """An export's page stacks (every pool's) padded to the install
        program's fixed `[L, max_pages, ps, heads, width]`; `ex=None`
        gives the all-zero stacks the warm-up installs."""
        from deeplearning4j_tpu.parallel.generation import (
            pool_depth,
            pool_layout,
        )

        lay = pool_layout(self.cfg)
        shape = (pool_depth(self.cfg), self.max_pages, self.page_size,
                 lay.heads, lay.width)
        out = []
        for i in range(len(lay.names)):
            st = np.zeros(shape, np.dtype(self.cfg.dtype))
            if ex is not None:
                st[:, :n] = ex.stacks[i]
            out.append(st)
        return tuple(out)

    def _export_slot(self, slot: _Slot) -> None:
        """Prefill just completed on an export lane: gather its pages
        out of the pool (one fixed-shape dispatch + one host sync),
        resolve the request with the shipment, and free the lane.  Runs
        BEFORE the lane's pages are released — the radix tree keeps the
        prompt pages for the next shared-prefix prefill, and page
        content is only ever recycled through the allocator."""
        req = slot.req
        t0 = time.perf_counter()
        n = -(-slot.pos // self.page_size)
        ex = PageExport(
            **self._gathered(slot.table, n),
            prompt=list(req.prompt), max_new=req.max_new,
            temperature=req.temperature, seed=req.seed,
            committed=list(slot.generated), pos=int(slot.pos),
            page_size=self.page_size,
            model=model_signature(self.cfg, self.page_size),
            session_id=req.session_id, priority=req.priority,
            tenant=req.tenant)
        self.metrics.record_ship("out", n, ex.nbytes(),
                                 time.perf_counter() - t0)
        req.export_result = ex
        self._finish_slot(slot)

    def _walk_plan(self, width: int) -> Tuple[int, int]:
        """(pages a block, fed columns a query block) of the paged kernel's
        walk in a round of `width` columns, by the kernel's own rule at
        this server's shapes: a lane that reads `n` live pages and feeds
        `f` columns takes `ceil(n / pages) * ceil(f / columns)` blocks a
        layer, the round's `walk_blocks`."""
        plan = self._walk_plans.get(width)
        if plan is None:
            from deeplearning4j_tpu.parallel import paged_kernel
            from deeplearning4j_tpu.parallel.generation import pool_layout

            cfg = self.cfg
            plan = self._walk_plans[width] = paged_kernel.walk_plan(
                self.page_size, pool_layout(cfg).row,
                np.dtype(cfg.dtype).itemsize, self.max_pages, width,
                cfg.n_heads, cfg.head_dim, self.block,
                cfg.latent is not None)
        return plan

    def _dispatch_paged(self, active, cow, installs,
                        level: int = 0, restores=()) -> bool:
        # land shipped-in pages first (their lane's committed state is
        # already live — its next feed reads them), then pending
        # copy-on-write pages: a CoW admitted in the same round may
        # diverge FROM a page the shipment just installed
        for item in installs:
            t0 = time.perf_counter()
            with compile_scope("lm:page_install"):
                self._cache = tuple(self._install(
                    *self._cache, *item["stacks"], item["row"],
                    np.int32(item["n"])))
            if item.get("swap"):
                # a preempted lane restoring from the host store — the
                # swap ledger, not the wire-shipping one
                self.metrics.record_swap("in", item["n"],
                                         item["nbytes"])
            elif item.get("hibernate"):
                # a hibernated session resuming from the tiered store —
                # the hibernation ledger (at-rest vs exact bytes feed
                # the compression ratio the bench gates on)
                self.metrics.record_hibernate("in", item["pages"],
                                              item["nbytes"],
                                              item["exact_nbytes"])
            else:
                self.metrics.record_ship("in", item["n"],
                                         item["nbytes"],
                                         time.perf_counter() - t0)
        for item in cow:
            with compile_scope("lm:page_copy"):
                self._cache = tuple(self._copy(
                    *self._cache, np.int32(item["src"]),
                    np.int32(item["dst"])))
            self._pool.release([item["src"]])
        if restores:
            # a recurrent lane's state: the matched snapshot's row into
            # its live row (or zeros), ahead of its first feed
            self._copy_state_rows([(r["src"], r["dst"]) for r in restores])
            held = [r["held"] for r in restores if r["held"] is not None]
            self._states.release(held)
            self._record_state(hit=len(held), copied=len(restores))
        if self.block > 1:
            return self._block_round(active, level)
        # brownout ladder effects (ISSUE-15, docs/robustness.md "The
        # degradation ladder"): level 1 turns speculation off (drafts
        # buy throughput with wide-dispatch compute — under pressure
        # that compute belongs to survival); level 2 additionally
        # shrinks the prefill ride-along width so active decode lanes
        # commit more often while admission throughput pays.
        clock = self._clock
        clock.to("plan")
        drafts = (self._draft_proposals()
                  if self._drafter is not None and level < 1 else {})
        chunk_eff = (max(1, self.prefill_chunk // 2) if level >= 2
                     else self.prefill_chunk)
        # chunk width: the wide program dispatches only while some lane
        # has a FULL chunk of prompt left to feed — sub-chunk tails and
        # pure-decode rounds ride the 1-wide program — or, with
        # speculation on, while some lane has drafts to verify (and
        # then prompt tails hitch a ride on the already-paid wide
        # dispatch).  Short-prompt non-speculative traffic therefore
        # never compiles (or pays for) the wide program at all; a long
        # prompt costs ceil(P/chunk) wide dispatches plus its tail.
        width = 1
        full_chunk = any(len(s.req.prompt) - s.fed >= chunk_eff
                         for s in active)
        if self.speculate != "off":
            if drafts or (full_chunk and self.prefill_chunk > 1):
                width = self.spec_width
        elif self._chunk_step is not None and full_chunk:
            width = self.prefill_chunk
        clock.to("marshal")
        fed = dict.fromkeys(("prefill", "decode", "draft"), 0)
        live_pages = attn_rows = attn_pairs = walk_blocks = 0
        walk_pages, walk_columns = self._walk_plan(width)
        tokens = np.zeros((self.n_slots, width), np.int32)
        pos = np.zeros((self.n_slots,), np.int32)
        n_feed = np.zeros((self.n_slots,), np.int32)
        n_draft = np.zeros((self.n_slots,), np.int32)
        temp = np.zeros((self.n_slots,), np.float32)
        seeds = np.zeros((self.n_slots,), np.int32)
        counts = np.zeros((self.n_slots,), np.int32)
        table = np.zeros((self.n_slots, self.max_pages), np.int32)
        rows = np.zeros((self.n_slots,), np.int32)
        for i, slot in enumerate(self._slots):
            if not slot.active:
                continue
            req = slot.req
            remaining = len(req.prompt) - slot.fed
            if remaining > 0:                  # chunked prefill
                f = min(remaining, width, chunk_eff)
                if self.recurrent:
                    # a feed that crosses a page boundary ends on the last
                    # one it crosses: the state is known where a round
                    # ends, and boundaries are where it is kept
                    end = (slot.pos + f) // self.page_size * self.page_size
                    if end > slot.pos:
                        f = end - slot.pos
                tokens[i, :f] = req.prompt[slot.fed:slot.fed + f]
                n_feed[i] = f
                fed["prefill"] += f
                req.prefill_rounds += 1
                req.prefill_wide_rounds += width > 1
            elif width > 1 and i in drafts:    # speculative verify
                prop = drafts[i]
                tokens[i, 0] = slot.generated[-1]
                tokens[i, 1:1 + len(prop)] = prop
                n_feed[i] = 1 + len(prop)
                n_draft[i] = len(prop)
                fed["decode"] += 1
                fed["draft"] += len(prop)
            else:                              # decode: feed last sample
                tokens[i, 0] = slot.generated[-1]
                n_feed[i] = 1
                fed["decode"] += 1
            # pages the attention reads for this lane: history and feed;
            # the rows in them, and the (fed column, visible row) pairs
            f = int(n_feed[i])
            pages = -(-(slot.pos + f) // self.page_size)
            live_pages += pages
            walk_blocks += -(-pages // walk_pages) * -(-f // walk_columns)
            attn_rows += slot.pos + f
            attn_pairs += f * slot.pos + f * (f + 1) // 2
            pos[i] = slot.pos
            temp[i] = req.temperature
            seeds[i] = req.seed
            counts[i] = len(slot.generated)
            table[i] = slot.table
            rows[i] = slot.row
        clock.to("dispatch")
        with compile_scope(f"lm:paged[w{width}]"):
            if self.speculate != "off":
                nxt, acc, *pools = self._step(
                    self.params, *self._cache, table, pos, n_feed,
                    n_draft, tokens, temp, seeds, counts)
            else:
                nxt, *pools = self._step(
                    self.params, *self._cache, table, pos, n_feed, tokens,
                    temp, seeds, counts, *((rows,) if self.recurrent
                                           else ()))
                acc = None
        if self.breaker is not None:
            self.breaker.record_success()
        self._cache = tuple(pools)
        # ONE host sync per round: the bonus tokens and the per-lane
        # accepted counts arrive together, never per token
        clock.to("sync")
        nxt = np.asarray(nxt)
        acc = np.asarray(acc) if acc is not None else None
        if self.cfg.experts is not None:
            # a `RoutedExperts` program's tokens carry the round's expert
            # load behind them (`generation.expert_load`): no second sync
            self.metrics.record_expert_load(*(int(x) for x in nxt[-3:]))
        clock.to("fold")
        self._steps += 1
        emitted = 0
        saves, taken = [], 0
        for i, slot in enumerate(self._slots):
            if not slot.active or n_feed[i] == 0:
                continue
            if slot.fed < len(slot.req.prompt):
                slot.pos += int(n_feed[i])
                slot.fed += int(n_feed[i])
                if self.recurrent:
                    taken += self._keep_state(slot, saves, prompt=True)
                if slot.fed < len(slot.req.prompt):
                    continue
                # prefill complete: its full pages become reusable, and
                # the last prompt token's logits yield the first sample
                self._insert_prompt_pages(slot)
                self._commit_tokens(slot, [int(nxt[i])])
                emitted += 1
                if slot.req.export:
                    # export lane: this pool's job ends at prefill —
                    # gather the pages, resolve with the shipment
                    self._export_slot(slot)
                    continue
            else:
                # decode fold with in-jit accept/rollback: commit the
                # accepted draft prefix plus the bonus token; rewind is
                # a pointer move — pos advances past ONLY the committed
                # feeds, so rejected columns' k/v (written into the
                # lane's own future pages) stay masked until real
                # writes land over them.  No pages move: the lane's
                # pages were granted at admission and flow back through
                # `_free_slot_pages` refcounts at completion.
                a = int(acc[i]) if acc is not None else 0
                k_drafted = int(n_draft[i])
                slot.pos += 1 + a
                if self.recurrent:
                    self._keep_state(slot, saves, prompt=False)
                if k_drafted:
                    slot.req.drafted += k_drafted
                    slot.req.accepted += a
                    self._commit_tokens(
                        slot, [int(t) for t in drafts[i][:a]]
                        + [int(nxt[i])])
                else:
                    self._commit_tokens(slot, [int(nxt[i])])
                emitted += 1 + a
                self.metrics.record_decode_round(
                    1 + a, drafted=k_drafted, accepted=a)
            if len(slot.generated) >= slot.req.max_new:
                self._finish_slot(slot)
        if self.recurrent:
            if saves:
                # after the step that made the states, before any later
                # dispatch that could write the rows: device order
                self._copy_state_rows(saves)
            self._record_state(taken=taken, copied=len(saves))
            self.metrics.record_kda_rows(width, len(active))
        self.metrics.record_dispatch(len(active), self.n_slots)
        if emitted:
            self.metrics.record_tokens(emitted)
        self.metrics.set_pages(self._pool.in_use, self._pool.free,
                               self.kv_pages)
        clock.to("yield")
        self.metrics.record_round(clock.take(), width, self.n_slots, fed,
                                  live_pages, attn_rows, attn_pairs,
                                  walk_blocks)
        return True

    def _no_block_held(self) -> np.ndarray:
        """The step program's `held` where no lane carries a block: the
        shape of its result, zeros."""
        load = 3 if self.cfg.experts is not None else 0
        return np.zeros((2 * self.n_slots * self.block + load,), np.int32)

    def _block_round(self, active, level: int = 0) -> bool:
        """`_dispatch_paged`'s round for a block model (module docstring,
        "The block round"): prefill lanes feed whole blocks of their
        prompt, decode lanes their block in flight, as a denoise round
        while a column is masked and as the commit pass when none is;
        one dispatch, one host sync.  Under the static schedule the
        dispatch of round N + 1 comes BEFORE the sync of round N: which
        lanes denoise, commit, begin a block or end is known from the
        counts alone, and a lane whose block round N is still unmasking
        feeds it from round N's result on the device (`carry`), so the
        device never waits for the host to read a round.  The dynamic
        schedule's counts come from the device, and its round is read
        before the next is built."""
        clock = self._clock
        flight = self._dispatch_block_round(active, level)
        before, self._ahead = self._ahead, flight  # noqa: LCK101
        if before is not None:
            self._fold_block_round(before)
        if self.unmask != "static":
            self._settle_block_round()
        clock.to("yield")
        if flight is None:
            # nothing left to feed: the lanes' last round was read above
            self.metrics.record_phase_seconds(clock.take())
        else:
            self.metrics.record_round(clock.take(), flight["width"],
                                      self.n_slots, *flight["account"])
        return True

    def _settle_block_round(self) -> None:
        """Read the round in flight, if one is: whoever moves a lane's
        durable state (a preemption's swap-out) first brings the host's
        account of it up to the device's."""
        flight, self._ahead = self._ahead, None  # noqa: LCK101
        if flight is not None:
            self._fold_block_round(flight)

    def _dispatch_block_round(self, active, level: int) -> Optional[Dict]:
        """Build and dispatch one block round from what is known WITHOUT
        the round in flight's result: positions, feeds and how many
        columns each block has masked.  -> the round in flight (its
        result still on the device, and each lane's part in it), or None
        where no lane has anything to feed."""
        clock = self._clock
        clock.to("plan")
        blk = self.block
        chunk_eff = (max(blk, self.prefill_chunk // 2 // blk * blk)
                     if level >= 2 else self.prefill_chunk)
        width = blk
        if self._chunk_step is not None and any(
                s.req.prefill_len - s.fed >= chunk_eff for s in active):
            width = self.prefill_chunk
        clock.to("marshal")
        before = self._ahead["lanes"] if self._ahead is not None else {}
        fed = dict.fromkeys(("prefill", "decode", "draft"), 0)
        live_pages = attn_rows = attn_pairs = walk_blocks = 0
        walk_pages, walk_columns = self._walk_plan(width)
        tokens = np.zeros((self.n_slots, width), np.int32)
        known = np.ones((self.n_slots, blk), np.int32)
        carry = np.zeros((self.n_slots,), np.int32)
        pos = np.zeros((self.n_slots,), np.int32)
        n_feed = np.zeros((self.n_slots,), np.int32)
        # static: B / S columns a step and a threshold no confidence
        # reaches; dynamic: the threshold, and one column where nothing
        # passes it (a prefill lane's choice is made and ignored)
        static = self.unmask == "static"
        per_step = blk // self.denoise_steps if static else 1
        quota = np.full((self.n_slots,), per_step, np.int32)
        tau = np.full((self.n_slots,), 2.0 if static else self.tau,
                      np.float32)
        table = np.zeros((self.n_slots, self.max_pages), np.int32)
        lanes: Dict[int, Dict] = {}
        for i, slot in enumerate(self._slots):
            if not slot.active:
                continue
            req = slot.req
            prior = before.get(i)
            if prior is not None and prior["req"] is not req:
                prior = None        # the lane has changed hands since
            remaining = req.prefill_len - slot.fed
            if remaining > 0:                  # whole blocks of the prompt
                f = min(remaining, width, chunk_eff)
                tokens[i, :f] = req.prompt[slot.fed:slot.fed + f]
                fed["prefill"] += f
                req.prefill_rounds += 1
                req.prefill_wide_rounds += width > blk
                lane = {"req": req, "kind": "prefill",
                        "ends": remaining == f}
            else:
                sent = len(slot.generated) + (
                    prior["served"] if prior is not None
                    and prior["kind"] == "commit" else 0)
                if sent >= req.max_new:
                    continue    # its last commit is in flight
                if slot.block is None:
                    slot.block = _Block(
                        slot.pos, req.prompt[slot.pos:slot.pos + blk], blk,
                        int(self.cfg.mask_token))
                block = slot.block
                if prior is not None and prior["kind"] == "denoise":
                    # the block is round N's result, on the device
                    masked, carry[i] = prior["left"], 1
                else:
                    masked = blk - sum(block.known)
                    tokens[i, :blk] = block.tokens
                    known[i] = block.known
                f = blk
                fed["decode"] += blk
                if masked:
                    # what stays masked, by the static schedule's count
                    lane = {"req": req, "kind": "denoise", "block": block,
                            "left": masked - min(per_step, masked)}
                else:
                    # the commit pass: its tokens are the client's once
                    # the round is read; the lane is past the block now
                    own = max(len(req.prompt) - slot.pos, 0)
                    lane = {"req": req, "kind": "commit", "block": block,
                            "served": min(blk - own, req.max_new - sent)}
                    slot.block = None
            n_feed[i] = f
            pages = -(-(slot.pos + f) // self.page_size)
            live_pages += pages
            walk_blocks += -(-pages // walk_pages) * -(-f // walk_columns)
            attn_rows += slot.pos + f
            # a column sees the history and its whole block
            n = f // blk
            attn_pairs += f * slot.pos + blk * blk * n * (n + 1) // 2
            pos[i] = slot.pos
            table[i] = slot.table
            lanes[i] = lane
            if lane["kind"] == "prefill":
                slot.pos += f
                slot.fed += f
            elif lane["kind"] == "commit":
                slot.pos += blk
        if not lanes:
            return None
        clock.to("dispatch")
        # the worker thread owns `_cache` between dispatches (as in
        # `_dispatch_paged`, whose round this is)
        cache = self._cache  # noqa: LCK101
        held = (self._ahead["out"] if self._ahead is not None
                else self._no_block_held())
        with compile_scope(f"lm:paged[w{width}]"):
            out, *pools = self._step(
                self.params, *cache, table, pos, n_feed, tokens, known,
                quota, tau, held, carry)
        if self.breaker is not None:
            self.breaker.record_success()
        self._cache = tuple(pools)  # noqa: LCK101
        self._steps += 1
        self.metrics.record_dispatch(len(active), self.n_slots)
        return {"out": out, "lanes": lanes, "width": width,
                "account": (fed, live_pages, attn_rows, attn_pairs,
                            walk_blocks)}

    def _fold_block_round(self, flight: Dict) -> None:
        """Read a dispatched round, ONE host sync (the blocks after the
        unmasking, their flags and the expert load arrive as one array),
        and fold it into its lanes: a denoise round's unmasked columns
        into the block, a commit pass's tokens to the client."""
        clock = self._clock
        clock.to("sync")
        out = np.asarray(flight["out"])
        if self.cfg.experts is not None:
            self.metrics.record_expert_load(*(int(x) for x in out[-3:]))
        clock.to("fold")
        blk = self.block
        cells = self.n_slots * blk
        new_tok = out[:cells].reshape(self.n_slots, blk)
        new_known = out[cells:2 * cells].reshape(self.n_slots, blk) != 0
        emitted = 0
        # the round's account, recorded once: lane-rounds by kind, columns
        # fed masked and known, columns unmasked
        denoised = committed = fed_masked = fed_known = unmasked = 0
        for i, lane in flight["lanes"].items():
            slot, req = self._slots[i], lane["req"]
            if slot.req is not req:
                continue        # abandoned and freed since the dispatch
            if lane["kind"] == "prefill":
                if lane["ends"]:
                    self._insert_prompt_pages(slot)
                continue
            block = lane["block"]
            masked = blk - sum(block.known)
            fed_masked += masked
            fed_known += blk - masked
            if lane["kind"] == "denoise":
                for c in range(blk):
                    if new_known[i, c] and not block.known[c]:
                        block.tokens[c] = int(new_tok[i, c])
                        block.known[c] = True
                        block.steps[c] = block.step
                        unmasked += 1
                block.step += 1
                req.denoise_rounds += 1
                denoised += 1
                if (self.unmask == "static"
                        and blk - sum(block.known) != lane["left"]):
                    raise RuntimeError(
                        f"lane {i}: the denoise round left "
                        f"{blk - sum(block.known)} columns masked where "
                        f"the static schedule counts {lane['left']}")
                continue
            # the commit pass wrote the K/V of the block's final tokens:
            # the block is durable, its answer tokens are the client's
            own = max(len(req.prompt) - block.first, 0)  # the prompt's tail
            toks, steps = block.tokens[own:], block.steps[own:]
            room = lane["served"]
            req.unmask_steps += steps[:room]
            req.surplus, req.surplus_steps = toks[room:], steps[room:]
            req.blocks += 1
            self._commit_tokens(slot, toks[:room])
            emitted += room
            committed += 1
            if len(slot.generated) >= req.max_new:
                self._finish_slot(slot)
        self.metrics.record_block_rounds(denoised, committed, fed_masked,
                                         fed_known, unmasked)
        if emitted:
            self.metrics.record_tokens(emitted)
        self.metrics.set_pages(self._pool.in_use, self._pool.free,
                               self.kv_pages)

    def _keep_state(self, slot: _Slot, saves: List, prompt: bool) -> int:
        """A recurrent lane just advanced to `slot.pos`.  Where that is a
        page boundary, its trailing row is refreshed (one row copy, queued
        in `saves`); where it is a multiple of `snapshot_every` inside
        the prompt, the refreshed row goes to the radix tree and the lane
        takes a fresh trailing row, if the state pool can give one.
        -> snapshots given to the tree (0 or 1)."""
        if slot.pos % self.page_size or not slot.trail:
            return 0
        saves.append((slot.row, slot.trail))
        slot.trail_pos = slot.pos
        if not prompt or slot.pos % self.snapshot_every:
            return 0
        if self._states.free < 1:
            self._tree.evict_snapshots(1)
        fresh = self._states.alloc(1)
        if fresh is None:
            return 0
        if self._give_snapshot(slot, slot.req.prompt[:slot.pos]):
            slot.trail = fresh[0]
            return 1
        self._states.release(fresh)
        return 0

    def _run(self) -> None:
        while True:
            with self._cond:
                if not self._running:
                    # abort in-flight + queued rather than leaving clients
                    # blocked on a dead worker
                    victims = [s.req for s in self._slots if s.active]
                    victims += list(self._queue)
                    for s in self._slots:
                        s.req = None
                    self._queue.clear()
                    # page contents survive a stop only as long as the
                    # buffers do — release everything in one sweep
                    self._reset_pool_locked()
                    if (self.hibernate and self._swap is not None
                            and self.state_dir is not None):
                        # a clean stop makes hibernation durable: demote
                        # host-tier entries (only hib- remain — the
                        # reset above dropped the swap- lane state) so a
                        # restarted server over the same state_dir
                        # resumes them instead of recomputing
                        self._swap.flush_to_disk()
                    if self._warm_req is not None:
                        # a warmup() waiting on a stopped server must
                        # unblock, not sit out its timeout
                        self._warm_req.set()
                        self._warm_req = None
                    for r in victims:
                        self.metrics.record_shed()
                        self.metrics.record_class("shed", r.priority)
                        if self.tenants is not None:
                            self.metrics.record_tenant("shed", r.tenant)
                        r.error = ServingUnavailableError(
                            "LM server stopped")
                        r.event.set()
                    self._clock.to(None)
                    return
            try:
                busy = self._drain_step()
            except BaseException as e:  # noqa: BLE001 — fail in-flight, keep serving
                if self.breaker is not None:
                    self.breaker.record_failure()
                with self._cond:
                    if self._warming is not None:
                        # the failed dispatch was warmup()'s: it re-raises
                        self._warm_error = e
                        self._warming.set()
                        self._warming = None
                    victims = [s for s in self._slots if s.active]
                    for s in victims:
                        s.req.error = e
                        s.req.event.set()
                        s.req = None
                    # the failed step consumed its donated k/v buffers
                    # AND whatever pages the radix tree pointed into:
                    # reset the host page state NOW (pure Python, cannot
                    # fail) so the next admit round allocates against a
                    # coherent pool, and mark the device cache dead so
                    # the next round rebuilds it inside this same
                    # protected loop (a rebuild that throws then fails
                    # THAT round's requests, not the worker)
                    self._reset_pool_locked()
                    self._cache = None
                busy = True
                self._clock.to("yield")
            if not busy:
                # no lane active: the wait is no phase's, counted beside
                # them, with the admit that found nothing to do
                self._clock.to("idle")
                with self._cond:
                    if not self._running:
                        self._clock.to(None)
                        return
                    if not self._queue:
                        self._cond.wait(0.05)
                self._clock.to("yield")
                self.metrics.record_phase_seconds(self._clock.take())
            else:
                time.sleep(0)  # yield: let submitters enqueue mid-decode
