"""First-class program-build accounting (ISSUE-8 satellite; stages: PR 40).

The zero-recompile storm tests always pinned compile counts via
hand-rolled ``jax.monitoring`` listeners; production had no equivalent.
`CompileWatcher` makes the account first-class: one process-wide watcher
hears every stage of every program the process builds,

========================================================  ==============
``/jax/core/compile/jaxpr_trace_duration``                ``trace``
``/jax/core/compile/jaxpr_to_mlir_module_duration``       ``lower``
``/jax/core/compile/backend_compile_duration``            ``backend``
``/jax/compilation_cache/cache_retrieval_time_sec``       ``cache_load``
``/jax/compilation_cache/cache_hits`` / ``cache_misses``  hit / miss
========================================================  ==============

``trace`` is Python tracing to a jaxpr, ``lower`` the jaxpr to StableHLO,
``backend`` the XLA compile OR the load from the persistent cache (JAX
fires the one event for both); ``cache_load`` is the part of ``backend``
a hit spent reading, and a miss is a compile that was written to the
cache (a compile under the cache's thresholds is neither).  It feeds

- ``compiles_total{program_key=...}`` — ``backend`` events per key,
  ``compile_seconds_total{program_key, stage}`` and
  ``compile_cache_total{program_key, result}``.  The key is whatever
  `compile_scope(key)` is active on the BUILDING thread (the serving
  engine scopes each dispatch/warmup with its ladder shape, the LM pool
  with its step width), so an off-ladder recompile shows up under the
  key of the exact program that paid for it.  With no scope active the
  key is ``fn:<name>``, the name of the outermost function being built
  on that thread (``fn:step`` for a bare ``jax.jit(step)``): nothing is
  unkeyed.
- a bounded ring of recent events ``(t_end, seconds, key, stage)`` on
  ``perf_counter``: `stage_seconds` / `cache_results` read a window of
  it, and `events_between` / `any_since` let the request tracer attach
  an ``xla_compile`` span to the request whose dispatch window a
  ``backend`` event landed in.

A jit traced inside another's trace fires its own ``trace`` event inside
the outer one's interval.  JAX marks every stage's START with a scalar
event of the same name, so the watcher keeps the open stages of each
thread as a stack: an event nested in one of its own stage is covered by
it and adds nothing, one nested in another stage is taken out of it, and
so the seconds of a key are the union of the intervals, never their sum
(``trace + lower + backend <=`` the wall time of the calls that built).

The watcher survives ``jax.monitoring.clear_event_listeners()`` (tests
use it liberally): `ensure_installed()` re-registers whichever of its
three listeners the lists no longer hold, and every read path calls it.
The listeners run only when something is built: a warmed path pays
nothing.

jax is imported lazily — importing this module costs nothing.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import re
import threading
import time
from typing import Dict, Iterable, List, Optional, Tuple

from deeplearning4j_tpu.obs.trace import annotate

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

# the stages that open (a scalar event) and close (a duration event)
_NESTING = {TRACE_EVENT: "trace", LOWER_EVENT: "lower",
            COMPILE_EVENT: "backend"}
STAGES = ("trace", "lower", "backend", "cache_load")
_CACHE_RESULTS = {CACHE_HIT_EVENT: "hit", CACHE_MISS_EVENT: "miss"}
# lowering and the backend name the module, ``jit(step)``; tracing the
# function, ``step``
_WRAPPED = re.compile(r"^\w+\((.*)\)$")

# stages open on one thread at once: far more than Python's stack allows
_DEEPEST = 1024

_scope: contextvars.ContextVar = contextvars.ContextVar(
    "dl4j_compile_scope", default="")


@contextlib.contextmanager
def compile_scope(key: str):
    """Attribute any program this thread builds inside the block to
    ``program_key=key`` (contextvars: thread/task local), and name the
    block on the profiler's host plane by the same key: every launched
    program then reads under its stable key beside the device trace,
    whatever the profiler calls the program itself."""
    token = _scope.set(str(key))
    try:
        with annotate(str(key)):
            yield
    finally:
        _scope.reset(token)


def over_keys(by_key: Dict[str, Dict[str, float]]) -> Dict[str, float]:
    """What `stage_seconds` or `cache_results` gave, summed over its
    keys: {stage: seconds} or {result: n}."""
    out: Dict[str, float] = {}
    for by in by_key.values():
        for name, value in by.items():
            out[name] = out.get(name, 0) + value
    return out


class _Open:
    """One stage open on a thread."""

    __slots__ = ("stage", "name", "taken_out")

    def __init__(self, stage: str, name: str):
        self.stage = stage
        self.name = name
        # seconds inside this interval accounted under ANOTHER stage
        self.taken_out = 0.0


class CompileWatcher:
    """Process-wide build accountant: per key and stage seconds and
    counts, per key cache hits and misses, and the recent-event ring."""

    def __init__(self, recent: int = 4096):
        self._lock = threading.Lock()
        # key -> stage -> [seconds, events]; "hit" and "miss" are kept
        # beside the stages, with no seconds
        self._totals: Dict[str, Dict[str, List[float]]] = {}
        # (t_end, seconds, key, stage), "hit" and "miss" among them
        self._events = collections.deque(maxlen=recent)
        self._threads = threading.local()
        # listeners found missing were cleared, perhaps with stages
        # open: what a thread's stack held before then is not trusted
        self._installs = 0

    # ---- listeners --------------------------------------------------------

    def _stack(self) -> List[_Open]:
        local = self._threads
        if getattr(local, "installs", None) != self._installs:
            local.installs = self._installs
            local.stack = []
        return local.stack

    @staticmethod
    def _key(stack: List[_Open], name: str) -> str:
        key = _scope.get()
        if key:
            return key
        name = stack[0].name if stack else name
        wrapped = _WRAPPED.match(name)
        return "fn:" + (wrapped.group(1) if wrapped else name)

    def _record(self, key: str, stage: str, seconds: float) -> None:
        with self._lock:
            cell = self._totals.setdefault(key, {}).setdefault(
                stage, [0.0, 0])
            cell[0] += seconds
            cell[1] += 1
            self._events.append((time.perf_counter(), seconds, key, stage))

    def _on_start(self, event: str, value: float, **kw) -> None:
        stage = _NESTING.get(event)
        if stage is None:
            return
        stack = self._stack()
        if len(stack) >= _DEEPEST:
            # nobody hears the ends (0.9.0's clear_event_listeners
            # leaves the scalar listeners in place): keep nothing
            del stack[:]
        stack.append(_Open(stage, str(kw.get("fun_name", ""))))

    def _listener(self, event: str, duration: float, **kw) -> None:
        if event == CACHE_LOAD_EVENT:       # inside its backend stage
            self._record(self._key(self._stack(), ""), "cache_load",
                         float(duration))
            return
        stage = _NESTING.get(event)
        if stage is None:
            return
        stack = self._stack()
        duration = float(duration)
        name = str(kw.get("fun_name", ""))
        taken_out = 0.0
        # its own opening, if it was heard (frames above it never closed)
        for depth in range(len(stack) - 1, -1, -1):
            if stack[depth].stage == stage and stack[depth].name == name:
                taken_out = stack[depth].taken_out
                del stack[depth:]
                break
        parent = stack[-1] if stack else None
        if parent is not None and parent.stage == stage:
            # covered by the interval it lies in: nothing of its own
            parent.taken_out += taken_out
            return
        if parent is not None:
            parent.taken_out += duration
        self._record(self._key(stack, name), stage,
                     max(0.0, duration - taken_out))

    def _on_event(self, event: str, **kw) -> None:
        result = _CACHE_RESULTS.get(event)
        if result is not None:
            self._record(self._key(self._stack(), ""), result, 0.0)

    def ensure_installed(self) -> None:
        """Register the jax.monitoring listeners; safe to call anywhere
        (idempotent, and re-installs after clear_event_listeners).

        The membership checks MUST consult the listener lists: skipping
        them would register a duplicate listener on EVERY call — each
        compile then counts once per listener and every /metrics scrape
        leaks one more.  The public ``jax.monitoring`` module does not
        re-export the ``get_*_listeners``; ``jax._src.monitoring`` has
        them."""
        from jax._src import monitoring

        for mine, held, register in (
                (self._listener, monitoring.get_event_duration_listeners,
                 monitoring.register_event_duration_secs_listener),
                (self._on_start, monitoring.get_scalar_listeners,
                 monitoring.register_scalar_listener),
                (self._on_event, monitoring.get_event_listeners,
                 monitoring.register_event_listener)):
            if mine not in held():
                self._installs += 1
                register(mine)

    # ---- reading ----------------------------------------------------------

    def total(self, prefix: Optional[str] = None) -> int:
        """Compiles or cache loads (``backend`` events) observed,
        optionally only for keys with `prefix`."""
        return sum(n for key, n in self.counts().items()
                   if prefix is None or key.startswith(prefix))

    def counts(self) -> Dict[str, int]:
        """``backend`` events by key."""
        with self._lock:
            return {key: int(stages["backend"][1])
                    for key, stages in self._totals.items()
                    if "backend" in stages}

    def any_since(self, t: float) -> bool:
        """O(1) hot-path guard: did ANY build event end at/after `t`?
        The tracer checks this before paying for `events_between` — on a
        warmed serving path it is False for every request."""
        # deliberately lock-free (this runs per REQUEST on the trace
        # path): deque ops are GIL-atomic, and the one observable race
        # — reading [-1] while a bounded rotation empties it — is
        # caught below and answered conservatively
        events = self._events  # noqa: LCK101 — lock-free hot-path guard, race handled
        if not events:
            return False
        try:
            return events[-1][0] >= t
        except IndexError:   # raced a rotation of the bounded deque
            return True

    def _recent(self, since: Optional[float], until: Optional[float]):
        """The ring's events that ended in [since, until)."""
        with self._lock:
            events = list(self._events)
        return [e for e in events
                if (since is None or e[0] >= since)
                and (until is None or e[0] < until)]

    def events_between(self, t0: float, t1: float
                       ) -> List[Tuple[float, float, str]]:
        """``backend`` events whose [start, end] overlaps [t0, t1] (perf
        seconds) — the tracer's 'which request paid for this compile'."""
        return [(t_end, dur, key)
                for t_end, dur, key, stage in self._recent(t0, None)
                if stage == "backend" and t_end - dur <= t1]

    def stage_seconds(self, since: Optional[float] = None,
                      until: Optional[float] = None
                      ) -> Dict[str, Dict[str, float]]:
        """{key: {stage: seconds}} of the builds that ended in
        [since, until) on ``perf_counter``, as far back as the ring
        reaches; a key has only the stages it went through."""
        out: Dict[str, Dict[str, float]] = {}
        for _, seconds, key, stage in self._recent(since, until):
            if stage in STAGES:
                by = out.setdefault(key, {})
                by[stage] = by.get(stage, 0.0) + seconds
        return out

    def cache_results(self, since: Optional[float] = None,
                      until: Optional[float] = None
                      ) -> Dict[str, Dict[str, int]]:
        """{key: {"hit": n, "miss": n}} of the persistent cache over the
        same window: programs loaded from it, programs written to it."""
        out: Dict[str, Dict[str, int]] = {}
        for _, _, key, stage in self._recent(since, until):
            if stage not in STAGES:
                by = out.setdefault(key, {"hit": 0, "miss": 0})
                by[stage] += 1
        return out

    def collector_samples(self) -> Iterable[Tuple]:
        """`MetricsRegistry.register_collector` source: per program key
        ``compiles_total``, the seconds of each stage and the persistent
        cache's hits and misses."""
        self.ensure_installed()
        with self._lock:
            totals = {key: {stage: tuple(cell)
                            for stage, cell in stages.items()}
                      for key, stages in self._totals.items()}
        for key, stages in sorted(totals.items()):
            for stage, (seconds, n) in sorted(stages.items()):
                if stage == "backend":
                    yield ("compiles_total", "counter",
                           "XLA backend compiles or cache loads observed "
                           "via jax.monitoring", {"program_key": key},
                           float(n))
                if stage in STAGES:
                    yield ("compile_seconds_total", "counter",
                           "cumulative seconds building programs, by "
                           "stage (cache_load lies inside backend)",
                           {"program_key": key, "stage": stage}, seconds)
                else:
                    yield ("compile_cache_total", "counter",
                           "programs loaded from (hit) or written to "
                           "(miss) the persistent compile cache",
                           {"program_key": key, "result": stage}, float(n))


_watcher: Optional[CompileWatcher] = None
_watcher_lock = threading.Lock()


def compile_watcher() -> CompileWatcher:
    """The process-wide watcher, installed on first use."""
    global _watcher
    with _watcher_lock:
        if _watcher is None:
            _watcher = CompileWatcher()
    _watcher.ensure_installed()
    return _watcher
