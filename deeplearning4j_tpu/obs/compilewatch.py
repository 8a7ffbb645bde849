"""First-class XLA compile accounting (ISSUE-8 satellite).

The zero-recompile storm tests always pinned compile counts via
hand-rolled ``jax.monitoring`` listeners; production had no equivalent.
`CompileWatcher` makes the counter first-class: one process-wide
listener on ``/jax/core/compile/backend_compile_duration`` feeding

- ``compiles_total{program_key=...}`` — a per-program-key counter.  The
  key is whatever `compile_scope(key)` is active on the COMPILING thread
  (the serving engine scopes each dispatch/warmup with its ladder shape,
  the LM pool with its step width), so an off-ladder recompile shows up
  under the key of the exact program that paid for it; unscoped
  compiles land under ``""``.
- a bounded ring of recent compile events ``(t_end, duration, key)`` so
  the request tracer can attach an ``xla_compile`` span to the request
  whose dispatch window the compile landed in.

The watcher survives ``jax.monitoring.clear_event_listeners()`` (tests
use it liberally): `ensure_installed()` re-registers when the listener
list no longer contains us, and every read path calls it.

jax is imported lazily — importing this module costs nothing.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import threading
import time
from typing import Dict, Iterable, List, Optional, Tuple

from deeplearning4j_tpu.obs.trace import annotate

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_scope: contextvars.ContextVar = contextvars.ContextVar(
    "dl4j_compile_scope", default="")


@contextlib.contextmanager
def compile_scope(key: str):
    """Attribute any XLA compile triggered by this thread inside the
    block to ``program_key=key`` (contextvars: thread/task local), and
    name the block on the profiler's host plane by the same key: every
    launched program then reads under its stable key beside the device
    trace, whatever the profiler calls the program itself."""
    token = _scope.set(str(key))
    try:
        with annotate(str(key)):
            yield
    finally:
        _scope.reset(token)


class CompileWatcher:
    """Process-wide compile-event counter + recent-event ring."""

    def __init__(self, recent: int = 512):
        self._lock = threading.Lock()
        self._counts: Dict[str, int] = {}
        self._total_duration = 0.0
        self._events = collections.deque(maxlen=recent)  # (t_end, dur, key)

    # ---- listener ---------------------------------------------------------

    def _listener(self, event: str, duration: float, **kw) -> None:
        if event != COMPILE_EVENT:
            return
        key = _scope.get()
        with self._lock:
            self._counts[key] = self._counts.get(key, 0) + 1
            self._total_duration += float(duration)
            self._events.append((time.perf_counter(), float(duration), key))

    def ensure_installed(self) -> None:
        """Register the jax.monitoring listener; safe to call anywhere
        (idempotent, and re-installs after clear_event_listeners).

        The membership check MUST consult the listener list: skipping
        it would register a duplicate listener on EVERY call — each
        compile then counts once per listener and every /metrics scrape
        leaks one more.  The public ``jax.monitoring`` module does not
        re-export ``get_event_duration_listeners``; ``jax._src.monitoring``
        has it."""
        import jax.monitoring as monitoring
        from jax._src.monitoring import get_event_duration_listeners

        if self._listener in get_event_duration_listeners():
            return
        monitoring.register_event_duration_secs_listener(self._listener)

    # ---- reading ----------------------------------------------------------

    def total(self, prefix: Optional[str] = None) -> int:
        """Compiles observed, optionally only for keys with `prefix`."""
        with self._lock:
            if prefix is None:
                return sum(self._counts.values())
            return sum(c for k, c in self._counts.items()
                       if k.startswith(prefix))

    def counts(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counts)

    def any_since(self, t: float) -> bool:
        """O(1) hot-path guard: did ANY compile end at/after `t`?  The
        tracer checks this before paying for `events_between` — on a
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

    def events_between(self, t0: float, t1: float
                       ) -> List[Tuple[float, float, str]]:
        """Compile events whose [start, end] overlaps [t0, t1] (perf
        seconds) — the tracer's 'which request paid for this compile'."""
        with self._lock:
            events = list(self._events)
        out = []
        for t_end, dur, key in events:
            if t_end - dur <= t1 and t_end >= t0:
                out.append((t_end, dur, key))
        return out

    def collector_samples(self) -> Iterable[Tuple]:
        """`MetricsRegistry.register_collector` source: one
        ``compiles_total`` sample per program key plus the cumulative
        compile seconds."""
        self.ensure_installed()
        with self._lock:
            counts = dict(self._counts)
            dur = self._total_duration
        for key, c in sorted(counts.items()):
            yield ("compiles_total", "counter",
                   "XLA backend compiles observed via jax.monitoring",
                   {"program_key": key}, float(c))
        yield ("compile_seconds_total", "counter",
               "cumulative XLA backend compile time", {}, dur)


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
