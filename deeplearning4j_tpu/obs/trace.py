"""Request tracing: where did this one slow request spend its time.

Every request carries an ``X-Request-Id`` (client-supplied or minted at
the first hop) that propagates across the fleet — the ``FleetRouter``
forwards it on failover resubmission, so a replica killed mid-storm
yields ONE trace whose spans name both the failed and the succeeding
replica, and the replica-side serving planes record their own spans
under the SAME id (queue wait, dispatch, device compute).

- `new_request_id()` — 16-hex-char id.
- `span(name, t0, t1, **attrs)` — one completed span (perf_counter
  seconds; monotonic and process-wide comparable).
- `annotate(name)` — the package's one wrapper of
  ``jax.profiler.TraceAnnotation``: a named span on the profiler's host
  plane, on the device trace's clock; inert unless a profiler session
  is open.
- `PhaseClock` — one thread's wall time split into consecutive named
  phases: each phase's ``perf_counter`` seconds and an `annotate` span
  of the same name over the same interval, so the host-clock counter
  and the device-clock span cannot drift apart.
- `TraceRecorder` — bounded ring buffer of completed traces (oldest
  evicted), queried by ``recent()``/``find()`` and served at
  ``GET /trace/recent``.
- `chrome_trace(traces)` — Chrome trace-event JSON (Perfetto-loadable:
  load the array in https://ui.perfetto.dev or chrome://tracing).  Each
  trace renders as one track (tid = hash of its request id) of "X"
  (complete) events; ``jax.monitoring`` compile events attached by the
  serving planes appear as ``xla_compile`` spans inside the request
  that paid for them.

Stdlib-only at import, like the rest of obs/ (`annotate` imports jax
when first called).
"""

from __future__ import annotations

import itertools
import os
import threading
import time
import zlib
from typing import Dict, List, Optional

# Request ids are a random per-process prefix plus an atomic counter:
# unique across processes (64 random bits) and ~50x cheaper than
# uuid4() — the id mint sits on the serving hot path, where the bench
# `obs` row budgets the whole observability plane at 3%.
_ID_PREFIX = os.urandom(8).hex()
_ID_COUNTER = itertools.count()


def new_request_id() -> str:
    return f"{_ID_PREFIX}{next(_ID_COUNTER):08x}"


def span(name: str, t0: float, t1: float, **attrs) -> Dict:
    """One completed span: perf_counter start/duration + free attrs."""
    s = {"name": str(name), "t0_s": float(t0),
         "dur_s": max(0.0, float(t1) - float(t0))}
    if attrs:
        s["attrs"] = {k: v for k, v in attrs.items() if v is not None}
    return s


def annotate(name: str):
    """Named span on the profiler's host plane (a context manager)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


class PhaseClock:
    """One thread's wall time as consecutive named phases.

    ``to(name)`` ends the open phase and opens ``name`` with ONE
    ``perf_counter`` stamp, so the phases partition the time between the
    first ``to`` and the last with no gap: what `seconds` holds always
    sums to the wall time spanned.  The open phase also holds an
    `annotate` span named ``<prefix><name>``, so a profiler trace shows
    on the host plane, on the device's clock, exactly the intervals the
    counters summed.  ``take()`` hands over the seconds accumulated
    since the last take (the owner adds them to its counters, once a
    round) and starts anew.  Not thread-safe: one clock per thread."""

    __slots__ = ("prefix", "seconds", "_name", "_t", "_span")

    def __init__(self, prefix: str = ""):
        self.prefix = str(prefix)
        self.seconds: Dict[str, float] = {}
        self._name: Optional[str] = None
        self._t = 0.0
        self._span = None

    def to(self, name: Optional[str]) -> None:
        """End the open phase, open ``name`` (None: open nothing)."""
        now = time.perf_counter()
        if self._name is not None:
            self.seconds[self._name] = (self.seconds.get(self._name, 0.0)
                                        + now - self._t)
            self._span.__exit__(None, None, None)
        self._name, self._t = name, now
        if name is not None:
            self._span = annotate(self.prefix + name)
            self._span.__enter__()

    def take(self) -> Dict[str, float]:
        out, self.seconds = self.seconds, {}
        return out


def trace(request_id: str, kind: str, spans: List[Dict],
          status: str = "ok", **attrs) -> Dict:
    """One completed trace.  ``spans`` are `span()` dicts; ``status`` is
    "ok" or an error word ("error", "timeout", "shed", ...)."""
    spans = sorted(spans, key=lambda s: s["t0_s"])
    t0 = spans[0]["t0_s"] if spans else time.perf_counter()
    t1 = max((s["t0_s"] + s["dur_s"] for s in spans), default=t0)
    out = {"request_id": str(request_id), "kind": str(kind),
           "status": str(status), "t0_s": t0,
           "dur_s": t1 - t0, "wall_time": time.time(), "spans": spans}
    if attrs:
        out["attrs"] = {k: v for k, v in attrs.items() if v is not None}
    return out


class TraceRecorder:
    """Thread-safe bounded ring of completed traces."""

    def __init__(self, capacity: int = 256):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self._lock = threading.Lock()
        self._traces: List[Dict] = []
        self._recorded = 0

    def record(self, tr: Dict) -> None:
        with self._lock:
            self._traces.append(tr)
            self._recorded += 1
            if len(self._traces) > self.capacity:
                del self._traces[:len(self._traces) - self.capacity]

    def record_lazy(self, builder, raw) -> None:
        """Hot-path variant: store ``(builder, raw)`` and materialize
        ``builder(raw)`` only when the ring is READ.  The serving
        batcher's per-request trace assembly (span/trace dict builds)
        thereby costs the request one tuple append instead of ~10 dict
        allocations — the bench `obs` row's 3% budget is why."""
        self.record((builder, raw))

    @staticmethod
    def _materialize(entry) -> Dict:
        if isinstance(entry, tuple):
            builder, raw = entry
            return builder(raw)
        return entry

    @property
    def recorded(self) -> int:
        """Lifetime count (the ring holds at most ``capacity``)."""
        with self._lock:
            return self._recorded

    def recent(self, n: Optional[int] = None,
               request_id: Optional[str] = None) -> List[Dict]:
        """Newest-last; optionally filtered by request id."""
        with self._lock:
            out = list(self._traces)
        out = [self._materialize(t) for t in out]
        if request_id is not None:
            out = [t for t in out if t.get("request_id") == request_id]
        if n is not None:
            out = out[-int(n):]
        return out

    def find(self, request_id: str) -> List[Dict]:
        return self.recent(request_id=request_id)

    def clear(self) -> None:
        with self._lock:
            self._traces.clear()


def _tid(request_id: str) -> int:
    return zlib.crc32(request_id.encode()) & 0x7FFFFFFF


def chrome_trace(traces: List[Dict]) -> List[Dict]:
    """Chrome trace-event array: one "X" (complete) event per span, all
    requests on pid 1 with one thread per request id.  Timestamps are
    perf_counter microseconds — relative ordering within a process is
    exact, which is what the span taxonomy needs."""
    events: List[Dict] = []
    for tr in traces:
        tid = _tid(tr.get("request_id", ""))
        meta = {"request_id": tr.get("request_id"),
                "status": tr.get("status")}
        meta.update(tr.get("attrs") or {})
        events.append({
            "name": f"{tr.get('kind', 'request')}",
            "cat": tr.get("kind", "request"), "ph": "X",
            "ts": tr["t0_s"] * 1e6, "dur": tr["dur_s"] * 1e6,
            "pid": 1, "tid": tid, "args": meta})
        for s in tr.get("spans", ()):
            events.append({
                "name": s["name"], "cat": tr.get("kind", "request"),
                "ph": "X", "ts": s["t0_s"] * 1e6, "dur": s["dur_s"] * 1e6,
                "pid": 1, "tid": tid, "args": s.get("attrs", {})})
    return events
