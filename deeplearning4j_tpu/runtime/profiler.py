"""Tracing/profiling.

The reference has NO profiling subsystem (SURVEY §5: "none — the
observation hook is the IterationListener SPI"). Here profiling is
first-class, per the survey's recommendation:

- `trace(logdir)`: context manager around `jax.profiler` emitting a
  TensorBoard-loadable XLA trace (device timelines, HLO cost analysis).
- `StepTimer`: listener-shaped wall-clock stats (mean/p50/p95 step time,
  examples/sec) — drop it into the same listener slot as
  ScoreIterationListener.
- `LatencyRecorder`: thread-safe reservoir of request latencies with
  p50/p95/p99 summaries — the serving subsystem's per-request metric
  primitive (`serving/metrics.py`).
- named spans inside the device trace: `obs.trace.annotate` (the
  package's one wrapper of jax.profiler.TraceAnnotation).
- `device_memory_stats()`: per-device live/peak HBM bytes where the
  backend exposes them.
"""

from __future__ import annotations

import collections
import contextlib
import statistics
import threading
import time
from typing import Dict, List, Optional, Sequence


def percentile(sorted_samples: Sequence[float], q: float) -> float:
    """Nearest-rank percentile of an ascending-sorted sample list
    (ceil-based rank — Python's round() half-to-even would bias p50/p99
    LOW on half-integer ranks, e.g. median([1..5]) -> 2)."""
    if not sorted_samples:
        raise ValueError("percentile of an empty sample set")
    import math

    idx = min(len(sorted_samples) - 1,
              max(0, math.ceil(q / 100.0 * len(sorted_samples)) - 1))
    return float(sorted_samples[idx])


class LatencyRecorder:
    """Thread-safe sliding-window latency reservoir with percentile
    summaries.  The window (default 4096 samples) bounds memory on a
    long-lived server while keeping p99 meaningful at serving rates."""

    def __init__(self, window: int = 4096):
        self._lock = threading.Lock()
        self._samples = collections.deque(maxlen=window)
        self._count = 0
        self._total = 0.0

    def record(self, seconds: float) -> None:
        with self._lock:
            self._samples.append(float(seconds))
            self._count += 1
            self._total += float(seconds)

    @property
    def count(self) -> int:
        with self._lock:
            return self._count

    def summary(self) -> Dict[str, float]:
        """{count, window, mean_ms, p50/p95/p99_ms}.  `count` is the
        lifetime total; mean and percentiles are all computed over the
        same sliding window (`window` samples) so they stay mutually
        consistent on long-lived servers."""
        with self._lock:
            samples = sorted(self._samples)
            count = self._count
        if not samples:
            return {"count": 0}
        return {
            "count": count,
            "window": len(samples),
            "mean_ms": round(sum(samples) / len(samples) * 1e3, 3),
            "p50_ms": round(percentile(samples, 50) * 1e3, 3),
            "p95_ms": round(percentile(samples, 95) * 1e3, 3),
            "p99_ms": round(percentile(samples, 99) * 1e3, 3),
        }


@contextlib.contextmanager
def trace(logdir: str, create_perfetto_link: bool = False):
    """Capture a jax.profiler trace into `logdir` (TensorBoard format)."""
    import jax

    jax.profiler.start_trace(logdir,
                             create_perfetto_link=create_perfetto_link)
    try:
        yield logdir
    finally:
        jax.profiler.stop_trace()


def device_memory_stats() -> List[Dict]:
    """Per-device memory stats (bytes) where the backend reports them."""
    import jax

    out = []
    for d in jax.devices():
        stats = {}
        try:
            raw = d.memory_stats()
            if raw:
                stats = {k: raw[k] for k in
                         ("bytes_in_use", "peak_bytes_in_use",
                          "bytes_limit") if k in raw}
        except (AttributeError, NotImplementedError, RuntimeError):
            pass
        out.append({"device": str(d), **stats})
    return out


class StepTimer:
    """Iteration listener recording wall-clock step times.

    Register with `net.add_listener(StepTimer(batch_size=...))`; read
    `.summary()` (mean/p50/p95 seconds, steps/sec, examples/sec). The first
    `skip` steps are excluded (jit compilation)."""

    def __init__(self, batch_size: Optional[int] = None, skip: int = 1):
        self.batch_size = batch_size
        self.skip = skip
        self._last: Optional[float] = None
        self._times: List[float] = []
        self._seen = 0

    def __call__(self, iteration: int, score: float) -> None:
        now = time.perf_counter()
        if self._last is not None:
            self._seen += 1
            if self._seen > self.skip:
                self._times.append(now - self._last)
        self._last = now

    def reset(self) -> None:
        self._last, self._times, self._seen = None, [], 0

    @property
    def times(self) -> List[float]:
        return list(self._times)

    def summary(self) -> Dict[str, float]:
        if not self._times:
            return {"steps": 0}
        ts = sorted(self._times)
        mean = statistics.fmean(ts)
        out = {
            "steps": len(ts),
            "mean_s": mean,
            "p50_s": ts[len(ts) // 2],
            "p95_s": ts[min(len(ts) - 1, int(len(ts) * 0.95))],
            "steps_per_sec": 1.0 / mean if mean else 0.0,
        }
        if self.batch_size:
            out["examples_per_sec"] = self.batch_size / mean
        return out
