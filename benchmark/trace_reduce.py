"""From the profiler's `.xplane.pb` to numbers: what ran on each device and
for how long, when no operation ran, and what the host was doing then.

Read with `jax.profiler.ProfileData` alone.  A TPU's plane is named
`/device:TPU:<n>`; its line `XLA Ops` holds one event per executed HLO
operation (a `while` or a `call` holds its body's operations nested inside
it) and its line `XLA Modules` one event per launched program.  Busy time is
the union of the operations' intervals, so nesting and overlap count once;
an operation's own time is its interval less its children's.  Host threads
are lines of the plane `/host:CPU`; the benchmark's own spans there
(`jax.profiler.TraceAnnotation`, names starting `bench:`) share the device
planes' clock and say what the host was doing in a gap.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil
import tempfile

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench:"
WINDOW_SPAN = "bench:traced_window"
KEEP_DIR = None     # `run.py --keep-trace`: where a trace's file is left


@dataclasses.dataclass
class Event:
    name: str
    start: float        # seconds on the trace's clock
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class DeviceTrace:
    name: str
    ops: list           # Events of the operations line, by start
    modules: list       # Events of the modules line, by start


@dataclasses.dataclass
class Reduction:
    window_s: float             # the traced window's length
    busy_s: float               # operations running, mean over the devices
    devices: list               # DeviceTrace per device
    op_self_s: dict             # operation name -> own seconds, device mean
    gaps: list                  # (label, seconds) of each idle gap of device 0

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, top: int = 10) -> dict:
        kinds = {}
        for name, sec in self.op_self_s.items():
            kinds[op_kind(name)] = kinds.get(op_kind(name), 0.0) + sec
        ops = sorted(kinds.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps, key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


_HLO = re.compile(r"^%([A-Za-z_\-]+)[.\d]* = (\(?[a-z0-9]+\[[\d,]*\])?"
                  r".*?\s([a-z\-]+)\(")


def op_kind(name: str) -> str:
    """A TPU operation event is named by its whole HLO instruction,
    `%fusion.12 = bf16[16,8,5120]{...} fusion(...)`: several thousand names a
    program.  Its kind is its opcode, its base name where that says more
    (a Pallas kernel is a `custom-call` named after the jitted function) and
    its first output shape, so that the pool's copies, the kernels and the
    matmul fusions of all layers each add up under one name."""
    m = _HLO.match(name)
    if not m:
        return name[:80]
    base, shape, opcode = m.group(1), m.group(2) or "", m.group(3)
    head = opcode if base == opcode else f"{opcode}:{base}"
    return f"{head} {shape.lstrip('(')}".strip()


def newest_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def load(path: str):
    """(devices, host spans) of an `.xplane.pb`, times in seconds."""
    from jax.profiler import ProfileData

    return planes_to_events(ProfileData.from_file(path))


def planes_to_events(profile):
    def events(line):
        return sorted((Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                       for e in line.events), key=lambda e: e.start)

    devices, spans = [], []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PLANE):
            lines = {line.name: line for line in plane.lines}
            devices.append(DeviceTrace(
                plane.name,
                events(lines[OPS_LINE]) if OPS_LINE in lines else [],
                events(lines[MODULES_LINE]) if MODULES_LINE in lines else []))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans += [e for e in events(line)
                          if e.name.startswith(SPAN_PREFIX)]
    devices.sort(key=lambda d: d.name)
    return devices, sorted(spans, key=lambda e: e.start)


def union(events, lo: float, hi: float):
    """Disjoint [start, end] intervals covered by `events` within [lo, hi],
    in order."""
    out = []
    for e in events:
        s, t = max(e.start, lo), min(e.end, hi)
        if t <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return out


def self_times(events, lo: float, hi: float) -> dict:
    """name -> seconds an operation ran less the operations nested in it,
    within [lo, hi].  `events` are sorted by start; a later event that starts
    before an earlier one ends is its child."""
    total, stack = {}, []

    def close(ev, child_s):
        own = max(0.0, min(ev.end, hi) - max(ev.start, lo)) - child_s
        total[ev.name] = total.get(ev.name, 0.0) + max(0.0, own)

    for e in events:
        if e.end <= lo or e.start >= hi:
            continue
        while stack and e.start >= stack[-1][0].end:
            ev, child_s = stack.pop()
            close(ev, child_s)
        if stack:
            stack[-1][1] += max(0.0, min(e.end, hi) - max(e.start, lo))
        stack.append([e, 0.0])
    while stack:
        ev, child_s = stack.pop()
        close(ev, child_s)
    return total


def label_gap(start: float, end: float, spans) -> str:
    """The benchmark's own span that covers most of [start, end], or
    `unattributed` (inside the program, which has no spans of its own yet)."""
    best, cover = "unattributed", 0.0
    for s in spans:
        if s.name == WINDOW_SPAN:
            continue
        c = min(end, s.end) - max(start, s.start)
        if c > cover:
            best, cover = s.name[len(SPAN_PREFIX):], c
    return best if cover >= 0.5 * (end - start) else "unattributed"


def reduce(devices, spans) -> Reduction:
    """The traced window is the benchmark's `bench:traced_window` span; where
    the trace lacks it, from the first operation to the last.  None where no
    operation ran on a device (a rehearsal on the CPU)."""
    if not devices or not any(d.ops for d in devices):
        return None
    window = [s for s in spans if s.name == WINDOW_SPAN]
    if window:
        lo, hi = window[0].start, window[0].end
    else:
        lo = min(d.ops[0].start for d in devices if d.ops)
        hi = max(max(e.end for e in d.ops) for d in devices if d.ops)
    busy, own = [], {}
    for d in devices:
        busy.append(sum(t - s for s, t in union(d.ops, lo, hi)))
        for name, sec in self_times(d.ops, lo, hi).items():
            own[name] = own.get(name, 0.0) + sec / len(devices)
    covered = union(devices[0].ops, lo, hi)
    edges = [lo] + [x for iv in covered for x in iv] + [hi]
    gaps = [(label_gap(s, t, spans), t - s)
            for s, t in zip(edges[0::2], edges[1::2]) if t > s]
    return Reduction(window_s=hi - lo, busy_s=sum(busy) / len(busy),
                     devices=devices, op_self_s=own, gaps=gaps)


class Capture:
    """A profiler trace of part of a window, taken by the process that holds
    the chip: `start()`, later `stop()`, then `reduction()` once."""

    def __init__(self):
        self._dir = self._span = None

    @property
    def started(self) -> bool:
        return self._dir is not None

    @property
    def running(self) -> bool:
        return self._span is not None

    def start(self) -> None:
        import jax

        self._dir = tempfile.mkdtemp(prefix="benchmark-trace-")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # the host spans are enough
        jax.profiler.start_trace(self._dir, profiler_options=options)
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()

    def stop(self) -> None:
        import jax

        if self.running:
            self._span.__exit__(None, None, None)
            self._span = None
            jax.profiler.stop_trace()

    def reduction(self):
        """The reduced trace, or None if none was taken; the files go."""
        self.stop()
        if self._dir is None:
            return None
        try:
            path = newest_xplane(self._dir)
            if KEEP_DIR:
                os.makedirs(KEEP_DIR, exist_ok=True)
                shutil.copy(path, KEEP_DIR)
            return reduce(*load(path))
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
            self._dir = None

