"""For a kept `.xplane.pb` (`run.py ... --trace 1 --keep-trace DIR`): each
idle gap of device 0 inside the traced window that is longer than a
threshold, with the program's own host spans that cover it (`lm:<phase>` of
the LM worker's round, `lm:paged[w8]` and the other program keys,
`train:hybrid`) and the share of the gap they cover; then the whole: how much
of the idle time is named, and which span holds most of it.

    python3 benchmark/tools/gap_dump.py <file-or-directory> [min_ms] [top]

`trace_reduce` keeps only the benchmark's own `bench:` spans, so the
`breakdown`'s gaps read `unattributed`; this reads the program's spans by
hand until a `benchmark` issue lets the reducer take them (PERF.md section
7).  Needs nothing but JAX; runs anywhere.
"""

from __future__ import annotations

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

PREFIXES = ("lm:", "train:")


def host_spans(profile, prefixes=PREFIXES):
    """Events of the host plane whose name starts with one of `prefixes`,
    and the benchmark's traced-window span, times in seconds."""
    from benchmark import trace_reduce

    spans, window = [], None
    for plane in profile.planes:
        if plane.name != trace_reduce.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                ev = trace_reduce.Event(e.name, e.start_ns * 1e-9,
                                        e.duration_ns * 1e-9)
                if e.name == trace_reduce.WINDOW_SPAN:
                    window = ev
                elif e.name.startswith(prefixes):
                    spans.append(ev)
    return sorted(spans, key=lambda e: e.start), window


def idle_gaps(ops, lo, hi):
    """[start, end] of every stretch of [lo, hi] no operation covers."""
    from benchmark import trace_reduce

    covered = trace_reduce.union(ops, lo, hi)
    edges = [lo] + [x for iv in covered for x in iv] + [hi]
    return [(s, t) for s, t in zip(edges[0::2], edges[1::2]) if t > s]


def cover(gap, spans):
    """(seconds of the gap under any span, {name: seconds under it})."""
    from benchmark import trace_reduce

    lo, hi = gap
    inside = [s for s in spans if s.end > lo and s.start < hi]
    by_name = {}
    for s in inside:
        by_name[s.name] = by_name.get(s.name, 0.0) + (
            min(hi, s.end) - max(lo, s.start))
    return sum(t - s for s, t in trace_reduce.union(inside, lo, hi)), by_name


def report(devices, spans, window, min_s=0.5e-3, top=12, out=print):
    """Prints the listing; -> (idle seconds in the gaps listed, seconds of
    them under a named span, {name: seconds})."""
    ops = devices[0].ops
    lo, hi = ((window.start, window.end) if window is not None
              else (ops[0].start, max(e.end for e in ops)))
    gaps = [g for g in idle_gaps(ops, lo, hi) if g[1] - g[0] >= min_s]
    total = named = 0.0
    by_name = {}
    rows = []
    for gap in gaps:
        covered, names = cover(gap, spans)
        total += gap[1] - gap[0]
        named += covered
        for name, sec in names.items():
            by_name[name] = by_name.get(name, 0.0) + sec
        rows.append((gap, covered, names))
    for gap, covered, names in sorted(rows, key=lambda r: r[0][0] - r[0][1]
                                      )[:top]:
        length = gap[1] - gap[0]
        held = ", ".join(f"{n} {1e3 * s:.2f}" for n, s in sorted(
            names.items(), key=lambda kv: -kv[1])[:5])
        out(f"gap {1e3 * length:8.3f} ms at {gap[0] - lo:8.4f} s  covered "
            f"{100 * covered / length:5.1f} %  [{held}]")
    out(f"{len(gaps)} gaps of at least {1e3 * min_s:.2f} ms: "
        f"{1e3 * total:.2f} ms idle in {hi - lo:.3f} s, "
        f"{100 * named / total if total else 0.0:.1f} % under a named span")
    for name, sec in sorted(by_name.items(), key=lambda kv: -kv[1]):
        out(f"  {name:24s} {1e3 * sec:9.2f} ms  "
            f"{100 * sec / total if total else 0.0:5.1f} %")
    return total, named, by_name


def main(argv) -> int:
    from jax.profiler import ProfileData

    from benchmark import trace_reduce

    path = pathlib.Path(argv[0])
    if path.is_dir():
        path = pathlib.Path(trace_reduce.newest_xplane(str(path)))
    min_s = 1e-3 * float(argv[1]) if len(argv) > 1 else 0.5e-3
    top = int(argv[2]) if len(argv) > 2 else 12
    profile = ProfileData.from_file(str(path))
    devices, _ = trace_reduce.planes_to_events(profile)
    if not devices or not devices[0].ops:
        print(f"{path}: no device operations", file=sys.stderr)
        return 1
    spans, window = host_spans(profile)
    report(devices, spans, window, min_s, top)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
