"""Look at one `.xplane.pb` by hand: its planes, their lines, and in each
line the event names that took most time, with one event's stats.

    python3 benchmark/tools/trace_dump.py <file-or-directory> [top]

A metric reader's patterns are taken from such a listing
(`benchmark/README.md`).  Needs nothing but JAX; runs anywhere.
"""

from __future__ import annotations

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))


def main(argv) -> int:
    from jax.profiler import ProfileData

    from benchmark import trace_reduce

    path = pathlib.Path(argv[0])
    if path.is_dir():
        path = pathlib.Path(trace_reduce.newest_xplane(str(path)))
    top = int(argv[1]) if len(argv) > 1 else 12
    profile = ProfileData.from_file(str(path))
    for plane in profile.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            if not events:
                continue
            total, count, sample = {}, {}, {}
            for e in events:
                total[e.name] = total.get(e.name, 0) + e.duration_ns
                count[e.name] = count.get(e.name, 0) + 1
                sample.setdefault(e.name, e)
            span = (max(e.start_ns + e.duration_ns for e in events)
                    - min(e.start_ns for e in events))
            print(f"  line {line.name!r}: {len(events)} events, "
                  f"{len(total)} names, spanning {span / 1e6:.1f} ms")
            for name in sorted(total, key=total.get, reverse=True)[:top]:
                print(f"    {total[name] / 1e6:10.3f} ms  x{count[name]:<6} "
                      f"{name[:100]}")
            first = sample[max(total, key=total.get)]
            stats = {k: str(v)[:120] for k, v in first.stats}
            print(f"    stats of one {first.name[:60]!r}: {stats}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
