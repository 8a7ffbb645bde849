"""Read, in one process, what `correct` compares: the program's numbers on
many seeds, and beside them the control's, which is the plain reference put
in the program's place and computed in a precision below the configuration's
(`reference/<family>.py`, `quant`: `fp8`, `int8`, and either with `_bf16`
after it, which rounds every intermediate to bfloat16 as well).

    python3 benchmark/tools/control.py <workload> <seconds> 101 102 +fp8 103 104

Every seed is one run of the cell through its driver, window and all, at the
cell's own load; seeds after a word `+<precisions>` (`+fp8`, `+int8_bf16,int8`)
run the control too, in those precisions.  A limit is set from
these two readings (PERF.md, "How `correct` is decided") and never from a
guess.  Not part of a run.
"""

from __future__ import annotations

import argparse
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))


def main(argv) -> int:
    from benchmark import device, spec
    from benchmark.observe import say

    cell = spec.load_cell(argv[0])
    devices = device.acquire(cell.chips, tiny=False)
    control = None
    for word in argv[2:]:
        if word.startswith("+"):
            control = word[1:]
            continue
        args = argparse.Namespace(seed=int(word), seconds=float(argv[1]),
                                  trace=0, tiny=False, control=control)
        _, checks, attempted, failed, _ = spec.driver(cell.config).run(
            cell, args, time.perf_counter(), devices)
        say("program", seed=args.seed, attempted=attempted, failed=failed,
            **{name: value for name, value, _ in checks})
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
