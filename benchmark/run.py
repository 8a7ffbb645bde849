#!/usr/bin/env python3
"""One run of one cell of `BENCHMARK.json`, in a new process:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

names its device and ends with code 2 and no result if that is not the TPU
chips the cell asks for; makes weights and traffic from `--seed`; warms the
cell's programs through the persistent compile cache; measures for
`--seconds`; holds what the timed path produced against the plain reference;
prints the contract's one JSON object as the last line of its output.
`--trace 0` reports the cell's end-to-end metrics, `--trace 1` its per-layer
metrics from counters, spans and a profiler trace of part of the window.

`--tiny` rehearses the same path at the toy sizes the data files keep under
`tiny`, on whatever device there is: the last line then holds no metric.
`--keep-trace DIR` leaves the traced run's `.xplane.pb` in DIR to be read by
hand (`benchmark/tools/trace_dump.py`).

This file and the drivers name no cell, configuration, mix or metric: they
find them by the names in `BENCHMARK.json` (`benchmark/README.md`).
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--keep-trace", default=None, metavar="DIR")
    args = ap.parse_args(argv)

    from benchmark import device, spec, trace_reduce
    from benchmark.observe import say

    try:
        import deeplearning4j_tpu  # noqa: F401
    except ImportError as e:
        print(f"benchmark: the program is not in this directory: {e}",
              file=sys.stderr)
        return 3
    cell = spec.load_cell(args.workload, tiny=args.tiny, root=ROOT)
    devices = device.acquire(cell.chips, args.tiny)
    trace_reduce.KEEP_DIR = args.keep_trace
    run, checks, attempted, failed, memory_peak = spec.driver(
        cell.config).run(cell, args, T_START, devices)

    if args.trace and not args.tiny and run.device_trace is None:
        print("benchmark: the traced window holds no device operation",
              file=sys.stderr)
        return 4
    correct = True
    for name, value, limit in checks:
        ok = value <= limit
        correct &= bool(ok)
        say("compared", number=name, value=value, limit=limit, ok=bool(ok))
    directory, names = (("layer_metrics", cell.per_layer) if args.trace
                        else ("end_to_end", cell.end_to_end))
    metrics = {}
    for name in () if args.tiny else names:
        reader = spec.reader(directory, name, ROOT)
        value = reader.read(run)
        if value is not None:
            metrics[name] = {"value": float(value), "unit": reader.UNIT}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics,
              "device": device.describe(devices,
                                        memory_peak_bytes=memory_peak)}
    if args.tiny:
        result["rehearsal"] = True
    if args.trace and run.device_trace is not None:
        result["device"]["busy_s"] = run.device_trace.busy_s
        result["device"]["window_s"] = run.device_trace.window_s
        result["breakdown"] = run.device_trace.breakdown()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
