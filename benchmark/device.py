"""The device a run got: found or the run ends, named in every result, its
peaks looked up by `device_kind`, its compile cache at a fixed path inside
the checkout."""

from __future__ import annotations

import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent


def acquire(chips: int, tiny: bool):
    """The devices of this process, after the compile cache is placed.
    Without `tiny`, anything but `chips` or more TPU chips ends the process
    with code 2 and no result line."""
    import jax

    from deeplearning4j_tpu.runtime.device import (
        device_line,
        enable_compile_cache,
    )

    cache = enable_compile_cache()
    # small programs too: set-up is steadier when nothing recompiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    print(f"benchmark: {device_line()} compile_cache={cache}", flush=True)
    devices = jax.devices()
    if tiny:
        print("benchmark: --tiny rehearsal at toy sizes: NOT A CHIP RESULT, "
              "no metric is printed", flush=True)
        return devices[:chips]
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"benchmark: needs {chips} TPU chip(s), found "
              f"{len(devices)} x {devices[0].platform}; `--tiny` rehearses "
              f"off-chip", file=sys.stderr)
        raise SystemExit(2)
    return devices[:chips]


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip.  A kind not in the table is an error,
    never a default."""
    table = json.loads((HERE / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks on record for device_kind {device_kind!r}; "
                       f"add it to benchmark/peaks.json with its source")
    return table[device_kind]


def memory_peak_bytes(devices):
    """`peak_bytes_in_use` of the fullest device; None where the backend
    keeps no statistics (the CPU)."""
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use")
              for d in devices]
    return max(p for p in peaks_) if all(
        p is not None for p in peaks_) else None


def describe(devices, **more) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), **more}
