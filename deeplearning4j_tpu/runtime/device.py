"""Which device this process got, and where its compiled programs persist.

The installed JAX registers the TPU backend with ``fail_quietly=True``:
with ``JAX_PLATFORMS`` unset, a process that cannot take the chip logs at
INFO and carries on on the CPU.  Every kernel policy in the package
pivots on ``jax.default_backend()``, so a worker that lost the chip would
serve from the CPU through the reference paths and nothing would say so.
Each entry point therefore prints `device_line()` once at start-up — the
first line of a worker log names what the worker actually got.

The persistent compile cache is placed from OUTSIDE the program: where
``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and no code path
sets another directory; otherwise every entry point shares one fixed,
git-ignored directory inside the checkout (the path is part of the cache
key, so a directory that moves never hits).
"""

from __future__ import annotations

import os
import pathlib

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def device_line() -> str:
    """``jax <version> platform=<p> device_kind=<k> devices=<n>`` for the
    devices this process holds (initializes the backend)."""
    import jax

    devices = jax.devices()
    return (f"jax {jax.__version__} platform={devices[0].platform} "
            f"device_kind={devices[0].device_kind} devices={len(devices)}")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache and return its directory.
    Call before the first compile of the process: the build account
    (`obs.compilewatch`) starts listening here too, so it hears every
    program the process builds and what the cache saved of each."""
    from deeplearning4j_tpu.obs.compilewatch import compile_watcher

    compile_watcher()
    placed = os.environ.get(CACHE_ENV)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)


def devices_of(tree) -> set:
    """The devices holding a shard of any array in `tree`."""
    import jax

    out = set()
    for leaf in jax.tree_util.tree_leaves(tree):
        out |= set(leaf.sharding.device_set)
    return out


def bytes_in_use(devices) -> list:
    """``bytes_in_use`` per device, None where the backend keeps no
    memory statistics (the CPU)."""
    return [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
