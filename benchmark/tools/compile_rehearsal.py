"""Compile a serve configuration's two step programs for a TPU v5e that is
described and not attached, and print what each holds on the device
(`on-chip-measurement` guide, section 2.3).  Run here, on the CPU:

    JAX_PLATFORMS=cpu python3 benchmark/tools/compile_rehearsal.py \
        benchmark/configs/gpt2-large-serve.json 32 40 48

For each lane count: the pool's bytes, the arguments, outputs and
temporaries of the width-1 and the wide program, and the sum a process would
hold (weights + pool + the larger temporary).  Nothing runs: these are the
compiler's numbers, not a chip run.
"""

from __future__ import annotations

import json
import os
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))


def main(argv) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark import spec
    from deeplearning4j_tpu.parallel.generation import (
        make_paged_step,
        pages_per_seq,
    )

    config = json.loads(pathlib.Path(argv[0]).read_text())
    adapter = spec.adapter(config)
    cfg = adapter.program_config(config, config["dtype"], remat=False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    serve = config["serve"]
    # defaults of `UiServer.serve_lm`, which the cell leaves alone
    ps, chunk = 16, 8

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: adapter.make_params(cfg, 0, config["dtype"])))
    weights = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(params))
    for lanes in (int(a) for a in argv[1:] or [serve["slots"]]):
        mp = pages_per_seq(cfg, ps)
        total = lanes * mp + 1
        pool = sds((cfg.n_layers, total, ps, cfg.n_heads, cfg.head_dim),
                   jnp.dtype(cfg.dtype))
        pool_bytes = 2 * int(np.prod(pool.shape)) * pool.dtype.itemsize
        row = {"lanes": lanes, "weights_bytes": weights,
               "pool_bytes": pool_bytes}
        for width in (1, chunk):
            step = make_paged_step(cfg, total, ps, width, paged_kernel=True)
            i32 = lambda *s: sds(s, np.int32)  # noqa: E731
            try:
                ma = step.lower(
                    params, pool, pool, i32(lanes, mp), i32(lanes),
                    i32(lanes), i32(lanes, width),
                    sds((lanes,), np.float32), i32(lanes),
                    i32(lanes)).compile().memory_analysis()
            except jax.errors.JaxRuntimeError as e:
                # the compiler's refusal is the answer for this lane count
                row[f"w{width}"] = {"refused": str(e).split("\n")[0][:300]}
                continue
            row[f"w{width}"] = {
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes}
        temps = [row[k].get("temp_bytes") for k in ("w1", f"w{chunk}")]
        if None not in temps:
            row["held_bytes"] = weights + pool_bytes + max(temps)
            row["share_of_16GB"] = round(row["held_bytes"] / 16e9, 4)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
