"""Compile a `train_mesh` configuration's two programs for a TPU v5e 2x2
host that is described and not attached, and print what each holds on a
chip (`on-chip-measurement` guide, section 2.3).  Run here, on the CPU:

    JAX_PLATFORMS=cpu python3 benchmark/tools/mesh_rehearsal.py \
        benchmark/configs/gpt2-large-train-4chip.json 8 1024

- the trainer's step: `HybridParallelTrainer`'s own jitted step, its
  parameters and optimizer state as shapes with the shardings the trainer
  gives them;
- the reference's step (`reference/<family>.py`, float32, `highest`) with the
  stacked-layer axis sharded over the four chips, as
  `drivers/train_mesh.py` places it.

For each: arguments, outputs, temporaries in bytes per chip, and the
collectives the compiler put in, counted by opcode.  Nothing runs: these are
the compiler's numbers, not a chip run.
"""

from __future__ import annotations

import collections
import json
import os
import pathlib
import re
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

COLLECTIVE = re.compile(r"\s(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all)(-start)?\(")


def describe(compiled) -> dict:
    ma = compiled.memory_analysis()
    counts = collections.Counter(
        m.group(1) for m in COLLECTIVE.finditer(compiled.as_text()))
    return {"argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "held_bytes": (ma.argument_size_in_bytes
                           + ma.output_size_in_bytes
                           - ma.alias_size_in_bytes
                           + ma.temp_size_in_bytes),
            "collectives": dict(counts)}


def main(argv) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmark import spec
    from benchmark.drivers.train_mesh import AXES
    from deeplearning4j_tpu.parallel import hybrid

    config = json.loads(pathlib.Path(argv[0]).read_text())
    batch, seq = int(argv[1]), int(argv[2])
    which = argv[3] if len(argv) > 3 else "both"
    opts = config["train_mesh"]
    adapter, reference = spec.adapter(config), spec.reference(config)
    cfg = adapter.program_config(config, config["dtype"], remat=opts["remat"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chips = np.array(topo.devices)
    mesh = Mesh(chips.reshape(tuple(opts["mesh"][a] for a in AXES)), AXES)

    def shaped(tree, shardings):
        return jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            tree, shardings)

    if which in ("both", "trainer"):
        # the trainer as it builds itself (its own weights: their values do
        # not matter here), with placement handed back as shapes with
        # shardings, since nothing can be put on a described chip
        place = hybrid.place_params

        def placed_shapes(mesh_, tree, specs):
            leaves, treedef = jax.tree_util.tree_flatten(tree)
            specs = jax.tree_util.tree_leaves(
                specs, is_leaf=lambda x: isinstance(x, P))
            return shaped(tree, treedef.unflatten(
                [NamedSharding(mesh_, s) for s in specs]))

        hybrid.place_params = placed_shapes
        zeros_like = jnp.zeros_like
        jnp.zeros_like = lambda a, *k, **kw: (
            a if isinstance(a, jax.ShapeDtypeStruct)
            else zeros_like(a, *k, **kw))
        try:
            trainer = hybrid.HybridParallelTrainer(
                cfg, mesh, lr=opts["lr"], updater=opts["updater"])
        finally:
            hybrid.place_params, jnp.zeros_like = place, zeros_like
        data = NamedSharding(mesh, P("data", "seq"))
        tok = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=data)
        whole = NamedSharding(mesh, P())
        opt = jax.tree_util.tree_map(
            lambda a: a if getattr(a, "sharding", None) is not None
            else jax.ShapeDtypeStruct(np.shape(a), jnp.asarray(a).dtype,
                                      sharding=whole), trainer.opt_state)
        print(json.dumps({"trainer_step": describe(
            trainer._step.lower(trainer.params, opt, tok, tok).compile())}),
            flush=True)
    if which in ("both", "reference"):
        line = Mesh(chips.reshape(-1), ("layers",))
        by_layer, whole = (NamedSharding(line, P("layers")),
                           NamedSharding(line, P()))
        stacked = jax.eval_shape(lambda: reference.stack(
            adapter.make_params(cfg, 0, "float32")))
        stacked = {k: jax.tree_util.tree_map(
            lambda a, k=k: jax.ShapeDtypeStruct(
                a.shape, a.dtype,
                sharding=by_layer if k == "layers" else whole), v)
            for k, v in stacked.items()}
        tok = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=whole)
        step = reference._train_step(float(config["layer_norm_epsilon"]),
                                     float(opts["lr"]), None)
        t = jax.ShapeDtypeStruct((), jnp.float32, sharding=whole)
        print(json.dumps({"reference_step": describe(
            step.lower(stacked, stacked, stacked, t, tok, tok).compile())}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
