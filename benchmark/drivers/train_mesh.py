"""Configurations of kind `train_mesh`: the program's
`HybridParallelTrainer` on a mesh (data x seq x model) over all the cell's
chips, trained from the benchmark's float32 weights, fed seeded batches by a
host thread that runs two steps ahead.

The run is `drivers/train.py`'s: set-up drives the one trainer through its
first steps by the window's own call and feed, the window goes on with that
same trainer, and what the first steps gave is held against the plain
reference after the window, once the trainer's state is freed.  What differs
is where things live.  The program's state is sharded by the trainer.  The
reference's float32 state with Adam written out does not fit one chip beside
its activations, so the same plain functions run under `jax.jit` with the
stacked-layer axis of every leaf sharded over the chips (`NamedSharding`);
nothing of the program is in it.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmark import device, generators, spec, trace_reduce
from benchmark.drivers.train import (
    CHECKED_STEPS,
    TRACE_STEPS,
    Feeder,
    compare,
    named_norms,
)
from benchmark.observe import Run, say

AXES = ("data", "seq", "model")


def run(cell, args, t_start, devices):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from deeplearning4j_tpu.obs.compilewatch import compile_watcher
    from deeplearning4j_tpu.parallel.hybrid import HybridParallelTrainer

    config = cell.config
    opts = config["train_mesh"]
    shape = tuple(opts["mesh"][a] for a in AXES)
    if int(np.prod(shape)) != len(devices):
        raise SystemExit(
            f"the mesh {dict(opts['mesh'])} needs {int(np.prod(shape))} "
            f"devices, the run has {len(devices)} (a rehearsal on the CPU "
            f"gets them from XLA_FLAGS="
            f"--xla_force_host_platform_device_count)")
    adapter = spec.adapter(config)
    cfg = adapter.program_config(config, config["dtype"], remat=opts["remat"])
    job = generators.build(cell.traffic, args.seed, args.seconds,
                           cfg.vocab_size, cfg.max_len)
    run_ = Run(cell=cell, chips=len(devices), model=cfg, job=job,
               tokens_per_step=job.batch * job.seq,
               peaks=None if args.tiny else device.peaks(
                   devices[0].device_kind))
    mesh = Mesh(np.array(devices).reshape(shape), AXES)
    params = adapter.make_params(cfg, args.seed, "float32")
    run_.n_params = sum(int(a.size) for a in jax.tree_util.tree_leaves(params))
    trainer = HybridParallelTrainer(cfg, mesh, lr=opts["lr"],
                                    updater=opts["updater"], params=params)
    del params
    feeder = Feeder(job)
    watch = compile_watcher()
    capture = trace_reduce.Capture()

    def one():
        return trainer.fit_batch_async(*feeder.next())

    try:
        # the first steps, through the window's own call and feed
        got = {"losses": []}
        for n in range(CHECKED_STEPS):
            got["losses"].append(float(one()))
            if n == 0:
                # Adam's first moment after one step is (1 - b1) * gradient
                b1 = spec.reference(config).ADAM_B1
                got["grad"] = {k: v / (1.0 - b1) for k, v in
                               named_norms(trainer.opt_state["m"]).items()}
        start = jax.device_put(
            adapter.make_params(cfg, args.seed, "float32"),
            jax.tree_util.tree_map(lambda a: a.sharding, trainer.params))
        got["change"] = named_norms(jax.jit(lambda a, b: jax.tree_util.tree_map(
            lambda x, y: x - y, a, b))(trainer.params, start))
        del start

        compiles = watch.total()
        losses, pending = [], None
        run_.t0 = time.perf_counter()
        run_.setup_s = run_.t0 - t_start
        while True:
            done = len(run_.step_ends)
            if args.trace and done >= 2 and not capture.started:
                jax.block_until_ready(pending)
                capture.start()
            elif capture.running and done >= 2 + TRACE_STEPS:
                jax.block_until_ready(pending)
                capture.stop()
            # dispatch the next step, then wait for the one before it
            loss = one()
            if pending is not None:
                jax.block_until_ready(pending)
                run_.step_ends.append(time.perf_counter())
            losses.append(loss)
            pending = loss
            if time.perf_counter() - run_.t0 >= args.seconds:
                jax.block_until_ready(pending)
                run_.step_ends.append(time.perf_counter())
                break
        run_.t_end = run_.step_ends[-1]
        compiled_in_window = watch.total() - compiles
    finally:
        capture.stop()
        feeder.close()
    losses = [float(v) for v in losses]
    finite = bool(jax.jit(lambda t: jnp.all(jnp.stack(
        [jnp.all(jnp.isfinite(x)) for x in jax.tree_util.tree_leaves(t)])))(
            trainer.params))
    memory_peak = device.memory_peak_bytes(devices)
    run_.device_trace = capture.reduction()
    bad_steps = sum(not np.isfinite(v) for v in losses)
    k = max(1, len(losses) // 4)
    say("trained", steps=len(run_.step_ends), mesh=dict(opts["mesh"]),
        tokens_per_step=run_.tokens_per_step, first_losses=got["losses"],
        window_first=losses[:3], window_last=losses[-3:],
        memory_peak_bytes=memory_peak,
        bytes_in_use_by_chip=[(d.memory_stats() or {}).get("bytes_in_use")
                              for d in devices])

    # the program's state goes before the reference's is made
    del trainer
    gc.collect()
    checks = [
        ("compiles_in_window", compiled_in_window, 0),
        ("nonfinite_losses", bad_steps, 0),
        ("nonfinite_parameters", int(not finite), 0),
        # fresh rows every step: the loss falls towards ln(vocabulary)
        ("loss_rise_over_window",
         float(np.mean(losses[-k:]) - np.mean(losses[:k])), 0.0),
    ]
    checks += check_against_reference(config, cfg, job, got, args.seed, opts,
                                      devices, getattr(args, "control", None))
    return run_, checks, len(losses), bad_steps, memory_peak


def reference_readings(config, cfg, job, seed, opts, devices, quant=None):
    """What the plain reference gives for the job's first steps from the
    seed's weights, its state's stacked-layer axis sharded over `devices`:
    losses, first gradient norms, change norms by leaf (`reference.train`,
    step for step, on arrays that are placed)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    adapter, reference = spec.adapter(config), spec.reference(config)
    mesh = Mesh(np.array(devices), ("layers",))
    by_layer = NamedSharding(mesh, PartitionSpec("layers"))
    whole = NamedSharding(mesh, PartitionSpec())

    def make():
        return reference.stack(adapter.make_params(cfg, seed, "float32"))

    placed = {key: jax.tree_util.tree_map(
        lambda _, key=key: by_layer if key == "layers" else whole, sub)
        for key, sub in jax.eval_shape(make).items()}
    start = jax.jit(make, out_shardings=placed)()
    state = jax.tree_util.tree_map(jnp.copy, start)
    m = jax.tree_util.tree_map(jnp.zeros_like, start)
    v = jax.tree_util.tree_map(jnp.zeros_like, start)
    step = reference._train_step(float(config["layer_norm_epsilon"]),
                                 float(opts["lr"]), quant)
    losses, first = [], None
    for t in range(1, CHECKED_STEPS + 1):
        tokens, targets = job.batch_at(t - 1)
        state, m, v, value, norms = step(state, m, v, jnp.float32(t),
                                         jnp.asarray(tokens),
                                         jnp.asarray(targets))
        losses.append(float(value))
        if first is None:
            first = reference._named(norms)
    change = jax.jit(lambda a, b: reference._norms(jax.tree_util.tree_map(
        lambda x, y: x - y, a, b)))(state, start)
    return {"losses": losses, "grad": first,
            "change": reference._named(change)}


def check_against_reference(config, cfg, job, got, seed, opts, devices,
                            control=None):
    t = time.perf_counter()
    want = reference_readings(config, cfg, job, seed, opts, devices)
    numbers = compare(got, want)
    say("reference", steps=CHECKED_STEPS, losses=want["losses"],
        worst_leaves=numbers.pop("worst_leaves"),
        seconds=time.perf_counter() - t)
    if control:     # `tools/control.py`: the reference in a lower precision
        for precision in control.split(","):
            low = reference_readings(config, cfg, job, seed, opts, devices,
                                     quant=precision)
            say("control", seed=seed, precision=precision,
                **compare(low, want))
    return [(name, value, config["check"][name])
            for name, value in numbers.items()]
