"""Configurations of kind `train`: the program's
`make_accum_train_step` on float32 masters, fed seeded batches by a host
thread that runs two steps ahead.

Set-up builds one object, the compiled step with its state, and drives it
through its first steps with the window's own call and feed; the window goes
on with that same object.  What those first steps gave is held against the
plain reference after the window, once the program's state is freed.
"""

from __future__ import annotations

import gc
import queue
import threading
import time

import numpy as np

from benchmark import device, generators, spec, trace_reduce
from benchmark.observe import Run, say

CHECKED_STEPS = 3
AHEAD = 2
TRACE_STEPS = 4


class Feeder:
    """Batches of the job, put on the device by a host thread `AHEAD` steps
    before the step that takes them."""

    def __init__(self, job):
        self.job = job
        self._q = queue.Queue(maxsize=AHEAD)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._feed, daemon=True,
                                        name="feeder")
        self._thread.start()

    def _feed(self):
        import jax

        step = 0
        while not self._stop.is_set():
            batch = jax.device_put(self.job.batch_at(step))
            while not self._stop.is_set():
                try:
                    self._q.put(batch, timeout=0.1)
                    break
                except queue.Full:
                    continue
            step += 1

    def next(self):
        return self._q.get(timeout=120)

    def close(self):
        self._stop.set()
        self._thread.join(10)
        if self._thread.is_alive():
            raise RuntimeError("the feeder thread did not end")


def named_norms(tree) -> dict:
    """{"layers/3/attn/wq": norm, ...} of a tree in the program's layout:
    the names `reference.train` gives its leaves."""
    import jax
    import jax.numpy as jnp

    norms = jax.jit(lambda t: jax.tree_util.tree_map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))),
        t))(tree)
    return {"/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path): float(v)
            for path, v in jax.tree_util.tree_leaves_with_path(norms)}


def worst_leaf_gap(got: dict, want: dict):
    """The largest gap between a leaf's norm and the reference's, against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero).  -> (gap, leaf)."""
    if got.keys() != want.keys():
        raise ValueError(f"leaves differ: {sorted(set(got) ^ set(want))[:6]}")
    floor = float(np.median(list(want.values())))
    return max((abs(got[k] - want[k]) / max(want[k], floor), k) for k in want)


def run(cell, args, t_start, devices):
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.obs.compilewatch import compile_watcher
    from deeplearning4j_tpu.parallel.hybrid import make_accum_train_step

    config = cell.config
    opts = config["train"]
    adapter = spec.adapter(config)
    cfg = adapter.program_config(config, config["dtype"], remat=opts["remat"])
    job = generators.build(cell.traffic, args.seed, args.seconds,
                           cfg.vocab_size, cfg.max_len)
    run_ = Run(cell=cell, chips=len(devices), model=cfg, job=job,
               tokens_per_step=job.batch * job.seq,
               peaks=None if args.tiny else device.peaks(
                   devices[0].device_kind))
    step, init_state = make_accum_train_step(
        cfg, lr=opts["lr"], accum=opts["accum"], updater=opts["updater"])
    params = adapter.make_params(cfg, args.seed, "float32")
    run_.n_params = sum(int(a.size) for a in jax.tree_util.tree_leaves(params))
    state = {"params": params, "opt": init_state(params)}
    del params
    feeder = Feeder(job)
    watch = compile_watcher()
    capture = trace_reduce.Capture()

    def one():
        tokens, targets = feeder.next()
        state["params"], state["opt"], loss = step(
            state["params"], state["opt"], tokens, targets)
        return loss

    try:
        # the first steps, through the window's own call and feed
        got = {"losses": []}
        for n in range(CHECKED_STEPS):
            got["losses"].append(float(one()))
            if n == 0:
                # Adam's first moment after one step is (1 - b1) * gradient
                b1 = spec.reference(config).ADAM_B1
                got["grad"] = {k: v / (1.0 - b1) for k, v in
                               named_norms(state["opt"]["m"]).items()}
        start = adapter.make_params(cfg, args.seed, "float32")
        got["change"] = named_norms(jax.jit(lambda a, b: jax.tree_util.tree_map(
            lambda x, y: x - y, a, b))(state["params"], start))
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
            state["params"]))
    memory_peak = device.memory_peak_bytes(devices)
    run_.device_trace = capture.reduction()
    bad_steps = sum(not np.isfinite(v) for v in losses)
    k = max(1, len(losses) // 4)
    say("trained", steps=len(run_.step_ends), tokens_per_step=run_.tokens_per_step,
        first_losses=got["losses"], window_first=losses[:3],
        window_last=losses[-3:], memory_peak_bytes=memory_peak)

    # the program's state goes before the reference's is made
    del state
    gc.collect()
    checks = [
        ("compiles_in_window", compiled_in_window, 0),
        ("nonfinite_losses", bad_steps, 0),
        ("nonfinite_parameters", int(not finite), 0),
        # fresh rows every step: the loss falls towards ln(vocabulary)
        ("loss_rise_over_window",
         float(np.mean(losses[-k:]) - np.mean(losses[:k])), 0.0),
    ]
    checks += check_against_reference(config, cfg, job, got, args.seed,
                                      opts, getattr(args, "control", None))
    return run_, checks, len(losses), bad_steps, memory_peak


def reference_readings(config, cfg, job, seed, opts, quant=None):
    """What the plain reference gives for the job's first steps from the
    seed's weights: losses, first gradient norms, change norms by leaf."""
    adapter, reference = spec.adapter(config), spec.reference(config)
    params = adapter.make_params(cfg, seed, "float32")
    batches = (job.batch_at(n) for n in range(CHECKED_STEPS))
    losses, grad, change = reference.train(
        params, batches, config["layer_norm_epsilon"], opts["lr"], quant)
    return {"losses": losses, "grad": grad, "change": change}


DEAD_GRADIENT = 1e-4    # of the median leaf's gradient norm


def compare(got, want):
    """The numbers a train cell is judged by, from its readings and the
    reference's.  A leaf whose gradient is zero by the mathematics (the key
    bias: softmax does not see a shift of every score) has only rounding
    noise for a gradient, and Adam makes steps of any size of that: its
    change is not compared."""
    grad_gap, grad_leaf = worst_leaf_gap(got["grad"], want["grad"])
    dead = DEAD_GRADIENT * float(np.median(list(want["grad"].values())))
    live = [k for k, v in want["grad"].items() if v > dead]
    change_gap, change_leaf = worst_leaf_gap(
        {k: got["change"][k] for k in live},
        {k: want["change"][k] for k in live})
    loss_gap = max(abs(a - b) for a, b in zip(got["losses"], want["losses"]))
    return {"loss_gap_max": loss_gap, "first_grad_norm_gap_worst_leaf":
            grad_gap, "change_norm_gap_worst_leaf": change_gap,
            "worst_leaves": [grad_leaf, change_leaf]}


def check_against_reference(config, cfg, job, got, seed, opts, control=None):
    t = time.perf_counter()
    want = reference_readings(config, cfg, job, seed, opts)
    numbers = compare(got, want)
    say("reference", steps=CHECKED_STEPS, losses=want["losses"],
        worst_leaves=numbers.pop("worst_leaves"),
        seconds=time.perf_counter() - t)
    if control:     # `tools/control.py`: the reference in a lower precision
        for precision in control.split(","):
            low = reference_readings(config, cfg, job, seed, opts,
                                     quant=precision)
            say("control", seed=seed, precision=precision,
                **compare(low, want))
    return [(name, value, config["check"][name])
            for name, value in numbers.items()]
