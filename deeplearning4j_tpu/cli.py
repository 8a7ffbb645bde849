"""`dl4j`-equivalent command-line interface.

Parity: reference `deeplearning4j-cli` — driver
`cli/driver/CommandLineInterfaceDriver.java:18-58` (subcommands
train/test/predict; the reference only wired `train` — here all three work)
and `cli/subcommands/Train.java:64` flags (:78-107): `-conf` properties
file, `-input` data path, `-model` MultiLayerConfiguration JSON, `-output`,
`-type multi|single`, `-runtime local|spark|hadoop` (here: local|spmd),
`-savemode binary|txt`, default SVMLight input format (:74).

Train path (ref `execLocal():151`): read records → build net from conf JSON
→ fit → write params — with the reference's Canova record readers replaced
by the datasets readers and `-runtime spmd` running the same fit
data-parallel over the local device mesh (replacing the Spark/Hadoop stubs).

Usage:
    python -m deeplearning4j_tpu.cli train -input iris.svmlight \
        -model model.json -output out/ [-conf train.props]
    python -m deeplearning4j_tpu.cli test  -input iris.svmlight -model out/model
    python -m deeplearning4j_tpu.cli predict -input iris.svmlight -model out/model -output preds.txt
    python -m deeplearning4j_tpu.cli lm -input corpus.txt -output lm/ \
        -generate "prompt"     # flagship TransformerLM on raw text
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time
from typing import Dict, Optional

import numpy as np


# --------------------------------------------------------------------------
# Properties-file config (reference key=value format,
# dl4j-test-resources confs/cli_train_unit_test_conf.txt)

def load_properties(path: Optional[str]) -> Dict[str, str]:
    props: Dict[str, str] = {}
    if not path:
        return props
    for line in pathlib.Path(path).read_text().splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        key, _, value = line.partition("=")
        props[key.strip()] = value.strip()
    return props


def _load_dataset(input_path: str, props: Dict[str, str]):
    from deeplearning4j_tpu.datasets.fetchers import (
        csv_dataset, svmlight_dataset)

    fmt = props.get("input.format", "").lower()
    if not fmt:
        fmt = ("csv" if input_path.endswith(".csv") else "svmlight")
    if fmt in ("svmlight", "svm", "libsvm"):
        from deeplearning4j_tpu.datasets.fetchers import (
            sniff_svmlight_features)
        n_features = int(props.get("input.num.features", 0))
        if not n_features:
            try:
                n_features = sniff_svmlight_features(input_path)
            except ValueError as e:
                raise SystemExit(
                    f"{e} — set input.num.features in the -conf "
                    "properties file") from e
        return svmlight_dataset(
            input_path, n_features,
            num_classes=_opt_int(props.get("input.num.classes")))
    if fmt == "csv":
        return csv_dataset(
            input_path,
            label_col=int(props.get("input.label.column", -1)),
            num_classes=_opt_int(props.get("input.num.classes")),
            skip_header=props.get("input.skip.header", "false") == "true")
    raise SystemExit(f"unknown input.format {fmt!r} (svmlight|csv)")


def _opt_int(v: Optional[str]) -> Optional[int]:
    return int(v) if v else None


def _build_net(model_path: str):
    """Model argument: a MultiLayerConfiguration JSON file (train), a saved
    model directory from `runtime.save_model` (test/predict), or
    ``zoo:<name>`` for a named zoo architecture (e.g. zoo:alexnet-cifar10)."""
    from deeplearning4j_tpu.models import MultiLayerNetwork
    from deeplearning4j_tpu.runtime import load_model

    if model_path.startswith("zoo:"):
        from deeplearning4j_tpu.models import get_model

        return MultiLayerNetwork(get_model(model_path[4:])).init()
    p = pathlib.Path(model_path)
    if p.is_dir():
        return load_model(p)
    net = MultiLayerNetwork.from_json(p.read_text())
    return net.init()


def _announce_device(cmd: str) -> None:
    """One start-up line naming the platform this process GOT (a worker
    that lost the chip runs on the CPU without any other sign — see
    `runtime.device`).  On stderr: `dl4j lm -generate` writes its text
    to stdout, and worker logs capture both streams."""
    from deeplearning4j_tpu.runtime.device import device_line

    print(f"{cmd}: {device_line()}", file=sys.stderr, flush=True)


# --------------------------------------------------------------------------
# Subcommands

def cmd_train(args) -> int:
    from deeplearning4j_tpu.runtime import save_model
    from deeplearning4j_tpu.runtime.checkpoint import save_params

    _announce_device("train")
    props = load_properties(args.conf)
    ds = _load_dataset(args.input, props)
    net = _build_net(args.model)
    epochs = int(props.get("train.epochs", args.epochs))
    batch = int(props.get("train.batch.size", args.batch))

    # Observability plane (ISSUE-8): -metrics-port starts a standalone
    # /metrics endpoint for the run and attaches a TrainingTelemetry
    # listener (same slot as ScoreIterationListener, chunk-aware) —
    # step time, examples/sec, grad norm, loss-scale events, supervisor
    # interventions.  The telemetry snapshot also rides every
    # resilience checkpoint manifest.
    telemetry = metrics_srv = None
    if args.metrics_port is not None:
        from deeplearning4j_tpu.obs import (
            MetricsRegistry,
            MetricsServer,
            TrainingTelemetry,
        )

        registry = MetricsRegistry()
        telemetry = TrainingTelemetry(registry=registry,
                                      sync_interval=args.metrics_interval,
                                      batch_size=batch)
        net.add_listener(telemetry)
        metrics_srv = MetricsServer(registry,
                                    port=args.metrics_port).start()
        print(f"train: metrics on {metrics_srv.url}/metrics "
              f"(every {telemetry.sync_interval} steps)")

    precision = props.get("train.precision", args.precision)
    if precision and precision != "fp32":
        # Precision plane: "bf16" = pure bf16 params+compute, "mixed" =
        # fp32 masters + bf16 compute + dynamic loss scaling (the
        # production TPU recipe; docs/performance.md precision model).
        net.set_precision(precision)
        print(f"precision: {net.precision.describe()}")

    divisor = 1
    if args.runtime == "spmd":
        import jax

        from deeplearning4j_tpu.parallel import DataParallelTrainer, make_mesh
        sync_every = int(props.get("train.sync.every", args.sync_every))
        shard_update = str(props.get(
            "train.shard.update",
            getattr(args, "shard_update", "on"))).lower() not in (
                "off", "false", "0")
        if sync_every > 1:
            # local-SGD / Hogwild-router analog: replicas step on their
            # own shard and average every N steps instead of every step
            print(f"spmd: local-SGD mode, averaging every {sync_every} "
                  f"steps")
        mesh = None
        if args.replicas is not None:
            # Elastic replica count: train on the FIRST N devices — the
            # shrunken-host restart (`-resume` restores a checkpoint
            # saved on ANY replica count onto this mesh).
            avail = jax.devices()
            if not 1 <= args.replicas <= len(avail):
                raise SystemExit(
                    f"-replicas must be in [1, {len(avail)}] (visible "
                    f"devices), got {args.replicas}")
            mesh = make_mesh((args.replicas,), ("data",),
                             devices=avail[:args.replicas])
            print(f"spmd: elastic mesh over {args.replicas} of "
                  f"{len(avail)} visible devices")
        runner = DataParallelTrainer(net, mesh=mesh, sync_every=sync_every,
                                     shard_update=shard_update)
        divisor = runner.n_devices
        if not shard_update:
            print("spmd: -shard-update off — replicated pmean updates")
    else:
        if args.replicas is not None:
            print("-replicas is an spmd-runtime flag; ignored under "
                  "-runtime local")
        runner = net
    from deeplearning4j_tpu.datasets.iterators import PrefetchDataSetIterator

    def _batches():
        for epoch in range(epochs):
            for b in ds.shuffle(seed=epoch).batch_by(batch):
                n = b.num_examples()
                if n % divisor:
                    # SPMD shards the batch over the mesh; pad the tail
                    # batch by wrapping so every shard stays equally sized.
                    reps = (-n) % divisor
                    idx = np.concatenate([np.arange(n),
                                          np.arange(reps) % n])
                    b = type(b)(b.features[idx], b.labels[idx])
                yield b

    out = pathlib.Path(args.output or "dl4j-output")
    ckpt_dir = (pathlib.Path(args.ckpt_dir) if args.ckpt_dir
                else out / "ckpts")
    will_resume = False
    if args.resilience or args.resume:
        from deeplearning4j_tpu.runtime.checkpoint import latest_checkpoint

        will_resume = latest_checkpoint(ckpt_dir) is not None
    fresh_model = (args.model.startswith("zoo:")
                   or not pathlib.Path(args.model).is_dir())
    if net.conf.pretrain and fresh_model and not will_resume:
        # Greedy layer-wise pretraining for DBN/deep-AE configs
        # (reference pretrain-then-finetune, MultiLayerNetwork.java:148)
        # — without this a `zoo:dbn-mnist` train would silently skip the
        # step the model family depends on.  Resuming from a SAVED model
        # dir skips it (re-pretraining finetuned weights would damage
        # them), as does a resilience resume (sup.resume() would discard
        # the pretraining result anyway by restoring checkpoint params).
        net.pretrain(list(ds.shuffle(seed=0).batch_by(batch)), epochs=1)
    if args.resume and not args.resilience and will_resume:
        # Explicit crash-safe resume without full supervision: restore
        # the newest GOOD checkpoint (checksums verified, corrupt steps
        # skipped for the previous good one) into the runner — elastic:
        # the saved replica count need not match this run's mesh.
        from deeplearning4j_tpu.runtime.checkpoint import (
            resume_train_state,
        )

        step = resume_train_state(ckpt_dir, runner)
        print(f"resume: restored checkpoint step {step} from {ckpt_dir}")
    elif args.resume and not args.resilience:
        print(f"resume: no committed checkpoint under {ckpt_dir}; "
              f"starting fresh")
    t0 = time.time()
    # Prefetch shuffles/slices/pads batch b+1 on a host thread while the
    # device trains on b; async stepping lets the device pipeline steps
    # (host syncs once at evaluation below).
    accum = max(1, int(props.get("train.accum.steps", args.accum)))
    if accum > 1 and runner is not net:
        print("-accum is a local-runtime feature; ignored under spmd")
        accum = 1
    chunk = max(1, int(props.get("train.chunk.size", args.chunk)))
    if chunk > 1 and accum > 1:
        print("-accum is ignored with -chunk (a chunk scans batches)")
        accum = 1
    if chunk > 1 and runner is not net and runner.sync_every != 1:
        print("-chunk needs plain sync spmd; ignored under -sync-every > 1")
        chunk = 1
    if args.resilience:
        # Supervised training: poison-batch skipping, divergence rollback,
        # retrying fetches, preemption-safe checkpointing.  The health
        # checks need the loss on the host, so steps do not pipeline —
        # the documented cost of supervision (docs/robustness.md).
        from deeplearning4j_tpu.resilience import (
            ResilienceConfig,
            TrainingSupervisor,
        )

        if accum > 1:
            print("-accum is ignored under -resilience")
            accum = 1
        sup = TrainingSupervisor(runner, telemetry=telemetry,
                                 config=ResilienceConfig(
            checkpoint_dir=ckpt_dir,
            checkpoint_every=args.ckpt_every,
            keep=args.ckpt_keep,
            skip_budget=args.skip_budget,
            divergence_factor=args.divergence_factor,
            step_timeout=args.step_timeout,
            chunk_size=chunk))
        sup.install_signal_handlers()
        stream = _batches()
        if sup.resume():
            print(f"resilience: resumed from checkpoint step {sup.step} "
                  f"under {ckpt_dir}")
            # Fast-forward the (deterministic, seed-per-epoch) schedule
            # past every batch the preempted run CONSUMED (not just its
            # update count — skipped poison batches consume a batch with
            # no step) so the resumed run trains the TAIL of the plan
            # instead of re-training its head.
            import itertools

            stream = itertools.islice(stream, sup.batches_consumed, None)
        # Bound the run by the PLANNED update budget (epochs x batches per
        # epoch): a resumed run completes the remaining steps instead of
        # replaying the whole schedule on top of the checkpoint.
        import math

        total_steps = epochs * math.ceil(ds.num_examples() / batch)
        report = sup.run(stream, max_steps=total_steps)
        print(f"resilience: {report.summary()}")
        for fault in report.faults:
            print(f"resilience:   {fault}")
        if report.preempted:
            print(f"resilience: preempted — emergency checkpoint at step "
                  f"{report.steps}; re-run the same command to resume")
    elif chunk > 1:
        # Fused multi-step driver: K steps per dispatch, the assembler/
        # device-prefetch/dispatch stages pipelined (runtime/fused.py).
        from deeplearning4j_tpu.runtime.fused import FusedTrainingDriver

        FusedTrainingDriver(runner, chunk_size=chunk).fit(_batches())
    else:
        last = None
        for b in PrefetchDataSetIterator(_batches()):
            if accum > 1 and runner is net:
                last = runner.fit_batch_async(b.features, b.labels,
                                              accum_steps=accum)
            else:
                last = runner.fit_batch_async(b.features, b.labels)
        if last is not None:
            import jax

            jax.block_until_ready(last)
    elapsed = time.time() - t0

    scaler = net.scaler_stats()
    if scaler is not None:
        print(f"precision: loss-scale {scaler['scale']:g}, "
              f"{scaler['overflow_count']} overflow step(s) skipped")
    out.mkdir(parents=True, exist_ok=True)
    save_model(net, out / "model")
    save_params(net, out / ("params.bin" if args.savemode == "binary"
                            else "params.txt"), mode=args.savemode)
    ev = net.evaluate(ds.features, ds.labels)
    total = epochs * ds.num_examples()
    print(f"Trained {epochs} epochs on {ds.num_examples()} examples "
          f"({total / max(elapsed, 1e-9):.1f} examples/sec)")
    print(ev.stats())
    print(f"Model saved to {out / 'model'}")
    if metrics_srv is not None:
        snap = telemetry.snapshot()
        print(f"train: telemetry — {snap['steps']} steps, "
              f"{snap['examples_per_sec']:.1f} examples/sec"
              + (f", interventions {snap['interventions']}"
                 if snap.get("interventions") else ""))
        metrics_srv.stop()
    return 0


def _placement_line(runtime: str, **trees) -> str:
    """Where a mesh runtime's arrays actually live: distinct devices per
    tree and `bytes_in_use` on every visible device (a layout that
    silently landed on one chip trains just as well — only this line
    shows it)."""
    import jax

    from deeplearning4j_tpu.runtime.device import bytes_in_use, devices_of

    held = ", ".join(f"{name} on {len(devices_of(tree))} devices"
                     for name, tree in trees.items())
    return (f"{runtime}: placement {held}; bytes_in_use="
            f"{bytes_in_use(jax.devices())}")


def _lm_mesh_layout(runtime: str, n: int, S: int, n_heads: int,
                    n_layers: int, B: int):
    """Pure layout choice for the lm mesh runtimes (unit-tested).

    Returns (mesh_shape, rounded_B, n_microbatches|None).  Every factor
    degrades to 1, so the same command works from one real chip up to a
    full slice — on n=1 both runtimes become plain local training."""
    if runtime == "hybrid":
        # a causal ring over sp chips deals the sequence in 2 * sp chunks
        sp = 2 if n % 2 == 0 and S % 4 == 0 else 1
        tp = 2 if (n // sp) % 2 == 0 and n_heads % 2 == 0 else 1
        dp = max(1, n // (sp * tp))
        if B % dp:
            B += dp - B % dp
        return (dp, sp, tp), B, None
    stages = next((s for s in (4, 2, 1)
                   if n % s == 0 and n_layers % s == 0), 1)
    dp = max(1, n // stages)
    if B % dp:
        B += dp - B % dp
    mb = 2 if (B // dp) % 2 == 0 else 1
    return (dp, stages), B, mb


def _lm_mesh_train(args, cfg, ids, B, S):
    """Train the byte LM on a multi-device mesh runtime and return the
    gathered host params (standard `init_params` tree layout).

    -runtime hybrid: dp/sp/tp via GSPMD + ring attention (the
    dp/sp/tp/ep tier); -runtime pipeline: dp/pp GPipe.  The visible
    devices are factorized into the layout; divisibility constraints
    fail with actionable messages."""
    import time

    import jax

    from deeplearning4j_tpu.parallel import make_mesh
    from deeplearning4j_tpu.parallel.hybrid import (
        HybridParallelTrainer,
        PipelineParallelTrainer,
    )

    n = len(jax.devices())
    if args.accum > 1:
        print("-accum is a local-runtime feature; ignored under mesh "
              "runtimes")
    shape, B_new, mb = _lm_mesh_layout(args.runtime, n, S, cfg.n_heads,
                                       cfg.n_layers, B)
    if B_new != B:
        print(f"{args.runtime}: -batch rounded up to {B_new} "
              f"({shape[0]} data shards)")
        B = B_new
    used = int(np.prod(shape))
    if args.runtime == "hybrid":
        dp, sp, tp = shape
        mesh = make_mesh(shape, ("data", "seq", "model"),
                         devices=jax.devices()[:used])
        trainer = HybridParallelTrainer(cfg, mesh, lr=args.lr, seed=0,
                                        updater=args.updater)
        layout = f"dp{dp}/sp{sp}/tp{tp} over {used} devices"
    else:
        dp, stages = shape
        mesh = make_mesh(shape, ("data", "stage"),
                         devices=jax.devices()[:used])
        trainer = PipelineParallelTrainer(cfg, mesh, n_microbatches=mb,
                                          lr=args.lr, seed=0,
                                          updater=args.updater)
        layout = f"dp{dp}/pp{stages} (microbatches={mb})"
    print(f"{args.runtime}: training on mesh {layout}")
    rng = np.random.default_rng(0)
    steps = max(1, args.epochs * (len(ids) // max(B * S, 1)))
    t0, loss = time.time(), None
    for k in range(steps):
        starts = rng.integers(0, len(ids) - S - 1, B)
        tokens = np.stack([ids[s:s + S] for s in starts])
        targets = np.stack([ids[s + 1:s + S + 1] for s in starts])
        # async step (JIT107): the loss stays on device so step k+1's
        # dispatch overlaps step k; only a due report forces the sync
        loss = trainer.fit_batch_async(tokens, targets)
        if k == 0:
            params = (trainer.params if args.runtime == "hybrid" else
                      (trainer.stage_params, trainer.io_params))
            print(_placement_line(args.runtime, params=params, loss=loss))
        if args.verbose and (k + 1) % 20 == 0:
            print(f"step {k + 1}/{steps} loss {float(loss):.4f}")
    final_loss = float(loss)   # sync BEFORE reading the clock, or the
    tok_rate = steps * B * S / max(time.time() - t0, 1e-9)  # rate lies
    print(f"Trained {steps} steps (final loss {final_loss:.4f}, "
          f"{tok_rate:.0f} tokens/sec)")
    return trainer.export_params()


def _load_saved_lm(out: pathlib.Path):
    """Load an LM saved by `dl4j lm` (lm_config.json + lm_params.npz)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.parallel import transformer as tfm
    from deeplearning4j_tpu.runtime.checkpoint import npz_to_tree

    cfg_path, params_path = out / "lm_config.json", out / "lm_params.npz"
    if not cfg_path.exists():
        raise SystemExit(f"no saved LM at {out}")
    if not params_path.exists():
        raise SystemExit(f"saved LM incomplete: {params_path} missing")
    cfg = tfm.TransformerConfig(**json.loads(cfg_path.read_text()))
    params = npz_to_tree(params_path,
                         tfm.init_params(cfg, jax.random.PRNGKey(0)))
    return cfg, jax.tree_util.tree_map(jnp.asarray, params)


def cmd_serve(args) -> int:
    """Serve a saved model and/or LM over HTTP with dynamic
    micro-batching, shape-bucketed compilation, continuous LM decode and
    the serving-plane resilience layer: bounded admission, per-request
    deadlines, circuit breaker, and SIGTERM graceful drain
    (deeplearning4j_tpu/serving/; docs/robustness.md "serving plane")."""
    import signal
    import threading

    from deeplearning4j_tpu.serving import BucketLadder
    from deeplearning4j_tpu.ui.server import UiServer

    if not args.model and not args.lm:
        raise SystemExit("serve needs -model and/or -lm")
    _announce_device("serve")
    max_queue = args.max_queue if args.max_queue > 0 else None
    deadline_s = args.deadline_ms / 1e3 if args.deadline_ms > 0 else None
    breaker_n = (args.breaker_threshold if args.breaker_threshold > 0
                 else None)
    # ONE registry shared by both planes (ISSUE-16): a tenant's token
    # bucket and burn rate span /model/predict and /lm/generate — two
    # per-plane registries would hand every tenant double its quota
    tenants = None
    if args.tenants:
        from deeplearning4j_tpu.serving.tenancy import TenantRegistry

        tenants = TenantRegistry.from_json(args.tenants)
    srv = UiServer(host=args.host, port=args.port)
    if args.model:
        net = _build_net(args.model)
        ladder = BucketLadder(tuple(
            int(b) for b in args.buckets.split(",")))
        quantize = args.quantize if args.quantize != "none" else None
        srv.serve_model(net,
                        max_batch=min(args.max_batch, ladder.max_batch),
                        max_wait_ms=args.max_wait_ms, ladder=ladder,
                        max_queue_depth=max_queue,
                        default_deadline_s=deadline_s,
                        breaker_threshold=breaker_n,
                        quantize=quantize, tenants=tenants)
        if quantize:
            rep = srv.state.engine._model().quantization_report()
            ratio = rep["float_param_bytes"] / max(rep["param_bytes"], 1)
            print(f"serve: {quantize} weights — "
                  f"{rep['quantized_layers']}/{rep['total_layers']} layers "
                  f"quantized, {rep['param_bytes']:,} param bytes "
                  f"({ratio:.1f}x smaller than fp32)")
        from deeplearning4j_tpu.nn.conf import DenseLayerConf

        first = net.conf.layers[0]
        # n_in is a FLAT feature width only for dense stacks; for conv /
        # RNN first layers it means channels / per-step features, so a
        # [b, n_in] warmup batch would crash the forward at startup
        flat = isinstance(first, DenseLayerConf) and first.n_in
        if args.warmup and flat:
            warmed = srv.state.engine.warmup(
                np.zeros((int(first.n_in),), np.float32))
            print(f"serve: pre-compiled {warmed} bucket shapes")
        elif args.warmup:
            print("serve: -warmup skipped (non-flat input layer "
                  f"{type(first).__name__}); the first request per "
                  "bucket compiles instead")
    if args.lm:
        if (args.lm_disk_dir is not None and args.lm_hibernate_idle_s
                is None and not args.lm_preempt):
            raise SystemExit(
                "serve: -lm-disk-dir needs -lm-hibernate-idle-s or "
                "-lm-preempt (nothing would ever reach the disk tier)")
        cfg, params = _load_saved_lm(pathlib.Path(args.lm))
        srv.serve_lm(cfg, params, slots=args.lm_slots,
                     max_queue_depth=max_queue,
                     default_deadline_s=deadline_s,
                     breaker_threshold=breaker_n,
                     page_size=args.page_size,
                     pages=(args.lm_pages if args.lm_pages > 0 else None),
                     prefill_chunk=args.prefill_chunk,
                     speculate=args.lm_speculate,
                     draft_len=args.draft_len,
                     ship=args.lm_ship,
                     preempt=args.lm_preempt,
                     swap_bytes=int(args.lm_swap_mb * (1 << 20)),
                     brownout=args.lm_brownout, tenants=tenants,
                     hibernate_idle_s=args.lm_hibernate_idle_s,
                     state_dir=args.lm_disk_dir,
                     state_disk_bytes=int(args.lm_disk_mb * (1 << 20)),
                     swap_quantize=args.lm_swap_quantize == "on")
        lm_srv = srv.state.lm_server
        # -warmup opts the LM pool into pre-traffic compiles too, same
        # contract as the classifier path: without it each program
        # compiles on its first dispatch.  A program that fails to
        # compile ends the worker here, not one request at a time.
        warmed = 0
        if lm_srv is not None and args.warmup:
            try:
                warmed = lm_srv.warmup()
            except Exception as e:  # noqa: BLE001 - whatever the compiler raised, the worker must not serve
                lm_srv.stop()
                raise SystemExit(f"serve: -warmup failed, not serving: "
                                 f"{type(e).__name__}: {e}")
        warm_note = (f"{warmed} programs warm" if warmed
                     else "programs compile on first use")
        if lm_srv is not None:
            spec_note = (f", speculate {lm_srv.speculate} "
                         f"(draft_len {lm_srv.draft_len})"
                         if lm_srv.speculate != "off" else "")
            spec_note += ", page shipping on" if lm_srv.ship else ""
            if lm_srv.preempt:
                spec_note += (f", preemption on (swap cap "
                              f"{args.lm_swap_mb:g} MiB)")
            if args.lm_brownout:
                spec_note += ", brownout ladder on"
            if lm_srv.hibernate:
                disk = (f", disk {args.lm_disk_dir}"
                        f" ({args.lm_disk_mb:g} MiB)"
                        if args.lm_disk_dir else "")
                spec_note += (f", hibernation on (idle "
                              f"{args.lm_hibernate_idle_s:g}s, "
                              f"{'int8' if lm_srv.swap_quantize else 'exact'}"
                              f" at rest{disk})")
            print(f"serve: LM registered ({cfg.n_layers}L/d{cfg.d_model}, "
                  f"max_len {cfg.max_len}, {args.lm_slots} decode slots, "
                  f"paged KV: {lm_srv.kv_pages} pages x "
                  f"{lm_srv.page_size} tokens, prefill chunk "
                  f"{lm_srv.prefill_chunk}{spec_note}, {warm_note})")
    srv.start()
    print(f"serve: resilience max_queue={max_queue or 'unbounded'} "
          f"deadline_ms={args.deadline_ms or 'none'} "
          f"breaker_threshold={breaker_n or 'off'} "
          f"drain_grace_s={args.drain_grace_s}")
    if tenants is not None:
        names = ", ".join(tenants.names())
        print(f"serve: tenancy on — WFQ + token quotas for [{names}] "
              f"(X-Tenant header or 'tenant' field; unknown tenants "
              f"get 400, over-quota gets 429 + Retry-After)")
    print(f"Serving on {srv.url} — POST /model/predict, /lm/generate; "
          f"GET /serving/stats, /metrics, /trace/recent, /healthz, "
          f"/readyz")

    # SIGTERM -> graceful drain (the serving analog of the training
    # supervisor's preemption handler): stop admission, let in-flight
    # work finish within the grace window, snapshot /serving/stats to
    # disk so the shed/rejected ledger survives the pod.
    term = threading.Event()
    installed = prev = None
    if threading.current_thread() is threading.main_thread():
        prev = signal.signal(signal.SIGTERM, lambda *_: term.set())
        installed = True
    try:
        if args.serve_seconds > 0:
            term.wait(args.serve_seconds)
        else:
            while not term.wait(3600):
                pass
    except KeyboardInterrupt:
        pass
    finally:
        if term.is_set():
            print(f"serve: SIGTERM — draining (grace "
                  f"{args.drain_grace_s}s)")
            drained = srv.drain(args.drain_grace_s)
            stats_path = pathlib.Path(args.drain_stats)
            try:
                stats_path.write_text(json.dumps(srv.serving_stats(),
                                                 indent=2))
                where = str(stats_path)
            except OSError as e:
                # a lost snapshot must not leave the HTTP server
                # unstopped or the signal handler unrestored
                where = f"LOST ({e})"
            print(f"serve: drain "
                  f"{'complete' if drained else 'grace expired'}; stats "
                  f"snapshot -> {where}")
        srv.stop()
        if installed:
            signal.signal(signal.SIGTERM, prev)
    return 0


def cmd_serve_fleet(args) -> int:
    """Serve a saved model through a replicated fleet: N thread-hosted
    `dl4j serve`-equivalent replicas behind a `FleetRouter` (least-loaded
    + failover dispatch, /readyz-driven health ejection with half-open
    re-admission, optional queue-depth autoscale) fronted by one
    `FleetServer` endpoint.  With `-processes`, each replica is instead
    a real spawned `dl4j serve` worker process supervised end-to-end —
    crash detection, backoff restart, crash-loop quarantine
    (serving/procfleet.py; docs/robustness.md "Process supervision").
    SIGTERM drains the WHOLE fleet gracefully and snapshots /fleet/stats
    (deeplearning4j_tpu/serving/fleet.py; docs/robustness.md "The
    serving fleet")."""
    import signal
    import threading

    from deeplearning4j_tpu.serving import FleetRouter, FleetServer

    if not args.model and not args.lm:
        raise SystemExit("serve-fleet needs -model and/or -lm")
    if args.replicas < 1:
        raise SystemExit(f"-replicas must be >= 1, got {args.replicas}")
    role_split = args.prefill_workers > 0 or args.decode_workers > 0
    if role_split:
        # disaggregated prefill/decode fleet (ISSUE-14): role scheduling
        # is an LM feature — prefill workers chew prompts and ship KV
        # pages; a classifier-only fleet has nothing to split
        if not args.lm:
            raise SystemExit(
                "serve-fleet: -prefill-workers/-decode-workers need -lm")
        if args.prefill_workers < 1 or args.decode_workers < 1:
            raise SystemExit(
                "serve-fleet: a disaggregated fleet needs BOTH "
                "-prefill-workers >= 1 and -decode-workers >= 1 "
                f"(got {args.prefill_workers}/{args.decode_workers})")
    max_queue = args.max_queue if args.max_queue > 0 else None
    deadline_s = args.deadline_ms / 1e3 if args.deadline_ms > 0 else None
    breaker_n = (args.breaker_threshold if args.breaker_threshold > 0
                 else None)
    quantize = args.quantize if args.quantize != "none" else None

    if args.processes:
        return _serve_fleet_processes(args, max_queue=max_queue,
                                      breaker_n=breaker_n,
                                      quantize=quantize,
                                      role_split=role_split)

    from deeplearning4j_tpu.nn.conf import DenseLayerConf
    from deeplearning4j_tpu.serving import BucketLadder, spawn_local_replica

    net = _build_net(args.model) if args.model else None
    lm_pair = _load_saved_lm(pathlib.Path(args.lm)) if args.lm else None
    buckets = tuple(int(b) for b in args.buckets.split(","))
    warmup_example = None
    if net is not None:
        first = net.conf.layers[0]
        # same flat-input rule as cmd_serve: a [b, n_in] warmup batch
        # only makes sense for dense stacks
        flat = isinstance(first, DenseLayerConf) and first.n_in
        warmup_example = (np.zeros((int(first.n_in),), np.float32)
                          if args.warmup and flat else None)
        if args.warmup and not flat:
            print("serve-fleet: -warmup skipped (non-flat input layer "
                  f"{type(first).__name__}); the first request per "
                  "bucket compiles instead")

    def spawn(name: str, role: str):
        ladder = BucketLadder(buckets)
        return spawn_local_replica(
            name, net, host=args.host, ladder=ladder,
            max_batch=min(args.max_batch, ladder.max_batch),
            max_wait_ms=args.max_wait_ms, warmup_example=warmup_example,
            max_queue_depth=max_queue, default_deadline_s=deadline_s,
            breaker_threshold=breaker_n, quantize=quantize,
            lm=lm_pair, lm_slots=args.lm_slots,
            lm_page_size=args.page_size,
            lm_prefill_chunk=args.prefill_chunk,
            lm_ship=bool(args.lm_ship), role=role)

    def factory(name: str, role: str = None):
        # autoscale/rolling-swap spawns: role-aware autoscaling names
        # the role pool it is growing (ISSUE-15 satellite — a prefill
        # backlog grows the prefill pool); unnamed spawns buy decode
        # capacity in a role-split fleet, "both" otherwise
        if role is None:
            role = "decode" if role_split else "both"
        return spawn(name, role)

    router = FleetRouter(
        factory, replicas=0 if role_split else args.replicas,
        min_replicas=min(args.min_replicas, args.replicas),
        max_replicas=max(args.max_replicas, args.replicas),
        health_interval_s=args.health_interval_s,
        disagg_min_prompt=args.disagg_min_prompt)
    if role_split:
        for i in range(args.prefill_workers):
            router.attach(spawn(f"prefill-{i}", "prefill"))
        for i in range(args.decode_workers):
            router.attach(spawn(f"decode-{i}", "decode"))
    router.autoscale = bool(args.autoscale)
    front = FleetServer(router, host=args.host, port=args.port).start()
    router.start_health_loop()
    names = ", ".join(f"{r.name}[{r.role}]" if r.role != "both"
                      else r.name for r in router.replicas())
    n_total = len(router.replicas())
    print(f"serve-fleet: {n_total} warm replicas in rotation "
          f"({names}); health every {args.health_interval_s}s; "
          f"autoscale {'on' if args.autoscale else 'off'} "
          f"[{router.min_replicas}, {router.max_replicas}]"
          + (f"; disagg: prompts >= {args.disagg_min_prompt} tokens "
             f"split prefill->decode" if role_split else ""))
    print(f"Serving fleet on {front.url} — POST /model/predict, "
          f"/lm/generate; GET /fleet/stats, /serving/stats, /metrics, "
          f"/trace/recent, /healthz, /readyz")

    # SIGTERM -> fleet-wide graceful drain: the front stops admission
    # (503 + /readyz not-ready), every replica drains its in-flight
    # work, and the final /fleet/stats — per-replica breakdown plus the
    # aggregated ledger — is snapshotted to disk.
    term = threading.Event()
    installed = prev = None
    if threading.current_thread() is threading.main_thread():
        prev = signal.signal(signal.SIGTERM, lambda *_: term.set())
        installed = True
    try:
        if args.serve_seconds > 0:
            term.wait(args.serve_seconds)
        else:
            while not term.wait(3600):
                pass
    except KeyboardInterrupt:
        pass
    finally:
        if term.is_set():
            print(f"serve-fleet: SIGTERM — draining fleet (grace "
                  f"{args.drain_grace_s}s)")
            drained = front.drain(args.drain_grace_s)
            stats_path = pathlib.Path(args.drain_stats)
            try:
                stats_path.write_text(json.dumps(
                    router.fleet_stats(), indent=2))
                where = str(stats_path)
            except OSError as e:
                # a lost snapshot must not leave the fleet unstopped or
                # the signal handler unrestored
                where = f"LOST ({e})"
            print(f"serve-fleet: drain "
                  f"{'complete' if drained else 'grace expired'}; stats "
                  f"snapshot -> {where}")
        front.stop()
        if installed:
            signal.signal(signal.SIGTERM, prev)
    return 0


def _serve_fleet_processes(args, *, max_queue, breaker_n, quantize,
                           role_split: bool = False) -> int:
    """`serve-fleet -processes`: each replica is a real spawned
    `dl4j serve` worker process on `worker-base-port + i`, supervised
    end-to-end by a `FleetSupervisor` — crash detection (exit status +
    /readyz), exponential-backoff restart with warm-then-attach
    re-admission, crash-loop quarantine — behind the same `FleetServer`
    front.  The parent stays model-free: the model string (dir / conf /
    zoo:) passes straight through to the worker command lines, so this
    process never pays the jax model build."""
    import signal
    import threading

    from deeplearning4j_tpu.runtime.launcher import FleetProcessLauncher
    from deeplearning4j_tpu.serving import FleetRouter, FleetServer
    from deeplearning4j_tpu.serving.procfleet import (
        FleetSupervisor,
        RestartPolicy,
    )

    if args.autoscale:
        print("serve-fleet: -autoscale ignored with -processes (worker "
              "count is the launcher's; scale by respawning with more "
              "replicas)")
    if role_split:
        # worker i in [0, P) is a prefill worker, the rest decode — the
        # role is ROUTER policy stamped on each incarnation's replica;
        # every worker runs the same `dl4j serve -lm ... -lm-ship` line
        n_workers = args.prefill_workers + args.decode_workers
        roles = (["prefill"] * args.prefill_workers
                 + ["decode"] * args.decode_workers)
    else:
        n_workers, roles = args.replicas, None
    launcher = FleetProcessLauncher(
        args.model or None, n_replicas=n_workers, host=args.host,
        base_port=args.worker_base_port, buckets=args.buckets,
        max_batch=args.max_batch, max_wait_ms=args.max_wait_ms,
        warmup=args.warmup, max_queue=max_queue,
        deadline_ms=(args.deadline_ms if args.deadline_ms > 0 else None),
        breaker_threshold=breaker_n, quantize=quantize,
        log_dir=args.worker_log_dir, lm_dir=args.lm or None,
        lm_slots=(args.lm_slots if args.lm else None),
        lm_page_size=(args.page_size if args.lm else None),
        prefill_chunk=(args.prefill_chunk if args.lm else None),
        lm_ship=bool(args.lm and (role_split or args.lm_ship)),
        roles=roles)
    router = FleetRouter(health_interval_s=args.health_interval_s,
                         disagg_min_prompt=args.disagg_min_prompt)
    supervisor = FleetSupervisor(
        router,
        policy=RestartPolicy(
            backoff_initial_s=args.restart_backoff_s,
            crash_loop_threshold=args.crash_loop_threshold,
            crash_loop_window_s=args.crash_loop_window_s),
        poll_interval_s=args.health_interval_s,
        ready_timeout_s=args.ready_timeout_s)
    supervisor.manage_launcher(launcher)
    supervisor.start()
    print(f"serve-fleet: spawned {n_workers} worker process(es) on "
          f"ports {launcher.port(0)}..{launcher.port(n_workers - 1)} "
          + (f"({args.prefill_workers} prefill + {args.decode_workers} "
             f"decode) " if role_split else "")
          + f"(logs under {launcher.log_dir}); waiting for /readyz "
          f"(timeout {args.ready_timeout_s}s)")
    try:
        ready = supervisor.wait_all_ready(args.ready_timeout_s)
        states = {n: w["state"]
                  for n, w in supervisor.stats()["workers"].items()}
        if not ready:
            raise SystemExit(
                f"serve-fleet: workers never went ready: {states}; see "
                f"logs under {launcher.log_dir}")
        if "ready" not in states.values():
            # wait_all_ready also returns when every worker SETTLED
            # without serving (all quarantined: port collisions, a bad
            # model dir) — an empty fleet front would answer only 503s
            raise SystemExit(
                f"serve-fleet: no worker became ready ({states}); see "
                f"logs under {launcher.log_dir}")
        # the front auto-registers the supervisor's fleet_process_*
        # counters on its /metrics (router.supervisor installed above)
        front = FleetServer(router, host=args.host,
                            port=args.port).start()
    except BaseException:  # noqa: BLE001 — cleanup-and-reraise: a failed boot must not LEAK spawned workers
        supervisor.stop(grace_s=args.drain_grace_s)
        router.stop()
        raise
    router.start_health_loop()
    print(f"serve-fleet: {n_workers} supervised worker processes in "
          f"rotation; restart backoff {args.restart_backoff_s}s, "
          f"crash-loop quarantine at {args.crash_loop_threshold} deaths "
          f"in {args.crash_loop_window_s}s; supervision every "
          f"{args.health_interval_s}s")
    print(f"Serving fleet on {front.url} — POST /model/predict; "
          f"GET /fleet/stats, /serving/stats, /metrics, /trace/recent, "
          f"/healthz, /readyz")

    term = threading.Event()
    installed = prev = None
    if threading.current_thread() is threading.main_thread():
        prev = signal.signal(signal.SIGTERM, lambda *_: term.set())
        installed = True
    try:
        if args.serve_seconds > 0:
            term.wait(args.serve_seconds)
        else:
            while not term.wait(3600):
                pass
    except KeyboardInterrupt:
        pass
    finally:
        if term.is_set():
            print(f"serve-fleet: SIGTERM — draining fleet (grace "
                  f"{args.drain_grace_s}s)")
            front.begin_drain()
            stats_path = pathlib.Path(args.drain_stats)
            try:
                stats_path.write_text(json.dumps(
                    router.fleet_stats(), indent=2))
                where = str(stats_path)
            except OSError as e:
                where = f"LOST ({e})"
            print(f"serve-fleet: stats snapshot -> {where}")
        # clean SIGTERM per worker (each drains itself — cli serve's
        # handler), escalation + reap on the grace expiring; the
        # supervisor classifies every one of these deaths `clean`
        supervisor.stop(grace_s=args.drain_grace_s)
        front.stop()
        if installed:
            signal.signal(signal.SIGTERM, prev)
    return 0


def cmd_lm(args) -> int:
    """Train the flagship TransformerLM on a raw text file (byte-level
    vocab, causal LM) and/or generate from a saved one — the CLI surface
    for the long-context/flagship model family (no reference analog; the
    2015 CLI stops at MultiLayerNetwork training, Train.java:64)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.parallel import transformer as tfm
    from deeplearning4j_tpu.parallel.generation import generate
    from deeplearning4j_tpu.runtime.checkpoint import (
        npz_to_tree,
        tree_to_npz,
    )

    _announce_device("lm")
    out = pathlib.Path(args.output or "dl4j-lm")
    cfg_path, params_path = out / "lm_config.json", out / "lm_params.npz"

    def save(cfg, params):
        out.mkdir(parents=True, exist_ok=True)
        cfg_path.write_text(json.dumps(cfg.__dict__))
        tree_to_npz(params_path, params)  # atomic write

    def load():
        return _load_saved_lm(out)

    if args.input:
        text = pathlib.Path(args.input).read_bytes()
        ids = np.frombuffer(text, np.uint8).astype(np.int32)
        S, B = args.seq, args.batch
        if len(ids) < S + 2:
            raise SystemExit(f"input too short for -seq {S}")
        import dataclasses

        from deeplearning4j_tpu.parallel.hybrid import (
            _master_f32,
            make_accum_train_step,
        )

        # Mixed precision, not pure bf16: params/updates stay float32
        # (a bf16 `w - lr*g` swallows updates below ~0.4% of the weight
        # and training silently stalls); the forward casts to bf16 on
        # TPU so the MXU runs at its native rate.
        on_tpu = jax.default_backend() == "tpu"
        if args.preset:
            # Byte-level flagship presets (small 768/12/12, medium
            # 1024/16/24, large 1280/20/36): tied embeddings, per-block
            # remat; -seq defaults are honored (S1024 recommended).
            make = {"gpt2-small": tfm.gpt2_small,
                    "gpt2-medium": tfm.gpt2_medium,
                    "gpt2-large": tfm.gpt2_large}[args.preset]
            cfg = dataclasses.replace(
                make(max_len=S, dtype="float32"), vocab_size=256)
        else:
            cfg = tfm.TransformerConfig(
                vocab_size=256, d_model=args.d_model, n_heads=args.heads,
                n_layers=args.layers, d_ff=4 * args.d_model, max_len=S)
        if args.experts:
            if args.runtime == "pipeline":
                # Documented boundary (PARITY): MoE rides the dp/sp/tp/ep
                # mesh; pipeline stages are dense-MLP only.
                raise SystemExit(
                    "-experts is not supported under -runtime pipeline; "
                    "use -runtime hybrid (expert parallelism rides the "
                    "model axis) or local/spmd")
            cfg = dataclasses.replace(cfg, n_experts=args.experts,
                                      moe_top_k=args.moe_top_k)
        if args.runtime in ("hybrid", "pipeline"):
            # Mesh runtimes own init (seed 0) and the whole train loop;
            # control falls through to the shared eval/generate tail
            # with the gathered host params.
            params = _lm_mesh_train(args, cfg, ids, B, S)
            save(cfg, params)
            print(f"LM saved to {out}")
            return _lm_tail(args, cfg, params)

        params = _master_f32(tfm.init_params(cfg, jax.random.PRNGKey(0)))
        compute_cfg = (dataclasses.replace(cfg, dtype="bfloat16")
                       if on_tpu else cfg)

        spmd_mesh = None
        if args.runtime == "spmd":
            # Data parallelism by GSPMD: the batch arrives sharded over
            # the mesh's data axis, params stay replicated, and XLA
            # inserts the gradient allreduce.  The step gets the mesh
            # (all devices on `data`) so the attention kernel runs under
            # shard_map — GSPMD cannot partition a Mosaic kernel.
            from deeplearning4j_tpu.parallel import make_mesh
            from deeplearning4j_tpu.parallel.mesh import (
                round_batch_to_mesh,
                shard_batch,
            )

            n = len(jax.devices())
            spmd_mesh = make_mesh((n, 1, 1), ("data", "seq", "model"))
            if n == 1:
                print("spmd: only 1 device visible — equivalent to local")
            rounded = round_batch_to_mesh(B, spmd_mesh)
            if rounded != B:
                print(f"spmd: -batch {B} rounded up to {rounded} "
                      f"({n}-device shards; `dl4j train` pads likewise)")
                B = rounded

        if args.accum > 1 and B % args.accum:
            raise SystemExit(f"-batch {B} (after any spmd rounding) must "
                             f"be divisible by -accum {args.accum}")
        step, init_opt = make_accum_train_step(
            compute_cfg, lr=args.lr, accum=args.accum,
            updater=args.updater, mesh=spmd_mesh)
        opt_state = init_opt(params)
        rng = np.random.default_rng(0)
        steps = max(1, args.epochs * (len(ids) // max(B * S, 1)))
        t0, loss = time.time(), None
        for k in range(steps):
            starts = rng.integers(0, len(ids) - S - 1, B)
            tokens = np.stack([ids[s:s + S] for s in starts])
            targets = np.stack([ids[s + 1:s + S + 1] for s in starts])
            if spmd_mesh is not None:
                # one sharded host transfer, not asarray + reshard
                tokens, targets = shard_batch(spmd_mesh, (tokens, targets))
            else:
                tokens, targets = jnp.asarray(tokens), jnp.asarray(targets)
            params, opt_state, loss = step(params, opt_state, tokens,
                                           targets)
            if spmd_mesh is not None and k == 0:
                print(_placement_line("spmd", batch=tokens, params=params,
                                      loss=loss))
            if args.verbose and (k + 1) % 20 == 0:
                print(f"step {k + 1}/{steps} loss {float(loss):.4f}")
        tok_rate = steps * B * S / max(time.time() - t0, 1e-9)
        print(f"Trained {steps} steps (final loss {float(loss):.4f}, "
              f"{tok_rate:.0f} tokens/sec)")
        save(cfg, params)
        print(f"LM saved to {out}")
    else:
        if not cfg_path.exists():
            raise SystemExit(f"no -input and no saved LM at {out}")
        cfg, params = load()

    return _lm_tail(args, cfg, params)


def _lm_tail(args, cfg, params) -> int:
    """Shared -eval / -generate tail for every lm runtime."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.parallel import transformer as tfm
    from deeplearning4j_tpu.parallel.generation import generate

    if args.eval is not None:
        # Held-out byte-level perplexity: mean NLL over non-overlapping
        # cfg.max_len windows, exp() at the end.  Scoring uses
        # apply(train=False) — true inference routing (dense-masked MoE,
        # no aux loss) — NOT the trainer's lm_loss, whose capacity-based
        # routing and auxiliary term belong to training.
        ev_ids = np.frombuffer(pathlib.Path(args.eval).read_bytes(),
                               np.uint8).astype(np.int32)
        S_ev = cfg.max_len
        if len(ev_ids) < S_ev + 1:
            raise SystemExit(f"-eval file too short for seq_len {S_ev}")
        n_win = min((len(ev_ids) - 1) // S_ev, 64)
        tok = np.stack([ev_ids[i * S_ev:(i + 1) * S_ev]
                        for i in range(n_win)])
        tgt = np.stack([ev_ids[i * S_ev + 1:(i + 1) * S_ev + 1]
                        for i in range(n_win)])

        def batch_nll(p, t, g):
            logp = jax.nn.log_softmax(
                tfm.apply(cfg, p, t, train=False), axis=-1)
            return -jnp.mean(
                jnp.take_along_axis(logp, g[..., None], axis=-1)[..., 0])

        nll_fn = jax.jit(batch_nll)
        # Batch windows to bound memory.  Windows all have S_ev tokens, so
        # the global mean is the WINDOW-count-weighted mean of per-batch
        # means — a ragged final batch must not be over-weighted.
        total = 0.0
        for i in range(0, n_win, 8):
            k = len(tok[i:i + 8])
            total += k * float(nll_fn(params, jnp.asarray(tok[i:i + 8]),
                                      jnp.asarray(tgt[i:i + 8])))
        nll = total / n_win
        print(f"eval: {n_win} windows x {S_ev} bytes, "
              f"nll {nll:.4f}, perplexity {float(np.exp(nll)):.2f}")

    if args.generate is not None:
        prompt = np.frombuffer(
            (args.generate or "\n").encode(), np.uint8).astype(np.int32)
        if len(prompt) + args.max_new > cfg.max_len:
            raise SystemExit(
                f"prompt ({len(prompt)} bytes) + -max-new ({args.max_new}) "
                f"exceeds the model's context ({cfg.max_len}, set by -seq "
                f"at training time) — shorten one of them")
        if args.beam > 1:
            from deeplearning4j_tpu.parallel.generation import beam_search

            toks, scores = beam_search(cfg, params, prompt[None, :],
                                       max_new_tokens=args.max_new,
                                       beam_size=args.beam)
            print(f"beam[{args.beam}] log-prob "
                  f"{float(scores[0]):.3f}", file=sys.stderr)
        else:
            toks = generate(cfg, params, prompt[None, :],
                            max_new_tokens=args.max_new,
                            temperature=args.temperature,
                            top_k=args.top_k, top_p=args.top_p,
                            rng=jax.random.PRNGKey(args.gen_seed))
        text = bytes(np.asarray(toks[0], np.uint8)).decode(
            errors="replace")
        print(text)
    return 0


def cmd_test(args) -> int:
    props = load_properties(args.conf)
    ds = _load_dataset(args.input, props)
    net = _build_net(args.model)
    ev = net.evaluate(ds.features, ds.labels)
    print(ev.stats())
    return 0


def cmd_predict(args) -> int:
    props = load_properties(args.conf)
    ds = _load_dataset(args.input, props)
    net = _build_net(args.model)
    preds = net.predict(ds.features)
    out = args.output or "predictions.txt"
    np.savetxt(out, preds, fmt="%d")
    print(f"Wrote {len(preds)} predictions to {out}")
    return 0


# --------------------------------------------------------------------------

def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="dl4j", description="deeplearning4j_tpu command line interface")
    sub = parser.add_subparsers(dest="command", required=True)

    def common(p):
        # Single-dash long flags accepted like the reference's args4j CLI.
        p.add_argument("-input", "--input", required=True,
                       help="input data file (svmlight/csv)")
        p.add_argument("-model", "--model", required=True,
                       help="model conf JSON (train) or saved model dir")
        p.add_argument("-conf", "--conf", default=None,
                       help="key=value properties file")
        p.add_argument("-output", "--output", default=None)
        p.add_argument("-verbose", "--verbose", action="store_true")

    p_train = sub.add_parser("train", help="train a model")
    common(p_train)
    p_train.add_argument("-type", "--type", choices=["multi", "single"],
                         default="multi")
    p_train.add_argument("-runtime", "--runtime",
                         choices=["local", "spmd"], default="local",
                         help="local = single chip; spmd = data-parallel "
                              "over the device mesh")
    p_train.add_argument("-savemode", "--savemode",
                         choices=["binary", "txt"], default="binary")
    p_train.add_argument("-epochs", "--epochs", type=int, default=50)
    p_train.add_argument("-batch", "--batch", type=int, default=32)
    p_train.add_argument("-accum", "--accum", type=int, default=1,
                         help="gradient-accumulation microbatches per "
                              "update (local runtime)")
    p_train.add_argument("-precision", "--precision",
                         choices=["fp32", "bf16", "mixed"], default="fp32",
                         help="precision policy: fp32; bf16 (pure bf16 "
                              "params+compute, half the train-state "
                              "bytes); mixed (fp32 master weights + "
                              "bf16 compute + dynamic loss scaling — "
                              "the production TPU recipe)")
    p_train.add_argument("-chunk", "--chunk", type=int, default=1,
                         help="fused multi-step driver: optimizer steps "
                              "per XLA dispatch (one host sync per "
                              "chunk; tail batches padded+masked so the "
                              "jit cache stays warm; with -resilience, "
                              "health checks read per-step loss vectors "
                              "and faults replay at chunk 1)")
    p_train.add_argument("-sync-every", "--sync-every", type=int,
                         default=1,
                         help="spmd runtime: average replicas every N "
                              "steps instead of every step (local-SGD / "
                              "Hogwild-router analog; 1 = sync SGD)")
    p_train.add_argument("-shard-update", "--shard-update",
                         choices=("on", "off"), default="on",
                         help="spmd runtime: ZeRO-1 weight-update "
                              "sharding — reduce-scatter grads, step "
                              "1/N of the flat parameter plane per "
                              "replica, all-gather (default on; "
                              "bitwise-equal to the replicated update "
                              "and ~1/N the optimizer-state bytes per "
                              "replica; 'off' restores the replicated "
                              "pmean update)")
    p_train.add_argument("-replicas", "--replicas", type=int,
                         default=None,
                         help="spmd runtime: data-parallel over the "
                              "first N visible devices (default: all) — "
                              "the elastic restart knob: resume a "
                              "checkpoint saved on ANY replica count "
                              "onto N (docs/robustness.md 'Elastic "
                              "restart')")
    p_train.add_argument("-resume", "--resume", action="store_true",
                         help="restore the newest GOOD checkpoint from "
                              "-ckpt-dir before training (shard "
                              "checksums verified; a corrupt newest "
                              "step falls back to the previous good "
                              "one); with -resilience this is "
                              "automatic")
    p_train.add_argument("-resilience", "--resilience",
                         action="store_true",
                         help="supervise training: skip poison batches, "
                              "roll back on divergence with LR backoff, "
                              "retry fetches, checkpoint periodically, "
                              "and flush an emergency checkpoint on "
                              "SIGTERM (resume by re-running)")
    p_train.add_argument("-ckpt-dir", "--ckpt-dir", dest="ckpt_dir",
                         default=None,
                         help="resilience checkpoint directory "
                              "(default <output>/ckpts)")
    p_train.add_argument("-ckpt-every", "--ckpt-every", dest="ckpt_every",
                         type=int, default=50,
                         help="steps between periodic checkpoints")
    p_train.add_argument("-ckpt-keep", "--ckpt-keep", dest="ckpt_keep",
                         type=int, default=3,
                         help="keep the newest K checkpoints (the best-"
                              "scoring one is always retained)")
    p_train.add_argument("-skip-budget", "--skip-budget",
                         dest="skip_budget", type=int, default=5,
                         help="max poison (non-finite) batches skipped "
                              "before aborting")
    p_train.add_argument("-divergence-factor", "--divergence-factor",
                         dest="divergence_factor", type=float,
                         default=10.0,
                         help="roll back when loss exceeds this multiple "
                              "of the rolling median")
    p_train.add_argument("-step-timeout", "--step-timeout",
                         dest="step_timeout", type=float, default=None,
                         help="watchdog: fail a training step exceeding "
                              "this many seconds (default: no watchdog)")
    p_train.add_argument("-metrics-port", "--metrics-port",
                         dest="metrics_port", type=int, default=None,
                         help="serve training telemetry (Prometheus "
                              "/metrics: step time, examples/sec, grad "
                              "norm, loss-scale events, supervisor "
                              "interventions) on this port (0 = pick a "
                              "free port; default: off)")
    p_train.add_argument("-metrics-interval", "--metrics-interval",
                         dest="metrics_interval", type=int, default=10,
                         help="steps between telemetry syncs (the "
                              "listener's sync_interval: off-interval "
                              "steps never force a host sync)")
    p_train.set_defaults(fn=cmd_train)

    p_lm = sub.add_parser(
        "lm", help="train/sample the TransformerLM on raw text")
    p_lm.add_argument("-input", "--input", default=None,
                      help="raw text file (omit to generate from a saved LM)")
    p_lm.add_argument("-output", "--output", default=None,
                      help="save/load directory (default dl4j-lm)")
    p_lm.add_argument("-epochs", "--epochs", type=int, default=1)
    p_lm.add_argument("-batch", "--batch", type=int, default=8)
    p_lm.add_argument("-seq", "--seq", type=int, default=128)
    p_lm.add_argument("-preset", "--preset",
                      choices=["gpt2-small", "gpt2-medium", "gpt2-large"],
                      default=None,
                      help="flagship config preset (small 768/12/12, "
                           "medium 1024/16/24, large 1280/20/36; tied "
                           "embeddings, remat) overriding -d-model/"
                           "-layers/-heads")
    p_lm.add_argument("-accum", "--accum", type=int, default=1,
                      help="gradient-accumulation microbatches per step")
    p_lm.add_argument("-experts", "--experts", type=int, default=0,
                      help="MoE experts per block (0 = dense MLP; "
                           "Switch/top-k routing with capacity dispatch "
                           "in training, dense-masked at inference)")
    p_lm.add_argument("-moe-top-k", "--moe-top-k", type=int, default=1,
                      help="experts routed per token (1 = Switch, "
                           "2 = GShard-style)")
    p_lm.add_argument("-d-model", "--d-model", dest="d_model", type=int,
                      default=128)
    p_lm.add_argument("-layers", "--layers", type=int, default=2)
    p_lm.add_argument("-heads", "--heads", type=int, default=4)
    p_lm.add_argument("-lr", "--lr", type=float, default=3e-3)
    p_lm.add_argument("-updater", "--updater", default="adam",
                      choices=["sgd", "adam", "adamw", "lion", "rmsprop",
                               "adagrad", "nesterovs"],
                      help="optimizer for lm training (default adam)")
    p_lm.add_argument("-generate", "--generate", nargs="?", const="",
                      default=None, metavar="PROMPT",
                      help="sample after training/loading (optional prompt)")
    p_lm.add_argument("-max-new", "--max-new", dest="max_new", type=int,
                      default=64)
    p_lm.add_argument("-temperature", "--temperature", type=float,
                      default=0.8)
    p_lm.add_argument("-top-k", "--top-k", dest="top_k", type=int,
                      default=0, help="truncate sampling to k best tokens")
    p_lm.add_argument("-top-p", "--top-p", dest="top_p", type=float,
                      default=1.0, help="nucleus sampling mass")
    p_lm.add_argument("-beam", "--beam", type=int, default=1,
                      help="beam-search width for -generate (1 = off)")
    p_lm.add_argument("-eval", "--eval", default=None,
                      help="report byte-level perplexity on this held-out "
                           "text file")
    p_lm.add_argument("-gen-seed", "--gen-seed", dest="gen_seed", type=int,
                      default=0)
    p_lm.add_argument("-runtime", "--runtime",
                      choices=["local", "spmd", "hybrid", "pipeline"],
                      default="local",
                      help="spmd = data-parallel over all devices "
                           "(GSPMD); hybrid = dp/sp/tp mesh (GSPMD + "
                           "ring attention); pipeline = dp/pp GPipe "
                           "stages")
    p_lm.add_argument("-verbose", "--verbose", action="store_true")
    p_lm.set_defaults(fn=cmd_lm)

    p_serve = sub.add_parser(
        "serve", help="serve a saved model/LM over HTTP with dynamic "
                      "micro-batching")
    p_serve.add_argument("-model", "--model", default=None,
                         help="saved model dir, conf JSON, or zoo:<name> "
                              "for POST /model/predict")
    p_serve.add_argument("-lm", "--lm", default=None,
                         help="saved LM dir (from `dl4j lm`) for "
                              "POST /lm/generate")
    p_serve.add_argument("-host", "--host", default="127.0.0.1")
    p_serve.add_argument("-port", "--port", type=int, default=8080,
                         help="0 picks a free port")
    p_serve.add_argument("-max-batch", "--max-batch", dest="max_batch",
                         type=int, default=32,
                         help="most rows one coalesced dispatch carries")
    p_serve.add_argument("-max-wait-ms", "--max-wait-ms",
                         dest="max_wait_ms", type=float, default=2.0,
                         help="how long the micro-batcher holds a request "
                              "open for co-travellers")
    p_serve.add_argument("-buckets", "--buckets", default="1,8,32",
                         help="comma-separated batch bucket ladder; every "
                              "dispatch pads up to the next bucket so the "
                              "compiled-program set stays bounded")
    p_serve.add_argument("-warmup", "--warmup", action="store_true",
                         help="pre-compile every bucket shape before "
                              "accepting traffic")
    p_serve.add_argument("-quantize", "--quantize",
                         choices=["none", "int8"], default="none",
                         help="serve int8 per-channel weight-quantized "
                              "dense/conv layers (~4x smaller resident "
                              "params, dequantize-in-kernel matmuls; "
                              "top-1 parity pinned by the bench "
                              "precision row)")
    p_serve.add_argument("-max-queue", "--max-queue", dest="max_queue",
                         type=int, default=256,
                         help="bounded admission: queued requests past "
                              "this depth are refused with HTTP 503 + "
                              "Retry-After (0 = unbounded)")
    p_serve.add_argument("-deadline-ms", "--deadline-ms",
                         dest="deadline_ms", type=float, default=0,
                         help="default per-request deadline; expired "
                              "requests are shed before dispatch as 504 "
                              "(0 = none; per-request deadline_ms / "
                              "X-Deadline-Ms override)")
    p_serve.add_argument("-breaker-threshold", "--breaker-threshold",
                         dest="breaker_threshold", type=int, default=5,
                         help="circuit breaker: consecutive whole-"
                              "dispatch failures before fast-failing "
                              "admission (0 = disabled)")
    p_serve.add_argument("-drain-grace-s", "--drain-grace-s",
                         dest="drain_grace_s", type=float, default=5.0,
                         help="SIGTERM grace window: seconds to let "
                              "queued + in-flight work finish before "
                              "stopping")
    p_serve.add_argument("-drain-stats", "--drain-stats",
                         dest="drain_stats", default="serving_stats.json",
                         help="path for the /serving/stats snapshot "
                              "written on SIGTERM drain")
    p_serve.add_argument("-lm-slots", "--lm-slots", dest="lm_slots",
                         type=int, default=4,
                         help="continuous-decode lanes for /lm/generate")
    p_serve.add_argument("-lm-pages", "--lm-pages", dest="lm_pages",
                         type=int, default=0,
                         help="KV pages in the paged pool (0 = full "
                              "worst-case capacity, slots * "
                              "ceil(max_len/page_size)); smaller pools "
                              "trade admission waits for memory")
    p_serve.add_argument("-page-size", "--page-size", dest="page_size",
                         type=int, default=16,
                         help="tokens per KV page (prefix sharing is "
                              "page-granular)")
    p_serve.add_argument("-lm-speculate", "--lm-speculate",
                         dest="lm_speculate",
                         choices=["off", "ngram", "model"],
                         default="off",
                         help="speculative multi-token decode for "
                              "greedy LM lanes (paged KV only): a "
                              "cheap drafter proposes draft-len "
                              "tokens per round, the target verifies "
                              "the chunk in ONE wide dispatch with "
                              "in-jit accept/rollback; 'ngram' = free "
                              "host-side prompt-lookup, 'model' = "
                              "self-drafting small-model plane "
                              "(docs/performance.md)")
    p_serve.add_argument("-draft-len", "--draft-len", dest="draft_len",
                         type=int, default=4,
                         help="max draft tokens proposed per lane per "
                              "round under -lm-speculate (default 4)")
    p_serve.add_argument("-prefill-chunk", "--prefill-chunk",
                         dest="prefill_chunk", type=int, default=8,
                         help="max prompt tokens fed per dispatch "
                              "during prefill (1 = token-at-a-time)")
    p_serve.add_argument("-lm-ship", "--lm-ship", dest="lm_ship",
                         action="store_true",
                         help="speak the KV page-shipping wire plane "
                              "(POST /lm/prefill export + "
                              "/lm/admit_pages import) so this worker "
                              "can serve a disaggregated prefill/"
                              "decode fleet (paged KV only; "
                              "docs/architecture.md)")
    p_serve.add_argument("-lm-preempt", "--lm-preempt",
                         dest="lm_preempt", action="store_true",
                         help="priority preemption for the LM pool: a "
                              "higher-priority request that would wait "
                              "on a dry KV pool preempts the lowest-"
                              "priority lane, swapping its state to a "
                              "host store; the lane resumes byte-"
                              "identically on re-admission (paged KV "
                              "only; docs/robustness.md \"The "
                              "degradation ladder\")")
    p_serve.add_argument("-lm-swap-mb", "--lm-swap-mb",
                         dest="lm_swap_mb", type=float, default=64.0,
                         help="host swap store byte cap in MiB for "
                              "preempted lanes (LRU past it; an "
                              "evicted lane recomputes from its "
                              "prompt, still byte-identical)")
    p_serve.add_argument("-lm-brownout", "--lm-brownout",
                         dest="lm_brownout", action="store_true",
                         help="brownout degradation ladder: under pool "
                              "pressure degrade speculation, prefill "
                              "width, then best_effort lanes before "
                              "shedding anything (paged KV only)")
    p_serve.add_argument("-lm-hibernate-idle-s", "--lm-hibernate-idle-s",
                         dest="lm_hibernate_idle_s", type=float,
                         default=None,
                         help="hibernate a sticky session's KV pages to "
                              "the tiered state store after this many "
                              "idle seconds; the next request on the "
                              "same prefix resumes byte-identically "
                              "(paged KV only; docs/robustness.md "
                              "\"The state hierarchy\")")
    p_serve.add_argument("-lm-disk-dir", "--lm-disk-dir",
                         dest="lm_disk_dir", default=None,
                         help="disk tier directory for the tiered state "
                              "store: host-tier overflow spills to "
                              "checksummed blob files here, and a "
                              "restarted server over the same dir "
                              "resumes hibernated sessions (needs "
                              "-lm-hibernate-idle-s or -lm-preempt)")
    p_serve.add_argument("-lm-disk-mb", "--lm-disk-mb",
                         dest="lm_disk_mb", type=float, default=1024.0,
                         help="disk tier byte cap in MiB (LRU past it; "
                              "an evicted session recomputes from its "
                              "prompt, still byte-identical)")
    p_serve.add_argument("-lm-swap-quantize", "--lm-swap-quantize",
                         dest="lm_swap_quantize",
                         choices=("on", "off"), default="on",
                         help="per-page int8 quantization for "
                              "swapped-out and hibernated KV frames "
                              "(~4x smaller in transit and at rest); "
                              "'off' keeps exact bytes")
    p_serve.add_argument("-tenants", "--tenants", default=None,
                         help="multi-tenant traffic shaping (JSON): an "
                              "object mapping tenant name -> spec, e.g. "
                              '\'{"interactive": {"weight": 4, '
                              '"rate": 2000, "slo_ms": 250}}\' — each '
                              "spec takes weight (WFQ share), "
                              "rate (tokens/s quota; 0 = unmetered), "
                              "burst, slo_ms and slo_budget; a "
                              "'default' tenant always exists, so "
                              "clients that never send a tenant keep "
                              "the exact single-tenant behavior")
    p_serve.add_argument("-serve-seconds", "--serve-seconds",
                         dest="serve_seconds", type=float, default=0,
                         help="stop after this many seconds (0 = run "
                              "until interrupted)")
    p_serve.set_defaults(fn=cmd_serve)

    p_fleet = sub.add_parser(
        "serve-fleet", help="serve a saved model through N replicated "
        "engines behind a failover router with health ejection and "
        "fleet-wide SIGTERM drain")
    p_fleet.add_argument("-model", "--model", default=None,
                         help="saved model dir, conf JSON, or zoo:<name>")
    p_fleet.add_argument("-lm", "--lm", default=None,
                         help="saved LM dir (from `dl4j lm`) served by "
                              "every replica's continuous pool for "
                              "POST /lm/generate (paged KV, page "
                              "shipping enabled)")
    p_fleet.add_argument("-replicas", "--replicas", type=int, default=2,
                         help="replicas spawned into rotation (default "
                              "2); ignored when -prefill-workers/"
                              "-decode-workers define a role-split "
                              "fleet")
    p_fleet.add_argument("-prefill-workers", "--prefill-workers",
                         dest="prefill_workers", type=int, default=0,
                         help="disaggregated serving: replicas "
                              "dedicated to chewing long prompts and "
                              "shipping the finished KV pages to "
                              "decode workers (needs -lm and "
                              "-decode-workers; docs/architecture.md "
                              "'Disaggregated serving')")
    p_fleet.add_argument("-decode-workers", "--decode-workers",
                         dest="decode_workers", type=int, default=0,
                         help="disaggregated serving: replicas running "
                              "the latency-bound token loop (they also "
                              "take short-prompt traffic directly)")
    p_fleet.add_argument("-disagg-min-prompt", "--disagg-min-prompt",
                         dest="disagg_min_prompt", type=int, default=32,
                         help="prompts at least this long split "
                              "prefill->decode when prefill workers "
                              "exist; shorter ones decode directly")
    p_fleet.add_argument("-lm-slots", "--lm-slots", dest="lm_slots",
                         type=int, default=4,
                         help="per-replica continuous-decode lanes for "
                              "/lm/generate")
    p_fleet.add_argument("-page-size", "--page-size", dest="page_size",
                         type=int, default=16,
                         help="per-replica KV page size (must match "
                              "across the fleet: shipped pages are "
                              "geometry-checked)")
    p_fleet.add_argument("-prefill-chunk", "--prefill-chunk",
                         dest="prefill_chunk", type=int, default=8,
                         help="per-replica max prompt tokens fed per "
                              "prefill dispatch")
    p_fleet.add_argument("-lm-ship", "--lm-ship", dest="lm_ship",
                         action="store_true",
                         help="enable page shipping on undifferentiated "
                              "(both-role) LM replicas too, so sticky-"
                              "session spill-over ships pages instead "
                              "of recomputing (role-split fleets ship "
                              "implicitly)")
    p_fleet.add_argument("-host", "--host", default="127.0.0.1")
    p_fleet.add_argument("-port", "--port", type=int, default=8080,
                         help="fleet front port (0 = ephemeral); each "
                              "replica gets its own ephemeral port")
    p_fleet.add_argument("-max-batch", "--max-batch", dest="max_batch",
                         type=int, default=32,
                         help="per-replica max coalesced batch")
    p_fleet.add_argument("-max-wait-ms", "--max-wait-ms",
                         dest="max_wait_ms", type=float, default=2.0,
                         help="per-replica idle coalescing window")
    p_fleet.add_argument("-buckets", "--buckets", default="1,8,32",
                         help="per-replica batch bucket ladder")
    p_fleet.add_argument("-warmup", "--warmup", action="store_true",
                         help="pre-compile every bucket shape per "
                              "replica before it enters rotation")
    p_fleet.add_argument("-quantize", "--quantize",
                         choices=["none", "int8"], default="none",
                         help="per-replica int8 weight quantization")
    p_fleet.add_argument("-max-queue", "--max-queue", dest="max_queue",
                         type=int, default=256,
                         help="per-replica admission bound, matching "
                              "the serve default: queued requests past "
                              "this depth are refused with HTTP 503 + "
                              "Retry-After (0 = unbounded)")
    p_fleet.add_argument("-deadline-ms", "--deadline-ms",
                         dest="deadline_ms", type=float, default=0,
                         help="per-replica default request deadline "
                              "(0 = none)")
    p_fleet.add_argument("-breaker-threshold", "--breaker-threshold",
                         dest="breaker_threshold", type=int, default=5,
                         help="per-replica engine circuit-breaker "
                              "threshold (0 = off)")
    p_fleet.add_argument("-health-interval-s", "--health-interval-s",
                         dest="health_interval_s", type=float, default=1.0,
                         help="router /readyz poll interval")
    p_fleet.add_argument("-processes", "--processes",
                         action="store_true",
                         help="process-per-replica: spawn real `dl4j "
                              "serve` worker processes (one per "
                              "replica, worker-base-port + i) and "
                              "supervise them end-to-end — crash "
                              "detection, backoff restart, crash-loop "
                              "quarantine (docs/robustness.md "
                              "\"Process supervision\")")
    p_fleet.add_argument("-worker-base-port", "--worker-base-port",
                         dest="worker_base_port", type=int, default=8081,
                         help="with -processes: worker i serves on "
                              "base_port + i")
    p_fleet.add_argument("-worker-log-dir", "--worker-log-dir",
                         dest="worker_log_dir", default="fleet_logs",
                         help="with -processes: per-worker rotating "
                              "stdout/stderr capture directory")
    p_fleet.add_argument("-restart-backoff-s", "--restart-backoff-s",
                         dest="restart_backoff_s", type=float,
                         default=0.5,
                         help="with -processes: initial restart "
                              "backoff (doubles per consecutive "
                              "crash, jittered, capped)")
    p_fleet.add_argument("-crash-loop-threshold",
                         "--crash-loop-threshold",
                         dest="crash_loop_threshold", type=int, default=3,
                         help="with -processes: deaths inside the "
                              "crash-loop window that quarantine a "
                              "worker (surfaced in /fleet/stats)")
    p_fleet.add_argument("-crash-loop-window-s", "--crash-loop-window-s",
                         dest="crash_loop_window_s", type=float,
                         default=60.0,
                         help="with -processes: the crash-loop "
                              "quarantine window")
    p_fleet.add_argument("-ready-timeout-s", "--ready-timeout-s",
                         dest="ready_timeout_s", type=float, default=120.0,
                         help="with -processes: how long a spawned "
                              "worker may take to go /readyz-green "
                              "before it is killed and counted a crash "
                              "(report carries its log tail)")
    p_fleet.add_argument("-autoscale", "--autoscale",
                         action="store_true",
                         help="queue-depth-driven scale up/down through "
                              "graceful drain")
    p_fleet.add_argument("-min-replicas", "--min-replicas",
                         dest="min_replicas", type=int, default=1)
    p_fleet.add_argument("-max-replicas", "--max-replicas",
                         dest="max_replicas", type=int, default=8)
    p_fleet.add_argument("-drain-grace-s", "--drain-grace-s",
                         dest="drain_grace_s", type=float, default=5.0,
                         help="fleet-wide SIGTERM drain grace window")
    p_fleet.add_argument("-drain-stats", "--drain-stats",
                         dest="drain_stats", default="fleet_stats.json",
                         help="where the final /fleet/stats snapshot is "
                              "written on SIGTERM drain")
    p_fleet.add_argument("-serve-seconds", "--serve-seconds",
                         dest="serve_seconds", type=float, default=0,
                         help="stop after this many seconds (0 = run "
                              "until interrupted)")
    p_fleet.set_defaults(fn=cmd_serve_fleet)

    p_test = sub.add_parser("test", help="evaluate a saved model")
    common(p_test)
    p_test.set_defaults(fn=cmd_test)

    p_pred = sub.add_parser("predict", help="write argmax predictions")
    common(p_pred)
    p_pred.set_defaults(fn=cmd_predict)
    return parser


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    from deeplearning4j_tpu.runtime.device import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
