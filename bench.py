"""Benchmark harness covering the five BASELINE.md configs.

Prints ONE JSON line to stdout — the metric of record (LeNet-5 MNIST
training throughput, BASELINE.md config #1):
    {"metric", "value", "unit", "vs_baseline"}
All five configs' results are written to `BENCH_full.json` at the repo root
and echoed (one JSON line each) to stderr.

The suite runs in THIS process on the device the process got; the first
stderr line names it (platform, device_kind, count).  A row that raises
becomes an error row and the exit code is non-zero.

The reference publishes no numbers (BASELINE.md), so `vs_baseline` compares
against the first canonical run of THIS harness (pinned per-metric in
`.bench_baseline.json`).

Procedure per BASELINE.md: warm up (compile excluded), time the steps,
report median-window examples/sec/chip.
"""

import json
import os
import pathlib
import sys
import time

import numpy as np

REPO = pathlib.Path(__file__).resolve().parent
BATCH = int(os.environ.get("BENCH_BATCH", 256))
WARMUP = int(os.environ.get("BENCH_WARMUP", 5))
STEPS = int(os.environ.get("BENCH_STEPS", 100))
ONLY = [s for s in os.environ.get("BENCH_ONLY", "").split(",") if s]
# Fused multi-step driver: optimizer steps per XLA dispatch for the
# train-throughput rows (1 host sync per chunk).  BENCH_CHUNK_UNROLL
# defaults to the chunk size: full unroll lets XLA fuse across steps —
# the fast (but not bit-stable across chunkings) mode; deterministic
# training uses unroll=1 (see docs/performance.md).
CHUNK = max(1, int(os.environ.get("BENCH_CHUNK", 8)))
CHUNK_UNROLL = int(os.environ.get("BENCH_CHUNK_UNROLL", CHUNK))
RECORD_METRIC = "LeNet-MNIST train examples/sec/chip"


# ---------------------------------------------------------------------------
# timing helper
# ---------------------------------------------------------------------------

def _staged(*arrays):
    """Stage batch data on the device ONCE before timing.  The throughput
    rows measure the train step, not host->device transfer (BASELINE.md
    procedure); re-uploading identical batches every step would skew the
    number."""
    import jax

    out = jax.device_put(arrays)
    jax.block_until_ready(out)
    return out


def _time_steps(step_fn, warmup: int, steps: int) -> float:
    """Median seconds/step over windows of up to 10 steps; step_fn must
    return a device array (blocked on per window, so steps pipeline)."""
    import jax

    last = None
    for _ in range(max(1, warmup)):
        last = step_fn()
    jax.block_until_ready(last)
    chunk = min(10, max(1, steps))
    times = []
    for _ in range(max(1, steps // chunk)):
        t0 = time.perf_counter()
        for _ in range(chunk):
            last = step_fn()
        jax.block_until_ready(last)
        times.append((time.perf_counter() - t0) / chunk)
    return float(np.median(times))


def _time_fused_steps(net, x, y, steps: int) -> tuple:
    """Median seconds/step for the fused K-steps-per-dispatch path
    (net.fit_chunk_async over a stacked chunk of the staged batch) and
    the host-sync count of the timed region — one block per chunk, which
    IS the path's sync cadence (per-step loss vectors come back as one
    device array per dispatch)."""
    import jax

    xs = jax.device_put(
        np.broadcast_to(np.asarray(x), (CHUNK,) + np.shape(x)).copy())
    ys = jax.device_put(
        np.broadcast_to(np.asarray(y), (CHUNK,) + np.shape(y)).copy())
    jax.block_until_ready((xs, ys))
    out = net.fit_chunk_async(xs, ys, unroll=CHUNK_UNROLL)  # compile
    jax.block_until_ready(out[0])
    times = []
    syncs = 0
    for _ in range(max(1, steps // CHUNK)):
        t0 = time.perf_counter()
        out = net.fit_chunk_async(xs, ys, unroll=CHUNK_UNROLL)
        jax.block_until_ready(out[0])
        syncs += 1
        times.append((time.perf_counter() - t0) / CHUNK)
    return float(np.median(times)), syncs


def _mem_fields(net=None, x=None, params=None, updater_state=None,
                compute_dtype: str = "float32",
                inference: bool = False) -> dict:
    """param_bytes / train_state_bytes columns (ISSUE-5): every row
    carries the memory trajectory so BENCH_*.json tracks it release
    over release.  `net` path uses the net's precision policy (and an
    example batch for the activation term); `params` path covers the
    raw-pytree transformer rows.  `inference=True` rows (e.g. KV
    decode) hold no gradients/optimizer state, so train_state_bytes is
    None rather than a fabricated training-memory model."""
    import jax

    from deeplearning4j_tpu.precision import (
        param_bytes,
        train_state_bytes,
        tree_bytes,
    )

    if net is not None:
        return {"param_bytes": int(param_bytes(net)),
                "train_state_bytes": int(train_state_bytes(net, x))}
    if inference:
        return {"param_bytes": int(tree_bytes(params)),
                "train_state_bytes": None}
    n = sum(int(np.prod(np.shape(a)))
            for a in jax.tree_util.tree_leaves(params))
    total = tree_bytes(params)
    if updater_state is not None:
        total += tree_bytes(updater_state)
    total += n * np.dtype(compute_dtype).itemsize  # gradient term
    return {"param_bytes": int(tree_bytes(params)),
            "train_state_bytes": int(total)}


def _fused_fields(sec_fused: float, sec_unfused: float, syncs: int,
                  steps: int) -> dict:
    """Shared row fields for the fused-vs-unfused before/after story."""
    return {
        "steps_per_dispatch": CHUNK,
        "chunk_unroll": CHUNK_UNROLL,
        "host_sync_count": syncs,
        "unfused_step_ms": round(sec_unfused * 1e3, 3),
        "unfused_host_sync_count": max(1, steps // 10),
        "fused_vs_unfused": round(sec_unfused / sec_fused, 3),
    }


# ---------------------------------------------------------------------------
# the five BASELINE.md configs
# ---------------------------------------------------------------------------

def _lenet_train_flops_per_example() -> float:
    """Matmul/conv FLOPs for one LeNet training example (fwd 2*MACs;
    train ~3x fwd for the backward's two GEMMs per layer)."""
    fwd = (
        2 * (28 * 28 * 6 * 5 * 5 * 1)        # conv1 SAME 28x28x6
        + 2 * (10 * 10 * 16 * 5 * 5 * 6)     # conv2 VALID 10x10x16
        + 2 * (400 * 120) + 2 * (120 * 84) + 2 * (84 * 10)
    )
    return 3.0 * fwd


# Peak dense bf16 matmul rate per chip, keyed by `device_kind` as JAX
# reports it.  Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s
# bf16 per chip).  A device that is not in the table is an error, not a
# default; the CPU has no peak, so CPU rows carry no `mfu`.
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}


def _peak_flops() -> float:
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in PEAK_BF16_FLOPS:
        raise KeyError(
            f"no peak FLOP/s on record for device_kind {kind!r}; add it "
            f"to PEAK_BF16_FLOPS with its source")
    return PEAK_BF16_FLOPS[kind]


def _mfu_fields(flops: float, sec: float, digits: int = 4,
                target: float = None) -> dict:
    """`mfu` (model FLOP/s over the chip's peak) for accelerator rows,
    with `mfu_target`/`meets_target` when the row states a target."""
    import jax

    if jax.default_backend() == "cpu":
        return {}
    mfu = flops / sec / _peak_flops()
    out = {"mfu": round(mfu, digits)}
    if target is not None:
        out.update(mfu_target=target, meets_target=bool(mfu >= target))
    return out


def bench_lenet() -> dict:
    """#1: LeNet-5 MNIST-shape training throughput (metric of record).
    bf16 compute on TPU (MXU native rate; master weights stay f32).
    The row value is the FUSED path (K steps per dispatch,
    `fit_chunk_async`); the per-step-dispatch figure rides along as
    `unfused_examples_per_sec` so the before/after of the fused driver
    is captured in one row."""
    import jax

    from deeplearning4j_tpu.models import MultiLayerNetwork, lenet_mnist

    on_tpu = jax.default_backend() == "tpu"
    dtype = "bfloat16" if on_tpu else "float32"
    net = MultiLayerNetwork(
        lenet_mnist(updater="sgd", compute_dtype=dtype)).init()
    rng = np.random.default_rng(0)
    x, y = _staged(rng.random((BATCH, 28, 28, 1), dtype=np.float32),
                   np.eye(10, dtype=np.float32)[rng.integers(0, 10, BATCH)])
    sec_unfused = _time_steps(lambda: net.fit_batch_async(x, y), WARMUP,
                              STEPS)
    net_f = MultiLayerNetwork(
        lenet_mnist(updater="sgd", compute_dtype=dtype)).init()
    sec_fused, syncs = _time_fused_steps(net_f, x, y, STEPS)
    # A/B like the LSTM row: the record value is the faster path (the
    # conv step is compute-bound on small hosts, dispatch-bound at
    # scale), with both figures recorded either way.
    sec = min(sec_fused, sec_unfused)
    flops = BATCH * _lenet_train_flops_per_example()
    return {"metric": RECORD_METRIC, "value": round(BATCH / sec, 1),
            "unit": "examples/sec", "dtype": dtype,
            "step_ms": round(sec * 1e3, 3),
            "path": ("fused-chunk" if sec_fused <= sec_unfused
                     else "per-step"),
            "fused_examples_per_sec": round(BATCH / sec_fused, 1),
            "unfused_examples_per_sec": round(BATCH / sec_unfused, 1),
            **_fused_fields(sec_fused, sec_unfused, syncs, STEPS),
            **_mem_fields(net=net_f, x=np.asarray(x)),
            **_mfu_fields(flops, sec, digits=5)}


def bench_iris() -> dict:
    """#2: 3-layer MLP on Iris — examples/sec + F1 (the reference's CLI
    `Train.java:151` convergence config; quality gate F1 >= 0.90).
    Measures the direct train-step throughput AND the full `dl4j train`
    CLI entrypoint (BASELINE names the CLI for this row)."""
    import contextlib
    import io
    import re
    import tempfile

    from deeplearning4j_tpu.datasets.fetchers import iris_dataset
    from deeplearning4j_tpu.models import MultiLayerNetwork, iris_mlp

    ds = iris_dataset()
    net = MultiLayerNetwork(iris_mlp()).init()
    x, y = _staged(np.asarray(ds.features), np.asarray(ds.labels))
    steps = max(60, STEPS)
    sec_unfused = _time_steps(lambda: net.fit_batch_async(x, y), WARMUP,
                              steps)
    net_f = MultiLayerNetwork(iris_mlp()).init()
    sec_fused, syncs = _time_fused_steps(net_f, x, y, steps)
    sec = min(sec_fused, sec_unfused)
    f1 = net_f.evaluate(x, y).f1()
    result = {"metric": "Iris-MLP train examples/sec",
              "unit": "examples/sec",
              "value": round(len(x) / sec, 1), "f1": round(float(f1), 4),
              "path": ("fused-chunk" if sec_fused <= sec_unfused
                       else "per-step"),
              "fused_examples_per_sec": round(len(x) / sec_fused, 1),
              "unfused_examples_per_sec": round(len(x) / sec_unfused, 1),
              **_fused_fields(sec_fused, sec_unfused, syncs, steps),
              **_mem_fields(net=net_f, x=np.asarray(x))}
    try:  # end-to-end CLI entrypoint (includes IO + eval + save)
        from deeplearning4j_tpu.cli import main as cli_main

        rows = ["%s,%d" % (",".join(f"{v:.5f}" for v in fx), int(fy.argmax()))
                for fx, fy in zip(x, y)]
        with tempfile.TemporaryDirectory() as td:
            csv = pathlib.Path(td) / "iris.csv"
            csv.write_text("\n".join(rows))
            out = io.StringIO()
            with contextlib.redirect_stdout(out):
                cli_main(["train", "-input", str(csv), "-model",
                          "zoo:iris-mlp", "-output", str(td),
                          "-epochs", "30", "-batch", "32"])
        m = re.search(r"\(([\d.]+) examples/sec\)", out.getvalue())
        if m:
            result["cli_examples_per_sec"] = round(float(m.group(1)), 1)
    except Exception as e:  # noqa: BLE001 - CLI figure is supplementary
        result["cli_error"] = f"{type(e).__name__}: {e}"
    return result


def bench_lstm() -> dict:
    """#4: character-level LSTM LM (GravesLSTM.java:47 parity config) —
    examples/sec/chip at batch 32, seq 64, vocab 80, hidden 256."""
    import jax

    from deeplearning4j_tpu.models import MultiLayerNetwork, char_lstm

    V, B, T, H = 80, 32, 64, 256
    on_tpu = jax.default_backend() == "tpu"
    dtype = "bfloat16" if on_tpu else "float32"
    rng = np.random.default_rng(0)
    ids = rng.integers(0, V, (B, T))
    x, y = _staged(np.eye(V, dtype=np.float32)[ids],
                   np.eye(V, dtype=np.float32)[np.roll(ids, -1, axis=1)])
    steps = max(20, STEPS // 2)

    def make_net():
        return MultiLayerNetwork(
            char_lstm(vocab_size=V, hidden=H, compute_dtype=dtype)).init()

    net = make_net()
    sec_scan = _time_steps(lambda: net.fit_batch_async(x, y), WARMUP, steps)
    result = {"path": "scan", "scan_ms": round(sec_scan * 1e3, 3)}
    sec = sec_scan
    # Fused multi-step driver on the scan path: K steps per dispatch.
    net_c = make_net()
    sec_chunked, syncs = _time_fused_steps(net_c, x, y, steps)
    if sec_chunked < sec:
        sec, result["path"] = sec_chunked, "scan+chunked"
    result.update(chunked_ms=round(sec_chunked * 1e3, 3),
                  **_fused_fields(sec_chunked, sec_scan, syncs, steps))
    # per-timestep MACs: input proj V*4H + recurrent H*4H + head H*V
    flops = 3.0 * 2 * B * T * (V * 4 * H + H * 4 * H + H * V)
    return {"metric": "charLSTM train examples/sec/chip",
            "unit": "examples/sec", "value": round(B / sec, 1),
            "batch": B, "seq_len": T, "dtype": dtype,
            "step_ms": round(sec * 1e3, 3),
            **_mem_fields(net=net_c, x=np.asarray(x)),
            **_mfu_fields(flops, sec, digits=5), **result}


def bench_word2vec() -> dict:
    """#3: Word2Vec skip-gram words/sec.  Prefers a REAL corpus — a
    cached/TEXT8_PATH text8 slice (real vocabulary scale, Huffman depth,
    frequency skew) — and falls back to a zipf-sampled synthetic corpus
    offline (throughput is corpus-agnostic; quality at scale is gated by
    tests/test_text8_gate.py).  With >1 visible device the mesh-parallel
    path (shard_map pair sharding + psum'd grads) carries the training."""
    import jax

    from deeplearning4j_tpu.nlp.word2vec import Word2Vec
    from deeplearning4j_tpu.parallel import make_mesh

    rng = np.random.default_rng(0)
    n_tokens = int(os.environ.get("BENCH_W2V_TOKENS", 120_000))
    corpus = "synthetic-zipf (text8 not cached; offline)"
    sentences = None
    try:  # cache/TEXT8_PATH only — the bench must never block on network
        from deeplearning4j_tpu.datasets.downloader import (
            cache_dir,
            fetch_text8,
        )

        path = (fetch_text8() if os.environ.get("TEXT8_PATH")
                or (cache_dir("text8") / "text8").is_file() else None)
        if path is not None:
            words = path.read_bytes()[: n_tokens * 8].decode().split()
            words = words[:n_tokens]
            sentences = [" ".join(words[i:i + 16])
                         for i in range(0, len(words), 16)]
            corpus = f"text8[: {len(words)} tokens]"
    except Exception:  # noqa: BLE001 - synthetic fallback below
        sentences = None
    if sentences is None:
        vocab = [f"w{i}" for i in range(2000)]
        zipf = 1.0 / np.arange(1, len(vocab) + 1)
        probs = zipf / zipf.sum()
        ids = rng.choice(len(vocab), size=n_tokens, p=probs)
        sentences, k = [], 0
        while k < n_tokens:
            n = int(rng.integers(8, 24))
            sentences.append(" ".join(vocab[i] for i in ids[k:k + n]))
            k += n
    n_dev = len(jax.devices())
    mesh = (make_mesh((n_dev,), ("data",)) if n_dev > 1 else None)
    w2v = Word2Vec(vector_length=128, window=5, negative=5, epochs=1,
                   batch_size=4096, mesh=mesh)
    # Warmup fit triggers the one-time XLA compiles (identical shapes);
    # the timed fit is the steady-state throughput — on TPU a cold fit
    # would measure the ~25s compile, not the training.
    w2v.fit(sentences)
    t0 = time.perf_counter()
    w2v.fit(sentences)
    sec = time.perf_counter() - t0
    return {"metric": "Word2Vec words/sec", "unit": "words/sec",
            "value": round(n_tokens / sec, 1), "tokens": n_tokens,
            "param_bytes": sum(
                int(np.prod(np.shape(t))) * np.asarray(t).dtype.itemsize
                for t in (w2v.syn0, w2v.syn1, w2v.syn1neg)
                if t is not None) or None,
            "train_state_bytes": None,
            "devices": n_dev, "corpus": corpus,
            "timing": "steady-state (post-compile)",
            "host_overlap": ("pair-gen runs on a background producer "
                             "thread overlapping device steps (the "
                             "reference thread pool's role); device no "
                             "longer idles between epoch chunks")}


def bench_scaling() -> dict:
    """#5: AlexNet-CIFAR10 data-parallel scaling efficiency, same per-chip
    batch, 1 vs N chips (N = all visible devices; BASELINE.md names AlexNet
    for this row).  On a single-device host this reports the 1-device
    DP-path throughput and "needs >1 device" for the efficiency."""
    import jax

    from deeplearning4j_tpu.models import MultiLayerNetwork, alexnet_cifar10
    from deeplearning4j_tpu.parallel import DataParallelTrainer, make_mesh

    n = len(jax.devices())
    on_tpu = jax.default_backend() == "tpu"
    per_chip = 128 if on_tpu else 16
    dtype = "bfloat16" if on_tpu else "float32"
    rng = np.random.default_rng(0)

    def throughput(n_dev: int) -> float:
        net = MultiLayerNetwork(alexnet_cifar10(compute_dtype=dtype)).init()
        fit = net.fit_batch_async
        if n_dev > 1:
            mesh = make_mesh((n_dev,), ("data",),
                             devices=jax.devices()[:n_dev])
            fit = DataParallelTrainer(net, mesh=mesh).fit_batch_async
        b = per_chip * n_dev
        x = np.asarray(rng.random((b, 32, 32, 3), dtype=np.float32))
        y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, b)]
        if n_dev == 1:  # DP trainer shards host arrays itself
            x, y = _staged(x, y)
        sec = _time_steps(lambda: fit(x, y), WARMUP, max(30, STEPS // 2))
        return b / sec

    mem = _mem_fields(
        net=MultiLayerNetwork(alexnet_cifar10(compute_dtype=dtype)).init())
    one = throughput(1)
    if n < 2:
        return {"metric": "AlexNet-CIFAR10 DP scaling efficiency",
                "unit": "fraction", "value": None, **mem,
                "one_chip_examples_per_sec": round(one, 1),
                "note": "needs >1 device"}
    many = throughput(n)
    return {"metric": f"AlexNet-CIFAR10 DP scaling efficiency 1->{n}",
            "unit": "fraction", **mem,
            "value": round(many / (n * one), 4),
            "one_chip_examples_per_sec": round(one, 1),
            f"{n}_chip_examples_per_sec": round(many, 1)}


def bench_transformer() -> dict:
    """TransformerLM train step — tokens/sec and model FLOPs utilization
    (MFU vs the chip's peak, `PEAK_BF16_FLOPS`).
    The long-context/flagship config the framework is designed around."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.parallel import transformer as tfm
    from deeplearning4j_tpu.parallel.hybrid import _sgd_tree

    on_tpu = jax.default_backend() == "tpu"
    B, S = (16, 512) if on_tpu else (2, 64)
    cfg = tfm.TransformerConfig(
        vocab_size=4096, d_model=512 if on_tpu else 64,
        n_heads=8 if on_tpu else 4, n_layers=6 if on_tpu else 2,
        d_ff=2048 if on_tpu else 128, max_len=S,
        dtype="bfloat16" if on_tpu else "float32")
    # The realistic mixed-precision step (f32 masters, bf16 compute) —
    # the same policy every trainer in the package uses; pure-bf16
    # params would measure a config nobody should train with.
    import dataclasses

    from deeplearning4j_tpu.parallel.hybrid import _cast_floating

    init_cfg = (cfg if not on_tpu
                else dataclasses.replace(cfg, dtype="float32"))
    params = tfm.init_params(init_cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
    targets = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)

    @jax.jit
    def step(p):
        def loss_fn(q):
            qc = (_cast_floating(q, jnp.bfloat16) if on_tpu else q)
            return tfm.lm_loss(cfg, qc, tokens, targets)

        loss, grads = jax.value_and_grad(loss_fn)(p)
        return _sgd_tree(p, grads, 1e-3), loss

    state = {"p": params}

    def one():
        state["p"], loss = step(state["p"])
        return loss

    sec = _time_steps(one, WARMUP, max(20, STEPS // 2))
    n_params = sum(int(np.prod(np.shape(x)))
                   for x in jax.tree_util.tree_leaves(params))
    # fwd+bwd matmul FLOPs ~ 6 * tokens * params, + attention
    # 12 * L * B * S^2 * d (score + value matmuls, fwd and bwd).
    flops = (6 * B * S * n_params
             + 12 * cfg.n_layers * B * S * S * cfg.d_model)
    # Workload shape is part of the metric name: changing B/S re-pins the
    # baseline instead of silently comparing different workloads.
    return {"metric": f"TransformerLM train tokens/sec/chip (B{B}xS{S})",
            "unit": "tokens/sec", "value": round(B * S / sec, 1),
            **_mem_fields(params=state["p"],
                          compute_dtype="bfloat16" if on_tpu else "float32"),
            # stated target: bf16 B16xS512 on v5e
            **_mfu_fields(flops, sec, target=0.30), "params": n_params,
            "batch": B, "seq_len": S,
            "dtype": ("bf16-compute/f32-master" if on_tpu else cfg.dtype)}


def bench_flash_ab() -> dict:
    """Fused flash backward vs dense-recompute backward at S=1024
    (VERDICT r1 'done' bar: fused >= dense throughput at S >= 1024).
    Meaningful only with the compiled Pallas kernel, so TPU-gated."""
    import jax
    import jax.numpy as jnp

    if jax.default_backend() != "tpu":
        return {"metric": "flash-bwd vs dense-bwd speedup @S=1024",
                "unit": "ratio", "value": None,
                "note": "needs TPU (interpret mode is not a perf path)"}
    from deeplearning4j_tpu.parallel.kernels import flash_attention

    B, S, H, D = 4, 1024, 8, 64
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.bfloat16)
               for _ in range(3))

    def grad_step():
        return jax.grad(lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, True).astype(jnp.float32) ** 2),
            (0, 1, 2))(q, k, v)

    jit_grad = jax.jit(grad_step)

    def timed():
        return _time_steps(lambda: jit_grad()[0], WARMUP,
                           max(20, STEPS // 2))

    os.environ["DL4J_TPU_FLASH_BWD"] = "1"
    jax.clear_caches()
    fused = timed()
    os.environ["DL4J_TPU_FLASH_BWD"] = "0"
    jax.clear_caches()
    dense = timed()
    os.environ.pop("DL4J_TPU_FLASH_BWD", None)
    return {"metric": "flash-bwd vs dense-bwd speedup @S=1024",
            "unit": "ratio", "value": round(dense / fused, 3),
            "param_bytes": None, "train_state_bytes": None,
            "mem_note": "kernel row: qkv operands only, no resident params",
            "fused_ms": round(fused * 1e3, 2),
            "dense_ms": round(dense * 1e3, 2)}


def bench_gpt2() -> dict:
    """GPT-2-small-class flagship LM (VERDICT r4 demand #2): ~124M params
    (tied embeddings), S=1024, bf16 compute / f32 masters, per-block
    remat, gradient accumulation.  Stated target: >=30% MFU on a single
    v5e chip.  Off-TPU this measures the SAME code path at a toy shape
    (proves the program; the 124M row is TPU-gated)."""
    import jax

    from deeplearning4j_tpu.parallel import transformer as tfm
    from deeplearning4j_tpu.parallel.hybrid import (
        _master_f32,
        make_accum_train_step,
    )

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        cfg = tfm.gpt2_small(max_len=1024)
        b_global, accum, steps = 8, 4, max(10, STEPS // 10)
    else:
        import dataclasses

        cfg = dataclasses.replace(
            tfm.gpt2_small(max_len=128), vocab_size=2048, d_model=128,
            n_heads=4, n_layers=2, d_ff=512, dtype="float32")
        b_global, accum, steps = 4, 2, 5
    S = cfg.max_len
    params = _master_f32(tfm.init_params(cfg, jax.random.PRNGKey(0)))
    n_params = sum(int(np.prod(np.shape(x)))
                   for x in jax.tree_util.tree_leaves(params))
    # Adam, not SGD: the realistic pretraining step (its state update is
    # part of what the MFU row should honestly include).
    step, init_state = make_accum_train_step(cfg, lr=1e-3, accum=accum,
                                             updater="adam")
    rng = np.random.default_rng(0)
    tokens, targets = _staged(
        rng.integers(0, cfg.vocab_size, (b_global, S)).astype(np.int32),
        rng.integers(0, cfg.vocab_size, (b_global, S)).astype(np.int32))

    state = {"p": params, "o": init_state(params)}

    def one():
        state["p"], state["o"], loss = step(state["p"], state["o"],
                                            tokens, targets)
        return loss

    sec = _time_steps(one, 2, steps)
    flops = (6 * b_global * S * n_params
             + 12 * cfg.n_layers * b_global * S * S * cfg.d_model)
    name = ("GPT2-small train tokens/sec/chip (B8xS1024,accum4)" if on_tpu
            else "GPT2-small smoke tokens/sec (toy shape; 124M row is "
                 "tpu-gated)")
    return {"metric": name, "unit": "tokens/sec",
            "value": round(b_global * S / sec, 1), "params": n_params,
            **_mem_fields(params=state["p"], updater_state=state["o"],
                          compute_dtype="bfloat16" if on_tpu else "float32"),
            "batch": b_global, "seq_len": S, "accum": accum,
            "step_ms": round(sec * 1e3, 1),
            **_mfu_fields(flops, sec, target=0.30),
            "remat": cfg.remat, "tied_embeddings": cfg.tie_embeddings,
            "dtype": ("bf16-compute/f32-master" if on_tpu else cfg.dtype)}


def bench_decode() -> dict:
    """KV-cached autoregressive decode throughput — the serving-side
    flagship metric (the 2015 reference has no generative inference;
    this is a beyond-parity row backing the UI /lm/generate endpoint).
    One jitted lax.scan over decode_step: no per-token retrace.
    TPU: the 124M GPT-2-small.  CPU: the same code path at toy shape."""
    import jax

    from deeplearning4j_tpu.parallel import transformer as tfm
    from deeplearning4j_tpu.parallel.generation import generate

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        cfg = tfm.gpt2_small(max_len=1024)
        b, new = 8, 128
    else:
        import dataclasses

        cfg = dataclasses.replace(
            tfm.gpt2_small(max_len=128), vocab_size=2048, d_model=128,
            n_heads=4, n_layers=2, d_ff=512, dtype="float32")
        b, new = 4, 32
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    (prompt,) = _staged(
        rng.integers(0, cfg.vocab_size, (b, 8)).astype(np.int32))

    def run():
        return generate(cfg, params, prompt, new)

    jax.block_until_ready(run())  # compile once
    reps = 5 if on_tpu else 2
    t0 = time.perf_counter()
    for _ in range(reps):
        out = run()
    jax.block_until_ready(out)
    sec = (time.perf_counter() - t0) / reps
    name = ("GPT2-small 124M KV-decode tokens/sec (B8, greedy)" if on_tpu
            else "TransformerLM KV-decode tokens/sec (toy; 124M row "
                 "tpu-gated)")
    return {"metric": name, "unit": "tokens/sec",
            "value": round(b * new / sec, 1), "batch": b,
            **_mem_fields(params=params, inference=True),
            "new_tokens": new, "prompt_len": 8,
            "ms_per_token": round(sec / new * 1e3, 3),
            "params": sum(int(np.prod(np.shape(x)))
                          for x in jax.tree_util.tree_leaves(params))}


def bench_longctx() -> dict:
    """Long-context row (VERDICT r4 missing #5): flash attention fwd+bwd
    at S=16384 on one chip — a length where the dense path's [S,S] scores
    (4 GiB in f32 at B4xH8) cannot exist, so only the blocked kernel can
    produce the number.  TPU-gated: interpret mode is not a perf path.
    The multi-chip ring at S>=2048 is certified on the virtual mesh by
    tests/test_long_context.py; this row is the single-chip kernel speed."""
    import jax
    import jax.numpy as jnp

    if jax.default_backend() != "tpu":
        return {"metric": "flash-attn fwd+bwd tokens/sec @S=16384",
                "unit": "tokens/sec", "value": None,
                "note": "needs TPU (interpret mode is not a perf path); "
                        "ring@S=2048 correctness: tests/test_long_context.py"}
    from deeplearning4j_tpu.parallel.kernels import flash_attention

    Bq, Sq, Hq, Dq = 1, 16384, 8, 64
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal((Bq, Sq, Hq, Dq)),
                           jnp.bfloat16) for _ in range(3))

    @jax.jit
    def fwd_bwd(q, k, v):
        return jax.grad(lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, True).astype(jnp.float32) ** 2),
            (0, 1, 2))(q, k, v)

    sec = _time_steps(lambda: fwd_bwd(q, k, v)[0], WARMUP,
                      max(20, STEPS // 5))
    return {"metric": "flash-attn fwd+bwd tokens/sec @S=16384",
            "unit": "tokens/sec", "value": round(Bq * Sq / sec, 1),
            "param_bytes": None, "train_state_bytes": None,
            "mem_note": "kernel row: qkv operands only, no resident params",
            "step_ms": round(sec * 1e3, 2), "batch": Bq, "heads": Hq,
            "head_dim": Dq, "dtype": "bfloat16"}


def bench_gpt2_mem() -> dict:
    """124M memory-path proof (VERDICT r4 missing #5 / next-round #4):
    build `gpt2_small()` at FULL size and execute train steps of the real
    flagship recipe — per-block remat, accum=4, bf16-compute/f32-master,
    Adam — recording peak RSS and step wall time.  Slow on CPU by design;
    an OOM here is exactly what the row exists to find before a TPU
    window.  Excluded from the default suite (minutes per step on CPU):
    run via `BENCH_ONLY=gpt2mem`."""
    import resource

    import jax

    from deeplearning4j_tpu.parallel import transformer as tfm
    from deeplearning4j_tpu.parallel.hybrid import (
        _master_f32,
        make_accum_train_step,
    )

    on_tpu = jax.default_backend() == "tpu"
    cfg = tfm.gpt2_small(max_len=1024)  # bf16 compute, remat, tied head
    b_global, accum = 8, 4
    params = _master_f32(tfm.init_params(cfg, jax.random.PRNGKey(0)))
    n_params = sum(int(np.prod(np.shape(x)))
                   for x in jax.tree_util.tree_leaves(params))
    step, init_state = make_accum_train_step(cfg, lr=1e-4, accum=accum,
                                             updater="adam")
    rng = np.random.default_rng(0)
    tokens, targets = _staged(
        rng.integers(0, cfg.vocab_size, (b_global, 1024)).astype(np.int32),
        rng.integers(0, cfg.vocab_size, (b_global, 1024)).astype(np.int32))
    state = {"p": params, "o": init_state(params)}
    # block_until_ready INSIDE each timed region: dispatch is async, so
    # an unblocked perf_counter window times the enqueue, not the step.
    t0 = time.perf_counter()
    state["p"], state["o"], loss = step(state["p"], state["o"],
                                        tokens, targets)
    losses = [float(jax.block_until_ready(loss))]
    first_s = time.perf_counter() - t0  # includes compile
    t0 = time.perf_counter()
    state["p"], state["o"], loss = step(state["p"], state["o"],
                                        tokens, targets)
    losses.append(float(jax.block_until_ready(loss)))
    steady_s = time.perf_counter() - t0
    assert all(np.isfinite(v) for v in losses), losses
    # ru_maxrss is KiB on Linux: host-process peak, which on CPU includes
    # the XLA buffers themselves — the number that answers "does the 124M
    # recipe fit".
    peak_gib = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20
    return {"metric": "GPT2-small 124M full-size train step "
                      "(B8xS1024,accum4,remat,adam)",
            "unit": "tokens/sec", "value": round(b_global * 1024 / steady_s, 1),
            **_mem_fields(params=state["p"], updater_state=state["o"],
                          compute_dtype="bfloat16"),
            "params": n_params, "losses": [round(v, 4) for v in losses],
            "step_s": round(steady_s, 1), "first_step_s": round(first_s, 1),
            "peak_rss_gib": round(peak_gib, 2),
            "dtype": "bf16-compute/f32-master", "remat": cfg.remat,
            "accum": accum, "tied_embeddings": cfg.tie_embeddings,
            "note": "memory-path proof: OOM, not speed, is the question "
                    "this row answers off-TPU"}


def bench_precision() -> dict:
    """Precision-plane row (ISSUE-5 acceptance): the memory/parity
    story of bf16-mixed training and int8 weight-quantized serving.

    - TRAIN leg: LeNet @ BATCH fp32 vs mixed — step time and the
      train-state-bytes model (fp32 masters + bf16 grads/activations);
      the acceptance bar is >=1.9x reduction.
    - PARITY leg: iris + lenet final-loss gap, bf16-mixed vs fp32,
      within the documented tolerance (docs/performance.md).
    - ZERO leg (ISSUE-17): the ZeRO-1 weight-update sharding composed
      with the precision plane — per-replica train_state_bytes columns
      at N=2 under the sharding cost model (docs/performance.md "The
      weight-update sharding cost model"): fp32-replicated vs fp32-ZeRO
      vs bf16+ZeRO, composed reduction >=3.5x; fp32 sharded-vs-
      replicated final loss bitwise; the `shard_update=False`
      off-ladder still compiles and trains.
    - SERVING leg: `mnist_mlp` int8 vs fp32 — resident param bytes
      (>=3.5x bar), top-1 agreement (>=99% bar) and batched-forward
      latency for both.
    """
    import jax

    from deeplearning4j_tpu.models import (
        MultiLayerNetwork,
        lenet_mnist,
        mnist_mlp,
    )
    from deeplearning4j_tpu.models.zoo import iris_mlp
    from deeplearning4j_tpu.precision import (
        QuantizedNet,
        param_bytes,
        train_state_bytes,
    )
    from deeplearning4j_tpu.serving import BucketLadder

    rng = np.random.default_rng(0)
    steps = max(20, STEPS // 5)

    # ---- train leg: lenet fp32 vs mixed --------------------------------
    x, y = _staged(rng.random((BATCH, 28, 28, 1), dtype=np.float32),
                   np.eye(10, dtype=np.float32)[rng.integers(0, 10, BATCH)])
    legs = {}
    for name in ("fp32", "mixed"):
        net = MultiLayerNetwork(lenet_mnist(updater="sgd")).init()
        net.set_precision(name)
        sec = _time_steps(lambda: net.fit_batch_async(x, y), WARMUP, steps)
        legs[name] = {
            "examples_per_sec": round(BATCH / sec, 1),
            "step_ms": round(sec * 1e3, 3),
            "train_state_bytes": int(train_state_bytes(net, np.asarray(x))),
        }
    mem_reduction = (legs["fp32"]["train_state_bytes"]
                     / legs["mixed"]["train_state_bytes"])

    # ---- parity leg: final-loss gap on iris + lenet --------------------
    ix = rng.normal(0, 0.25, (96, 4)).astype(np.float32)
    iy = rng.integers(0, 3, 96)
    ix += iy[:, None]
    iyh = np.eye(3, dtype=np.float32)[iy]
    parity = {}
    for row_name, conf, (px, py), n_steps, tol in (
            ("iris", iris_mlp(), (ix, iyh), 120, 0.05),
            ("lenet", lenet_mnist(updater="sgd"),
             (np.asarray(x)[:64], np.asarray(y)[:64]), 25, 0.1)):
        finals = {}
        for pol in ("fp32", "mixed"):
            net = MultiLayerNetwork(conf).init()
            net.set_precision(pol)
            for _ in range(n_steps):
                loss = net.fit_batch_async(px, py)
            finals[pol] = float(loss)
        gap = abs(finals["fp32"] - finals["mixed"])
        parity[row_name] = {
            "fp32_final_loss": round(finals["fp32"], 5),
            "bf16_mixed_final_loss": round(finals["mixed"], 5),
            "gap": round(gap, 5), "tolerance": tol,
            "within_tolerance": bool(gap <= tol)}

    # ---- zero leg: ZeRO-1 update sharding x precision plane ------------
    from deeplearning4j_tpu.parallel import DataParallelTrainer, make_mesh

    n_zero = min(2, len(jax.devices()))
    zmesh = make_mesh((n_zero,), ("data",),
                      devices=jax.devices()[:n_zero])

    def zero_run(policy: str, shard: bool, n_steps: int = 60):
        znet = MultiLayerNetwork(iris_mlp()).init()   # adam: 16P fp32 state
        znet.set_precision(policy)
        tr = DataParallelTrainer(znet, mesh=zmesh, shard_update=shard)
        for _ in range(n_steps):
            loss = tr.fit_batch_async(ix, iyh)
        return znet, float(loss)

    net_rep, loss_rep = zero_run("fp32", shard=False)   # the off-ladder
    net_z32, loss_z32 = zero_run("fp32", shard=True)
    net_zbf, loss_zbf = zero_run("bf16", shard=True)
    # Byte columns are the N=2 sharding COST MODEL (padded 1/N extents
    # for params/moments/grads, scalars replicated) — device-count
    # independent, so a 1-device host still reports the N=2 accounting.
    zb_rep = int(net_rep.train_state_bytes())
    zb_z32 = int(net_z32.train_state_bytes(shards=2))
    zb_zbf = int(net_zbf.train_state_bytes(shards=2))
    composed = zb_rep / zb_zbf
    zero_leg = {
        "model": "iris-mlp 4-16-16-3 adam", "replicas_modeled": 2,
        "mesh_devices": n_zero,
        "train_state_bytes_fp32_replicated": zb_rep,
        "train_state_bytes_fp32_zero": zb_z32,
        "train_state_bytes_bf16_zero": zb_zbf,
        "composed_reduction": round(composed, 3),
        "fp32_replicated_final_loss": round(loss_rep, 6),
        "fp32_zero_final_loss": round(loss_z32, 6),
        "bf16_zero_final_loss": round(loss_zbf, 6),
        "fp32_shard_gap": abs(loss_rep - loss_z32),
        "bf16_vs_fp32_gap": round(abs(loss_rep - loss_zbf), 5)}

    # ---- serving leg: mnist_mlp int8 vs fp32 ---------------------------
    net = MultiLayerNetwork(mnist_mlp()).init()
    sy = rng.integers(0, 10, 512)
    sx = rng.normal(0, 0.3, (512, 784)).astype(np.float32)
    sx[np.arange(512), sy * 78] += 3.0      # separable synthetic classes
    for _ in range(10):                      # logits must not be degenerate
        net.fit_batch(sx, np.eye(10, dtype=np.float32)[sy])
    qnet = QuantizedNet(net)
    ladder = BucketLadder((1, 8, 32))
    probe = rng.normal(0, 0.3, (512, 784)).astype(np.float32)
    probe[np.arange(512), (np.arange(512) % 10) * 78] += 3.0

    def batched_argmax(model):
        outs = [model.output_bucketed(probe[i:i + 32], ladder=ladder)
                for i in range(0, 512, 32)]
        return np.concatenate(outs).argmax(-1)

    agree = float((batched_argmax(qnet) == batched_argmax(net)).mean())
    batch32 = probe[:32]
    jax.block_until_ready(qnet.output(batch32))   # compile both
    jax.block_until_ready(net.output(batch32))
    sec_f = _time_steps(lambda: net.output(batch32), 2, steps)
    sec_q = _time_steps(lambda: qnet.output(batch32), 2, steps)
    fp32_bytes = int(param_bytes(net))
    int8_bytes = int(qnet.param_bytes())
    serving = {
        "model": "mnist-mlp 784-2048-2048-10",
        "fp32_param_bytes": fp32_bytes, "int8_param_bytes": int8_bytes,
        "param_bytes_reduction": round(fp32_bytes / int8_bytes, 2),
        "top1_agreement": round(agree, 4),
        "fp32_batch32_ms": round(sec_f * 1e3, 3),
        "int8_batch32_ms": round(sec_q * 1e3, 3),
        "int8_vs_fp32_latency": round(sec_f / sec_q, 2)}

    guards = {
        "train_state_reduction_min": 1.9,
        "train_state_reduction_pass": bool(mem_reduction >= 1.9),
        "int8_param_reduction_min": 3.5,
        "int8_param_reduction_pass": bool(fp32_bytes / int8_bytes >= 3.5),
        "top1_agreement_min": 0.99,
        "top1_agreement_pass": bool(agree >= 0.99),
        "parity_pass": all(p["within_tolerance"] for p in parity.values()),
        # ZeRO leg (ISSUE-17): bf16+ZeRO per-replica state vs
        # fp32-replicated at N=2; fp32 sharded == replicated exactly
        # (same reduction tree); bf16 loss gap within the pure-bf16
        # tolerance; the shard_update=False off-ladder still trains.
        "zero_composed_reduction_min": 3.5,
        "zero_composed_reduction_pass": bool(composed >= 3.5),
        "zero_fp32_bitwise_pass": bool(zero_leg["fp32_shard_gap"] == 0.0),
        "zero_loss_gap_max": 0.25,
        "zero_loss_gap_pass": bool(zero_leg["bf16_vs_fp32_gap"] <= 0.25),
        "zero_off_ladder_pass": bool(np.isfinite(loss_rep))}
    return {"metric": "Precision plane: bf16-mixed train-state reduction",
            "unit": "x", "value": round(mem_reduction, 3),
            "train": legs, "parity": parity, "zero": zero_leg,
            "serving": serving, "guards": guards,
            "meets_acceptance": all(v for k, v in guards.items()
                                    if k.endswith("_pass"))}


def _serving_storm(n_clients: int, requests, handler) -> float:
    """Drive `requests` through `handler(x) -> result` from `n_clients`
    threads (round-robin assignment, barrier start); returns elapsed
    wall seconds for ALL requests."""
    import threading

    barrier = threading.Barrier(n_clients + 1)
    errors = []

    def client(cid):
        try:
            barrier.wait()
            for i in range(cid, len(requests), n_clients):
                handler(requests[i])
        except BaseException as e:  # noqa: BLE001 — surface in the parent
            errors.append(e)

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(n_clients)]
    for t in threads:
        t.start()
    barrier.wait()
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    sec = time.perf_counter() - t0
    if errors:
        raise errors[0]
    return sec


def bench_serving() -> dict:
    """Serving row (ISSUE-3 acceptance): dynamic micro-batching vs
    sequential single-request dispatch at concurrency 16 on the
    MNIST-class MLP classifier (`mnist_mlp`, 784-2048-2048-10 — wide
    enough that a single-request forward is weight-bandwidth-bound, the
    regime real serving classifiers live in).  The sequential leg is
    what the HTTP handler did before this subsystem — one batch-1 XLA
    dispatch per request, serialized; the batched leg routes the same
    requests through the ServingEngine (coalesce + bucket-pad + slice:
    one pass over the weights serves the whole coalesced batch)."""
    from deeplearning4j_tpu.models import MultiLayerNetwork, mnist_mlp
    from deeplearning4j_tpu.serving import BucketLadder, ServingEngine

    conc = 16
    total = conc * max(20, STEPS // 5)
    net = MultiLayerNetwork(mnist_mlp()).init()
    rng = np.random.default_rng(0)
    reqs = [rng.random((1, 784)).astype(np.float32) for _ in range(total)]

    import threading

    lock = threading.Lock()
    np.asarray(net.output(reqs[0]))          # compile the batch-1 program

    def sequential(x):
        with lock:                           # one request per dispatch
            return np.asarray(net.output(x))

    # best-of-2 per leg: thread-scheduling noise on small hosts swings
    # single storms by 2x (same reason _time_steps uses median windows)
    sec_seq = min(_serving_storm(conc, reqs, sequential)
                  for _ in range(2))

    engine = ServingEngine(net, ladder=BucketLadder((1, 8, 16, 32)),
                           max_wait_ms=2.0)
    engine.warmup(np.zeros((784,), np.float32))
    try:
        sec_bat = min(_serving_storm(conc, reqs, engine.predict_proba)
                      for _ in range(2))
        stats = engine.stats()
    finally:
        engine.stop()
    lat = stats.get("latency", {})
    return {"metric": "MLP-classifier serving requests/sec "
                      f"(concurrency {conc}, micro-batched)",
            "unit": "requests/sec", "value": round(total / sec_bat, 1),
            "concurrency": conc, "requests": total,
            "model": "mnist-mlp 784-2048-2048-10",
            "sequential_requests_per_sec": round(total / sec_seq, 1),
            "batched_vs_sequential": round(sec_seq / sec_bat, 2),
            **_mem_fields(net=net),
            "p50_ms": lat.get("p50_ms"), "p99_ms": lat.get("p99_ms"),
            "compiled_programs": stats.get("compiled_programs"),
            "mean_batch_occupancy": stats.get("mean_batch_occupancy"),
            "max_batch_occupancy": stats.get("max_batch_occupancy"),
            "bucket_ladder": stats.get("bucket_ladder")}


def bench_obs() -> dict:
    """Observability-overhead row (ISSUE-8 acceptance): the same
    concurrency-16 serving storm as the `serving` row, run twice — once
    with the full observability plane on (metrics registry published,
    per-request tracing, compile watcher) and once with it off.  The
    gate: instrumented requests/s >= 0.97x the uninstrumented baseline,
    i.e. observing the system costs at most 3% of its throughput."""
    from deeplearning4j_tpu.models import MultiLayerNetwork, mnist_mlp
    from deeplearning4j_tpu.obs import MetricsRegistry, TraceRecorder
    from deeplearning4j_tpu.serving import BucketLadder, ServingEngine

    conc = 16
    total = conc * max(15, STEPS // 7)
    net = MultiLayerNetwork(mnist_mlp()).init()
    rng = np.random.default_rng(0)
    reqs = [rng.random((1, 784)).astype(np.float32) for _ in range(total)]

    registry, tracer = MetricsRegistry(), TraceRecorder(capacity=256)

    def make(instrumented: bool) -> ServingEngine:
        kw = (dict(tracer=tracer, registry=registry) if instrumented
              else {})
        e = ServingEngine(net, ladder=BucketLadder((1, 8, 16, 32)),
                          max_wait_ms=2.0, **kw)
        e.warmup(np.zeros((784,), np.float32))
        return e

    # TWO engine instances per leg, storms INTERLEAVED, min across
    # rounds AND instances per leg.  Two identical engines on a small
    # shared host differ by >10% per instance (batch-formation regime
    # plus scheduling luck) — far more than the ~µs/request
    # instrumentation under test — so the comparison must control for
    # instance luck, and the min only needs ONE quiet window per leg.
    # If the gate still misses, double the sample once: on a contended
    # box a first block can fail to give one leg any quiet window.
    engines: list = []
    secs = {False: [], True: []}

    def redraw():
        for _, e in engines:
            e.stop()
        engines[:] = [(False, make(False)), (True, make(True)),
                      (False, make(False)), (True, make(True))]

    try:
        for block in range(3):
            redraw()     # fresh instances = a fresh regime draw
            for _ in range(4):
                for on, e in engines:
                    secs[on].append(_serving_storm(
                        conc, reqs, e.predict_proba))
            # throughput ratio = sec_off / sec_on (same request count)
            if min(secs[False]) / min(secs[True]) >= 0.97:
                break
        # the scrape itself is part of the enabled cost model
        expo_bytes = len(registry.exposition())
        traced = tracer.recorded
    finally:
        for _, e in engines:
            e.stop()
    sec_off, sec_on = min(secs[False]), min(secs[True])
    rps_on = total / sec_on
    rps_off = total / sec_off
    ratio = round(rps_on / rps_off, 3)
    return {"metric": "serving requests/sec with full observability "
                      f"(concurrency {conc}: registry + tracing + "
                      "compile watcher)",
            "unit": "requests/sec", "value": round(rps_on, 1),
            "concurrency": conc, "requests": total,
            "baseline_requests_per_sec": round(rps_off, 1),
            "instrumented_vs_baseline": ratio,
            "overhead_budget": 0.97,
            "traces_recorded": traced,
            "exposition_bytes": expo_bytes,
            "meets_acceptance": ratio >= 0.97,
            # throughput ratio is the metric; the absolute rps is the
            # host's business — never pinned, never regression-gated
            "no_pin": True}


def bench_serving_overload() -> dict:
    """Overload row (ISSUE-4): a concurrency-32 storm against the
    serving engine with and without admission control.  Without it the
    queue is unbounded — every request eventually serves, but tail
    latency is the whole backlog.  With `max_queue_depth` + per-request
    deadlines the engine sheds what it cannot serve in time (503/504 in
    HTTP terms) and the p99 of what it DOES serve stays bounded.  The
    row reports completed requests/s, p99, and the shed rate for the
    admission-controlled leg, with the uncontrolled leg alongside."""
    import threading

    from deeplearning4j_tpu.models import MultiLayerNetwork, mnist_mlp
    from deeplearning4j_tpu.serving import (
        BucketLadder,
        DeadlineExceededError,
        ServingEngine,
        ServingOverloadError,
    )

    conc = 32
    total = conc * max(8, STEPS // 10)
    net = MultiLayerNetwork(mnist_mlp()).init()
    rng = np.random.default_rng(0)
    reqs = [rng.random((1, 784)).astype(np.float32) for _ in range(total)]

    def one_storm(max_queue_depth, deadline_s):
        engine = ServingEngine(net, ladder=BucketLadder((1, 8, 16, 32)),
                               max_wait_ms=2.0,
                               max_queue_depth=max_queue_depth,
                               default_deadline_s=deadline_s)
        engine.warmup(np.zeros((784,), np.float32))
        lock = threading.Lock()
        outcomes = {"ok": 0, "shed": 0}

        def handler(x):
            try:
                engine.predict_proba(x, timeout=120)
                key = "ok"
            except (ServingOverloadError, DeadlineExceededError):
                key = "shed"   # admission rejection or deadline shed
            with lock:
                outcomes[key] += 1

        try:
            sec = _serving_storm(conc, reqs, handler)
            stats = engine.stats()
        finally:
            engine.stop()
        lat = stats.get("latency", {})
        return {"sec": sec, "ok": outcomes["ok"],
                "shed_rate": round(outcomes["shed"] / total, 3),
                "p99_ms": lat.get("p99_ms"),
                "rejected": stats.get("rejected"),
                "deadline_missed": stats.get("deadline_missed")}

    def storm(max_queue_depth, deadline_s):
        # best-of-2 per leg: same thread-scheduling-noise policy as the
        # other serving rows
        return min((one_storm(max_queue_depth, deadline_s)
                    for _ in range(2)), key=lambda r: r["sec"])

    # the storm is closed-loop (each client has ONE outstanding request),
    # so queue depth tops out at conc-1: the bound must sit BELOW that
    # for admission control to actually engage
    queue_bound = max(2, conc // 4)
    open_loop = storm(max_queue_depth=None, deadline_s=None)
    bounded = storm(max_queue_depth=queue_bound, deadline_s=0.5)
    return {"metric": "MLP-classifier serving under overload "
                      f"(concurrency {conc}, admission-controlled)",
            "unit": "requests/sec",
            "value": round(bounded["ok"] / bounded["sec"], 1),
            "concurrency": conc, "requests": total,
            "max_queue_depth": queue_bound, "deadline_ms": 500,
            "p99_ms": bounded["p99_ms"],
            "shed_rate": bounded["shed_rate"],
            "rejected": bounded["rejected"],
            "deadline_missed": bounded["deadline_missed"],
            "uncontrolled_requests_per_sec": round(
                open_loop["ok"] / open_loop["sec"], 1),
            **_mem_fields(net=net),
            "uncontrolled_p99_ms": open_loop["p99_ms"],
            "uncontrolled_shed_rate": open_loop["shed_rate"],
            "model": "mnist-mlp 784-2048-2048-10",
            "note": "shed work answers in microseconds (503/504); "
                    "completed work keeps the bounded queue's p99"}


def bench_serving_fleet() -> dict:
    """Fleet row (ISSUE-6 acceptance): a concurrency-32 storm against a
    3-replica serving fleet with one replica HARD-KILLED mid-storm.
    Predict is pure, so the router resubmits every dispatch that died
    with the replica on a surviving one — the row's acceptance bar is
    `failed == 0`: a replica death costs failovers (counted) but zero
    failed requests.  Reports completed requests/s and the p99 of the
    storm (which absorbs the kill + failover transient)."""
    import threading

    from deeplearning4j_tpu.models import MultiLayerNetwork, mnist_mlp
    from deeplearning4j_tpu.serving import (
        BucketLadder,
        FleetRouter,
        spawn_local_replica,
    )

    conc = 32
    total = conc * max(8, STEPS // 10)
    replicas = 3
    kill_after = total // 3
    net = MultiLayerNetwork(mnist_mlp()).init()
    rng = np.random.default_rng(0)
    reqs = [rng.random((1, 784)).astype(np.float32) for _ in range(total)]
    warm = np.zeros((784,), np.float32)

    def one_storm():
        def factory(name):
            return spawn_local_replica(
                name, net, ladder=BucketLadder((1, 8, 16, 32)),
                max_wait_ms=2.0, warmup_example=warm)

        router = FleetRouter(factory, replicas=replicas,
                             request_timeout_s=120.0)
        lock = threading.Lock()
        state = {"done": 0, "failed": 0, "killed": False}

        def handler(x):
            try:
                router.predict_proba(x, timeout=120)
            except Exception:  # noqa: BLE001 — the row COUNTS failures
                with lock:
                    state["failed"] += 1
                return
            with lock:
                state["done"] += 1
                kill = state["done"] >= kill_after and not state["killed"]
                if kill:
                    state["killed"] = True
            if kill:
                router.replicas()[0].kill()   # mid-storm replica death

        try:
            sec = _serving_storm(conc, reqs, handler)
            stats = router.fleet_stats(include_replica_stats=False)
        finally:
            router.stop()
        lat = stats["fleet"].get("latency", {})
        return {"sec": sec, "failed": state["failed"],
                "p99_ms": lat.get("p99_ms"),
                "failovers": stats["fleet"]["failovers"],
                "routable": stats["fleet"]["replicas_routable"]}

    # best-of-2: same thread-scheduling-noise policy as the other
    # serving rows (each leg builds its own fleet, so the kill replays).
    # Throughput comes from the faster leg, but the failed==0 acceptance
    # gate must hold across BOTH legs — a leg that dropped requests is a
    # failed kill replay even when the other leg happened to be faster.
    runs = [one_storm() for _ in range(2)]
    run = min(runs, key=lambda r: r["sec"])
    failed_all_legs = sum(r["failed"] for r in runs)
    ok = total - run["failed"]

    # ---- fleet LM leg (ISSUE-7 satellite, ROADMAP item 5 tie-in):
    # a shared-prefix LM storm through the router's prefix-affinity
    # dispatch, measuring the fleet-aggregated prefix_hit_rate the
    # affinity hashing exists to maximize (one prefix -> one replica ->
    # one radix-cached prefill, reused by every follow-up)
    import dataclasses

    import jax

    from deeplearning4j_tpu.parallel import transformer as tfm

    lm_cfg = dataclasses.replace(
        tfm.gpt2_small(max_len=64), vocab_size=256, d_model=128,
        n_heads=4, n_layers=2, d_ff=512, dtype="float32", remat=False)
    lm_params = tfm.init_params(lm_cfg, jax.random.PRNGKey(0))
    lm_rng = np.random.default_rng(1)
    lm_system = lm_rng.integers(0, lm_cfg.vocab_size, (32,)).tolist()
    lm_n, lm_new = 12, 16

    def lm_factory(name):
        return spawn_local_replica(
            name, lm=(lm_cfg, lm_params), lm_slots=4,
            lm_page_size=16, lm_prefill_chunk=8)

    lm_router = FleetRouter(lm_factory, replicas=2,
                            request_timeout_s=120.0)
    try:
        lm_prompts = [lm_system + [int(t) for t in
                                   lm_rng.integers(0, lm_cfg.vocab_size,
                                                   (2,))]
                      for _ in range(lm_n)]
        lm_sec = _serving_storm(
            4, lm_prompts,
            lambda p: lm_router.generate(list(p), lm_new, timeout=120))
        lm_stats = lm_router.fleet_stats()
    finally:
        lm_router.stop()
    lm_prefix = lm_stats["fleet"].get("lm_prefix", {})

    return {"metric": "MLP-classifier serving fleet under a mid-storm "
                      f"replica kill (concurrency {conc}, "
                      f"{replicas} replicas)",
            "unit": "requests/sec",
            "value": round(ok / run["sec"], 1),
            "concurrency": conc, "requests": total,
            "replicas": replicas, "killed_replicas": 1,
            "kill_after_requests": kill_after,
            "failed": run["failed"],
            "failed_all_legs": failed_all_legs,
            "failovers": run["failovers"],
            "replicas_routable_after": run["routable"],
            "p99_ms": run["p99_ms"],
            **_mem_fields(net=net),
            "model": "mnist-mlp 784-2048-2048-10",
            "meets_acceptance": failed_all_legs == 0,
            "lm_prefix_storm": {
                "replicas": 2, "requests": lm_n, "new_tokens": lm_new,
                "shared_prefix_tokens": len(lm_system),
                "tokens_per_sec": round(lm_n * lm_new / lm_sec, 1),
                "prefix_hit_rate": lm_prefix.get("hit_rate"),
                "prefix_tokens_saved": lm_prefix.get("tokens_saved"),
                "prefix_queries": lm_prefix.get("queries"),
                "note": "prefix-affinity routing concentrates the "
                        "shared prefix on one replica's radix cache; "
                        "hit rate aggregated through /fleet/stats"},
            "note": "predict is pure, so dispatches that died with the "
                    "replica were resubmitted on survivors — a replica "
                    "death costs failovers, never failed requests"}


def bench_procfleet() -> dict:
    """Process-supervision row (ISSUE-10 acceptance): a storm against 3
    REAL spawned `dl4j serve` worker processes behind the failover
    router, with one worker hard-killed (SIGKILL, process group) mid-
    storm.  The `FleetSupervisor` must detect the death from exit
    status, restart the worker with backoff, wait for its /readyz
    (warm-then-attach) and re-admit it — while the router's failover
    keeps the storm at ZERO failed requests throughout.  Reports
    requests/s, the death-to-readmission restart latency, and the
    supervision counters.

    The workers are CPU workers, stated in their environment: this
    process holds the chip, and a child left to find its own platform
    would fail to take it and drop to the CPU without saying so."""
    import tempfile
    import threading

    from deeplearning4j_tpu.runtime.launcher import (
        FleetProcessLauncher,
        kill_process_tree,
    )
    from deeplearning4j_tpu.serving import FleetRouter
    from deeplearning4j_tpu.serving.procfleet import (
        FleetSupervisor,
        RestartPolicy,
        WORKER_READY,
        WorkerSpec,
    )

    def _free_port():
        import socket

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    conc = 16
    total = conc * max(8, STEPS // 10)
    workers = 3
    kill_after = total // 3
    log_dir = tempfile.mkdtemp(prefix="bench-procfleet-")
    launcher = FleetProcessLauncher(
        "zoo:iris-mlp", n_replicas=workers,
        base_port=_free_port(), buckets="1,8,16,32", warmup=True,
        log_dir=log_dir)
    router = FleetRouter(request_timeout_s=120.0)
    sup = FleetSupervisor(
        router, policy=RestartPolicy(backoff_initial_s=0.2,
                                     backoff_max_s=2.0),
        poll_interval_s=0.2, ready_timeout_s=300.0, probe_timeout_s=2.0)
    rng = np.random.default_rng(0)
    reqs = [rng.random((1, 4)).astype(np.float32) for _ in range(total)]
    lock = threading.Lock()
    state = {"done": 0, "failed": 0, "killed": False}

    def handler(x):
        try:
            router.predict_proba(x, timeout=120)
        except Exception:  # noqa: BLE001 — the row COUNTS failures
            with lock:
                state["failed"] += 1
            return
        with lock:
            state["done"] += 1
            kill = state["done"] >= kill_after and not state["killed"]
            if kill:
                state["killed"] = True
        if kill:
            victim = sup.workers["worker-0"]
            kill_process_tree(victim.proc)     # real SIGKILL, mid-storm

    worker_env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        for i in range(workers):
            sup.manage(WorkerSpec(
                name=f"worker-{i}", url=launcher.url(i),
                command=launcher.command(i),
                log_path=str(launcher.log_path(i)), env=worker_env))
        sup.start()
        if not sup.wait_all_ready(300.0):
            raise RuntimeError(
                f"procfleet bench: workers never ready; logs in "
                f"{log_dir}: {launcher.tail_log(0)}")
        sec = _serving_storm(conc, reqs, handler)
        # the restart may complete after the storm's last request —
        # give the supervisor its backoff + worker boot window
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            st = sup.stats()
            w0 = st["workers"]["worker-0"]
            if (w0["state"] == WORKER_READY
                    and w0["last_restart_latency_s"] is not None):
                break
            time.sleep(0.2)
        st = sup.stats()
        fleet = router.fleet_stats(include_replica_stats=False)["fleet"]
    finally:
        sup.stop(grace_s=10.0)
        router.stop()
    w0 = st["workers"]["worker-0"]
    restarted = (w0["state"] == WORKER_READY
                 and st["counters"]["restarts"] >= 1)
    ok = total - state["failed"]
    return {"metric": "iris-mlp serving fleet of REAL worker processes "
                      f"under a mid-storm SIGKILL (concurrency {conc}, "
                      f"{workers} workers)",
            "unit": "requests/sec",
            "value": round(ok / sec, 1),
            "concurrency": conc, "requests": total,
            "worker_processes": workers, "killed_workers": 1,
            "worker_platform": "cpu (JAX_PLATFORMS=cpu in each worker's "
                               "environment)",
            "kill_after_requests": kill_after,
            "failed": state["failed"],
            "failovers": fleet["failovers"],
            "restart_latency_s": w0["last_restart_latency_s"],
            "restarts": st["counters"]["restarts"],
            "deaths": {k.split("_", 1)[1]: v
                       for k, v in st["counters"].items()
                       if k.startswith("deaths_")},
            "quarantines": st["counters"]["quarantines"],
            "worker_restarted": restarted,
            "p99_ms": fleet.get("latency", {}).get("p99_ms"),
            "model": "iris-mlp (per-worker `dl4j serve` process)",
            "meets_acceptance": state["failed"] == 0 and restarted,
            "note": "a SIGKILL'd worker process is detected from exit "
                    "status, restarted with backoff, warmed, and "
                    "re-admitted through warm-then-attach; failover "
                    "keeps the storm at zero failed requests while it "
                    "is gone (restart latency = death detection -> "
                    "back in rotation, including worker jax boot)"}


def bench_serving_lm() -> dict:
    """Continuous LM decode (slot pool, prompts join mid-flight) vs the
    pre-serving behavior: concurrent requests served one-at-a-time, each
    through the whole-sequence `generate()` scan.  Reports tokens/s and
    requests/s for both legs; the structural win is occupancy — decode
    FLOPs are nearly free across lanes on a TPU's MXU while the
    sequential leg strictly serializes requests."""
    import dataclasses

    import jax

    from deeplearning4j_tpu.parallel import transformer as tfm
    from deeplearning4j_tpu.parallel.generation import generate
    from deeplearning4j_tpu.serving import ContinuousLMServer

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        cfg = tfm.gpt2_small(max_len=256)
        slots, n_req, new = 8, 16, 64
    else:
        cfg = dataclasses.replace(
            tfm.gpt2_small(max_len=64), vocab_size=256, d_model=128,
            n_heads=4, n_layers=2, d_ff=512, dtype="float32", remat=False)
        slots, n_req, new = 8, 16, 24
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    plen = 8
    prompts = [rng.integers(0, cfg.vocab_size, (plen,)).astype(np.int32)
               for _ in range(n_req)]

    import threading

    lock = threading.Lock()

    def sequential(p):
        with lock:                 # one request per whole-sequence decode
            return np.asarray(generate(cfg, params, p[None, :], new))

    sequential(prompts[0])                           # compile
    sec_seq = min(_serving_storm(min(8, n_req), prompts, sequential)
                  for _ in range(2))                 # best-of-2 (noise)

    srv = ContinuousLMServer(cfg, params, slots=slots)
    try:
        srv.generate(prompts[0].tolist(), new)       # compile slot program
        from deeplearning4j_tpu.serving import ServingMetrics

        srv.metrics = ServingMetrics()   # drop the compile-tainted warmup
        sec_bat = min(_serving_storm(
            min(8, n_req), prompts,
            lambda p: srv.generate(p.tolist(), new)) for _ in range(2))
        stats = srv.stats()
    finally:
        srv.stop()
    lat = stats.get("latency", {})
    return {"metric": "TransformerLM continuous-decode serving tokens/sec "
                      f"({slots} slots)",
            "unit": "tokens/sec", "value": round(n_req * new / sec_bat, 1),
            "requests": n_req, "new_tokens": new, "prompt_len": plen,
            **_mem_fields(params=params),
            "requests_per_sec": round(n_req / sec_bat, 2),
            "sequential_tokens_per_sec": round(n_req * new / sec_seq, 1),
            "continuous_vs_sequential": round(sec_seq / sec_bat, 2),
            "p50_ms": lat.get("p50_ms"), "p99_ms": lat.get("p99_ms"),
            # time-to-first-token (ISSUE-14 satellite): admission to the
            # first committed token, the latency the disagg row protects
            "ttft_p50_ms": stats.get("ttft", {}).get("p50_ms"),
            "ttft_p99_ms": stats.get("ttft", {}).get("p99_ms"),
            "compiled_programs": stats.get("compiled_programs"),
            "mean_slot_occupancy": stats.get("mean_batch_occupancy"),
            "slots": slots}


def bench_pressure() -> dict:
    """Overload-survival row (ISSUE-15 acceptance): a mixed-priority
    storm whose total KV page demand is sized to >2x the paged pool's
    capacity, served twice by the SAME pool sizing:

    - baseline: the pre-ISSUE-15 pool — no priorities (every request
      FIFO by arrival), no preemption, no brownout.  Latency-sensitive
      requests queue behind long best_effort lanes pinning pages.
    - survival: priorities + KV lane preemption with host swap-out +
      the brownout degradation ladder.

    Gates: ZERO failed interactive requests on the survival leg
    (best_effort may be shed with Retry-After at ladder level 4 —
    those retry and are counted, never silent); interactive p99 under
    the all-FIFO baseline; at least one degradation-ladder transition
    counted; the page ledger balanced and the swap store's byte high
    water under its cap; zero XLA compiles after warmup."""
    import dataclasses
    import threading

    import jax
    import jax.monitoring

    from deeplearning4j_tpu.parallel import transformer as tfm
    from deeplearning4j_tpu.serving import ContinuousLMServer
    from deeplearning4j_tpu.serving.resilience import (
        ServingOverloadError,
    )

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        cfg = tfm.gpt2_small(max_len=256)
        ps, pool_pages, slots = 16, 24, 8
        shapes = [("interactive", 8, 24), ("batch", 24, 48),
                  ("best_effort", 8, 120)]
        per_class = 8
    else:
        cfg = dataclasses.replace(
            tfm.gpt2_small(max_len=80), vocab_size=256, d_model=64,
            n_heads=4, n_layers=2, d_ff=256, dtype="float32",
            remat=False)
        ps, pool_pages, slots = 16, 12, 4
        shapes = [("interactive", 8, 12), ("batch", 16, 40),
                  ("best_effort", 8, 72)]
        per_class = 6
    rng = np.random.default_rng(0)
    requests = []      # (priority, prompt, max_new)
    demand_pages = 0
    for prio, plen, new in shapes:
        for _ in range(per_class):
            prompt = rng.integers(0, cfg.vocab_size, (plen,)).tolist()
            requests.append((prio, prompt, new))
            demand_pages += -(-(plen + new - 1) // ps)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))

    def storm(srv, with_priority: bool):
        """Batch + best_effort clients release at t0; the interactive
        wave lands 50ms later, when the long lanes already pin pages —
        the head-of-line scenario the survival plane exists for (both
        legs get the identical arrival pattern).  Returns (per-class
        latencies, failed-by-class, shed-retries)."""
        lats = {p: [] for p, _, _ in shapes}
        failed = {p: 0 for p, _, _ in shapes}
        shed_retries = [0]
        barrier = threading.Barrier(len(requests) + 1)
        lock = threading.Lock()

        def client(i):
            prio, prompt, new = requests[i]
            kw = {"priority": prio} if with_priority else {}
            barrier.wait()
            if prio == "interactive":
                time.sleep(0.05)
            t0 = time.perf_counter()
            for _ in range(200):
                try:
                    srv.generate(list(prompt), new, timeout=600, **kw)
                    with lock:
                        lats[prio].append(time.perf_counter() - t0)
                    return
                except ServingOverloadError as e:
                    # ladder level 4 shedding best_effort: back off
                    # as told and retry — counted, never silent
                    with lock:
                        shed_retries[0] += 1
                    time.sleep(min(0.25, e.retry_after_s))
                except Exception:  # noqa: BLE001 — tallied as failed
                    break
            with lock:
                failed[prio] += 1

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(requests))]
        for t in threads:
            t.start()
        barrier.wait()
        for t in threads:
            t.join()
        return lats, failed, shed_retries[0]

    def p99(xs):
        if not xs:
            return None
        return round(float(np.percentile(xs, 99)) * 1e3, 1)

    # ---- baseline: all-FIFO, no survival plane ---------------------------
    base = ContinuousLMServer(cfg, params, slots=slots,
                              page_size=ps, pages=pool_pages,
                              prefill_chunk=4)
    try:
        base.warmup()
        base_lats, base_failed, _ = storm(base, with_priority=False)
    finally:
        base.stop()

    # ---- survival: priorities + preemption + brownout --------------------
    srv = ContinuousLMServer(cfg, params, slots=slots,
                             page_size=ps, pages=pool_pages,
                             prefill_chunk=4, preempt=True,
                             brownout=True)
    compiles = []

    def listener(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    try:
        srv.warmup()
        jax.monitoring.register_event_duration_secs_listener(listener)
        try:
            lats, failed, shed_retries = storm(srv, with_priority=True)
        finally:
            jax.monitoring.clear_event_listeners()
        stats = srv.stats()
        with srv._cond:
            ledger = srv._pool.check_ledger()
            swap = srv._swap.stats()
    finally:
        srv.stop()

    ia_p99, base_ia_p99 = p99(lats["interactive"]), p99(
        base_lats["interactive"])
    br = stats.get("pressure", {}).get("brownout", {})
    transitions = int(br.get("transitions_up", 0)
                      + br.get("transitions_down", 0))
    swap_cap_ok = swap["peak_bytes"] <= swap["capacity_bytes"]
    meets = bool(
        failed["interactive"] == 0
        and ia_p99 is not None and base_ia_p99 is not None
        and ia_p99 < base_ia_p99
        and transitions >= 1
        and ledger["balanced"] and swap_cap_ok and not compiles)
    return {"metric": "TransformerLM overload-survival interactive p99 "
                      f"(mixed-priority storm, {demand_pages}-page "
                      f"demand on a {pool_pages}-page pool)",
            "unit": "ms", "value": ia_p99,
            "requests": len(requests),
            "demand_pages": demand_pages, "pool_pages": pool_pages,
            "demand_over_capacity": round(demand_pages / pool_pages, 2),
            **_mem_fields(params=params),
            "fifo_interactive_p99_ms": base_ia_p99,
            "interactive_p99_vs_fifo": (
                round(base_ia_p99 / ia_p99, 2)
                if ia_p99 and base_ia_p99 else None),
            "batch_p99_ms": p99(lats["batch"]),
            "best_effort_p99_ms": p99(lats["best_effort"]),
            "failed": dict(failed),
            "fifo_failed": dict(base_failed),
            "shed_retries": shed_retries,
            "preemptions": stats.get("preemptions", 0),
            "swap": stats.get("swap"),
            "swap_peak_bytes": swap["peak_bytes"],
            "swap_capacity_bytes": swap["capacity_bytes"],
            "brownout_level_final": br.get("level"),
            "brownout_transitions": transitions,
            "ledger_balanced": ledger["balanced"],
            "off_ladder_compiles": len(compiles),
            "meets_acceptance": meets,
            "note": "same pool sizing both legs; the survival leg adds "
                    "priorities, preemption with host swap-out, and "
                    "the brownout ladder — interactive latency is what "
                    "the plane exists to protect"}


def bench_tenants() -> dict:
    """Multi-tenant isolation row (ISSUE-16 acceptance): tenant A
    (interactive class, weight 4, generous quota, an SLO target) served
    twice by identically-sized pools with the SAME tenant registry:

    - baseline: A's request wave alone — its no-flood p99;
    - flood: tenant B (best_effort class, small token quota) floods at
      5x its quota via `chaos_tenant` while A runs the identical wave.

    Gates: A's flood-leg p99 within 1.5x its no-flood baseline (WFQ +
    quotas absorb the noisy neighbor), B actually throttled (429s
    observed AND admitted tokens bounded by bucket refill + burst), A
    never throttled, the per-tenant ledgers re-adding to the plane
    totals with the page ledger balanced, and zero off-ladder compiles
    — the flood must not push the pool onto new shapes."""
    import dataclasses
    import threading

    import jax
    import jax.monitoring

    from deeplearning4j_tpu.parallel import transformer as tfm
    from deeplearning4j_tpu.resilience.chaos import (
        TenantChaosConfig,
        chaos_tenant,
    )
    from deeplearning4j_tpu.serving import ContinuousLMServer

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        cfg = tfm.gpt2_small(max_len=256)
        ps, pool_pages, slots = 16, 24, 8
        a_threads, a_per_thread, plen, new = 4, 8, 8, 24
        b_rate = 160.0
    else:
        cfg = dataclasses.replace(
            tfm.gpt2_small(max_len=80), vocab_size=256, d_model=64,
            n_heads=4, n_layers=2, d_ff=256, dtype="float32",
            remat=False)
        ps, pool_pages, slots = 16, 12, 4
        a_threads, a_per_thread, plen, new = 3, 8, 8, 12
        b_rate = 40.0
    flood_cost = 8  # prompt 4 + max_new 4, the flood request's shape
    # burst = ONE flood request: the bucket throttles from the second
    # request on, so the 429 path fires even in a short smoke window
    tenants = {"team-a": {"weight": 4.0, "rate": 1e5, "slo_ms": 500.0},
               "team-b": {"weight": 1.0, "rate": b_rate,
                          "burst": float(flood_cost)}}
    rng = np.random.default_rng(0)
    prompts = [[rng.integers(0, cfg.vocab_size, (plen,)).tolist()
                for _ in range(a_per_thread)] for _ in range(a_threads)]
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))

    def a_wave(srv):
        """Tenant A's interactive wave: identical requests both legs,
        closed-loop from a_threads clients.  Returns (latencies,
        failed-count)."""
        lats: list = []
        failed = [0]
        lock = threading.Lock()

        def client(i):
            for prompt in prompts[i]:
                t0 = time.perf_counter()
                try:
                    srv.generate(list(prompt), new, timeout=600,
                                 priority="interactive",
                                 tenant="team-a")
                    with lock:
                        lats.append(time.perf_counter() - t0)
                except Exception:  # noqa: BLE001 — tallied as failed
                    with lock:
                        failed[0] += 1

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(a_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return lats, failed[0]

    def p99(xs):
        if not xs:
            return None
        return round(float(np.percentile(xs, 99)) * 1e3, 1)

    # Both legs run the wave ROUNDS times and keep each leg's best p99
    # (the disagg row's discipline): on a single-core smoke host one
    # scheduler hiccup lands a 24-sample p99 anywhere, and the gate is
    # about what the WFQ/quota plane can hold, not OS noise.
    rounds = 2

    def make_server():
        return ContinuousLMServer(cfg, params, slots=slots,
                                  page_size=ps, pages=pool_pages,
                                  prefill_chunk=4, tenants=tenants)

    # ---- baseline leg: tenant A alone ------------------------------------
    base = make_server()
    try:
        base.warmup()
        base_legs = [a_wave(base) for _ in range(rounds)]
        base_failed = sum(f for _, f in base_legs)
        base_p99 = min(p99(ls) for ls, _ in base_legs)
    finally:
        base.stop()

    # ---- flood leg: tenant B at 5x quota under tenant A's wave -----------
    srv = make_server()
    compiles: list = []

    def listener(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    try:
        srv.warmup()
        jax.monitoring.register_event_duration_secs_listener(listener)
        flood = chaos_tenant(srv, TenantChaosConfig(
            tenant="team-b", rate_multiple=5.0, prompt_tokens=4,
            max_new_tokens=4, priority="best_effort", threads=2,
            timeout_s=2.0))
        t_flood = time.perf_counter()
        flood_thread = threading.Thread(target=flood.run, args=(600.0,),
                                        daemon=True)
        flood_thread.start()
        try:
            time.sleep(0.1)  # the neighbor is already noisy at t0
            flood_legs = [a_wave(srv) for _ in range(rounds)]
            failed = sum(f for _, f in flood_legs)
            a_p99 = min(p99(ls) for ls, _ in flood_legs)
            # hold the flood for a minimum window: A's wave can finish
            # in well under a second on a small model, and the
            # throttled-to-quota gate needs enough refill cycles for
            # stable counts
            while time.perf_counter() - t_flood < 1.0:
                time.sleep(0.05)
        finally:
            flood.stop()
            flood_thread.join(timeout=30)
            flood_s = time.perf_counter() - t_flood
            jax.monitoring.clear_event_listeners()
        stats = srv.stats()
        with srv._cond:
            page_ledger = srv._pool.check_ledger()
    finally:
        srv.stop()

    fstats = flood.stats()
    tenancy = stats.get("tenancy", {})
    # per-tenant ledgers must re-add to the plane totals (the same
    # invariant check_fleet_ledger enforces fleet-wide)
    cells = stats.get("tenants", {})
    reconciled = all(
        sum(int(c.get(e) or 0) for c in cells.values())
        == int(stats.get(e) or 0)
        for e in ("requests", "rejected", "shed", "deadline_missed"))
    b_tokens_in = int(tenancy.get("team-b", {}).get("tokens_in") or 0)
    # admitted tokens bounded by what the bucket could have refilled:
    # burst + rate x window, with 1.5x slack + one request of slop
    b_quota_cap = 1.5 * (b_rate + b_rate * flood_s) + flood_cost
    a_throttled = int(tenancy.get("team-a", {}).get("throttled") or 0)
    meets = bool(
        failed == 0 and base_failed == 0
        and a_p99 is not None and base_p99 is not None
        and a_p99 <= 1.5 * base_p99
        and fstats["throttled"] > 0
        and b_tokens_in <= b_quota_cap
        and a_throttled == 0
        and reconciled and page_ledger["balanced"]
        and not compiles)
    return {"metric": "TransformerLM multi-tenant interactive p99 "
                      "(tenant-B best_effort flood at 5x quota)",
            "unit": "ms", "value": a_p99,
            "requests": a_threads * a_per_thread * rounds,
            "rounds": rounds,
            **_mem_fields(params=params),
            "no_flood_p99_ms": base_p99,
            "p99_vs_no_flood": (round(a_p99 / base_p99, 2)
                                if a_p99 and base_p99 else None),
            "a_failed": failed, "a_throttled": a_throttled,
            "flood": fstats, "flood_window_s": round(flood_s, 2),
            "flood_tokens_admitted": b_tokens_in,
            "flood_quota_cap_tokens": round(b_quota_cap, 1),
            "tenant_ledgers_reconciled": reconciled,
            "page_ledger_balanced": page_ledger["balanced"],
            "off_ladder_compiles": len(compiles),
            "meets_acceptance": meets,
            "note": "same pool sizing and registry both legs; the "
                    "flood leg adds only the noisy neighbor — WFQ "
                    "weights plus the token bucket are what keep "
                    "tenant A's p99 inside 1.5x of its quiet baseline"}


def bench_speculative() -> dict:
    """Speculative-decode row (ISSUE-13 acceptance): a shared-prefix
    greedy storm served by the PR-7 paged pool
    (speculate off — the baseline) vs the same pool with the FREE
    n-gram drafter (`speculate="ngram"`): each greedy lane proposes up
    to draft_len continuation tokens per round from its own history,
    the target verifies the chunk in ONE wide dispatch and commits the
    accepted prefix + its bonus token in-jit.

    Gates: per-lane decode cadence `tokens_per_dispatch` > 1.5 (the
    baseline is exactly 1.0 by construction), a tokens/s win over the
    paged baseline, BYTE-PARITY of every speculative output against
    whole-sequence `generate()` (the suite's standing discipline —
    draft quality must never touch correctness), and ZERO XLA compiles
    across the storm after warmup."""
    import dataclasses

    import jax
    import jax.monitoring

    from deeplearning4j_tpu.parallel import transformer as tfm
    from deeplearning4j_tpu.parallel.generation import generate
    from deeplearning4j_tpu.serving import ContinuousLMServer

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        cfg = tfm.gpt2_small(max_len=256)
        slots, n_req, new, sys_len, ps, chunk, dlen = 8, 16, 32, 128, 16, 8, 4
    else:
        # decode-dominant regime: small model, long greedy tails — the
        # per-dispatch cost is mostly width-independent (weights, page
        # gather, dispatch overhead), which is exactly the regime where
        # buying >1 token per dispatch converts to wall-clock
        cfg = dataclasses.replace(
            tfm.gpt2_small(max_len=160), vocab_size=256, d_model=64,
            n_heads=4, n_layers=1, d_ff=256, dtype="float32", remat=False)
        slots, n_req, new, sys_len, ps, chunk, dlen = 8, 16, 48, 48, 8, 4, 6
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    system = rng.integers(0, cfg.vocab_size, (sys_len,)).tolist()
    prompts = [system + rng.integers(0, cfg.vocab_size, (3,)).tolist()
               for _ in range(n_req)]
    conc = min(8, n_req)
    # the byte-parity sentinel: whole-sequence greedy ground truth
    want = {tuple(p): np.asarray(generate(
        cfg, params, np.asarray([p], np.int32), new))[0].tolist()
        for p in prompts}
    mismatches = []

    def storm(srv):
        def one(p):
            out = srv.generate(list(p), new, timeout=600)
            if out != want[tuple(p)]:
                mismatches.append(tuple(p))
        return min(_serving_storm(conc, prompts, one) for _ in range(2))

    def run_leg(speculate):
        srv = ContinuousLMServer(
            cfg, params, slots=slots, page_size=ps,
            prefill_chunk=chunk,
            **({"speculate": speculate, "draft_len": dlen}
               if speculate else {}))
        compiles = []

        def listener(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                compiles.append(event)

        try:
            srv.warmup()
            jax.monitoring.register_event_duration_secs_listener(listener)
            try:
                sec = storm(srv)
            finally:
                jax.monitoring.clear_event_listeners()
            stats = srv.stats()
            ledger = srv._pool.check_ledger()
        finally:
            srv.stop()
        return sec, stats, len(compiles), ledger

    sec_base, base_stats, base_compiles, _ = run_leg(None)
    sec_spec, spec_stats, spec_compiles, ledger = run_leg("ngram")

    toks = n_req * new
    speedup = round(sec_base / sec_spec, 2)
    tpd = spec_stats.get("tokens_per_decode_round", 0.0)
    accept = spec_stats.get("spec_accept_rate", 0.0)
    lat = spec_stats.get("latency", {})
    return {"metric": "TransformerLM speculative decode tokens/sec "
                      f"(n-gram drafter, shared {sys_len}-token prefix "
                      f"greedy storm, {slots} slots)",
            "unit": "tokens/sec", "value": round(toks / sec_spec, 1),
            "requests": n_req, "new_tokens": new,
            "prompt_len": sys_len + 3, "shared_prefix_tokens": sys_len,
            "page_size": ps, "prefill_chunk": chunk, "draft_len": dlen,
            **_mem_fields(params=params),
            "paged_baseline_tokens_per_sec": round(toks / sec_base, 1),
            "speculative_vs_paged": speedup,
            "tokens_per_dispatch": tpd,
            "baseline_tokens_per_dispatch":
                base_stats.get("tokens_per_decode_round", 1.0),
            "accept_rate": accept,
            "drafted": spec_stats.get("spec_drafted", 0),
            "accepted": spec_stats.get("spec_accepted", 0),
            "decode_rounds": spec_stats.get("decode_rounds", 0),
            "baseline_decode_rounds":
                base_stats.get("decode_rounds", 0),
            "byte_parity": not mismatches,
            "page_ledger_balanced": bool(ledger["balanced"]),
            "p50_ms": lat.get("p50_ms"), "p99_ms": lat.get("p99_ms"),
            "ttft_p50_ms": spec_stats.get("ttft", {}).get("p50_ms"),
            "ttft_p99_ms": spec_stats.get("ttft", {}).get("p99_ms"),
            "compiled_programs": spec_stats["compiled_programs"],
            "off_ladder_compiles": spec_compiles + base_compiles,
            "meets_acceptance": bool(
                tpd > 1.5 and speedup > 1.0 and not mismatches
                and ledger["balanced"] and not spec_compiles
                and not base_compiles),
            "note": "same pool, same storm, same greedy outputs — the "
                    "only change is how many committed tokens each "
                    "decode dispatch buys; the n-gram drafter is pure "
                    "host-side lookup (zero extra device programs)"}


def bench_disagg() -> dict:
    """Disaggregated serving row (ISSUE-14 acceptance): a mixed storm of
    long-prompt traffic (the compute-bound, bursty shape) and short
    chats (latency-bound) against TWO fleet topologies — 3
    undifferentiated `both` workers vs 1 prefill + 2 decode workers
    with KV page shipping.  The short chats stream over SSE through the
    router, so TTFT is measured CLIENT-side: time to the first `data:`
    event.  In the baseline every worker interleaves wide prefill-chunk
    dispatches with its decode rounds, so long prompts stall short
    chats' first tokens; disaggregation moves that work to the prefill
    worker and the decode workers' p99 TTFT drops.

    Gates: the kill leg (one prefill worker SIGKILL'd mid-storm)
    completes with failed == 0 (peer resubmission / recompute ladder);
    every output byte-identical to whole-sequence `generate()`; page
    ledger balanced on BOTH decode workers; zero off-ladder compiles
    after warmup; and — on TPU or multi-core hosts, where the prefill
    worker's compute actually runs concurrently with the decode
    workers' — disagg short-chat p99 TTFT beats the all-`both`
    baseline.  On a single-core host that last ratio is reported but
    not gated: every worker's dispatches serialize onto one execution
    unit, so the concurrency the split buys cannot manifest (see the
    ttft_gate field)."""
    import dataclasses
    import threading

    import jax
    import jax.monitoring

    from deeplearning4j_tpu.parallel import transformer as tfm
    from deeplearning4j_tpu.parallel.generation import generate
    from deeplearning4j_tpu.serving import FleetRouter, spawn_local_replica

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        cfg = tfm.gpt2_small(max_len=512)
        sys_len, tail, short_len = 256, 16, 6
        n_long, n_short, new_long, new_short = 12, 24, 16, 16
        slots, ps, chunk = 8, 16, 16
    else:
        # a model scale at which wide dispatches cost real
        # milliseconds, so prefill interference is measurable — the
        # regime the role split exists for
        cfg = dataclasses.replace(
            tfm.gpt2_small(max_len=160), vocab_size=256, d_model=128,
            n_heads=4, n_layers=2, d_ff=512, dtype="float32",
            remat=False)
        # moderate long pressure (~2 long prompts in flight): shorts
        # keep colliding with wide prefill dispatches on a `both`
        # worker without the single prefill worker saturating the host
        sys_len, tail, short_len = 88, 8, 4
        n_long, n_short, new_long, new_short = 16, 24, 24, 8
        slots, ps, chunk = 4, 16, 8
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    # DISTINCT long prompts: each conversation brings its own long
    # context, so in the all-`both` baseline the prefix-affinity hash
    # spreads them over every worker and every worker's decode loop
    # interleaves wide prefill chunks — exactly the interference tail
    # the role split removes (a shared system prompt would concentrate
    # on one worker and radix-cache away)
    long_prompts = [rng.integers(
        0, cfg.vocab_size, (sys_len + tail,)).tolist()
        for _ in range(n_long)]
    short_prompts = [rng.integers(0, cfg.vocab_size,
                                  (short_len,)).tolist()
                     for _ in range(n_short)]
    # byte-parity sentinels (compiled HERE, outside any compile count)
    want = {}
    for p in long_prompts:
        want[tuple(p)] = np.asarray(generate(
            cfg, params, np.asarray([p], np.int32),
            new_long))[0].tolist()
    for p in short_prompts:
        want[tuple(p)] = np.asarray(generate(
            cfg, params, np.asarray([p], np.int32),
            new_short))[0].tolist()

    def mk(name, role):
        # the all-`both` baseline is the CLASSIC fleet (no shipping):
        # role-differentiated workers ship implicitly, both-role ones
        # here must not — a baseline that spill-ships is not a baseline
        return spawn_local_replica(
            name, lm=(cfg, params), lm_slots=slots, lm_page_size=ps,
            lm_prefill_chunk=chunk, role=role)

    def storm(router, kill_after_longs=None, kill_replica=None):
        failed, mismatches, ttfts = [], [], []
        lock = threading.Lock()
        done_long = [0]

        def long_req(p):
            out = router.generate(list(p), new_long, timeout=600)
            if out != want[tuple(p)]:
                with lock:
                    mismatches.append(tuple(p))
            kill = False
            with lock:
                done_long[0] += 1
                if (kill_after_longs is not None
                        and done_long[0] == kill_after_longs):
                    kill = True
            if kill:
                kill_replica.kill()      # mid-storm prefill-worker death

        def short_req(p):
            # shorts are STICKY chat turns (one session per prompt):
            # real conversations pin to a replica, so in the baseline a
            # session whose replica is chewing a long prompt eats the
            # interference on every turn instead of dodging by load —
            # the tail shape the role split exists to fix
            t0 = time.perf_counter()
            resp = router.open_lm_stream(
                list(p), new_short, timeout=600,
                session_id=f"chat-{sum(p) % 1009}")
            first, buf = None, b""
            try:
                while True:
                    chunk_b = (resp.read1(4096)
                               if hasattr(resp, "read1")
                               else resp.read(4096))
                    if not chunk_b:
                        break
                    buf += chunk_b
                    if first is None and b"data: " in buf:
                        first = time.perf_counter() - t0
            finally:
                resp.close()
            done_ev = [e for e in buf.decode(errors="replace")
                       .split("\n\n") if e.startswith("event: done")]
            ids = (json.loads(done_ev[0].split("data: ", 1)[1])["ids"]
                   if done_ev else None)
            with lock:
                if ids != want[tuple(p)]:
                    mismatches.append(tuple(p))
                if first is not None:
                    ttfts.append(first * 1e3)

        def handler(item):
            tag, p = item
            try:
                (long_req if tag == "L" else short_req)(p)
            except Exception as e:  # noqa: BLE001 — the row COUNTS failures
                with lock:
                    failed.append(f"{tag}: {type(e).__name__}: {e}")

        # interleave long and short traffic across the client threads
        items, li, si = [], 0, 0
        while li < n_long or si < n_short:
            if li < n_long:
                items.append(("L", long_prompts[li]))
                li += 1
            if si < n_short:
                items.append(("S", short_prompts[si]))
                si += 1
            if si < n_short:
                items.append(("S", short_prompts[si]))
                si += 1
        compiles = []

        def listener(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                compiles.append(event)

        jax.monitoring.register_event_duration_secs_listener(listener)
        try:
            sec = _serving_storm(6, items, handler)
        finally:
            jax.monitoring.clear_event_listeners()
        return {"sec": sec, "failed": failed, "mismatches": mismatches,
                "ttfts": ttfts, "compiles": len(compiles)}

    def p99(ms):
        return round(float(np.percentile(ms, 99)), 1) if ms else None

    def p50(ms):
        return round(float(np.percentile(ms, 50)), 1) if ms else None

    def run_leg(roles):
        router = FleetRouter(disagg_min_prompt=sys_len // 2,
                             request_timeout_s=600)
        workers = [router.attach(mk(f"{role}-{i}", role))
                   for i, role in enumerate(roles)]
        try:
            out = storm(router)
            out["ships"] = router.ships
            out["ledgers"] = [
                r.server.state.lm_server._pool.check_ledger()
                for r in workers if r.role != "prefill"]
            out["stats"] = router.fleet_stats()
            out["pages_shipped"] = (out["stats"]["fleet"]
                                    .get("disagg", {})
                                    .get("pool_ship", {})
                                    .get("pages_shipped", 0))
        finally:
            router.stop()
        return out

    # 4 time-interleaved rounds (baseline storm, then disagg storm,
    # per round — alternating symmetrizes host-load drift on shared
    # CPUs).  Each topology's TTFT tail is its BEST round's p99: on a
    # contended single-core test host, thread-scheduling hiccups
    # (~5-15ms per hop) land on random rounds and inflate random
    # tails; the minimum over identically-shaped rounds is the
    # scheduling-noise-robust estimate of the tail each topology can
    # actually sustain, applied to BOTH sides.  Correctness/failure
    # counts accumulate across every storm.
    def best_round(rounds):
        out = min(rounds, key=lambda r: (p99(r["ttfts"]) or 1e9))
        out["failed"] = [f for leg in rounds for f in leg["failed"]]
        out["mismatches"] = [m for leg in rounds
                             for m in leg["mismatches"]]
        out["compiles"] = sum(leg["compiles"] for leg in rounds)
        out["ledgers"] = [lg for leg in rounds for lg in leg["ledgers"]]
        out["ships"] = sum(leg["ships"] for leg in rounds)
        # one accounting window for EVERY counter: pages sum across the
        # same rounds ships/compiles/failures do
        out["pages_shipped"] = sum(leg["pages_shipped"]
                                   for leg in rounds)
        return out

    base_rounds, dis_rounds = [], []
    for _ in range(3):
        base_rounds.append(run_leg(["both", "both", "both"]))
        dis_rounds.append(run_leg(["prefill", "decode", "decode"]))

    # ---- baseline vs 1 prefill + 2 decode (the TTFT measurement) ----------
    base = best_round(base_rounds)
    dis = best_round(dis_rounds)
    ships = dis["ships"]
    ledgers = dis["ledgers"]

    # ---- leg 3: disagg with the prefill worker SIGKILL'd mid-storm --------
    kill_router = FleetRouter(disagg_min_prompt=sys_len // 2,
                              request_timeout_s=600)
    pre0 = kill_router.attach(mk("prefill-0", "prefill"))
    kill_decodes = [kill_router.attach(mk(f"decode-{i}", "decode"))
                    for i in range(2)]
    try:
        kill = storm(kill_router, kill_after_longs=max(2, n_long // 4),
                     kill_replica=pre0)
        kill_fallbacks = kill_router.ship_fallbacks
        kill_ledgers = [r.server.state.lm_server._pool.check_ledger()
                        for r in kill_decodes]
    finally:
        kill_router.stop()

    # ---- leg 4: the cross-host shipping frame itself (ISSUE-19) -----------
    # quantized vs exact frame bytes and ship (serialize + deserialize)
    # latency for ONE real long-prompt export — the bytes a cross-host
    # hop actually moves, measured on the wire functions alone so the
    # number is host-count independent
    from deeplearning4j_tpu.serving import (
        ContinuousLMServer,
        deserialize_export,
        quantize_export,
        serialize_export,
    )

    ship_srv = ContinuousLMServer(cfg, params, slots=2,
                                  page_size=ps, prefill_chunk=chunk,
                                  ship=True)
    try:
        frame = ship_srv.prefill_export(long_prompts[0], new_long,
                                        timeout=600)
    finally:
        ship_srv.stop()

    def ship_ms(ex):
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            deserialize_export(serialize_export(ex))
            best = min(best, time.perf_counter() - t0)
        return round(best * 1e3, 3)

    frame_q = quantize_export(frame)
    ship_frame = {
        "prompt_tokens": len(long_prompts[0]),
        "exact_bytes": len(serialize_export(frame)),
        "quantized_bytes": len(serialize_export(frame_q)),
        "exact_ship_ms": ship_ms(frame),
        "quantized_ship_ms": ship_ms(frame_q)}
    ship_frame["bytes_ratio"] = round(
        ship_frame["quantized_bytes"] / ship_frame["exact_bytes"], 4)

    ttft_gain = (round(p99(base["ttfts"]) / p99(dis["ttfts"]), 2)
                 if base["ttfts"] and dis["ttfts"] else None)
    # The TTFT improvement gate presupposes what disaggregation buys:
    # a prefill worker whose compute runs CONCURRENTLY with the decode
    # workers'.  A single-core host serializes every worker's
    # dispatches onto one execution unit — total work is conserved, the
    # split's scheduling benefit physically cannot manifest, and the
    # shipping overhead (hashing + gather/install + a wire hop) is all
    # that remains measurable.  So the gate applies on TPU and
    # multi-core hosts; on a single core the ratio is REPORTED honestly
    # but not gated (every other gate — failed==0 under the kill, byte
    # parity, ledgers, zero compiles — holds everywhere).
    ttft_gated = bool(on_tpu or (os.cpu_count() or 1) >= 2)
    ttft_ok = (ttft_gain is not None and ttft_gain > 1.0
               if ttft_gated else True)
    toks = n_long * new_long + n_short * new_short
    failed_total = len(base["failed"]) + len(dis["failed"]) + len(
        kill["failed"])
    mismatch_total = (len(base["mismatches"]) + len(dis["mismatches"])
                      + len(kill["mismatches"]))
    ledgers_ok = all(lg["balanced"] for lg in ledgers + kill_ledgers)
    compile_total = base["compiles"] + dis["compiles"] + kill["compiles"]
    return {"metric": "Disaggregated LM serving short-chat p99 TTFT "
                      f"(mixed storm: {n_long} x {sys_len + tail}-token "
                      f"prompts + {n_short} short chats, 1 prefill + "
                      f"2 decode vs 3 both)",
            "unit": "ms", "value": p99(dis["ttfts"]),
            "long_prompts": n_long, "short_chats": n_short,
            "long_prompt_len": sys_len + tail,
            "short_prompt_len": short_len,
            "new_tokens": {"long": new_long, "short": new_short},
            "total_tokens": toks, "page_size": ps,
            "prefill_chunk": chunk, "slots_per_worker": slots,
            **_mem_fields(params=params),
            "ttft_p50_ms": p50(dis["ttfts"]),
            "ttft_p99_ms": p99(dis["ttfts"]),
            "baseline_ttft_p50_ms": p50(base["ttfts"]),
            "baseline_ttft_p99_ms": p99(base["ttfts"]),
            "ttft_p99_improvement": ttft_gain,
            "storm_sec": {"baseline": round(base["sec"], 2),
                          "disagg": round(dis["sec"], 2),
                          "kill": round(kill["sec"], 2)},
            "pages_shipped": dis["pages_shipped"],
            "ship_frame": ship_frame,
            "ships": ships, "kill_recompute_fallbacks": kill_fallbacks,
            "failed": failed_total,
            "failed_legs": {"baseline": len(base["failed"]),
                            "disagg": len(dis["failed"]),
                            "kill": len(kill["failed"])},
            "byte_parity": mismatch_total == 0,
            "page_ledger_balanced": ledgers_ok,
            "off_ladder_compiles": compile_total,
            "ttft_gate": ("p99 improvement > 1.0" if ttft_gated else
                          "reported, not gated: single-core host "
                          "serializes every worker's dispatches, so "
                          "the concurrency the split buys cannot "
                          "manifest"),
            "meets_acceptance": bool(
                ttft_ok and ships > 0
                and failed_total == 0 and mismatch_total == 0
                and ledgers_ok and compile_total == 0
                and kill["failed"] == []),
            "note": "TTFT measured client-side as time to the first "
                    "SSE data: event through the fleet front's "
                    "routing; the kill leg SIGKILLs the only prefill "
                    "worker mid-storm — remaining long prompts "
                    "recompute on the decode pool, zero failed "
                    "requests"}


def bench_hibernate() -> dict:
    """Tiered KV state hierarchy row (ISSUE-19 acceptance): N sticky
    sessions run one chat turn each, go idle past the hibernation
    deadline (the sweep parks their pages in the `TieredStateStore`,
    int8-quantized at rest), a host byte-cap sized for ~2.5 blobs
    FORCES the overflow down to the checksummed disk tier, and every
    remaining host entry is flushed so each turn-2 resume is COLD —
    manifest probe, SHA-256 verify, dequantize, page install.

    Gates: quantized at-rest bytes <= 0.3x exact; failed resumes == 0
    (every session installs from the store: no evictions, no
    corruption, `resumed == N`); disk spill actually happened (the
    host cap did its job); every turn-2 output byte-identical to an
    uninterrupted whole-sequence `generate()`; page ledger balanced;
    zero off-ladder compiles after warmup.  The row value is the
    median resume-to-first-token latency (stream-measured, the
    cold-resume cost a returning user actually feels)."""
    import dataclasses
    import tempfile

    import jax
    import jax.monitoring

    from deeplearning4j_tpu.parallel import transformer as tfm
    from deeplearning4j_tpu.parallel.generation import generate
    from deeplearning4j_tpu.serving import ContinuousLMServer
    from deeplearning4j_tpu.serving.transfer import (
        PageExport,
        quantize_export,
        serialize_export,
    )

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        cfg = tfm.gpt2_small(max_len=256)
        n_sessions, plen, new1, new2, ps = 16, 48, 24, 16, 16
        slots, pages = 8, 256
    else:
        cfg = dataclasses.replace(
            tfm.gpt2_small(max_len=96), vocab_size=256, d_model=64,
            n_heads=4, n_layers=2, d_ff=256, dtype="float32",
            remat=False)
        n_sessions, plen, new1, new2, ps = 8, 24, 16, 8, 8
        slots, pages = 4, 96
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (plen,)).tolist()
               for _ in range(n_sessions)]

    # size the host tier from a real quantized frame of the hibernated
    # shape (~2.5 blobs): the cap, not luck, forces the disk spill
    n_full = (plen + new1 - 1) // ps
    probe_shape = (cfg.n_layers, n_full, ps, cfg.n_heads,
                   cfg.d_model // cfg.n_heads)
    probe = PageExport(
        prompt=list(range(n_full * ps)), max_new=1, temperature=0.0,
        seed=0, committed=[], pos=n_full * ps, page_size=ps,
        pages_k=np.zeros(probe_shape, np.float32),
        pages_v=np.zeros(probe_shape, np.float32),
        model={"n_layers": cfg.n_layers})
    blob_est = len(serialize_export(quantize_export(probe)))
    host_cap = int(2.5 * blob_est)

    state_dir = tempfile.mkdtemp(prefix="bench-hibernate-")
    srv = ContinuousLMServer(cfg, params, slots=slots,
                             page_size=ps, pages=pages,
                             hibernate_idle_s=0.2, state_dir=state_dir,
                             swap_bytes=host_cap)
    compiles = []

    def listener(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    resume_ms, mismatches, failed = [], 0, []
    try:
        srv.warmup()
        turn1 = {}
        for i, p in enumerate(prompts):
            turn1[i] = srv.generate(p, new1, timeout=600,
                                    session_id=f"user-{i}")
        # idle past the deadline: the sweep hibernates every session
        deadline = time.perf_counter() + 60
        while time.perf_counter() < deadline:
            if (srv.stats().get("hibernate", {}).get("out", 0)
                    >= n_sessions):
                break
            time.sleep(0.05)
        mid = srv.stats()
        hibernated = mid.get("hibernate", {}).get("out", 0)
        with srv._cond:
            spills = srv._swap.spills
            # flush the survivors: EVERY resume below reads the disk
            srv._swap.flush_to_disk()
            disk_entries = len(srv._swap.disk)

        # byte-parity sentinels (compiled HERE, outside the compile
        # count — the whole-sequence oracle is not a serving program)
        turn2, want = {}, {}
        for i in range(n_sessions):
            turn2[i] = turn1[i] + [int(t) for t in
                                   rng.integers(0, cfg.vocab_size, (2,))]
            want[i] = np.asarray(generate(
                cfg, params, np.asarray([turn2[i]], np.int32),
                new2))[0].tolist()

        jax.monitoring.register_event_duration_secs_listener(listener)
        try:
            for i in range(n_sessions):
                t0 = time.perf_counter()
                toks, first = [], None
                try:
                    for t in srv.generate_stream(
                            turn2[i], new2, timeout=600,
                            session_id=f"user-{i}"):
                        if first is None:
                            first = time.perf_counter() - t0
                        toks.append(t)
                except Exception as e:  # noqa: BLE001 — the row COUNTS
                    failed.append(f"user-{i}: {type(e).__name__}: {e}")
                    continue
                resume_ms.append(first * 1e3)
                if turn2[i] + toks != want[i]:
                    mismatches += 1
        finally:
            jax.monitoring.clear_event_listeners()
        stats = srv.stats()
        with srv._cond:
            ledger = srv._pool.check_ledger()
    finally:
        srv.stop()

    hib = stats.get("hibernate", {})
    ratio = hib.get("bytes_ratio", 1.0)
    resumed = hib.get("in", 0)
    failed_resumes = (n_sessions - resumed + hib.get("evicted", 0)
                      + hib.get("corrupt", 0) + len(failed))
    med = (round(float(np.median(resume_ms)), 1) if resume_ms else None)
    return {"metric": f"Cold session resume to first token "
                      f"({n_sessions} sessions hibernated int8 to the "
                      f"disk tier under a {host_cap}-byte host cap)",
            "unit": "ms", "value": med,
            "sessions": n_sessions, "prompt_tokens": plen,
            "turn1_new_tokens": new1, "turn2_new_tokens": new2,
            "page_size": ps, "hibernated_pages_each": n_full,
            **_mem_fields(params=params),
            "resume_ms_p50": med,
            "resume_ms_p99": (round(float(np.percentile(
                resume_ms, 99)), 1) if resume_ms else None),
            "hibernated": hibernated, "resumed": resumed,
            "host_cap_bytes": host_cap,
            "host_spills_to_disk": spills,
            "disk_entries_at_resume": disk_entries,
            "at_rest_bytes": hib.get("bytes", 0),
            "exact_bytes": hib.get("exact_bytes", 0),
            "at_rest_bytes_ratio": ratio,
            "failed_resumes": failed_resumes,
            "byte_parity": mismatches == 0,
            "page_ledger_balanced": bool(ledger["balanced"]),
            "off_ladder_compiles": len(compiles),
            "meets_acceptance": bool(
                hibernated == n_sessions and resumed == n_sessions
                and failed_resumes == 0 and mismatches == 0
                and ratio <= 0.3 and spills > 0 and disk_entries > 0
                and ledger["balanced"] and not compiles),
            "note": "every resume is cold: the host tier is flushed "
                    "after hibernation, so turn 2 walks manifest probe "
                    "-> SHA-256 verify -> int8 dequantize -> page "
                    "install before its first token; byte parity is "
                    "against an uninterrupted whole-sequence "
                    "generate()"}


def bench_elastic() -> dict:
    """Elastic checkpoint plane row (ISSUE-12 acceptance): train on a
    4-replica DP mesh, save a SHARDED snapshot (4 shard files + SHA-256
    manifest), then restore it onto a 2-replica trainer.  Gates: the
    restored full tree (params AND updater moments) is bitwise-identical
    to the save; a flipped byte in a shard is DETECTED and the previous
    good step restores automatically.  The row value is the verified
    restore latency (checksum + join + adopt)."""
    import tempfile

    import jax

    from deeplearning4j_tpu.models import MultiLayerNetwork, iris_mlp
    from deeplearning4j_tpu.parallel import DataParallelTrainer, make_mesh
    from deeplearning4j_tpu.resilience import (
        ResilienceConfig,
        TrainingSupervisor,
        corrupt_checkpoint,
    )
    from deeplearning4j_tpu.runtime.checkpoint import (
        latest_checkpoint,
        load_checkpoint,
        read_ckpt_manifest,
    )
    from jax.flatten_util import ravel_pytree

    n_dev = len(jax.devices())
    n_from = min(4, n_dev)
    n_to = max(1, n_from // 2)
    rng = np.random.default_rng(0)
    y = rng.integers(0, 3, 64)
    x = (rng.normal(0, 0.3, (64, 4)).astype(np.float32) + y[:, None])
    yh = np.eye(3, dtype=np.float32)[y]
    ckdir = pathlib.Path(tempfile.mkdtemp(prefix="bench-elastic-"))

    net = MultiLayerNetwork(iris_mlp(updater="adam")).init()
    big = DataParallelTrainer(net, mesh=make_mesh(
        (n_from,), ("data",), devices=jax.devices()[:n_from]))
    sup = TrainingSupervisor(big, ResilienceConfig(
        checkpoint_dir=ckdir, checkpoint_every=100, min_history=100))
    for _ in range(5):
        big.fit_batch(x, yh)
    sup.step = 5
    t0 = time.perf_counter()
    sup.checkpoint(score=None)
    save_s = time.perf_counter() - t0
    saved_p = np.asarray(ravel_pytree(net.params)[0])
    saved_u = np.asarray(ravel_pytree(net.updater_state)[0])
    manifest = read_ckpt_manifest(latest_checkpoint(ckdir))

    net2 = MultiLayerNetwork(iris_mlp(updater="adam")).init()
    small = DataParallelTrainer(net2, mesh=make_mesh(
        (n_to,), ("data",), devices=jax.devices()[:n_to]))
    t0 = time.perf_counter()
    step = small.resume(ckdir)
    restore_s = time.perf_counter() - t0
    bitwise = bool(
        step == 5
        and np.array_equal(np.asarray(ravel_pytree(net2.params)[0]),
                           saved_p)
        and np.array_equal(
            np.asarray(ravel_pytree(net2.updater_state)[0]), saved_u))
    post_restore_loss = float(small.fit_batch(x, yh))

    # corruption gate: flip a byte in a shard of a NEWER step; restore
    # must detect it and land on the previous good step automatically
    small.fit_batch(x, yh)
    sup2 = TrainingSupervisor(small, ResilienceConfig(
        checkpoint_dir=ckdir, checkpoint_every=100, min_history=100))
    sup2.step = 7
    sup2.checkpoint(score=None)
    corrupt_checkpoint(ckdir / "ckpt-7")
    net3 = MultiLayerNetwork(iris_mlp(updater="adam")).init()
    try:
        got_step, _p, _u, _ = load_checkpoint(ckdir, net3.params)
        corruption_detected = got_step == 5
    except Exception:  # noqa: BLE001 — the row REPORTS the gate outcome
        corruption_detected = False

    return {"metric": f"elastic checkpoint: save sharded on {n_from} "
                      f"replicas, verified restore on {n_to}",
            "unit": "restore ms",
            "value": round(restore_s * 1e3, 2),
            "no_pin": True,  # host-IO latency: never regression-gated
            "save_ms": round(save_s * 1e3, 2),
            "replicas_saved": n_from, "replicas_restored": n_to,
            "shard_files": len(manifest["trees"]["params"]["files"]),
            "manifest_format": manifest["format"],
            "bitwise_identical": bitwise,
            "corruption_detected": corruption_detected,
            "post_restore_loss": round(post_restore_loss, 5),
            "model": "iris-mlp (adam; params + moments round-trip)",
            "meets_acceptance": bitwise and corruption_detected,
            "note": "sharded snapshot (per-replica shard files + "
                    "SHA-256 manifest, two-phase atomic commit) saved "
                    "on N replicas restores onto M bitwise-identically; "
                    "a flipped byte in any shard is detected and the "
                    "previous good step restores automatically"}


BENCHES = {
    "lenet": bench_lenet,
    "iris": bench_iris,
    "lstm": bench_lstm,
    "word2vec": bench_word2vec,
    "scaling": bench_scaling,
    "transformer": bench_transformer,
    "gpt2": bench_gpt2,
    "decode": bench_decode,
    "serving": bench_serving,
    "servinglm": bench_serving_lm,
    "servingoverload": bench_serving_overload,
    "servingfleet": bench_serving_fleet,
    "procfleet": bench_procfleet,
    "disagg": bench_disagg,
    "hibernate": bench_hibernate,
    "elastic": bench_elastic,
    "obs": bench_obs,
    "speculative": bench_speculative,
    "pressure": bench_pressure,
    "tenants": bench_tenants,
    "precision": bench_precision,
    "flashab": bench_flash_ab,
    "longctx": bench_longctx,
    "gpt2mem": bench_gpt2_mem,
}

# Rows that are explicit-only: too slow for the canonical suite's budget
# (gpt2mem steps a full 124M model, minutes per step on CPU).
EXPLICIT_ONLY = {"gpt2mem"}


# ---------------------------------------------------------------------------
# baseline pinning
# ---------------------------------------------------------------------------

def _load_pin_file() -> tuple:
    """Single source of truth for the .bench_baseline.json schema.

    Returns (pinned: metric -> {backend: value}, pin_hosts: metric ->
    {backend: cpu_count}).  Normalizes the two historical formats — the
    transitional single-slot {value, backend} entry and legacy bare
    numbers (backend unknown) — so no other reader re-implements this."""
    path = REPO / ".bench_baseline.json"
    pinned: dict = {}
    pin_hosts: dict = {}
    if path.exists():
        data = json.loads(path.read_text())
        for metric, entry in data.get("pinned", {}).items():
            if isinstance(entry, dict) and "value" in entry:
                # transitional single-slot {value, backend} format
                pinned[metric] = {entry.get("backend") or "unknown":
                                  entry["value"]}
            elif isinstance(entry, dict):
                pinned[metric] = dict(entry)  # backend -> value
            else:  # legacy bare number: backend unknown
                pinned[metric] = {"unknown": entry}
        pin_hosts = data.get("pin_hosts", {})
    return pinned, pin_hosts


def _apply_baselines(results: list, canonical: bool,
                     backend: str = None) -> None:
    """Pin per-(metric, backend) baselines and fill vs_baseline.

    Ratios are only ever computed within one backend: a CPU run never
    compares against a TPU pin or vice versa, and pins are keyed by
    backend, not overwritten on backend change.

    CPU pins are additionally host-fingerprinted (`pin_hosts`: metric ->
    backend -> os.cpu_count() at pin time): CPU throughput scales with
    host cores, so a pin from an N-core box is not a baseline for an
    M-core box.  Such rows report `vs_pin_other_host` instead of
    `vs_baseline` and are exempt from the regression gate.  (Discovered
    the hard way: a 1-core session read Word2Vec at 0.41x its pin from a
    multi-core session — 0.80x of it host size, the rest sibling-row
    contention on the one core.)  TPU rows are device-bound and never
    host-gated."""
    path = REPO / ".bench_baseline.json"
    pinned, pin_hosts = _load_pin_file()
    key = backend or "unknown"
    cpus = os.cpu_count()
    changed = False
    for r in results:
        if r.get("value") is None:
            r["vs_baseline"] = None
            continue
        if r.get("no_pin"):
            # Mechanical checks (e.g. the virtual-cpu DP plumbing row)
            # whose value is host-contention noise by design: never
            # pinned, never ratioed, never regression-guarded.
            r["vs_baseline"] = None
            continue
        per_backend = pinned.setdefault(r["metric"], {})
        if key not in per_backend and canonical:
            per_backend[key] = r["value"]
            pin_hosts.setdefault(r["metric"], {})[key] = cpus
            changed = True
        # No pin for this (metric, backend) -> honest None, never a
        # self-ratio of 1.0 pretending a baseline exists.
        base = per_backend.get(key)
        if base and key == "cpu":
            pin_cpus = pin_hosts.get(r["metric"], {}).get(key)
            # pin_cpus None = legacy pin (pre-fingerprint): compare as
            # before rather than inventing a host it was measured on.
            if pin_cpus is not None and pin_cpus != cpus:
                r["vs_baseline"] = None
                r["vs_pin_other_host"] = round(r["value"] / base, 3)
                r["pin_host_cpus"] = pin_cpus
                continue
        r["vs_baseline"] = round(r["value"] / base, 3) if base else None
    if changed:
        path.write_text(json.dumps(
            {"pinned": pinned, "pin_hosts": pin_hosts,
             "recorded": time.strftime("%Y-%m-%d")},
            indent=1))


# ---------------------------------------------------------------------------
# the suite
# ---------------------------------------------------------------------------

def run_suite() -> int:
    """Run the sub-benches in this process, streaming results as they
    complete.  Returns non-zero when any requested row raised (or a
    canonical run regressed without an annotation)."""
    import jax

    from deeplearning4j_tpu.runtime.device import (
        device_line,
        enable_compile_cache,
    )

    cache_dir = enable_compile_cache()
    print(f"bench: {device_line()} compile_cache={cache_dir}",
          file=sys.stderr, flush=True)
    backend = jax.default_backend()
    names = ONLY or [n for n in BENCHES if n not in EXPLICIT_ONLY]
    canonical = (BATCH == 256 and STEPS == 100 and not ONLY
                 and not os.environ.get("BENCH_NONCANONICAL"))
    # Only canonical runs may overwrite the results-of-record file; smoke
    # runs (BENCH_ONLY / small steps) write a sidecar instead.
    out_name = "BENCH_full.json" if canonical else "BENCH_smoke.json"
    results, record = [], None
    for name in names:
        print(f"bench {name}: start", file=sys.stderr, flush=True)
        t0 = time.perf_counter()
        try:
            r = BENCHES[name]()
        except Exception as e:  # noqa: BLE001 - one row must not end the suite; the exit code reports it
            r = {"metric": name, "value": None, "unit": None,
                 "error": f"{type(e).__name__}: {e}"}
        r["elapsed_s"] = round(time.perf_counter() - t0, 1)
        r.setdefault("backend", backend)
        r.setdefault("host_cpus", os.cpu_count())
        results.append(r)
        _apply_baselines(results, canonical, backend)
        print(json.dumps(r), file=sys.stderr, flush=True)
        try:  # progressive write to a SIDECAR: a dying run must not
            # clobber the last complete results-of-record
            (REPO / (out_name + ".partial")).write_text(
                json.dumps(results, indent=1))
        except OSError as e:
            print(f"bench: could not write {out_name}: {e}", file=sys.stderr)
        if record is None and (name == "lenet" or len(names) == 1
                               or "lenet" not in names):
            record = r
            print(json.dumps({k: record.get(k) for k in
                              ("metric", "value", "unit", "vs_baseline")}
                             | ({"error": record["error"]}
                                if "error" in record else {})), flush=True)
    # A canonical run with an unexplained >10% same-backend drop must not
    # silently become the results-of-record: demand an annotation
    # (BENCH_REGRESSION_NOTE) or leave the old record in place and park
    # the new rows in a .flagged sidecar for analysis.
    dropped = [r for r in results
               if r.get("vs_baseline") is not None and r["vs_baseline"] < 0.9]
    note = os.environ.get("BENCH_REGRESSION_NOTE")
    if canonical and dropped and not note:
        flagged = REPO / (out_name + ".flagged")
        try:
            (REPO / (out_name + ".partial")).replace(flagged)
        except OSError:
            pass
        for r in dropped:
            print(f"bench: REGRESSION {r['metric']}: vs_baseline="
                  f"{r['vs_baseline']} — record NOT overwritten; "
                  f"set BENCH_REGRESSION_NOTE='why' to accept, or re-pin",
                  file=sys.stderr, flush=True)
        print(f"bench: rows parked in {flagged.name}", file=sys.stderr)
        return 1
    if dropped and note:
        for r in dropped:
            r["regression_note"] = note
        try:
            (REPO / (out_name + ".partial")).write_text(
                json.dumps(results, indent=1))
        except OSError:
            pass
    try:  # suite completed: promote the sidecar to the record file
        (REPO / (out_name + ".partial")).replace(REPO / out_name)
    except OSError as e:
        print(f"bench: could not finalize {out_name}: {e}", file=sys.stderr)
    failed = [r["metric"] for r in results if "error" in r]
    for name in failed:
        print(f"bench: row {name} raised", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(run_suite())
