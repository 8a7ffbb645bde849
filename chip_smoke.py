#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, one TPU chip, the entry points a user calls, at the full width
of GPT-2-small (`parallel.transformer.gpt2_small(max_len=1024)`: 12 layers,
d=768, 12 heads x 64, vocab 50304, bf16, tied head, remat) with seeded random
weights:

  kernels  every Pallas kernel on the main path against its reference,
           before anything is timed: flash attention forward and backward
           against `attention()`, and the paged server's logits (kernel,
           bf16) against the gather oracle (float32, matmul precision
           "highest") through chunked prefill and a decode step
  serve    `UiServer.serve_lm(cfg, params)` with its defaults, `warmup()`,
           `start()`, then real HTTP `POST /lm/generate` requests: a prompt
           shorter than a chunk, one that straddles a page boundary, one of
           several hundred tokens, two that share a prefix ending mid-page
  train    `make_accum_train_step(cfg, accum=4, updater="adam")` on f32
           masters, global batch 8 x 1024: four steps, falling loss, first
           loss against a float32 reference

The decode, chunk and train programs must each hold a Mosaic custom call: the
proof that neither the Pallas interpreter nor a reference path served.  Any
phase that raises ends the run with a non-zero exit code; nothing is caught
and carried past.

    python3 chip_smoke.py          on a chip: exit 0, last stdout line
                                   {"ok": true, "device": {...}}
                                   no accelerator: exit 2, no result line
    python3 chip_smoke.py --tiny   the same phases at toy shapes on whatever
                                   device there is (Pallas interpreter off-TPU)
                                   — a rehearsal, NOT a chip result

Sizes come from `--tiny` alone, never from the backend.
"""

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time
import urllib.request

SEED = 0
MOSAIC_CALL = "tpu_custom_call"


@dataclasses.dataclass(frozen=True)
class Sizes:
    max_len: int          # model context
    batch: int            # global train batch
    accum: int
    prefill: int          # tokens prefilled in the logits check
    long_prompt: int      # the "several hundred tokens" request
    new_tokens: int


FULL = Sizes(max_len=1024, batch=8, accum=4, prefill=296, long_prompt=300,
             new_tokens=8)
TINY = Sizes(max_len=128, batch=4, accum=2, prefill=40, long_prompt=80,
             new_tokens=4)


@contextlib.contextmanager
def env(**kv):
    """Set the package's existing kernel switches for the duration of a
    trace (they are read at trace time)."""
    old = {k: os.environ.get(k) for k in kv}
    os.environ.update(kv)
    try:
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def peak_bytes():
    import jax

    return (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")


def require(ok, what) -> None:
    """A check that survives `python -O` (which strips `assert`)."""
    if not ok:
        raise AssertionError(what)


def cache_entries(cache_dir: str) -> int:
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def report(phase: str, **fields) -> None:
    print(f"chip_smoke {phase}: " + json.dumps(fields), flush=True)


def assert_mosaic(name: str, compiled_text: str, on_tpu: bool) -> None:
    if not on_tpu:
        print(f"chip_smoke: {name}: Mosaic check skipped off-TPU (the "
              f"kernels ran in the Pallas interpreter)", flush=True)
        return
    n = compiled_text.count(MOSAIC_CALL)
    require(n > 0, f"{name}: no {MOSAIC_CALL} in the compiled program")
    print(f"chip_smoke: {name}: {n} x {MOSAIC_CALL} in the compiled "
          f"program", flush=True)


def max_abs(a, b) -> float:
    import jax.numpy as jnp

    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def check_flash(cfg, sz: Sizes, on_tpu: bool) -> None:
    """Flash attention forward and backward against `attention()` at the
    train step's micro-batch shape.

    Tolerance: the kernel feeds the MXU bf16 operands (the probabilities
    among them), accumulates in f32 and stores bf16, so against an exact
    reference each output is off by up to half a bf16 ulp, 2^-9 * |x|,
    plus the rounding of the probabilities; one ulp of the largest
    reference value, 2^-7 * max|ref|, bounds both.  A wrong
    mask, a dropped block or a mis-scaled softmax is an error of the order
    of the output itself and fails it.
    """
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.parallel.kernels import flash_attention
    from deeplearning4j_tpu.parallel.ring_attention import attention

    shape = (sz.batch // sz.accum, sz.max_len, cfg.n_heads, cfg.head_dim)
    q, k, v, w = (jax.random.normal(kk, shape, jnp.bfloat16)
                  for kk in jax.random.split(jax.random.PRNGKey(SEED), 4))
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]

    fwd = jax.jit(lambda q, k, v: flash_attention(q, k, v, True))
    t0 = time.perf_counter()
    fwd = fwd.lower(q, k, v).compile()
    compile_s = time.perf_counter() - t0
    assert_mosaic("flash forward", fwd.as_text(), on_tpu)
    out = fwd(q, k, v)
    ref = jax.jit(lambda q, k, v: attention(q, k, v, True))(*f32)
    tol = 2.0 ** -7 * max(1.0, float(jnp.max(jnp.abs(ref))))
    err = max_abs(out, ref)
    require(bool(jnp.all(jnp.isfinite(out.astype(jnp.float32)))),
            "flash forward: non-finite output")
    require(err <= tol, f"flash forward: max|err| {err} > {tol}")

    def loss(attn):
        return lambda q, k, v: jnp.sum(
            attn(q, k, v).astype(jnp.float32) * w.astype(jnp.float32))

    bwd = jax.jit(jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, True)), (0, 1, 2)))
    t0 = time.perf_counter()
    bwd = bwd.lower(q, k, v).compile()
    compile_bwd_s = time.perf_counter() - t0
    assert_mosaic("flash backward", bwd.as_text(), on_tpu)
    got = bwd(q, k, v)
    want = jax.jit(jax.grad(loss(lambda q, k, v: attention(
        q, k, v, True)), (0, 1, 2)))(*f32)
    errs = {}
    for name, g, r in zip(("dq", "dk", "dv"), got, want):
        gtol = 2.0 ** -7 * max(1.0, float(jnp.max(jnp.abs(r))))
        errs[name] = {"max_err": round(max_abs(g, r), 5),
                      "tol": round(gtol, 5)}
        require(errs[name]["max_err"] <= gtol, f"flash {name}: {errs[name]}")
    report("kernels.flash", shape=list(shape), compile_s=round(compile_s, 2),
           compile_bwd_s=round(compile_bwd_s, 2),
           fwd={"max_err": round(err, 5), "tol": round(tol, 5)}, **errs)


# bf16 against float32 through the whole model: every matmul input and
# every stored k/v is rounded to 8 significant bits, and the error random-
# walks through the layers into logits of unit scale.  The bound is
# loose by design of the comparison — a wrong page, mask or position is an
# O(1) error on every logit and fails it; so would computing in fp8.
LOGIT_TOL = 0.25
# the kernel against the gather oracle at the SAME precision (both bf16):
# what is left is f32-vs-bf16 softmax arithmetic inside one attention call
LOGIT_TOL_SAME_DTYPE = 0.125


def check_paged_logits(cfg, params, sz: Sizes, page_size: int, chunk: int,
                       slots: int) -> None:
    """The served configuration's logits — paged kernel, bf16 — against
    the gather oracle in float32 under matmul precision "highest", through
    chunked prefill (crossing page boundaries) and one decode step, at
    the server's own page size, chunk width and lane count."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.parallel.generation import (
        init_paged_cache,
        paged_forward,
        pages_per_seq,
    )
    from deeplearning4j_tpu.parallel.hybrid import _master_f32

    mp = pages_per_seq(cfg, page_size)
    pages = slots * mp + 1
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    params32 = _master_f32(params)
    rng = np.random.default_rng(SEED)
    # lane b owns pages [1 + b*mp, 1 + (b+1)*mp), shuffled: logical order
    # is not physical order
    table = np.stack([1 + b * mp + rng.permutation(mp)
                      for b in range(slots)]).astype(np.int32)
    tokens = rng.integers(0, cfg.vocab_size, (slots, sz.prefill + 1))

    def forward(c, kernel):
        def run(p, cache, pos, n_feed, tok):
            return paged_forward(c, p, cache, table, pos, n_feed, tok,
                                 paged_kernel=kernel)
        return jax.jit(run, donate_argnums=(1,))

    served = forward(cfg, True)                    # widths chunk and 1
    gather = forward(cfg, False)                   # width chunk only
    oracle = forward(cfg32, False)                 # width chunk only
    caches = {name: init_paged_cache(c, pages, page_size)
              for name, c in (("served", cfg), ("gather", cfg),
                              ("oracle", cfg32))}
    worst = {"oracle": 0.0, "gather": 0.0}
    agree = total = 0

    def step(pos, n_feed, tok_served, tok_wide):
        nonlocal agree, total
        pos = jnp.full((slots,), pos, jnp.int32)
        nf = jnp.full((slots,), n_feed, jnp.int32)
        got, caches["served"] = served(params, caches["served"], pos, nf,
                                       tok_served)
        same, caches["gather"] = gather(params, caches["gather"], pos, nf,
                                        tok_wide)
        with jax.default_matmul_precision("highest"):
            want, caches["oracle"] = oracle(params32, caches["oracle"],
                                            pos, nf, tok_wide)
        got = got[:, :n_feed].astype(jnp.float32)
        require(bool(jnp.all(jnp.isfinite(got))), "non-finite logits")
        worst["oracle"] = max(worst["oracle"],
                              max_abs(got, want[:, :n_feed]))
        worst["gather"] = max(worst["gather"],
                              max_abs(got, same[:, :n_feed]))
        agree += int(jnp.sum(jnp.argmax(got, -1)
                             == jnp.argmax(want[:, :n_feed], -1)))
        total += slots * n_feed

    t0 = time.perf_counter()
    for start in range(0, sz.prefill, chunk):
        tok = jnp.asarray(tokens[:, start:start + chunk], jnp.int32)
        step(start, chunk, tok, tok)
    last = jnp.asarray(tokens[:, sz.prefill:], jnp.int32)   # [slots, 1]
    step(sz.prefill, 1, last, jnp.pad(last, ((0, 0), (0, chunk - 1))))
    wall_s = time.perf_counter() - t0
    report("kernels.paged", prefill_tokens=sz.prefill, chunk=chunk,
           page_size=page_size, lanes=slots,
           max_logit_err_vs_f32_oracle=round(worst["oracle"], 4),
           tol=LOGIT_TOL,
           max_logit_err_vs_bf16_gather=round(worst["gather"], 4),
           tol_same_dtype=LOGIT_TOL_SAME_DTYPE,
           argmax_agreement=f"{agree}/{total}",
           wall_incl_compile_s=round(wall_s, 1))
    require(worst["oracle"] <= LOGIT_TOL, worst)
    require(worst["gather"] <= LOGIT_TOL_SAME_DTYPE, worst)


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def post(url: str, body: dict):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=300) as resp:
        return resp.status, json.loads(resp.read())


def serve_phase(cfg, params, sz: Sizes, on_tpu: bool, cache_dir: str):
    import jax
    import numpy as np

    from deeplearning4j_tpu.parallel.generation import (
        init_paged_cache,
        make_paged_step,
    )
    from deeplearning4j_tpu.ui import UiServer

    srv = UiServer(port=0)
    srv.serve_lm(cfg, params)      # defaults: paged KV, kernel by policy
    lm = srv.state.lm_server
    try:
        check_paged_logits(cfg, params, sz, lm.page_size, lm.prefill_chunk,
                           lm.n_slots)
        t0 = time.perf_counter()
        warmed = lm.warmup()       # raises if a program fails to compile
        compile_s = time.perf_counter() - t0
        require(warmed == lm.compiled_programs() > 0, warmed)
        srv.start()
        compiles_warm = lm.stats()["compiles_total"]

        rng = np.random.default_rng(SEED + 1)

        def prompt(n):
            return rng.integers(0, cfg.vocab_size, n).tolist()

        ps, chunk = lm.page_size, lm.prefill_chunk
        shared = prompt(2 * ps + ps // 2)          # ends mid-page
        prompts = {
            "shorter_than_chunk": prompt(chunk - 5),
            "straddles_page": prompt(ps - 2),      # decode crosses it
            "long": prompt(sz.long_prompt),
            "prefix_a": shared + prompt(ps // 2),  # 3 whole pages cached
            "prefix_b": shared + prompt(ps),       # diverges mid-page 3
        }
        latencies = {}
        for name, ids in prompts.items():
            t0 = time.perf_counter()
            status, body = post(srv.url + "/lm/generate",
                                {"prompt_ids": ids,
                                 "max_new_tokens": sz.new_tokens})
            latencies[name] = round(time.perf_counter() - t0, 3)
            require(status == 200, (name, status, body))
            out = body["ids"]
            require(out[:len(ids)] == ids, name)
            require(len(out) == len(ids) + sz.new_tokens, (name, len(out)))
            require(all(0 <= t < cfg.vocab_size for t in out), name)
        # steady decode: one lane, 64 new tokens, ends when the response
        # (whose tokens the worker synced from the device) arrives
        n_new = min(64, cfg.max_len - chunk)
        t0 = time.perf_counter()
        status, body = post(srv.url + "/lm/generate",
                            {"prompt_ids": prompt(chunk - 5),
                             "max_new_tokens": n_new})
        decode_s = time.perf_counter() - t0
        require(status == 200, body)

        with urllib.request.urlopen(srv.url + "/serving/stats",
                                    timeout=30) as resp:
            stats = json.loads(resp.read())["lm"]
        require(stats["kv"]["paged_kernel"] is True, stats["kv"])
        require(stats["compiles_total"] == compiles_warm,
                f"compiled after warm-up: {stats['compiles_total']} != "
                f"{compiles_warm}")
        require(stats["prefix_hits"] >= 1, stats)
        # a match that ends mid-page is what schedules the CoW page copy
        require(stats["prefix_tokens_saved"] % ps, stats)
        total_pages = lm.kv_pages + 1
    finally:
        srv.stop()

    # the programs the server dispatched, from its own program factory
    # (same lru-cached jit objects; the persistent cache makes this cheap)
    k = jax.eval_shape(
        lambda: init_paged_cache(cfg, total_pages, ps))["k"]
    lanes = lm.n_slots
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, np.int32)  # noqa: E731
    for name, width in (("decode step", 1), ("chunk step", chunk)):
        program = make_paged_step(cfg, total_pages, ps, width,
                                  paged_kernel=lm.paged_kernel)
        text = program.lower(
            params, k, k, i32(lanes, lm.max_pages), i32(lanes), i32(lanes),
            i32(lanes, width), jax.ShapeDtypeStruct((lanes,), np.float32),
            i32(lanes), i32(lanes)).compile().as_text()
        assert_mosaic(name, text, on_tpu)
    report("serve", programs_warm=warmed, compile_s=round(compile_s, 1),
           request_s=latencies,
           decode_ms_per_token=round(decode_s / n_new * 1e3, 2),
           decode_tokens=n_new, kv=stats["kv"],
           compiles_after_warmup=stats["compiles_total"] - compiles_warm,
           prefix_hits=stats["prefix_hits"],
           prefix_tokens_saved=stats["prefix_tokens_saved"],
           peak_bytes_in_use=peak_bytes(), compile_cache=cache_dir,
           cache_entries=cache_entries(cache_dir))


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

# the step's bf16-compute loss against the float32 loss of the same
# parameters and batch: per-logit bf16 error averages over batch x seq
# tokens, leaving well under 1e-2 on a loss of ln(vocab) ~ 10.8
LOSS_TOL = 2e-2


def train_phase(cfg, sz: Sizes, on_tpu: bool, cache_dir: str):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.parallel import transformer as tfm
    from deeplearning4j_tpu.parallel.hybrid import (
        _master_f32,
        make_accum_train_step,
    )

    step, init_state = make_accum_train_step(cfg, lr=1e-3, accum=sz.accum,
                                             updater="adam")
    params = _master_f32(tfm.init_params(cfg, jax.random.PRNGKey(SEED)))
    opt = init_state(params)
    seq = np.random.default_rng(SEED).integers(
        0, cfg.vocab_size, (sz.batch, sz.max_len + 1)).astype(np.int32)
    tokens, targets = jax.device_put((seq[:, :-1], seq[:, 1:]))

    # float32 reference loss at the initial parameters, micro-batch by
    # micro-batch, XLA attention (the flash kernel switched off)
    cfg32 = dataclasses.replace(cfg, dtype="float32", remat=False)
    with env(DL4J_TPU_FLASH="0"), jax.default_matmul_precision("highest"):
        ref_fn = jax.jit(lambda p, t, g: tfm.lm_loss(cfg32, p, t, g))
        mb = sz.batch // sz.accum
        ref = float(np.mean([
            float(ref_fn(params, tokens[i:i + mb], targets[i:i + mb]))
            for i in range(0, sz.batch, mb)]))

    t0 = time.perf_counter()
    params, opt, loss = step(params, opt, tokens, targets)
    losses = [float(loss)]
    compile_s = time.perf_counter() - t0
    text = step.lower(params, opt, tokens, targets).compile().as_text()
    assert_mosaic("train step", text, on_tpu)
    t0 = time.perf_counter()
    for _ in range(3):
        params, opt, loss = step(params, opt, tokens, targets)
        losses.append(loss)
    jax.block_until_ready(loss)
    step_s = (time.perf_counter() - t0) / 3
    losses = [float(v) for v in losses]
    require(all(np.isfinite(losses)), losses)
    require(losses[2] < losses[0] and losses[3] < losses[0], losses)
    require(abs(losses[0] - ref) <= LOSS_TOL, (losses[0], ref))
    require(all(bool(jnp.all(jnp.isfinite(leaf)))
                for leaf in jax.tree_util.tree_leaves(params)),
            "non-finite parameters after training")
    report("train", batch=sz.batch, seq=sz.max_len, accum=sz.accum,
           losses=[round(v, 4) for v in losses],
           f32_reference_loss=round(ref, 4), loss_tol=LOSS_TOL,
           first_step_incl_compile_s=round(compile_s, 1),
           step_s=round(step_s, 4),
           tokens_per_s=round(sz.batch * sz.max_len / step_s),
           peak_bytes_in_use=peak_bytes(), compile_cache=cache_dir,
           cache_entries=cache_entries(cache_dir))


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="rehearse the same phases at toy shapes on "
                         "whatever device there is; not a chip result")
    args = ap.parse_args(argv)

    import jax

    from deeplearning4j_tpu.parallel import transformer as tfm
    from deeplearning4j_tpu.runtime.device import (
        device_line,
        enable_compile_cache,
    )

    cache_dir = enable_compile_cache()
    print(f"chip_smoke: {device_line()}", flush=True)
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.tiny:
        print(f"chip_smoke: needs a TPU, found platform={dev.platform}; "
              f"`--tiny` rehearses the phases off-chip", file=sys.stderr)
        return 2
    t_start = time.perf_counter()
    if args.tiny:
        print("chip_smoke: --tiny rehearsal at toy shapes: NOT A CHIP "
              "RESULT", flush=True)
        sz = TINY
        cfg = dataclasses.replace(
            tfm.gpt2_small(max_len=sz.max_len), vocab_size=512, d_model=64,
            n_heads=4, n_layers=2, d_ff=256)
    else:
        sz = FULL
        cfg = tfm.gpt2_small(max_len=sz.max_len)
    # off-TPU the kernel rules give the reference paths: switch the
    # kernels on so the rehearsal runs the code the chip will (flash by
    # its env name; the paged kernel's rule is the platform alone, so
    # the rehearsal stands in for it)
    forced = {}
    if not on_tpu:
        from deeplearning4j_tpu.parallel import paged_kernel

        forced = {"DL4J_TPU_FLASH": "1"}
        paged_kernel.paged_kernel_enabled = lambda: True
    with env(**forced):
        params = tfm.init_params(cfg, jax.random.PRNGKey(SEED))
        check_flash(cfg, sz, on_tpu)
        serve_phase(cfg, params, sz, on_tpu, cache_dir)
        del params
        train_phase(cfg, sz, on_tpu, cache_dir)
    print(f"chip_smoke: all phases passed in "
          f"{time.perf_counter() - t_start:.0f} s", flush=True)
    result = {"ok": True,
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(jax.devices())}}
    if args.tiny:
        result["chip_result"] = False
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
