"""Configurations of kind `serve`: the model behind the program's
`UiServer.serve_lm`, driven in process through
`lm_server.generate_stream`, under the mix's schedule.

Only capacity (`serve.slots` in the configuration's file) is set; every
other tunable of the server stays at the program's default, so that a later
PR moves it in the program and not in a pinned file.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmark import device, generators, load, spec, trace_reduce
from benchmark.observe import Run, say

TRACE_AFTER_S = 3.0       # into the window, once it is steady
TRACE_FOR_S = 4.0


def run(cell, args, t_start, devices):
    import jax

    from deeplearning4j_tpu.obs.compilewatch import compile_watcher
    from deeplearning4j_tpu.ui import UiServer

    config = cell.config
    phases = {"imports_and_device": time.perf_counter() - t_start}
    mark = time.perf_counter()
    adapter = spec.adapter(config)
    cfg = adapter.program_config(config, config["dtype"], remat=False)
    params = jax.block_until_ready(
        adapter.make_params(cfg, args.seed, config["dtype"]))
    phases["weights"] = time.perf_counter() - mark
    schedule = generators.build(cell.traffic, args.seed, args.seconds,
                                cfg.vocab_size, cfg.max_len)
    run_ = Run(cell=cell, chips=len(devices), model=cfg,
               peaks=None if args.tiny else device.peaks(
                   devices[0].device_kind))
    srv = UiServer(port=0)
    srv.serve_lm(cfg, params, **config["serve"])
    srv.start()
    lm = srv.state.lm_server
    watch = compile_watcher()
    seen, compiles = {}, {}
    capture, capturing = trace_reduce.Capture(), {}

    def read_traces():
        for tr in srv.tracer.recent():
            seen[tr["request_id"]] = tr

    def at_start():
        run_.counters["before"] = lm.stats()
        compiles["before"] = watch.total()

    def while_open():
        read_traces()
        if not args.trace:
            return
        since = time.perf_counter() - offer.t0
        if not capture.started:
            if since >= min(TRACE_AFTER_S, args.seconds / 4):
                capture.start()
                capturing["until"] = since + min(TRACE_FOR_S,
                                                 args.seconds / 2)
        elif since >= capturing["until"]:
            capture.stop()

    def at_end():
        run_.counters["after"] = lm.stats()
        compiles["after"] = watch.total()

    try:
        mark = time.perf_counter()
        warmed = lm.warmup()
        phases["warmup"] = time.perf_counter() - mark
        phases["preroll"] = schedule.preroll_s
        offer = load.Offer(lm, schedule, args.seconds)
        offer.run(at_start, while_open, at_end)
        read_traces()
        lanes, pages = lm.n_slots, lm.kv_pages
    finally:
        capture.stop()
        srv.stop()
    run_.t0, run_.t_end = offer.t0, offer.t_end
    run_.setup_s = offer.t0 - t_start
    run_.requests = sorted(offer.requests, key=lambda r: r.due)
    run_.lateness_s = offer.lateness_s
    run_.traces = [t for t in seen.values() if run_.in_window(t["t0_s"])]
    memory_peak = device.memory_peak_bytes(devices)
    run_.device_trace = capture.reduction()
    issued = run_.issued_in_window()
    failed = [r for r in issued if r.status == "failed"]
    say("setup", seconds=run_.setup_s, **phases)
    say("served", lanes=lanes, pages=pages, programs_warm=warmed,
        issued_in_window=len(issued), failed=len(failed),
        cancelled=sum(r.status == "cancelled" for r in run_.requests),
        preroll=sum(r.due < run_.t0 for r in run_.requests),
        # how much of the pool behind the peak below holds live tokens
        pages_in_use={k: v.get("kv", {}).get("pages_in_use")
                      for k, v in run_.counters.items()},
        first_failures=[r.error for r in failed[:3]],
        generator_late_ms={"median": 1e3 * float(np.median(run_.lateness_s)),
                           "max": 1e3 * float(np.max(run_.lateness_s))},
        memory_peak_bytes=memory_peak)

    # the program's state goes before the reference's is made
    del lm, srv, offer
    gc.collect()
    compiled_in_window = compiles["after"] - compiles["before"]
    checks = [("compiles_in_window", compiled_in_window, 0)]
    checks += check_answers(run_.requests, cfg.vocab_size)
    longest_answer = max(t.max_new for s in schedule.sessions
                         for t in s.turns)
    checks += check_against_reference(config, cfg, params, run_.requests,
                                      args.seed, longest_answer,
                                      getattr(args, "control", None))
    return run_, checks, len(issued), len(failed), memory_peak


def check_answers(requests, vocab_size):
    """Every finished answer has the asked number of tokens, ids in range."""
    finished = [r for r in requests if r.status == "ok"]
    bad = sum(len(r.tokens) != r.asked
              or not all(0 <= t < vocab_size for t in r.tokens)
              for r in finished)
    return [("malformed_answers", bad, 0),
            ("finished_answers_missing", int(not finished), 0)]


def sample_finished(requests, seed, k):
    """k finished requests drawn from the seed, the longest among them."""
    finished = [r for r in requests if r.status == "ok"]
    if not finished:
        return []
    longest = max(finished, key=lambda r: len(r.prompt) + len(r.tokens))
    rest = [r for r in finished if r is not longest]
    rng = np.random.default_rng([abs(int(seed)), 3])
    picks = rng.permutation(len(rest))[:max(0, k - 1)]
    return [longest] + [rest[i] for i in picks]


def served_positions(sample, max_len, n_pad):
    """The sampled sequences as one padded batch, and where each served
    token was predicted: after position `len(prompt) + i - 1` of its row."""
    tokens = np.zeros((len(sample), max_len), np.int32)
    rows, cols, served = [], [], []
    for k, r in enumerate(sample):
        seq = r.prompt + r.tokens
        tokens[k, :len(seq) - 1] = seq[:-1]
        rows += [k] * len(r.tokens)
        cols += [len(r.prompt) - 1 + i for i in range(len(r.tokens))]
        served += r.tokens
    n = len(served)
    pad = [0] * (n_pad - n)
    return (tokens, np.array(rows + pad, np.int32),
            np.array(cols + pad, np.int32), np.array(served, np.int32), n)


def served_gaps(config, params, sample, max_len, longest_answer,
                quant=None):
    """For each served token of the sample, how far the reference's logit of
    it lies below the reference's best at that position.  With `quant` the
    token is not the served one but the one the reference, computed in that
    lower precision over the same inputs, puts first: the control."""
    import jax.numpy as jnp

    reference = spec.reference(config)
    # one shape for every seed: the mix's longest answer, not the sample's
    n_pad = len(sample) * longest_answer
    tokens, rows, cols, served, n = served_positions(sample, max_len, n_pad)
    stacked = reference.stack(params)
    eps = config["layer_norm_epsilon"]
    logits = reference.served_logits(stacked, tokens, rows, cols, eps)[:n]
    if quant is not None:
        low = reference.served_logits(stacked, tokens, rows, cols, eps,
                                      quant)[:n]
        served = jnp.argmax(low, axis=-1)
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, jnp.asarray(served)[:, None], -1)[:, 0]
    return np.asarray(best - got, np.float64)


def check_against_reference(config, cfg, params, requests, seed,
                            longest_answer, control=None):
    check = config["check"]
    sample = sample_finished(requests, seed, check["requests"])
    if not sample:
        return []
    t = time.perf_counter()
    gaps = served_gaps(config, params, sample, cfg.max_len, longest_answer)
    say("reference", requests=len(sample), served_tokens=len(gaps),
        longest=len(sample[0].prompt) + len(sample[0].tokens),
        tokens_off_the_reference_best=int(np.sum(gaps > 0)),
        mean_gap=float(np.mean(gaps)), seconds=time.perf_counter() - t)
    if control:     # `tools/control.py`: the reference in a lower precision
        for precision in control.split(","):
            low = served_gaps(config, params, sample, cfg.max_len,
                              longest_answer, quant=precision)
            say("control", seed=seed, precision=precision,
                served_logit_gap_max=float(np.max(low)),
                served_logit_gap_mean=float(np.mean(low)),
                tokens_off_the_reference_best=int(np.sum(low > 0)))
    return [("served_logit_gap_max", float(np.max(gaps)),
             check["served_logit_gap_max"])]
