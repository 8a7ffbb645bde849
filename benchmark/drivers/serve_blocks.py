"""Configurations of kind `serve_blocks`: a block-diffusion model behind the
program's `UiServer.serve_lm`, driven as `drivers/serve.py` drives a causal
one (its `run`, its window, its trace, its counters: that file's functions,
by import) and judged by a comparison of its own.

`drivers/serve.py` places served token `i` after position `len(prompt) + i -
1` and hands the reference the sequence without its last token.  A block
model's token was predicted AT its own position, in the state its block was
in when it was unmasked, so `correct` here REPLAYS THE DENOISING: for each
sampled request the reference (`reference/<family>.py`: `replay_rows`,
`state_logits`, `choices`) computes, in one forward, the logits the
program's denoise rounds saw, from the tokens the request was served and the
step at which each was unmasked.  The server records those steps on the
request and puts them on its `decode` span (`unmask_steps`, and what the
last block held past the answer's end); this file's offer gives every request
an id to find its trace by.

Two numbers over the sampled requests:

- `served_logit_gap_max`: over every served token, how far its reference
  logit, at the state in which it was unmasked, lies below the reference's
  best there (the mask id's logit left out); the largest;
- `unmask_steps_off_share`: the share of denoise steps at which the columns
  the program unmasked are not the ones the reference would have: at every
  step the reference's confidence of the best column the program left masked
  minus that of the worst it unmasked, in nats of log-confidence (a
  confidence here is about 3e-4, one token of 151,936 under random weights,
  so the difference of two is a number of no scale; the difference of their
  logarithms is the difference of the two columns' `top logit - logsumexp`),
  is 0 where the program chose as the reference would, and the share counts
  the steps where it is not.  Under seeded weights a block's masked columns
  are near-copies (the mask id's embedding, one history, another rotation),
  so rounding flips a near-tie at a step in ten and the LARGEST such gap is
  the tail of that rounding, which a wrong rule does not pass (PERF.md
  section 2); the share is what a wrong rule moves by a multiple.  The
  largest and the mean gap are printed beside it and held to nothing.

With `control` (`tools/control.py`) the choices are not the program's but
those the reference makes when computed in that lower precision over the same
states: which token it puts first at each column it would unmask, and which
columns it would unmask.
"""

from __future__ import annotations

import threading
import time
from unittest import mock

import numpy as np

from benchmark import load, spec
from benchmark.drivers import serve
from benchmark.observe import say


_CAUSAL_ANSWERS = serve.check_answers     # `run` puts this file's in its place


class _WithIds:
    """The served model as `load.Offer` calls it, each stream under the
    request id its thread has set."""

    def __init__(self, lm, local):
        self._lm, self._local = lm, local

    def __getattr__(self, name):
        return getattr(self._lm, name)

    def generate_stream(self, prompt, asked, timeout=None):
        return self._lm.generate_stream(prompt, asked, timeout=timeout,
                                        request_id=self._local.request_id)


class Offer(load.Offer):
    """`load.Offer` whose requests carry an id; a finished request keeps its
    `decode` span's attributes as `req.decode`."""

    def __init__(self, lm, schedule, seconds):
        self._local = threading.local()
        super().__init__(_WithIds(lm, self._local), schedule, seconds)

    def _ask(self, req):
        rid = f"bench-{req.session}-{req.turn}"
        self._local.request_id = rid
        super()._ask(req)
        req.decode = None
        for tr in self.lm.tracer.find(rid):
            for span in tr["spans"]:
                if span["name"] == "decode":
                    req.decode = span["attrs"]


def run(cell, args, t_start, devices):
    """`drivers/serve.py:run` with this file's offer, and this file's
    comparison in the place of the causal one."""
    config = cell.config

    def answers(requests, vocab_size):
        return check_answers(requests, vocab_size, config["mask_token_id"])

    with mock.patch.object(serve.load, "Offer", Offer), \
            mock.patch.object(serve, "check_answers", answers), \
            mock.patch.object(serve, "check_against_reference",
                              check_against_reference):
        return serve.run(cell, args, t_start, devices)


def check_answers(requests, vocab_size, mask_id):
    """`serve.check_answers`, and no answer holds the mask id."""
    masked = sum(mask_id in r.tokens for r in requests if r.status == "ok")
    return [(name, value + masked * (name == "malformed_answers"), limit)
            for name, value, limit in _CAUSAL_ANSWERS(requests, vocab_size)]


def unmasked_by(conf, masked, quota, tau):
    """The columns a step unmasks of `masked`, by the schedule's rule: the
    `quota` of highest confidence (ties to the lower position) and every
    one over `tau`."""
    order = sorted(masked, key=lambda c: (-conf[c], c))
    return sorted(set(order[:quota]) | {c for c in masked if conf[c] > tau})


def schedule(config, block):
    """(denoise steps a block at the most, columns a step unmasks by rank,
    threshold) of the configuration's `serve` group, as the server reads
    them: static is `B / denoise_steps` columns a step and a threshold no
    confidence reaches, dynamic one column and `tau`."""
    serving = config["serve"]
    if serving.get("unmask", "static") == "static":
        steps = serving.get("denoise_steps", block)
        return steps, block // steps, 2.0
    return block, 1, serving.get("tau", 0.9)


def replayed_gaps(config, cfg, stacked, req, states_pad, rows_pad,
                  quant=None):
    """(served-logit gaps, log-confidence gaps) of one request, one entry a
    token unmasked and one a denoise step."""
    import jax.numpy as jnp

    reference = spec.reference(config)
    block, mask_id = cfg.block_length, cfg.mask_token
    eps, top_k = config["rms_norm_eps"], config["num_experts_per_tok"]
    d = req.decode
    rows = reference.replay_rows(
        req.prompt, req.tokens, d["unmask_steps"], d["surplus"],
        d["surplus_steps"], block, mask_id, pad_to=rows_pad)
    states = rows["states"]
    logits = reference.state_logits(stacked, rows, block, eps, top_k=top_k,
                                    states_pad=states_pad)
    _, top, conf = reference.choices(logits, mask_id)
    clean = np.asarray(rows["tokens"])
    when = ([-1] * len(req.prompt) + list(d["unmask_steps"])
            + list(d["surplus_steps"]))
    took = [[c for c in range(block) if when[first + c] == step]
            for _, first, step, _ in states]
    put = np.stack([clean[first:first + block] for _, first, *_ in states])
    if quant is not None:       # the control's tokens and the control's choice
        low = reference.state_logits(stacked, rows, block, eps, quant,
                                     top_k=top_k, states_pad=states_pad)
        low_best, _, low_conf = (np.asarray(a) for a in
                                 reference.choices(low, mask_id))
        _, quota, tau = schedule(config, block)
        took = [unmasked_by(low_conf[n], [c for c in range(block)
                                          if not known[c]], quota, tau)
                for n, (*_, known) in enumerate(states)]
        put = low_best
    got = np.asarray(jnp.take_along_axis(
        logits, jnp.asarray(put)[..., None], axis=-1)[..., 0], np.float64)
    top, logc = np.asarray(top, np.float64), np.log(np.asarray(conf,
                                                               np.float64))
    token_gaps, choice_gaps = [], []
    for n, (*_, known) in enumerate(states):
        left = [c for c in range(block) if not known[c] and c not in took[n]]
        token_gaps += [top[n, c] - got[n, c] for c in took[n]]
        choice_gaps.append(max(0.0, max(logc[n, c] for c in left)
                               - min(logc[n, c] for c in took[n]))
                           if left and took[n] else 0.0)
    return token_gaps, choice_gaps


def check_against_reference(config, cfg, params, requests, seed,
                            longest_answer, control=None):
    check = config["check"]
    sample = serve.sample_finished(requests, seed, check["requests"])
    if not sample:
        return []
    traced = [r for r in sample if getattr(r, "decode", None)
              and len(r.decode.get("unmask_steps", ())) == len(r.tokens)]
    checks = [("answers_without_their_steps", len(sample) - len(traced), 0)]
    if not traced:
        return checks
    stacked = spec.reference(config).stack(params)
    block = cfg.block_length
    # one shape for every request and seed: the served context, and a noisy
    # copy of every block of the mix's longest answer a step
    per_block, _, _ = schedule(config, block)
    states_pad = (longest_answer // block + 2) * per_block
    rows_pad = cfg.max_len + states_pad * block

    def gaps(quant=None):
        tokens, choices = [], []
        for r in traced:
            t, c = replayed_gaps(config, cfg, stacked, r, states_pad,
                                 rows_pad, quant)
            tokens += t
            choices += c
        return np.asarray(tokens), np.asarray(choices)

    t = time.perf_counter()
    tokens, choices = gaps()
    say("reference", requests=len(traced), served_tokens=len(tokens),
        denoise_steps=len(choices),
        longest=len(sample[0].prompt) + len(sample[0].tokens),
        tokens_off_the_reference_best=int(np.sum(tokens > 0)),
        steps_off_the_reference_choice=int(np.sum(choices > 0)),
        mean_gap=float(np.mean(tokens)),
        unmask_confidence_gap_max=float(np.max(choices)),
        unmask_confidence_gap_mean=float(np.mean(choices)),
        seconds=time.perf_counter() - t)
    if control:     # `tools/control.py`: the reference in a lower precision
        for precision in control.split(","):
            low_t, low_c = gaps(precision)
            say("control", seed=seed, precision=precision,
                served_logit_gap_max=float(np.max(low_t)),
                served_logit_gap_mean=float(np.mean(low_t)),
                unmask_steps_off_share=float(np.mean(low_c > 0)),
                unmask_confidence_gap_max=float(np.max(low_c)),
                unmask_confidence_gap_mean=float(np.mean(low_c)),
                tokens_off_the_reference_best=int(np.sum(low_t > 0)),
                steps_off_the_reference_choice=int(np.sum(low_c > 0)))
    return checks + [
        ("served_logit_gap_max", float(np.max(tokens)),
         check["served_logit_gap_max"]),
        ("unmask_steps_off_share", float(np.mean(choices > 0)),
         check["unmask_steps_off_share"])]
