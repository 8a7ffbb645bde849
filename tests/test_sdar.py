"""The block-diffusion family (`sdar_moe`: rotary grouped-query heads with
q/k norms, a softmax router over experts all held, a block mask) against its
plain reference, at tiny widths on the CPU, seeded weights, logits compared;
the block round of `serving/lm.py` through the paged pool, the radix tree,
preemption and a stream; the refused paths.

The reference is the benchmark's (`benchmark/reference/sdar.py`: float32,
"highest", one forward a request over the clean sequence and a noisy copy of
every generated block a denoise step, dense-masked experts, no cache).  The
program's paths are held to it within `TOL` = 1e-4 on logits of deviation
about 1: both sides compute in float32 here and differ in the order of their
sums and in how the rotary angles are rounded (float64 frequencies cast to
float32 against float32 powers: a few 1e-7 of an angle).  The readings are a
few 1e-6; computing any part in bfloat16 reads 1e-2.
"""

import dataclasses
import functools
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.adapters import sdar as adapter
from benchmark.reference import sdar as reference
from deeplearning4j_tpu.obs.trace import TraceRecorder
from deeplearning4j_tpu.parallel import generation as gen
from deeplearning4j_tpu.parallel import paged_kernel as pk
from deeplearning4j_tpu.parallel import transformer as tfm
from deeplearning4j_tpu.parallel.transformer import UnsupportedLayerKind
from deeplearning4j_tpu.serving.lm import ContinuousLMServer

TOL = 1e-4
EPS = 1e-6
PS = 8          # a page: two blocks
B = 4
MASK = 500
TOP_K = 2


def tiny(block=B, qk_norm=True, layers=4, max_len=128):
    return tfm.TransformerConfig(
        vocab_size=512, d_model=64, n_heads=4, n_layers=layers, d_ff=96,
        max_len=max_len, dtype="float32", norm="rms", norm_eps=EPS,
        mlp="swiglu", head_width=16, kv_heads=2,
        rope=tfm.YarnRope(theta=1e6), qk_norm=qk_norm,
        experts=tfm.RoutedExperts(published=8, held=(0, 8), per_token=TOP_K,
                                  width=32, score="softmax",
                                  renormalize=True),
        block_length=block, mask_token=MASK if block > 1 else None)


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    return cfg, adapter.make_params(cfg, 2_147_483_777, "float32")


def _prompt(seed, n, vocab=512):
    return [int(t) for t in np.random.default_rng(seed).integers(0, vocab, n)]


@functools.lru_cache(maxsize=None)
def _forward(cfg, kernel):
    return jax.jit(lambda params, cache, table, pos, nf, tok:
                   gen.paged_forward(cfg, params, cache, table, pos, nf, tok,
                                     paged_kernel=kernel))


def _lane(cfg, pages=16):
    cache = gen.init_paged_cache(cfg, pages + 1, PS)
    table = jnp.arange(1, pages + 1, dtype=jnp.int32)[None, :gen.pages_per_seq(
        cfg, PS)]
    return cache, table


def _feed(cfg, params, cache, table, pos, tokens, width, kernel):
    """One dispatch of `tokens` (a list) at `pos`, padded to `width`."""
    tok = np.zeros((1, width), np.int32)
    tok[0, :len(tokens)] = tokens
    logits, cache = _forward(cfg, kernel)(
        params, cache, table, jnp.array([pos], jnp.int32),
        jnp.array([len(tokens)], jnp.int32), jnp.asarray(tok))
    return logits[0, :len(tokens)], cache


# -- (a) the paged path against the reference's one forward ------------------

@pytest.mark.parametrize("kernel", [False, True])
@pytest.mark.parametrize("plen, chunk", [(16, 16), (16, 8), (21, 16),
                                         (22, 8), (3, 8)])
def test_block_rounds_through_the_pool_match_the_reference(model, kernel,
                                                           plen, chunk):
    """Prefill of whole blocks (chunked or not), then for each block two
    denoise rounds and a commit pass through the paged cache: the logits of
    every denoise round are the reference's, for prompts that end on and
    off a block boundary."""
    cfg, params = model
    prompt, n_out = _prompt(plen, plen), 10
    cache, table = _lane(cfg)
    pos = 0
    while plen // B * B - pos > 0:              # whole blocks, by chunks
        f = min(plen // B * B - pos, chunk)
        _, cache = _feed(cfg, params, cache, table, pos, prompt[pos:pos + f],
                         chunk, kernel)
        pos += f
    seq, when, seen = list(prompt), [-1] * plen, []
    while len(seq) < plen + n_out or len(seq) % B:
        block = (seq[pos:] + [MASK] * B)[:B]
        known = [True] * (len(seq) - pos) + [False] * (B - len(seq) + pos)
        steps = [-1] * B
        for step in range(2):
            if all(known):
                break
            logits, cache = _feed(cfg, params, cache, table, pos, block, B,
                                  kernel)
            seen.append(np.asarray(logits))
            new, now = gen.block_unmask(
                logits[None], jnp.asarray([block]), jnp.asarray([known]),
                jnp.array([2]), jnp.array([2.0]), MASK)
            for c in range(B):
                if bool(now[0, c]) and not known[c]:
                    block[c], known[c], steps[c] = int(new[0, c]), True, step
        assert all(known)
        _, cache = _feed(cfg, params, cache, table, pos, block, B, kernel)
        seq += block[len(seq) - pos:]
        when += steps[len(when) - pos:]
        pos += B
    rows = reference.replay_rows(prompt, seq[plen:plen + n_out],
                                 when[plen:plen + n_out], seq[plen + n_out:],
                                 when[plen + n_out:], B, MASK)
    want = np.asarray(reference.state_logits(params, rows, B, EPS,
                                             top_k=TOP_K))
    assert len(seen) == len(want) >= 2 * (n_out // B)
    assert float(np.max(np.abs(np.stack(seen) - want))) < TOL


def test_apply_is_the_references_function(model):
    """The whole-sequence oracle under the block mask, and causal."""
    cfg, params = model
    tokens = np.array(_prompt(7, 24))
    for block in (B, 1):
        c = dataclasses.replace(cfg, block_length=block)
        got = tfm.apply(c, params, jnp.asarray(tokens)[None])[0]
        want = reference.logits(params, tokens, block, EPS, top_k=TOP_K)
        assert float(jnp.max(jnp.abs(got - want))) < TOL


# -- (b) block_length 1 is the causal program --------------------------------

def test_block_length_one_serves_causally_and_matches_the_reference():
    """A rotary, q/k-normed model with `block_length` 1 takes the causal
    step programs (`make_paged_step`): prefill in chunks, then a token a
    round, against the reference's causal forward."""
    cfg = tiny(block=1)
    params = adapter.make_params(dataclasses.replace(cfg, block_length=B,
                                                     mask_token=MASK),
                                 11, "float32")
    tokens = _prompt(3, 20)
    cache, table = _lane(cfg)
    got, pos = [], 0
    for f in (8, 8, 1, 1, 1, 1):
        logits, cache = _feed(cfg, params, cache, table, pos,
                              tokens[pos:pos + f], 8, False)
        got.append(np.asarray(logits))
        pos += f
    want = np.asarray(reference.logits(params, np.array(tokens), 1, EPS,
                                       top_k=TOP_K))
    assert float(np.max(np.abs(np.concatenate(got) - want))) < TOL
    with pytest.raises(ValueError, match="causal"):
        gen.make_block_step(cfg, 9, PS, 4)


def test_a_causal_model_lowers_to_the_program_it_had(monkeypatch):
    """The grouped-query path handed a causal configuration without rotary
    positions or q/k norms (Solar-Open2's toy preset) lowers to the same
    text as it does with the norms-and-rotation step taken out, which is
    the code as it was: no existing cell compiles anything new."""
    cfg = tfm.TransformerConfig(
        vocab_size=256, d_model=64, n_heads=4, n_layers=1, d_ff=96,
        max_len=64, norm="rms", mlp="swiglu", head_width=16, kv_heads=2,
        positions="none", attn_gate=True)
    p = tfm.init_params(cfg, jax.random.PRNGKey(0))["layers"][0]["attn"]
    cache = gen.init_paged_cache(cfg, 9, PS)
    args = (p, jnp.zeros((2, 4, 64)), cache["k"], cache["v"], 0,
            jnp.zeros((2, 8), jnp.int32), jnp.zeros((2,), jnp.int32),
            jnp.full((2,), 4, jnp.int32))

    def text(**kw):
        return jax.jit(lambda *a: gen._grouped_paged_attn(
            a[0], a[1], a[2], a[3], 0, a[4], a[5], a[6], **kw)).lower(
                *args[:4], *args[5:]).as_text()

    with_step = [text(paged_kernel=kernel, cfg=cfg) for kernel in (0, 1)]
    monkeypatch.setattr(gen, "normed_rotated",
                        lambda cfg, p, q, k, positions: (q, k))
    assert with_step == [text(paged_kernel=kernel, cfg=cfg)
                         for kernel in (0, 1)]


@pytest.mark.paged_kernel
@pytest.mark.parametrize("width", [1, 12])
def test_the_grouped_kernel_with_block_one_is_todays(width):
    """`block=1` is the causal kernel bit for bit; a block mask agrees with
    the gather oracle at every fed column."""
    cfg = tiny(block=1, layers=2)
    key = jax.random.PRNGKey(width)
    pages, lanes = 12, 3
    pool = {n: jax.random.normal(jax.random.fold_in(key, i),
                                 (2, pages + 1, PS, 32))
            for i, n in enumerate(("k", "v"))}
    q = jax.random.normal(jax.random.fold_in(key, 9), (lanes, width, 4, 16))
    table = jnp.arange(1, pages + 1, dtype=jnp.int32).reshape(lanes, 4)
    pos = jnp.array([0, 8, 12], jnp.int32)
    nf = jnp.array([width, max(width - 4, 1), 0], jnp.int32)
    plain = pk.paged_flash_attention(q, pool["k"], pool["v"], table, pos, nf,
                                     layer=1)
    again = pk.paged_flash_attention(q, pool["k"], pool["v"], table, pos, nf,
                                     layer=1, block=1)
    assert bool(jnp.all(plain == again))
    if width == 1:
        return
    # the block mask: the kernel against the oracle, same pools
    p = tfm.init_params(cfg, key)["layers"][0]["attn"]
    x = jax.random.normal(jax.random.fold_in(key, 5), (lanes, width, 64))
    blocked = dataclasses.replace(cfg, block_length=B, mask_token=MASK)
    outs = [gen._grouped_paged_attn(p, x, pool["k"], pool["v"], 1, table,
                                    pos, nf, paged_kernel=kernel,
                                    cfg=blocked)[0] for kernel in (0, 1)]
    fed = (jnp.arange(width)[None, :] < nf[:, None])[..., None]
    assert float(jnp.max(jnp.abs(jnp.where(fed, outs[0] - outs[1], 0.0)))
                 ) < 1e-5


# -- (c) rotary positions and q/k norms --------------------------------------

def test_plain_rotary_is_yarn_with_factor_one():
    """`YarnRope(factor=1)` is rotary written out: frequency i of K / 2 is
    `theta ** (-2 i / K)`, the pair `(x[i], x[i + K / 2])`."""
    kd, theta = 16, 1e6
    pos = jnp.array([[0, 1, 7, 1000]])
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 4, 3, kd))
    cos, sin = tfm.rope_cos_sin(tfm.YarnRope(theta=theta), kd, pos)
    got = tfm.apply_rope(x, cos[..., None, :], sin[..., None, :])
    inv = theta ** (-np.arange(0, kd, 2) / kd)
    ang = np.asarray(pos)[0][:, None] * inv[None, :]
    a, b = np.asarray(x)[0, ..., :kd // 2], np.asarray(x)[0, ..., kd // 2:]
    c, s = np.cos(ang)[:, None, :], np.sin(ang)[:, None, :]
    want = np.concatenate([a * c - b * s, b * c + a * s], axis=-1)
    assert float(np.max(np.abs(np.asarray(got)[0] - want))) < 1e-5


@pytest.mark.parametrize("qk_norm", [True, False])
def test_qk_norms_on_and_off_match_the_reference(model, qk_norm):
    cfg, params = model
    cfg = dataclasses.replace(cfg, qk_norm=qk_norm)
    if not qk_norm:
        params = {**params, "layers": [
            {**layer, "attn": {k: v for k, v in layer["attn"].items()
                               if k not in ("q_norm", "k_norm")}}
            for layer in params["layers"]]}
    tokens = np.array(_prompt(5, 16))
    got = tfm.apply(cfg, params, jnp.asarray(tokens)[None])[0]
    want = reference.logits(params, tokens, B, EPS, top_k=TOP_K)
    assert float(jnp.max(jnp.abs(got - want))) < TOL


def test_the_pool_keeps_the_rotated_keys(model):
    cfg, params = model
    prompt = _prompt(9, 8)
    cache, table = _lane(cfg)
    _, cache = _feed(cfg, params, cache, table, 0, prompt, 8, False)
    layer = params["layers"][0]
    x = tfm.norm(cfg, layer["ln1"], params["embed"][jnp.asarray(prompt)])
    k = jnp.einsum("sd,dhk->shk", x, layer["attn"]["wk"])
    k = reference._rms_norm(layer["attn"]["k_norm"], k, EPS)
    want = reference.rotary(k, jnp.arange(8)).reshape(8, -1)
    assert float(jnp.max(jnp.abs(cache["k"][0, 1] - want))) < 1e-5
    unrotated = k.reshape(8, -1)
    assert float(jnp.max(jnp.abs(cache["k"][0, 1] - unrotated))) > 1e-2


# -- (d) the unmasking schedule ----------------------------------------------

def _logits_with(conf_order, vocab=16):
    """[1, 4, V] logits whose column c prefers token c + 1 with a margin
    that grows with `conf_order[c]`."""
    out = np.zeros((1, 4, vocab), np.float32)
    for c, rank in enumerate(conf_order):
        out[0, c, c + 1] = 1.0 + rank
    return jnp.asarray(out)


def test_static_schedule_unmasks_b_over_s_with_ties_to_the_lower_position():
    tokens = jnp.full((1, 4), 15)
    known = jnp.array([[False, True, False, False]])
    new, now = gen.block_unmask(_logits_with([2, 9, 3, 1]), tokens, known,
                                jnp.array([2]), jnp.array([2.0]), 15)
    assert now.tolist() == [[True, True, True, False]]
    assert new.tolist() == [[1, 15, 3, 15]]     # a known column is kept
    # all alike (the same row of logits in every column, so the confidences
    # are equal to the bit): the two lowest positions that are masked
    alike = jnp.tile(_logits_with([1, 1, 1, 1])[:, :1], (1, 4, 1))
    new, now = gen.block_unmask(alike, tokens, known, jnp.array([2]),
                                jnp.array([2.0]), 15)
    assert now.tolist() == [[True, True, True, False]]
    # fewer masked than the quota: all of them, no more
    new, now = gen.block_unmask(alike, tokens,
                                jnp.array([[True, True, True, False]]),
                                jnp.array([2]), jnp.array([2.0]), 15)
    assert now.tolist() == [[True] * 4] and int(new[0, 3]) == 1


def test_dynamic_schedule_takes_what_passes_tau_and_falls_back_to_one():
    tokens = jnp.full((1, 4), 15)
    known = jnp.zeros((1, 4), bool)
    logits = _logits_with([9, 0, 9, 0])
    conf = jnp.max(jax.nn.softmax(logits, -1), -1)[0]
    tau = float((conf[0] + conf[1]) / 2)
    _, now = gen.block_unmask(logits, tokens, known, jnp.array([1]),
                              jnp.array([tau]), 15)
    assert now.tolist() == [[True, False, True, False]]
    _, now = gen.block_unmask(logits, tokens, known, jnp.array([1]),
                              jnp.array([0.999999]), 15)
    assert now.tolist() == [[True, False, False, False]]    # the fallback


def test_the_mask_ids_logit_is_left_out_of_the_choice():
    logits = np.zeros((1, 4, 16), np.float32)
    logits[0, :, 15] = 5.0          # the mask id would win everywhere
    logits[0, :, 3] = 1.0
    new, now = gen.block_unmask(jnp.asarray(logits), jnp.full((1, 4), 15),
                                jnp.zeros((1, 4), bool), jnp.array([4]),
                                jnp.array([2.0]), 15)
    assert new.tolist() == [[3] * 4] and bool(jnp.all(now))


# -- the server ---------------------------------------------------------------

def _srv(cfg, params, **kw):
    kw.setdefault("slots", 3)
    kw.setdefault("page_size", PS)
    kw.setdefault("prefill_chunk", 16)
    kw.setdefault("denoise_steps", 2)
    return ContinuousLMServer(cfg, params, tracer=TraceRecorder(64), **kw)


def _span(srv, request_id):
    tr = next(t for t in srv.tracer.recent()
              if t["request_id"] == request_id)
    return next(s for s in tr["spans"] if s["name"] == "decode")["attrs"]


def _replayed(params, prompt, answer, attrs, **kw):
    """(the reference's best token, its confidence) at every state of the
    request, and the states, from what the server recorded of it."""
    rows = reference.replay_rows(prompt, answer, attrs["unmask_steps"],
                                 attrs["surplus"], attrs["surplus_steps"], B,
                                 MASK)
    logits = reference.state_logits(params, rows, B, EPS, top_k=TOP_K, **kw)
    best, _, conf = reference.choices(logits, MASK)
    return np.asarray(best), np.asarray(conf), rows["states"]


def _holds_to_the_reference(params, prompt, answer, attrs, quota=2):
    """Every token was the reference's best where it was unmasked, and the
    columns unmasked at a step were the reference's `quota` most confident
    (float32 on both sides: no tie at this size)."""
    best, conf, states = _replayed(params, prompt, answer, attrs)
    clean = list(prompt) + list(answer) + list(attrs["surplus"])
    when = ([-1] * len(prompt) + list(attrs["unmask_steps"])
            + list(attrs["surplus_steps"]))
    for n, (_, first, step, known) in enumerate(states):
        took = [c for c in range(B) if when[first + c] == step]
        masked = [c for c in range(B) if not known[c]]
        want = sorted(masked, key=lambda c: (-conf[n, c], c))[:quota]
        assert sorted(took) == sorted(want)
        assert all(clean[first + c] == best[n, c] for c in took)


def test_the_server_serves_the_references_blocks_cold_and_warm(model):
    """(d), (e): a prompt that holds the mask id as an ordinary token and
    ends off a block boundary; then a second turn, `prompt + answer + more`,
    on the warm radix tree against a cold server: the same tokens at the
    same steps, both the reference's."""
    cfg, params = model
    p1 = _prompt(21, 21)
    p1[3], p1[20] = MASK, MASK      # in a prefilled block and in the tail
    srv, cold = _srv(cfg, params), _srv(cfg, params)
    try:
        srv.warmup()
        out1 = srv.generate(p1, 10, request_id="t1", timeout=600)
        a1 = out1[len(p1):]
        assert len(a1) == 10 and MASK not in a1
        attrs = _span(srv, "t1")
        assert attrs["blocks"] == 3 and attrs["commit_rounds"] == 3
        assert attrs["denoise_rounds"] == 6 and len(attrs["surplus"]) == 1
        _holds_to_the_reference(params, p1, a1, attrs)
        p2 = out1 + _prompt(22, 6)
        warm = srv.generate(p2, 9, request_id="t2", timeout=600)
        assert _span(srv, "t2")["prefix_matched"] == 16    # p1's two pages
        assert cold.generate(p2, 9, request_id="c2", timeout=600) == warm
        for key in ("unmask_steps", "surplus", "surplus_steps"):
            assert _span(srv, "t2")[key] == _span(cold, "c2")[key]
        _holds_to_the_reference(params, p2, warm[len(p2):], _span(srv, "t2"))
        blocks = srv.stats()["blocks"]
    finally:
        srv.stop()
        cold.stop()
    assert blocks["rounds"] == {"denoise": 12, "commit": 6}
    assert blocks["committed"] == 6 and blocks["redone"] == 0
    assert blocks["positions"]["masked"] == 32 and blocks["unmasked"] == 22
    assert blocks["block_length"] == B and blocks["denoise_steps"] == 2


def test_dynamic_unmasking_through_the_server(model):
    """No confidence of a random model passes tau 0.9: one column a denoise
    round, the most confident, four rounds a block."""
    cfg, params = model
    srv = _srv(cfg, params, denoise_steps=None, unmask="dynamic", tau=0.9)
    try:
        prompt = _prompt(4, 8)
        out = srv.generate(prompt, 8, request_id="d", timeout=600)
        attrs = _span(srv, "d")
    finally:
        srv.stop()
    assert attrs["denoise_rounds"] == 8 and attrs["blocks"] == 2
    assert sorted(attrs["unmask_steps"][:4]) == [0, 1, 2, 3]
    _holds_to_the_reference(params, prompt, out[8:], attrs, quota=1)


def test_a_lane_preempted_mid_block_resumes_at_its_last_committed_block(
        model):
    """(f): the interactive request arrives while the victim's block is
    half denoised; the victim's committed blocks swap out and back, the
    block in flight is denoised again, and the tokens are those of a run
    left alone."""
    cfg, params = model
    plain = _srv(cfg, params)
    srv = _srv(cfg, params, pages=5, preempt=True, swap_quantize=False)
    res, sent = {}, []
    admit = srv._admit_locked

    def admit_and_interrupt():
        s = srv._slots[0]
        if (not sent and s.active and s.block is not None and s.block.step == 1
                and len(s.generated) >= 4):
            req = srv._build_request([4, 5, 6, 7], 8, 0.0, 0, None, "ia",
                                     priority="interactive")
            sent.append(req)
            srv._queue_insert_locked(req)
        admit()

    srv._admit_locked = admit_and_interrupt
    try:
        want = plain.generate([1, 2, 3], 26, timeout=600)
        want_ia = plain.generate([4, 5, 6, 7], 8, timeout=600)
        srv.warmup()
        t = threading.Thread(target=lambda: res.update(v=srv.generate(
            [1, 2, 3], 26, priority="best_effort", request_id="v",
            timeout=600)))
        t.start()
        t.join(timeout=600)
        ia = srv._wait(sent[0], 600)
        stats = srv.stats()
        attrs = _span(srv, "v")
    finally:
        plain.stop()
        srv.stop()
    assert stats["preemptions"] == 1 and stats["swap"]["out"] == 1
    assert stats["swap"]["in"] == 1 and stats["blocks"]["redone"] == 1
    assert res["v"] == want and ia == want_ia
    assert len(attrs["unmask_steps"]) == 26 and attrs["preempted"] == 1
    _holds_to_the_reference(params, [1, 2, 3], want[3:], attrs)


def test_a_hibernated_session_resumes_at_its_last_committed_block(
        model, tmp_path):
    """The pages parked are those of whole committed blocks whose every
    token is the sequence's own: the answer's last block, which also saw
    what was dropped past its end, is not among them; the next turn gets a
    cold server's tokens."""
    cfg, params = model
    cold = _srv(cfg, params)
    srv = _srv(cfg, params, hibernate_idle_s=0.15, state_dir=str(tmp_path),
               swap_quantize=False)
    try:
        srv.warmup()
        # 17 + 9 = 26 tokens: blocks through position 24 are the sequence's
        out1 = srv.generate(_prompt(8, 17), 9, timeout=600, session_id="s1")
        deadline = time.perf_counter() + 15
        while (time.perf_counter() < deadline and not
               srv.stats().get("hibernate", {}).get("out", 0)):
            time.sleep(0.02)
        p2 = out1 + _prompt(9, 5)
        out2 = srv.generate(p2, 6, request_id="h2", timeout=600,
                            session_id="s1")
        stats = srv.stats()
        matched = _span(srv, "h2")["prefix_matched"]
        want = cold.generate(p2, 6, timeout=600)
    finally:
        srv.stop()
        cold.stop()
    assert stats["hibernate"]["out"] >= 1 and stats["hibernate"]["in"] >= 1
    assert stats["hibernate"]["pages"] >= 2 * 3 and matched == 24
    assert out2 == want


# -- the round ahead of the host -----------------------------------------------

def test_a_carried_block_is_the_one_the_round_before_returned(model):
    """The step program's `held` / `carry`: a lane that carries feeds the
    block and the flags of the round before's result, whatever the host
    sends in their place; a lane that does not feeds what the host sends."""
    cfg, params = model
    step = gen.make_block_step(cfg, 9, PS, B, paged_kernel=False)
    cache = gen.init_paged_cache(cfg, 9, PS)
    table = np.array([[1, 2, 3, 4], [5, 6, 7, 8]], np.int32)
    table = np.pad(table, ((0, 0), (0, gen.pages_per_seq(cfg, PS) - 4)))
    zi = np.zeros((2,), np.int32)
    feed = np.full((2,), B, np.int32)
    quota, tau = np.full((2,), 2, np.int32), np.full((2,), 2.0, np.float32)
    masked = np.full((2, B), MASK, np.int32)
    none = np.zeros((2, B), np.int32)
    empty = np.zeros((2 * 2 * B + 3,), np.int32)
    first, k, v = step(params, cache["k"], cache["v"], table, zi, feed,
                       masked, none, quota, tau, empty, zi)
    sent = np.asarray(first)
    tok, now = sent[:2 * B].reshape(2, B), sent[2 * B:4 * B].reshape(2, B)
    assert now.sum(axis=1).tolist() == [2, 2]
    # lane 0 carries, lane 1 is sent its block by the host: the same round
    junk = np.stack([np.full((B,), 7, np.int32), tok[1]])
    flags = np.stack([np.ones((B,), np.int32), now[1]])
    carried, k, v = step(params, k, v, table, zi, feed, junk, flags, quota,
                         tau, first, np.array([1, 0], np.int32))
    by_host, _, _ = step(params, k, v, table, zi, feed, tok, now, quota,
                         tau, empty, zi)
    assert np.array_equal(np.asarray(carried)[:4 * B],
                          np.asarray(by_host)[:4 * B])
    assert np.asarray(carried)[2 * B:4 * B].sum() == 2 * B


def test_the_static_schedule_dispatches_a_round_before_it_reads_the_last(
        model):
    """Three lanes at different places in their blocks: round N + 1 is
    dispatched before round N is read (the device never waits for the
    host), a lane's block stays on the device between its denoise rounds,
    and the tokens and steps are those of a server that reads every round
    before it builds the next (the dynamic schedule, with a threshold no
    confidence passes: one column a round, as `denoise_steps=4`)."""
    cfg, params = model
    ahead = _srv(cfg, params, denoise_steps=4)
    serial = _srv(cfg, params, denoise_steps=None, unmask="dynamic",
                  tau=1.0)
    events, carried = [], []

    def stepped(*args):
        carried.append(int(np.sum(args[-1])))
        events.append("dispatch")
        return step(*args)

    def folded(flight):
        events.append("read")
        fold(flight)

    prompts = [_prompt(31, 5), _prompt(32, 18), _prompt(33, 8)]
    res = {}

    def ask(srv, name, n, prompt):
        res[name, n] = srv.generate(prompt, 11, request_id=f"{name}{n}",
                                    timeout=600)

    try:
        ahead.warmup()
        serial.warmup()
        step, fold = ahead._step, ahead._fold_block_round
        ahead._step, ahead._fold_block_round = stepped, folded
        threads = [threading.Thread(target=ask, args=(srv, name, n, p))
                   for name, srv in (("a", ahead), ("s", serial))
                   for n, p in enumerate(prompts)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        spans = {key: _span(srv, f"{key[0]}{key[1]}")
                 for key, srv in ((k, ahead if k[0] == "a" else serial)
                                  for k in res)}
        stats = ahead.stats(), serial.stats()
    finally:
        ahead.stop()
        serial.stop()
    for n, prompt in enumerate(prompts):
        assert res["a", n] == res["s", n]
        for key in ("unmask_steps", "surplus", "surplus_steps", "blocks",
                    "denoise_rounds"):
            assert spans["a", n][key] == spans["s", n][key]
        _holds_to_the_reference(params, prompt, res["a", n][len(prompt):],
                                spans["a", n], quota=1)
    assert stats[0]["blocks"] == stats[1]["blocks"] | {
        "denoise_steps": 4, "unmask": "static",
        "tau": stats[0]["blocks"]["tau"]}
    # once two rounds are out, every read follows the NEXT round's dispatch
    text = "".join(e[0] for e in events)
    assert "ddr" in text and "rr" not in text.rstrip("r")
    assert max(carried) >= 1


def test_a_lane_abandoned_with_its_round_in_flight_is_dropped_from_it(model):
    """The client leaves mid-answer: the lane is freed at the next admission,
    with a round of it still unread, and that round's result skips the lane
    (whoever holds it next); the neighbour's answer is a lone run's."""
    cfg, params = model
    srv, plain = _srv(cfg, params, slots=2), _srv(cfg, params, slots=2)
    res = {}
    try:
        want = plain.generate(_prompt(41, 9), 24, timeout=600)
        srv.warmup()
        t = threading.Thread(target=lambda: res.update(v=srv.generate(
            _prompt(41, 9), 24, timeout=600)))
        stream = srv.generate_stream(_prompt(42, 6), 100, timeout=600)
        head = [next(stream) for _ in range(3)]
        t.start()
        stream.close()
        t.join(timeout=600)
        late = srv.generate(_prompt(43, 7), 6, timeout=600)
        stats = srv.stats()
    finally:
        srv.stop()
        plain.stop()
    assert len(head) == 3 and res["v"] == want and len(late) == 13
    assert stats["shed"] == 1


@pytest.mark.parametrize("kw, error", [
    (dict(speculate="ngram"), UnsupportedLayerKind),
    (dict(ship=True), UnsupportedLayerKind),
    (dict(page_size=6), ValueError),
    (dict(prefill_chunk=6), ValueError),
    (dict(denoise_steps=3), ValueError),
    (dict(unmask="greedy"), ValueError),
])
def test_what_a_block_server_refuses_where_it_is_built(model, kw, error):
    cfg, params = model
    with pytest.raises(error):
        _srv(cfg, params, **kw)


def test_block_options_and_temperature_are_refused_where_they_mean_nothing(
        model):
    cfg, params = model
    with pytest.raises(ValueError, match="causal"):
        ContinuousLMServer(tiny(block=1), params, denoise_steps=2)
    srv = _srv(cfg, params)
    with pytest.raises(ValueError, match="greedily"):
        srv.generate([1, 2, 3], 4, temperature=0.7)
    with pytest.raises(UnsupportedLayerKind):
        gen.make_spec_step(cfg, 9, PS, 8)
    with pytest.raises(ValueError, match="mask_token"):
        tiny().__class__(**{**dataclasses.asdict(tiny()), "rope": None,
                            "experts": None, "mask_token": None})


def test_a_stream_yields_committed_blocks_256_tokens_in_all():
    """(g): every burst is one block's commit, at most B tokens, and what
    was streamed is the answer."""
    cfg = tiny(layers=2, max_len=320)
    params = adapter.make_params(cfg, 5, "float32")
    srv = _srv(cfg, params, pages=48, prefill_chunk=8)
    bursts = []
    commit = srv._commit_tokens

    def counted(slot, toks):
        bursts.append(len(toks))
        commit(slot, toks)

    srv._commit_tokens = counted
    try:
        prompt = _prompt(1, 13)
        streamed = list(srv.generate_stream(prompt, 256, request_id="s",
                                            timeout=600))
        attrs = _span(srv, "s")
        stats = srv.stats()
    finally:
        srv.stop()
    assert len(streamed) == 256 and sum(bursts) == 256
    assert max(bursts) <= B and bursts[0] == 3 and bursts[-1] == 1
    assert attrs["blocks"] == 65 == attrs["commit_rounds"]
    assert len(attrs["unmask_steps"]) == 256 and len(attrs["surplus"]) == 3
    assert MASK not in streamed
    assert stats["tokens"] == 256 and stats["compiled_programs"] == 3
    assert stats["rounds"]["by_width"].keys() <= {"4", "8"}
