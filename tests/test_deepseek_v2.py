"""The latent-attention / routed-experts family (`deepseek_v2`) against its
plain reference, at tiny widths on the CPU, seeded weights, logits compared.

The reference is the benchmark's (`benchmark/reference/deepseek_v2.py`:
float32, "highest", not absorbed, dense-masked experts, no cache); the
program's paths are held to it within `TOL`, a float32 tolerance: both
sides compute in float32 here and differ in the order of their sums
(absorbed projections, grouped matmuls, online softmax), a few 1e-6 on
logits of deviation 1.
"""

import dataclasses
import math
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.adapters import deepseek_v2 as adapter
from benchmark.reference import deepseek_v2 as reference
from deeplearning4j_tpu.parallel import generation as gen
from deeplearning4j_tpu.parallel import transformer as tfm
from deeplearning4j_tpu.serving.lm import ContinuousLMServer
from deeplearning4j_tpu.serving.transfer import (
    check_compatible,
    deserialize_export,
    model_signature,
    quantize_export,
    serialize_export,
)

TOL = 5e-5
EPS = 1e-6
PS = 8


def tiny(held=(0, 8), published=32, shared=True, layers=3, max_len=128):
    """The reference's published routing constants (8 groups keep 3, top 6,
    scale 16) and YaRN numbers at toy widths."""
    return tfm.TransformerConfig(
        vocab_size=256, d_model=64, n_heads=4, n_layers=layers, d_ff=96,
        max_len=max_len, dtype="float32", norm="rms", norm_eps=EPS,
        mlp="swiglu",
        rope=tfm.YarnRope(theta=10000.0, factor=40.0, original_max_len=4096,
                          beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                          mscale_all_dim=0.707),
        latent=tfm.LatentAttention(q_rank=24, kv_rank=16, nope_dim=8,
                                   rope_dim=8, v_dim=8),
        experts=tfm.RoutedExperts(published=published, held=held,
                                  per_token=6, width=32, groups=8,
                                  groups_kept=3, scale=16.0,
                                  shared_width=64 if shared else 0),
        dense_layers=1)


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    return cfg, adapter.make_params(cfg, 2_147_483_777, "float32")


def _tokens(seed, shape, vocab=256):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0, vocab)


def _prefill_then_decode(cfg, params, tokens, kernel, chunk=8, prefill=16):
    """Logits [B, S, V] of `tokens` fed through the paged cache: `prefill`
    tokens in chunks of `chunk`, the rest one a round."""
    b, s = tokens.shape
    pages = 1 + b * gen.pages_per_seq(cfg, PS)
    cache = gen.init_paged_cache(cfg, pages, PS)
    mp = gen.pages_per_seq(cfg, PS)
    table = jnp.asarray(1 + np.arange(b * mp).reshape(b, mp), jnp.int32)
    pos, out = np.zeros(b, np.int32), []
    while pos[0] < s:
        w = chunk if pos[0] < prefill else 1
        lg, cache = gen.paged_forward(
            cfg, params, cache, table, jnp.asarray(pos),
            jnp.full((b,), w, jnp.int32), tokens[:, pos[0]:pos[0] + w],
            paged_kernel=kernel)
        out.append(lg)
        pos += w
    return jnp.concatenate(out, axis=1)


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["gather_oracle", "kernel_interpreted"])
def test_prefill_in_chunks_then_decode_agrees_with_the_reference(model,
                                                                 kernel):
    cfg, params = model
    tokens = _tokens(5, (2, 29))
    want = reference.logits(reference.stack(params), tokens, EPS)
    got = _prefill_then_decode(cfg, params, tokens, kernel)
    assert float(jnp.std(want)) > 0.5           # logits are O(1), not 0
    np.testing.assert_allclose(got, want, atol=TOL)


def test_whole_sequence_apply_agrees_with_the_reference(model):
    cfg, params = model
    tokens = _tokens(6, (2, 40))
    np.testing.assert_allclose(
        tfm.apply(cfg, params, tokens),
        reference.logits(reference.stack(params), tokens, EPS), atol=TOL)


def test_absorbed_attention_is_the_published_form(model):
    """One attention layer: the paged path's absorbed projections against
    `_latent_attn`, which up-projects keys and values a head."""
    cfg, params = model
    p = params["layers"][1]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, cfg.d_model))
    want = tfm._latent_attn(cfg, p, x, causal=True)
    pool = gen.init_paged_cache(cfg, 9, PS)["kv"]
    table = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    zero = jnp.zeros((2,), jnp.int32)
    for kernel in (False, True):
        got, _ = gen._latent_paged_attn(
            cfg, p, x, pool, 1, table, zero, jnp.full((2,), 24, jnp.int32),
            paged_kernel=kernel)
        np.testing.assert_allclose(got, want, atol=TOL)


@pytest.mark.parametrize("position", [1, 4095, 16000])
def test_yarn_frequencies_by_hand(position):
    """cos/sin of the rotary against the formula worked here: at 64 dims,
    theta 10000, 4096 original positions, beta 32 and 1, the ramp runs from
    dimension 10 to 23 (floor and ceil of 64 ln(4096 / 2 pi beta) / 2 ln
    theta); below it a frequency is unchanged, above it divided by 40."""
    rope = tiny().rope
    low = math.floor(64 * math.log(4096 / (32 * 2 * math.pi))
                     / (2 * math.log(10000)))
    high = math.ceil(64 * math.log(4096 / (1 * 2 * math.pi))
                     / (2 * math.log(10000)))
    assert (low, high) == (10, 23)
    cos, sin = tfm.rope_cos_sin(rope, 64, jnp.asarray([position]))
    for i in (0, 16, 31):
        plain = 10000.0 ** (-2 * i / 64)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        freq = plain * (1 - ramp) + plain / 40.0 * ramp
        np.testing.assert_allclose(
            [float(cos[0, i]), float(sin[0, i])],
            [math.cos(position * freq), math.sin(position * freq)],
            atol=2e-3 if position > 4000 else 1e-5)
    # mscale / mscale_all_dim = 1; the score's factor is m^2
    m = 0.1 * 0.707 * math.log(40) + 1
    assert tfm.latent_softmax_scale(tiny()) == pytest.approx(
        16 ** -0.5 * m * m)
    assert reference.yarn_inv_freq(64) == pytest.approx(
        tfm.yarn_inv_freq(rope, 64).tolist(), rel=1e-6)


def test_group_limited_router_against_a_loop():
    ex = tiny().experts
    scores = np.asarray(jax.nn.softmax(2 * jax.random.normal(
        jax.random.PRNGKey(4), (20, ex.published)), axis=-1))
    idx, w = tfm.group_limited_top_k(jnp.asarray(scores), ex)
    per = ex.published // ex.groups
    for t in range(scores.shape[0]):
        best = [max(scores[t, g * per:(g + 1) * per])
                for g in range(ex.groups)]
        kept = sorted(range(ex.groups), key=lambda g: -best[g])[:3]
        standing = [e for e in range(ex.published) if e // per in kept]
        top = sorted(standing, key=lambda e: -scores[t, e])[:ex.per_token]
        assert [int(e) for e in idx[t]] == top
        np.testing.assert_allclose(w[t], [16.0 * scores[t, e] for e in top],
                                   rtol=1e-6)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The routed parts the shares (0,8) .. (24,32) give, with the shared
    expert counted once, are the uncut reference's whole layer."""
    whole = tiny(held=(0, 32), layers=2)
    p = adapter.make_params(whole, 11, "float32")["layers"][1]["experts"]
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 24, whole.d_model))
    want = reference.expert_layer(p, x[0], held_lo=0)
    total = tfm._swiglu(p["shared"], x)
    for lo in range(0, 32, 8):
        share = {"gate": p["gate"],
                 **{k: p[k][lo:lo + 8] for k in ("wg", "wu", "wd")}}
        ex = dataclasses.replace(whole.experts, held=(lo, lo + 8),
                                 shared_width=0)
        part, load = tfm._routed_experts(ex, share, x)
        # the reference, given the same share, leaves out the same experts
        np.testing.assert_allclose(
            part[0], reference.expert_layer(share, x[0], held_lo=lo,
                                            shared=False), atol=TOL)
        assert int(load[0]) + int(load[1]) == 24 * 6
        total = total + part
    np.testing.assert_allclose(total[0], want, atol=TOL)


def test_a_requests_logits_do_not_depend_on_who_shares_its_round(model):
    cfg, params = model
    tokens = _tokens(8, (3, 20))
    alone = _prefill_then_decode(cfg, params, tokens[:1], False)
    # lanes 1 and 2 feed one token over and over: their pairs pile onto
    # six experts, which a capacity dispatch would answer with drops
    crowd = tokens.at[1:].set(tokens[1, 0])
    for others in (tokens, crowd):
        shared = _prefill_then_decode(cfg, params, others, False)
        # equal to float32 rounding (the CPU's matmul tiles by the batch's
        # rows); a dropped or re-weighted pair would move a logit by O(0.1)
        np.testing.assert_allclose(shared[0], alone[0], atol=TOL)


def test_dropless_dispatch_equals_the_dense_masked_oracle():
    """`_moe_dropless` (what every inference path now runs for the Switch /
    GShard block) against `_moe_dense`, to rounding."""
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                                n_layers=1, d_ff=48, n_experts=8,
                                moe_top_k=2)
    p = tfm.init_params(cfg, jax.random.PRNGKey(0))["layers"][0]["moe"]
    p = dict(p, b1=0.1 * jax.random.normal(jax.random.PRNGKey(1),
                                           p["b1"].shape),
             b2=0.1 * jax.random.normal(jax.random.PRNGKey(2),
                                        p["b2"].shape))
    x = jax.random.normal(jax.random.PRNGKey(3), (3, 7, 32))
    for k in (1, 2):
        np.testing.assert_allclose(tfm._moe_dropless(p, x, k),
                                   tfm._moe_dense(p, x, k), atol=1e-5)
    np.testing.assert_allclose(tfm._moe(p, x, top_k=2),
                               tfm._moe_dense(p, x, 2), atol=1e-5)


def test_one_function_gives_the_pool_its_row():
    cfg = tiny()
    lay = gen.pool_layout(cfg)
    assert (lay.names, lay.heads, lay.width) == (("kv",), 1, 24)
    assert gen.pool_token_bytes(cfg) == 3 * 24 * 4
    assert gen.init_paged_cache(cfg, 5, PS)["kv"].shape == (3, 5, PS, 24)
    full = tfm.deepseek_v2(layers=5, experts_held=(0, 40), vocab=25600,
                           max_len=16384)
    lay = gen.pool_layout(full)
    # 576 values a token, held in whole 128-lane tiles
    assert full.latent.row_values == 576 and lay.row == 640
    assert gen.pool_token_bytes(full) == 5 * 640 * 2
    gpt2 = gen.pool_layout(tfm.gpt2_large())
    assert (gpt2.names, gpt2.heads, gpt2.width) == (("k", "v"), 20, 64)
    sig = model_signature(cfg, PS)
    assert (sig["n_heads"], sig["head_dim"], sig["pools"]) == (1, 24, 1)
    assert "pools" not in model_signature(tfm.gpt2_large(), 16)


def test_other_paths_refuse_the_new_kinds_with_a_typed_error(model):
    from deeplearning4j_tpu.parallel import hybrid

    cfg, params = model
    with pytest.raises(tfm.UnsupportedLayerKind):
        hybrid.make_accum_train_step(cfg)
    with pytest.raises(tfm.UnsupportedLayerKind):
        hybrid.HybridParallelTrainer(cfg, mesh=None)
    with pytest.raises(tfm.UnsupportedLayerKind):
        tfm.lm_loss(cfg, params, _tokens(1, (1, 8)), _tokens(2, (1, 8)))
    with pytest.raises(tfm.UnsupportedLayerKind):
        gen.generate(cfg, params, np.asarray([[1, 2, 3]]), 4)
    assert issubclass(tfm.UnsupportedLayerKind, ValueError)


# ---------------------------------------------------------------------------
# The serving plane on latent pages


@jax.jit
def _padded_logits(params, seq):
    return tfm.apply(tiny(), params, seq)


def _greedy(cfg, params, prompt, new):
    """Greedy decoding by full recompute, one shape (causal: the padding
    after a position cannot reach it)."""
    assert cfg == tiny()
    seq = list(prompt)
    for _ in range(new):
        padded = np.zeros((1, cfg.max_len), np.int32)
        padded[0, :len(seq)] = seq
        seq.append(int(jnp.argmax(_padded_logits(params, padded)[
            0, len(seq) - 1])))
    return seq


def _srv(cfg, params, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("page_size", PS)
    kw.setdefault("pages", 40)
    kw.setdefault("prefill_chunk", 8)
    return ContinuousLMServer(cfg, params, **kw)


def test_serves_with_radix_reuse_and_copy_on_write(model):
    cfg, params = model
    base = [int(t) for t in _tokens(9, (21,))]      # 2 full pages + 5
    other = base[:19] + [7, 9, 11, 13]              # diverges mid-page
    srv = _srv(cfg, params)
    try:
        assert srv.generate(base, 6, timeout=600) == _greedy(
            cfg, params, base, 6)
        assert srv.generate(base, 6, timeout=600) == _greedy(
            cfg, params, base, 6)
        assert srv.generate(other, 5, timeout=600) == _greedy(
            cfg, params, other, 5)
        stats = srv.stats()
        with srv._cond:
            assert srv._pool.check_ledger()["balanced"]
    finally:
        srv.stop()
    assert stats["prefix_hits"] >= 2
    assert stats["prefix_tokens_saved"] >= 2 * 16
    assert stats["kv_bytes"]["per_token"] == gen.pool_token_bytes(cfg)
    held, absent = (stats["experts"]["pairs"][k] for k in ("held", "absent"))
    assert held > 0 and absent > 0
    assert stats["rounds"]["attn_rows"]["w1"] > 0


def test_ship_and_wire_round_trip_of_latent_pages(model):
    cfg, params = model
    prompt = [int(t) for t in _tokens(10, (19,))]
    pre, dec = _srv(cfg, params, ship=True), _srv(cfg, params, ship=True)
    try:
        ex = pre.prefill_export(prompt, 6, timeout=600)
        assert ex.pages_v is None and ex.pages_k.shape == (3, 3, PS, 1, 24)
        wire = deserialize_export(serialize_export(ex))
        check_compatible(wire, cfg, PS)
        np.testing.assert_array_equal(wire.pages_k, ex.pages_k)
        assert dec.admit_with_pages(wire, timeout=600) == _greedy(
            cfg, params, prompt, 6)
        # the int8 frame of one pool: one page stack, one scale stack
        q = deserialize_export(serialize_export(quantize_export(ex)))
        assert q.pages_v is None and q.scales_v is None
        assert q.dequantized().pages_k.shape == ex.pages_k.shape
    finally:
        pre.stop()
        dec.stop()


def test_preempted_lane_swaps_its_latent_pages_out_and_back(model):
    cfg, params = model
    srv = _srv(cfg, params, pages=5, prefill_chunk=4, preempt=True,
               swap_quantize=False)
    res = {}
    try:
        srv.warmup()
        t = threading.Thread(target=lambda: res.update(v=srv.generate(
            [1, 2, 3], 28, priority="best_effort", timeout=600)))
        t.start()
        deadline = time.perf_counter() + 20
        while time.perf_counter() < deadline:
            with srv._cond:
                s = srv._slots[0]
                if (s.active and s.req is not None
                        and s.fed >= len(s.req.prompt)
                        and len(s.generated) >= 2):
                    break
            time.sleep(0.002)
        res["ia"] = srv.generate([4, 5, 6, 7], 8, priority="interactive",
                                 timeout=600)
        t.join(timeout=600)
        stats = srv.stats()
    finally:
        srv.stop()
    assert stats.get("preemptions", 0) >= 1 and stats["swap"]["out"] >= 1
    assert res["v"] == _greedy(cfg, params, [1, 2, 3], 28)
    assert res["ia"] == _greedy(cfg, params, [4, 5, 6, 7], 8)


def test_hibernated_session_resumes_from_latent_pages(model, tmp_path):
    cfg, params = model
    srv = _srv(cfg, params, hibernate_idle_s=0.15, state_dir=str(tmp_path),
               swap_quantize=False)
    try:
        srv.warmup()
        out1 = srv.generate(list(range(1, 18)), 8, timeout=600,
                            session_id="s1")
        deadline = time.perf_counter() + 15
        while (time.perf_counter() < deadline and not
               srv.stats().get("hibernate", {}).get("out", 0)):
            time.sleep(0.02)
        p2 = out1 + [3, 5, 7]
        out2 = srv.generate(p2, 6, timeout=600, session_id="s1")
        stats = srv.stats()
    finally:
        srv.stop()
    assert stats["hibernate"]["out"] >= 1 and stats["hibernate"]["in"] >= 1
    assert out2 == _greedy(cfg, params, p2, 6)
