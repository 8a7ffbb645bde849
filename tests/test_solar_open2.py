"""The grouped-query / KDA / sigmoid-router family (`solar_open2`) against
its plain reference, at tiny widths on the CPU, seeded weights, logits
compared; the state-row economy beside the pages; the refused paths.

The reference is the benchmark's (`benchmark/reference/solar_open2.py`:
float32, "highest", a `lax.scan` over positions for the KDA recurrence,
dense-masked experts, no cache).  The program's paths are held to it
within `TOL` = 1e-3 on logits of deviation about 1: both sides compute in
float32 here and differ in the order of their sums, and the chunked form
of the delta rule multiplies and divides by cumulative decays (a few
1e-6 of a state's value a chunk, carried through the layers).
The readings are a few 1e-5; computing any part in bfloat16 reads 1e-2.
"""

import dataclasses
import functools
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.adapters import solar_open2 as adapter
from benchmark.reference import solar_open2 as reference
from deeplearning4j_tpu.parallel import generation as gen
from deeplearning4j_tpu.parallel import kda
from deeplearning4j_tpu.parallel import paged_kernel as pk
from deeplearning4j_tpu.parallel import transformer as tfm
from deeplearning4j_tpu.parallel.transformer import UnsupportedLayerKind
from deeplearning4j_tpu.serving.lm import ContinuousLMServer
from deeplearning4j_tpu.serving.paged import (
    PagePool,
    RadixPrefixCache,
    StateLeakError,
    StatePool,
)

TOL = 1e-3
EPS = 1e-5
PS = 8


def tiny(held=(0, 8), published=32, layers=5, max_len=128, shared=32):
    """A period and the next one's first layer (so that both mixer kinds
    recur) at toy widths, the reference's routing constants (top 8,
    scale 1, renormalised)."""
    return tfm.TransformerConfig(
        vocab_size=256, d_model=64, n_heads=4, n_layers=layers, d_ff=96,
        max_len=max_len, dtype="float32", norm="rms", norm_eps=EPS,
        mlp="swiglu", head_width=16, kv_heads=2, positions="none",
        attn_gate=True,
        mixers=tuple("full" if i % 4 == 0 else "kda" for i in range(layers)),
        linear=tfm.LinearAttention(heads=2, k_dim=16, v_dim=16, conv_taps=4,
                                   gate_rank=16, neg_eigval=True),
        experts=tfm.RoutedExperts(published=published, held=held,
                                  per_token=8, width=32, score="sigmoid",
                                  scale=1.0, renormalize=True,
                                  shared_width=shared))


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    return cfg, adapter.make_params(cfg, 2_147_483_777, "float32")


def _tokens(seed, shape, vocab=256):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0, vocab)


@functools.lru_cache(maxsize=None)
def _forward(cfg, kernel):
    return jax.jit(lambda params, cache, table, pos, nf, tok, rows:
                   gen.paged_forward(cfg, params, cache, table, pos, nf, tok,
                                     paged_kernel=kernel, rows=rows))


def _feed(cfg, params, cache, table, rows, tokens, start, widths, kernel):
    """Feed `tokens[:, start:]` through the paged pools, `widths` columns a
    round (the last width repeats).  -> (logits [B, fed, V], cache)."""
    b, s = tokens.shape
    pos, out, i = np.full(b, start, np.int32), [], 0
    while pos[0] < s:
        w = widths[min(i, len(widths) - 1)]
        n = min(w, s - int(pos[0]))
        chunk = np.zeros((b, w), np.int32)
        chunk[:, :n] = np.asarray(tokens[:, pos[0]:pos[0] + n])
        lg, cache = _forward(cfg, kernel)(
            params, cache, table, jnp.asarray(pos),
            jnp.full((b,), n, jnp.int32), jnp.asarray(chunk), rows)
        out.append(lg[:, :n])
        pos = pos + n       # a new array: the dispatch may still read the old
        i += 1
    return jnp.concatenate(out, axis=1), cache


def _pools(cfg, lanes, rows=8):
    mp = gen.pages_per_seq(cfg, PS)
    cache = {**gen.init_paged_cache(cfg, 1 + lanes * mp, PS),
             **gen.init_state_pool(cfg, rows)}
    table = jnp.asarray(1 + np.arange(lanes * mp).reshape(lanes, mp),
                        jnp.int32)
    return cache, table


# ---- (i) served logits against the reference --------------------------------

@pytest.mark.parametrize("kernel", [False, True])
def test_prefill_in_chunks_then_decode_matches_the_reference(model, kernel):
    """Cold: 37 prompt tokens in rounds of 16 (so the last wide round is
    part fed and the chunked delta rule pads), then a token a round,
    through pages and state rows; against the reference's full forward."""
    cfg, params = model
    tokens = _tokens(1, (2, 50))
    cache, table = _pools(cfg, 2)
    got, _ = _feed(cfg, params, cache, table, jnp.asarray([1, 2]), tokens,
                   0, [16, 16, 16] + [1], kernel)
    want = reference.logits(params, tokens, EPS)
    assert float(jnp.max(jnp.abs(got - want))) < TOL
    assert float(jnp.std(want)) > 0.3


def test_a_lane_resumed_from_a_snapshot_matches_the_reference(model):
    """Lane 0 feeds 32 tokens (four pages); its state row is copied by the
    row-copy program; lane 1 starts from the copy over the SAME pages and
    feeds the rest: what a snapshot restore does."""
    cfg, params = model
    tokens = _tokens(2, (1, 60))
    cache, table = _pools(cfg, 2)
    _, cache = _feed(cfg, params, cache, table[:1], jnp.asarray([1]),
                     tokens[:, :32], 0, [16], True)
    copy = gen.make_state_copy(cfg, 4)
    cache["state"], cache["tail"] = copy(
        cache["state"], cache["tail"], jnp.asarray([1, -1, 0, 0]),
        jnp.asarray([5, 6, 0, 0]))
    assert float(jnp.max(jnp.abs(cache["state"][:, 6]))) == 0.0
    # lane 1: lane 0's four pages, then pages of its own
    shared = jnp.concatenate([table[0, :4], table[1, 4:]])[None]
    got, _ = _feed(cfg, params, cache, shared, jnp.asarray([5]), tokens,
                   32, [16, 1], True)
    want = reference.logits(params, tokens, EPS)[:, 32:]
    assert float(jnp.max(jnp.abs(got - want))) < TOL


def test_apply_is_the_references_function(model):
    cfg, params = model
    tokens = _tokens(3, (2, 24))
    got = tfm.apply(cfg, params, tokens)
    want = reference.logits(params, tokens, EPS)
    assert float(jnp.max(jnp.abs(got - want))) < TOL


def test_the_server_serves_the_references_tokens_cold_and_resumed(model):
    """Through `ContinuousLMServer`: a document cold, the session's next
    turn (resumed from the snapshot its end left), another session on the
    document (resumed from a prompt snapshot), greedy: each token is the
    reference's best."""
    cfg, params = model
    lm = ContinuousLMServer(cfg, params, slots=3, page_size=PS, pages=48,
                            prefill_chunk=16, state_rows=12,
                            snapshot_every=16)

    def best(prompt, n):
        """What was served, if each of its tokens is the reference's best
        after everything before it (one full forward of the answer)."""
        return served if _is_greedy(served, len(prompt)) else None

    def _is_greedy(seq, plen):
        lg = reference.logits(params, jnp.asarray([seq[:-1]]), EPS)[0]
        return [int(t) for t in jnp.argmax(lg[plen - 1:], -1)] == seq[plen:]

    try:
        assert lm.warmup() == lm.compiled_programs() == 3
        warm_compiles = lm.stats()["compiles_total"]
        doc = [int(t) for t in np.asarray(_tokens(4, (50,)))]
        first = served = lm.generate(doc + [1, 2, 3], 10)
        assert first == best(doc + [1, 2, 3], 10)
        taken = lm.stats()["state"]["snapshots"]["taken"]
        assert taken == 4       # at 16, 32, 48 and the request's end (56)
        second = served = lm.generate(first + [5, 6], 9)
        assert second == best(first + [5, 6], 9)
        third = served = lm.generate(doc + [9, 9, 9, 9], 6)
        assert third == best(doc + [9, 9, 9, 9], 6)
        st = lm.stats()
        assert st["state"]["snapshots"]["hit"] == 2
        assert st["prefix_tokens_saved"] == 56 + 48
        assert st["compiles_total"] == warm_compiles    # none since warm-up
        assert lm._states.check_ledger()["balanced"]
        assert lm._pool.check_ledger()["balanced"]
        # every row held is a snapshot's: no lane is active
        assert (st["state"]["rows_in_use"]
                == st["state"]["snapshots_held"] == lm._tree.snapshots)
    finally:
        lm.stop()


# ---- (ii) the delta rule's three forms ---------------------------------------

def _recurrence_inputs(seed, b, c, h, k, strong=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = kda._l2norm(jax.random.normal(ks[0], (b, c, h, k))) * k ** -0.5
    kk = kda._l2norm(jax.random.normal(ks[1], (b, c, h, k)))
    v = jax.random.normal(ks[2], (b, c, h, k))
    la = -jax.random.uniform(ks[3], (b, c, h, k), minval=0.001,
                             maxval=2.0 if strong else 0.3)
    # write strengths over the whole of (0, 2): negative eigenvalues
    beta = 2 * jax.nn.sigmoid(3 * jax.random.normal(ks[4], (b, c, h)))
    s0 = jax.random.normal(ks[5], (b, h, k, k))
    return q, kk, v, la, beta, s0


@pytest.mark.parametrize("width, strong", [(150, False), (64, False),
                                           (77, True)])
def test_chunked_delta_rule_matches_the_scan(width, strong):
    """A width that is no multiple of the chunk, a lane fed part of it,
    betas past 1, and decays down to exp(-2) a token (the quotients of
    cumulative decays are formed about the chunk's middle)."""
    q, k, v, la, beta, s0 = _recurrence_inputs(width, 2, width, 3, 16,
                                               strong)
    assert float(beta.max()) > 1.5
    fed = jnp.arange(width)[None] < jnp.asarray([width, width // 3])[:, None]
    la = jnp.where(fed[:, :, None, None], la, 0.0)
    beta = jnp.where(fed[:, :, None], beta, 0.0)
    o1, s1 = kda.scan_delta(q, k, v, la, beta, s0)
    o2, s2 = kda.chunk_delta(q, k, v, la, beta, s0)
    assert float(jnp.max(jnp.abs((o1 - o2) * fed[:, :, None, None]))) < 2e-5
    assert float(jnp.max(jnp.abs(s1 - s2))) < 2e-5


def test_width_one_update_matches_the_scan():
    q, k, v, la, beta, s0 = _recurrence_inputs(5, 2, 6, 3, 16)
    want, s_want = kda.scan_delta(q, k, v, la, beta, s0)
    s, outs = s0, []
    for t in range(6):
        o, s = kda.step_delta(q[:, t:t + 1], k[:, t:t + 1], v[:, t:t + 1],
                              la[:, t:t + 1], beta[:, t:t + 1], s)
        outs.append(o)
    assert float(jnp.max(jnp.abs(jnp.concatenate(outs, 1) - want))) < 1e-6
    assert float(jnp.max(jnp.abs(s - s_want))) < 1e-6


def test_an_idle_lane_and_padding_leave_state_and_tail_untouched(model):
    cfg, params = model
    p = params["layers"][1]["attn"]
    la = cfg.linear
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, cfg.d_model))
    state = jax.random.normal(jax.random.PRNGKey(1),
                              (2, la.heads, la.k_dim, la.v_dim))
    tail = jax.random.normal(
        jax.random.PRNGKey(2),
        (2, la.conv_taps - 1, la.heads * (2 * la.k_dim + la.v_dim)))
    for kernel in (False, True):
        _, s, t = kda.attend(cfg, p, x, state, tail, jnp.asarray([0, 5]),
                             kernel)
        np.testing.assert_array_equal(np.asarray(s[0]), np.asarray(state[0]))
        np.testing.assert_array_equal(np.asarray(t[0]), np.asarray(tail[0]))
        assert float(jnp.max(jnp.abs(s[1] - state[1]))) > 1e-3
    # a lane's result does not depend on what follows its fed columns
    a, sa, ta = kda.attend(cfg, p, x, state, tail, jnp.asarray([5, 5]), True)
    b, sb, tb = kda.attend(cfg, p, x.at[:, 5:].set(7.0), state, tail,
                           jnp.asarray([5, 5]), True)
    np.testing.assert_allclose(np.asarray(a[:, :5]), np.asarray(b[:, :5]),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(sa), np.asarray(sb), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(ta), np.asarray(tb))


@pytest.mark.paged_kernel
@pytest.mark.parametrize("width", [1, 12])
def test_grouped_paged_kernel_matches_the_gather_oracle(width):
    """8 query heads over 2 K/V heads, lanes at other positions, one part
    fed and one idle: the interpreted kernel against the oracle."""
    b, h, hkv, kd, ps, pages, mp = 3, 8, 2, 16, 8, 24, 6
    cfg = dataclasses.replace(tiny(layers=4), n_heads=h, head_width=kd,
                              kv_heads=hkv)
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    p = {"wq": jax.random.normal(ks[0], (cfg.d_model, h, kd)) / 8,
         "wk": jax.random.normal(ks[1], (cfg.d_model, hkv, kd)) / 8,
         "wv": jax.random.normal(ks[2], (cfg.d_model, hkv, kd)) / 8,
         "wgate": jax.random.normal(ks[3], (cfg.d_model, h, kd)) / 8,
         "wo": jax.random.normal(ks[4], (h, kd, cfg.d_model)) / 8}
    pool = {n: jax.random.normal(jax.random.PRNGKey(7 + i),
                                 (2, pages, ps, hkv * kd))
            for i, n in enumerate("kv")}
    table = jnp.asarray([[1, 2, 3, 4, 0, 0], [5, 6, 7, 8, 9, 0],
                         [0, 0, 0, 0, 0, 0]], jnp.int32)
    pos = jnp.asarray([10, 20, 0], jnp.int32)
    nf = jnp.minimum(jnp.asarray([12, 5, 0], jnp.int32), width)
    x = jax.random.normal(jax.random.PRNGKey(9), (b, width, cfg.d_model))
    outs = [gen._grouped_paged_attn(p, x, pool["k"], pool["v"], 1, table,
                                    pos, nf, paged_kernel=kernel,
                                    cfg=cfg)[0]
            for kernel in (False, True)]
    fed = (jnp.arange(width)[None] < nf[:, None])[:, :, None]
    assert float(jnp.max(jnp.abs((outs[0] - outs[1]) * fed))) < 1e-5
    assert pk._grouped_query_block(256) == 32
    assert pk._grouped_query_block(1) == 8


# ---- (iii) the eight shares of one expert layer ------------------------------

def test_the_shares_of_an_expert_layer_add_up_to_the_whole(model):
    """Each of the 4 chips of this toy deployment computes its 8 experts'
    part and the shared expert; the parts, the shared expert counted once,
    are the uncut reference's layer (all 32 experts held)."""
    cfg, _ = model
    whole = dataclasses.replace(
        cfg, experts=dataclasses.replace(cfg.experts, held=(0, 32)))
    p = adapter.make_params(whole, 11, "float32")["layers"][0]["experts"]
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 24, cfg.d_model))
    want = reference.expert_layer(p, x[0])
    shared = tfm._swiglu(p["shared"], x[0])
    total = jnp.zeros_like(want)
    for lo in range(0, 32, 8):
        ex = dataclasses.replace(cfg.experts, held=(lo, lo + 8))
        part = {**p, **{k: p[k][lo:lo + 8] for k in ("wg", "wu", "wd")}}
        y, load = tfm._routed_experts(ex, part, x)
        total = total + (y[0] - shared)
        # the reference, handed the same share, gives the same part
        ref_part = reference.expert_layer(part, x[0], held_lo=lo,
                                          shared=False)
        assert float(jnp.max(jnp.abs(y[0] - shared - ref_part))) < 1e-5
        assert int(load[0]) + int(load[1]) == 24 * 8
    assert float(jnp.max(jnp.abs(total + shared - want))) < 1e-5
    assert float(jnp.std(want - shared)) > 1e-3     # the routed part counts


def test_the_bias_moves_the_choice_and_not_the_weights():
    ex = tfm.RoutedExperts(published=8, held=(0, 8), per_token=2, width=4,
                           score="sigmoid", renormalize=True)
    scores = jnp.asarray([[0.9, 0.8, 0.7, 0.1, 0.1, 0.1, 0.1, 0.1]])
    bias = jnp.asarray([0.0, 0.0, 0.5, 0, 0, 0, 0, 0])
    idx, w = tfm.group_limited_top_k(scores, ex, bias)
    assert sorted(int(i) for i in idx[0]) == [0, 2]
    np.testing.assert_allclose(sorted(np.asarray(w[0])),
                               [0.7 / 1.6, 0.9 / 1.6], rtol=1e-6)
    with pytest.raises(ValueError, match="softmax.*sigmoid"):
        tfm.RoutedExperts(published=8, held=(0, 8), per_token=2, width=4,
                          score="tanh")


# ---- (iv) the state-row ledger ------------------------------------------------

@pytest.mark.parametrize("seed", range(4))
def test_state_rows_balance_under_any_order_of_events(seed):
    """Admit / snapshot / evict / finish in random order over a small pool:
    the two ledgers balance after every event, `match()` never returns a
    boundary that has no snapshot, and at the end every held row is a
    snapshot's."""
    rng = random.Random(seed)
    pages, states = PagePool(40, 4), StatePool(9)
    tree = RadixPrefixCache(pages, states)
    docs = [[rng.randrange(3) for _ in range(24)] for _ in range(4)]
    lanes = []

    def has_snapshot(tokens, n_pages):
        node = tree.root
        for i in range(n_pages):
            node = node.children[tuple(tokens[4 * i:4 * i + 4])]
        return node.snap is not None

    for _ in range(300):
        event = rng.choice(["admit", "snapshot", "finish", "evict"])
        if event == "admit" and len(lanes) < 3:
            tokens = rng.choice(docs)[:rng.randrange(5, 25)]
            full, row = tree.match_snapshot(tokens[:-1])
            assert (row is None) == (not full)
            if full:
                assert has_snapshot(tokens, len(full))
                states.release([row])       # the copy is done
            need = -(-len(tokens) // 4) - len(full)
            if states.free < 2:
                tree.evict_snapshots(2)
            if pages.free < need:
                tree.evict(need)
            fresh, rows = pages.alloc(need), states.alloc(2)
            if fresh is None or rows is None:
                pages.release(full + (fresh or []))
                states.release(rows or [])
            else:
                lanes.append({"tokens": tokens, "pages": full + fresh,
                              "rows": rows})
        elif event == "snapshot" and lanes:
            lane = rng.choice(lanes)
            n = rng.randrange(1, len(lane["tokens"]) // 4 + 1)
            fresh = states.alloc(1)
            if fresh is not None:
                tree.insert(lane["tokens"][:4 * n], lane["pages"][:n])
                if tree.attach(lane["tokens"][:4 * n], lane["rows"][1]):
                    lane["rows"][1] = fresh[0]
                else:
                    states.release(fresh)
        elif event == "finish" and lanes:
            lane = lanes.pop(rng.randrange(len(lanes)))
            pages.release(lane["pages"])
            states.release(lane["rows"])
        elif event == "evict":
            rng.choice([tree.evict, tree.evict_snapshots])(rng.randrange(6))
        assert pages.check_ledger()["balanced"]
        assert states.check_ledger()["balanced"]
        pages_match, partial = tree.match(rng.choice(docs))
        assert partial is None
        pages.release(pages_match)
        assert tree.snapshots == len(tree._snapshot_nodes())
    for lane in lanes:
        pages.release(lane["pages"])
        states.release(lane["rows"])
    assert states.in_use == tree.snapshots
    tree.clear()
    assert states.in_use == 0 and pages.in_use == 0
    with pytest.raises(StateLeakError):
        states.release([1])


def test_eviction_frees_a_snapshot_with_its_page_and_may_drop_one_alone():
    pages, states = PagePool(10, 2), StatePool(4)
    tree = RadixPrefixCache(pages, states)
    got = pages.alloc(3)
    tree.insert([1, 2, 3, 4, 5, 6], got)
    pages.release(got)
    rows = states.alloc(2)
    assert tree.attach([1, 2], rows[0]) and tree.attach([1, 2, 3, 4],
                                                        rows[1])
    assert not tree.attach([1, 2], 3)           # it has one already
    full, row = tree.match_snapshot([1, 2, 3, 4, 5, 6, 7])
    assert len(full) == 2 and row == rows[1]    # page 3 has no snapshot
    assert tree.snapshots_evictable() == 1      # the matched one is pinned
    pages.release(full)
    states.release([row])
    assert tree.evict_snapshots(2) == 1 and states.free == 2
    assert tree.match([1, 2, 3, 4, 5, 6])[0] == got[:2]    # [1, 2]'s went
    pages.release(got[:2])
    assert tree.evict(9) == 3                   # leaf first, all of them
    assert states.in_use == 0 and tree.snapshots == 0
    assert tree.snapshots_evicted == 2


# ---- (v) what the recurrent kinds are refused ---------------------------------

@pytest.mark.parametrize("path", [
    "speculate", "ship", "preempt", "hibernate", "spec_step", "page_gather",
    "page_install", "model_signature", "generate", "beam_search",
    "param_specs", "train"])
def test_refused_paths_raise_unsupported_layer_kind(model, path):
    cfg, params = model
    serve = {"speculate": {"speculate": "ngram"}, "ship": {"ship": True},
             "preempt": {"preempt": True},
             "hibernate": {"hibernate_idle_s": 1.0}}
    prompt = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(UnsupportedLayerKind):
        if path in serve:
            ContinuousLMServer(cfg, params, **serve[path])
        elif path == "spec_step":
            gen.make_spec_step(cfg, 8, PS, 4)
        elif path == "page_gather":
            gen.make_page_gather(cfg, 8, PS)
        elif path == "page_install":
            gen.make_page_install(cfg, 8, PS)
        elif path == "model_signature":
            from deeplearning4j_tpu.serving.transfer import model_signature

            model_signature(cfg, PS)
        elif path == "generate":
            gen.generate(cfg, params, prompt, 2)
        elif path == "beam_search":
            gen.beam_search(cfg, params, prompt, 2, beam_size=2)
        elif path == "param_specs":
            tfm.param_specs(cfg, "model")
        else:
            tfm.lm_loss(cfg, params, prompt, prompt)


def test_state_knobs_are_refused_for_a_model_without_recurrent_layers():
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                                n_layers=1, d_ff=64, max_len=32)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    for knob in ({"state_rows": 8}, {"snapshot_every": 16}):
        with pytest.raises(ValueError, match="recurrent"):
            ContinuousLMServer(cfg, params, **knob)
    # and a recurrent model's own: two rows a lane, boundaries on pages
    rec = tiny(layers=4)
    rp = tfm.init_params(rec, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="2 a lane"):
        ContinuousLMServer(rec, rp, slots=4, state_rows=7)
    with pytest.raises(ValueError, match="multiple"):
        ContinuousLMServer(rec, rp, page_size=8, prefill_chunk=8,
                           snapshot_every=12)


# ---- sizes, the one place every reader takes them from ------------------------

def test_pool_and_state_sizes_at_the_published_widths():
    cfg = tfm.solar_open2(layers=4, experts_held=(0, 40), vocab=24576,
                          max_len=24576)
    assert not cfg.classic and cfg.recurrent
    assert cfg.head_dim == 128 and cfg.n_kv_heads == 8
    assert cfg.mixer_kinds() == ("full", "kda", "kda", "kda")
    assert gen.pool_layers(cfg) == (0, None, None, None)
    lay = gen.pool_layout(cfg)
    assert (lay.heads, lay.width, lay.row) == (8, 128, 1024)
    assert gen.pool_token_bytes(cfg) == 4096        # ONE full layer, k and v
    # 3 layers x (64 x 128 x 128 x 4 B + 3 x 24,576 x 2 B)
    assert gen.state_row_bytes(cfg) == 3 * (4194304 + 147456) == 13025280
    assert gen.pool_names(cfg) == ("k", "v", "state", "tail")
    shapes = jax.eval_shape(lambda: gen.init_state_pool(cfg, 17))
    assert shapes["state"].shape == (3, 32, 64, 128, 128)   # 16-row tiles
    assert shapes["tail"].shape == (3, 32, 3 * 24576)
    # GPT-2 and DeepSeek-V2 keep what they had
    g2 = tfm.gpt2_large()
    assert g2.classic and g2.head_dim == 64 and g2.n_kv_heads == 20
    assert gen.pool_names(g2) == ("k", "v") and gen.state_row_bytes(g2) == 0
    assert gen.pool_layers(g2) == tuple(range(36))
    ds = tfm.deepseek_v2(layers=5, experts_held=(0, 40))
    assert gen.pool_names(ds) == ("kv",) and not ds.recurrent
