"""Hybrid-parallelism tests on the 8-device virtual CPU mesh.

The gold check everywhere: the sharded computation must equal the
single-device computation — ring attention vs dense attention, dp x sp x tp
(+ep) training vs one-device SGD, pipeline vs sequential stack.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.parallel import make_mesh
from deeplearning4j_tpu.parallel import transformer as tfm
from deeplearning4j_tpu.parallel.hybrid import (
    HybridParallelTrainer,
    PipelineParallelTrainer,
    _sgd_tree,
)
from deeplearning4j_tpu.parallel.ring_attention import (
    attention,
    ring_attention,
    ring_flash_attention,
    zigzag_order,
    zigzag_schedule,
)
from deeplearning4j_tpu.parallel.mesh import shard_map
from jax.sharding import PartitionSpec as P


def _all_devices(n):
    return jax.devices()[:n]


def _ring_on_mesh(fn, n, causal):
    """`fn` as a ring over `n` host devices, natural order in and out: a
    causal ring's rows are dealt zigzag on the way in and put back on the
    way out, as the trainer deals a batch."""
    mesh = make_mesh((n,), ("seq",), devices=_all_devices(n))
    ring = shard_map(
        lambda q, k, v: fn(q, k, v, "seq", causal=causal), mesh=mesh,
        in_specs=(P(None, "seq"),) * 3, out_specs=P(None, "seq"))
    if not causal:
        return ring

    def dealt(q, k, v):
        order = zigzag_order(n, q.shape[1])
        return ring(q[:, order], k[:, order], v[:, order])[
            :, np.argsort(order)]

    return dealt


def _qkv(seed, b, s, h, d):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
                 for _ in range(3))


def _weighted(out):
    # a different weight a position, so that a row put back in the wrong
    # place shows in the loss and in every gradient
    return jnp.sum(out ** 2 * (1 + jnp.arange(out.shape[1]))[:, None, None])


class TestRingAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_dense_attention(self, causal):
        q, k, v = _qkv(0, 2, 16, 2, 8)
        expected = attention(q, k, v, causal=causal)
        got = jax.jit(_ring_on_mesh(ring_attention, 4, causal))(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                                   atol=2e-5)

    def test_grads_match_dense(self):
        q, k, v = _qkv(1, 1, 8, 2, 4)
        ring = _ring_on_mesh(ring_attention, 4, True)
        ge = jax.grad(lambda q, k, v: jnp.sum(
            attention(q, k, v, causal=True) ** 2), (0, 1, 2))(q, k, v)
        gr = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
            ring(q, k, v) ** 2), (0, 1, 2)))(q, k, v)
        for a, b_ in zip(gr, ge):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=2e-4)


class TestRingFlashAttention:
    """The Pallas-inner-block ring path (interpret mode on the CPU mesh)
    vs dense single-device attention — forward and distributed backward."""

    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_dense_attention(self, causal):
        q, k, v = _qkv(2, 2, 16, 2, 8)
        expected = attention(q, k, v, causal=causal)
        got = jax.jit(_ring_on_mesh(ring_flash_attention, 4, causal))(
            q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                                   atol=2e-5)

    @pytest.mark.parametrize("causal", [True, False])
    def test_ring_backward_matches_dense(self, causal):
        q, k, v = _qkv(3, 1, 16, 2, 4)
        ring = _ring_on_mesh(ring_flash_attention, 4, causal)
        ge = jax.grad(lambda q, k, v: jnp.sum(
            attention(q, k, v, causal=causal) ** 2), (0, 1, 2))(q, k, v)
        gr = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
            ring(q, k, v) ** 2), (0, 1, 2)))(q, k, v)
        for a, b_ in zip(gr, ge):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=2e-4)

    def test_axis_none_is_single_device_flash(self):
        q, k, v = _qkv(4, 2, 16, 2, 8)
        got = ring_flash_attention(q, k, v, None, causal=True)
        want = attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-6)


# the ring's layout follows from `causal` and the axis size alone (ISSUE
# 36): causal over 2, 4 and 8 chips is dealt zigzag, without a mask the
# layout stays contiguous
RING_LAYOUTS = [(True, 2), (True, 4), (True, 8), (False, 2)]
RING_ENGINES = {"plain": ring_attention, "flash": ring_flash_attention}


@pytest.mark.parametrize("causal,n", RING_LAYOUTS)
@pytest.mark.parametrize("engine", sorted(RING_ENGINES))
class TestRingSchedule:
    """Both engines against dense attention on one device, at every
    layout the code derives."""

    def test_forward(self, engine, causal, n):
        q, k, v = _qkv(10 + n, 2, 32, 2, 8)
        got = jax.jit(_ring_on_mesh(RING_ENGINES[engine], n, causal))(
            q, k, v)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(attention(q, k, v, causal=causal)),
            atol=2e-5)

    def test_gradients(self, engine, causal, n):
        q, k, v = _qkv(20 + n, 1, 32, 2, 4)
        ring = _ring_on_mesh(RING_ENGINES[engine], n, causal)
        want = jax.grad(lambda q, k, v: _weighted(
            attention(q, k, v, causal=causal)), (0, 1, 2))(q, k, v)
        got = jax.jit(jax.grad(lambda q, k, v: _weighted(ring(q, k, v)),
                               (0, 1, 2)))(q, k, v)
        for a, b_ in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=2e-3, rtol=1e-4)


class TestZigzagOrder:
    @pytest.mark.parametrize("n", [2, 4, 8])
    def test_is_a_permutation_with_chunks_i_and_2n_1_i_on_chip_i(self, n):
        s = 8 * n
        order = zigzag_order(n, s)
        assert sorted(order.tolist()) == list(range(s))
        c = s // (2 * n)
        for i in range(n):
            chip = order[i * 2 * c:(i + 1) * 2 * c]
            assert chip[:c].tolist() == list(range(i * c, (i + 1) * c))
            late = 2 * n - 1 - i
            assert chip[c:].tolist() == list(range(late * c, (late + 1) * c))

    def test_indivisible_length_raises_with_the_numbers(self):
        with pytest.raises(ValueError, match=r"4 chips.*8.*chunks.*20"):
            zigzag_order(4, 20)

    @pytest.mark.parametrize("n", [2, 4, 8])
    def test_every_chip_does_the_same_work_and_all_of_it(self, n):
        """Each chip: one step on its own block, then n-1 remote steps of
        two unmasked chunk pairs; together exactly the pairs of chunks
        that the causal mask leaves live, each once."""
        seen = set()
        for i in range(n):
            steps = zigzag_schedule(n, i)
            assert len(steps) == n
            assert [c for _, _, c in steps[0]] == [True, False, True]
            assert all(len(st) == 2 and not any(c for _, _, c in st)
                       for st in steps[1:])
            pairs = [(qc, kc) for st in steps for qc, kc, _ in st]
            assert {qc for qc, _ in pairs} <= {i, 2 * n - 1 - i}
            assert all((qc == kc) == c for st in steps for qc, kc, c in st)
            assert not seen & set(pairs) and len(set(pairs)) == len(pairs)
            seen |= set(pairs)
        assert seen == {(qc, kc) for qc in range(2 * n)
                        for kc in range(qc + 1)}


@pytest.mark.parametrize("engine", sorted(RING_ENGINES))
def test_causal_ring_permutes_no_scalar(engine):
    """The schedule is computed, not received: forward and backward of a
    causal ring over 2 chips send K/V (and dK/dV) round and nothing else,
    no block index among them."""
    import re

    q = jnp.zeros((1, 16, 2, 8), jnp.float32)
    mesh = make_mesh((2,), ("seq",), devices=_all_devices(2))
    ring = shard_map(
        lambda q, k, v: RING_ENGINES[engine](q, k, v, "seq", causal=True),
        mesh=mesh, in_specs=(P(None, "seq"),) * 3, out_specs=P(None, "seq"))
    lowered = jax.jit(jax.value_and_grad(
        lambda q, k, v: jnp.sum(ring(q, k, v) ** 2), (0, 1, 2))).lower(
            q, q, q)
    sent = [line for line in lowered.as_text().splitlines()
            if "collective_permute" in line or "ppermute" in line]
    assert sent
    operands = [re.findall(r"tensor<([^>]*)>", line) for line in sent]
    assert all(types and all("x" in t for t in types)
               for types in operands), operands
    hlo = lowered.compile().as_text()
    moved = re.findall(r"= (\S+) collective-permute(?:-start)?\(", hlo)
    assert moved and not [t for t in moved if "[]" in t], moved


class TestMoEDispatch:
    """Capacity-based dispatch vs the dense-masked oracle (VERDICT r3 #3)."""

    def _moe_params(self, e, d=16, f=32, seed=0):
        k = jax.random.PRNGKey(seed)
        ks = jax.random.split(k, 3)
        return {
            "gate": jax.random.normal(ks[0], (d, e), jnp.float32) * 0.5,
            "w1": jax.random.normal(ks[1], (e, d, f)) / np.sqrt(d),
            "b1": jnp.zeros((e, f)),
            "w2": jax.random.normal(ks[2], (e, f, d)) / np.sqrt(f),
            "b2": jnp.zeros((e, d)),
        }

    def test_dispatch_matches_dense_oracle_at_full_capacity(self):
        """capacity = all tokens -> no drops -> bitwise-same routing as the
        dense-masked oracle, for values AND gradients."""
        e = 4
        p = self._moe_params(e)
        x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 8, 16)),
                        jnp.float32)
        got = tfm._moe_dispatch(p, x, capacity_factor=float(e))
        want = tfm._moe_dense(p, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)
        g_got = jax.grad(lambda p_: jnp.sum(
            tfm._moe_dispatch(p_, x, float(e)) ** 2))(p)
        g_want = jax.grad(lambda p_: jnp.sum(
            tfm._moe_dense(p_, x) ** 2))(p)
        for a, b in zip(jax.tree_util.tree_leaves(g_got),
                        jax.tree_util.tree_leaves(g_want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4)

    def test_overflow_tokens_drop_to_identity(self):
        """With capacity C, at most E*C tokens get a nonzero branch output
        (Switch drop rule: overflow rides the residual untouched)."""
        e = 4
        p = self._moe_params(e, seed=3)
        n = 32
        x = jnp.asarray(np.random.default_rng(1).standard_normal((1, n, 16)),
                        jnp.float32)
        out = tfm._moe_dispatch(p, x, capacity_factor=0.25)  # C = 2
        nonzero_rows = int(np.sum(
            np.any(np.abs(np.asarray(out))[0] > 0, axis=-1)))
        assert nonzero_rows <= e * 2
        # and the kept tokens match the oracle exactly
        oracle = np.asarray(tfm._moe_dense(p, x))[0]
        outn = np.asarray(out)[0]
        kept = np.any(np.abs(outn) > 0, axis=-1)
        np.testing.assert_allclose(outn[kept], oracle[kept], atol=1e-5)

    def test_expert_flops_scale_with_capacity_not_n_experts(self):
        """The point of dispatch: quadrupling n_experts at fixed capacity
        factor must NOT quadruple FLOPs (dense-masked does)."""

        def flops(fn, p, x):
            c = jax.jit(fn).lower(p, x).compile().cost_analysis()
            if isinstance(c, list):  # older jax returns [dict]
                c = c[0]
            return float(c["flops"])

        x = jnp.asarray(np.random.default_rng(2).standard_normal((4, 32, 16)),
                        jnp.float32)
        disp = lambda p, x: tfm._moe_dispatch(p, x, 1.25)  # noqa: E731
        f4 = flops(disp, self._moe_params(4), x)
        f16 = flops(disp, self._moe_params(16), x)
        assert f16 < 1.7 * f4, (f4, f16)
        dense = lambda p, x: tfm._moe_dense(p, x)  # noqa: E731
        d4 = flops(dense, self._moe_params(4), x)
        d16 = flops(dense, self._moe_params(16), x)
        assert d16 > 3.0 * d4, (d4, d16)  # the oracle DOES scale with E

    def test_top_k_config_validation(self):
        with pytest.raises(ValueError, match="moe_top_k"):
            tfm.TransformerConfig(n_experts=4, moe_top_k=0)
        with pytest.raises(ValueError, match="moe_top_k"):
            tfm.TransformerConfig(n_experts=4, moe_top_k=8)
        tfm.TransformerConfig(n_experts=0, moe_top_k=1)  # dense: unused

    def test_top2_dispatch_matches_dense_oracle_at_full_capacity(self):
        """GShard-style top-2: dispatch == dense oracle when no
        assignment is dropped (values AND gradients), and top-2 output
        is a renormalized two-expert blend (differs from top-1)."""
        e = 4
        p = self._moe_params(e, seed=7)
        x = jnp.asarray(np.random.default_rng(7).standard_normal((2, 8, 16)),
                        jnp.float32)
        got = tfm._moe_dispatch(p, x, capacity_factor=float(e), top_k=2)
        want = tfm._moe_dense(p, x, top_k=2)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)
        one = tfm._moe_dense(p, x, top_k=1)
        assert not np.allclose(np.asarray(want), np.asarray(one))
        g_got = jax.grad(lambda q: jnp.sum(
            tfm._moe_dispatch(q, x, float(e), top_k=2) ** 2))(p)
        g_want = jax.grad(lambda q: jnp.sum(
            tfm._moe_dense(q, x, top_k=2) ** 2))(p)
        for a, b in zip(jax.tree_util.tree_leaves(g_got),
                        jax.tree_util.tree_leaves(g_want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4)

    @pytest.mark.slow  # ~16s full-model MoE train+decode; the
    # dispatch-vs-dense-oracle equivalences above stay in tier-1
    def test_top2_full_model_trains_and_decodes_consistently(self):
        """moe_top_k=2 end to end: lm_loss trains (finite, decreasing)
        and the decode contract holds (dense top-2 inference both
        sides)."""
        from deeplearning4j_tpu.parallel.generation import (
            decode_step, init_cache)

        cfg = tfm.TransformerConfig(vocab_size=31, d_model=16, n_heads=4,
                                    n_layers=1, d_ff=32, n_experts=4,
                                    moe_top_k=2, max_len=16)
        params = tfm.init_params(cfg, jax.random.PRNGKey(3))
        rng = np.random.default_rng(8)
        tokens = jnp.asarray(rng.integers(0, 31, (2, 10)), jnp.int32)
        targets = jnp.roll(tokens, -1, axis=1)
        losses = []
        p = params
        step = jax.jit(lambda q, t, g: (
            _sgd_tree(q, jax.grad(
                lambda z: tfm.lm_loss(cfg, z, t, g))(q), 0.1),
            tfm.lm_loss(cfg, q, t, g)))
        for _ in range(8):
            p, l = step(p, tokens, targets)
            losses.append(float(l))
        assert np.isfinite(losses).all() and losses[-1] < losses[0]
        full = np.asarray(tfm.apply(cfg, p, tokens))
        cache = init_cache(cfg, 2)
        for t in range(tokens.shape[1]):
            logits, cache = decode_step(cfg, p, cache, tokens[:, t])
            np.testing.assert_allclose(np.asarray(logits), full[:, t],
                                       atol=2e-4)

    def test_aux_load_balance_loss(self):
        """Switch aux loss: 1 at a perfectly balanced assignment, larger
        when routing collapses; lm_loss adds exactly moe_aux_weight * aux
        in training mode."""
        import dataclasses

        e, d = 4, 8
        p = self._moe_params(e, d=d, f=16)
        # uniform gate -> balanced-ish; zero gate weights = exact uniform
        p_uni = dict(p, gate=jnp.zeros((d, e)))
        x = jnp.asarray(np.random.default_rng(5).standard_normal((2, 16, d)),
                        jnp.float32)
        # argmax over identical logits picks expert 0 for every token:
        # f=(1,0,0,0), P uniform -> aux = E * (1/E) = 1
        assert np.isclose(float(tfm._moe_aux_loss(p_uni, x)), 1.0)
        # fully concentrated routing: all-ones inputs + gate favoring
        # expert 0 -> f=(1,0,0,0), P_0 ~ 1 -> aux ~ E
        p_hot = dict(p, gate=jnp.zeros((d, e)).at[:, 0].set(10.0))
        x_ones = jnp.ones((2, 16, d), jnp.float32)
        aux_hot = float(tfm._moe_aux_loss(p_hot, x_ones))
        assert aux_hot > 0.9 * e  # far above the balanced value of 1

        cfg = tfm.TransformerConfig(vocab_size=31, d_model=16, n_heads=4,
                                    n_layers=1, d_ff=32, n_experts=4,
                                    max_len=16, moe_aux_weight=0.5)
        params = tfm.init_params(cfg, jax.random.PRNGKey(2))
        tokens = jnp.asarray(
            np.random.default_rng(6).integers(0, 31, (2, 8)), jnp.int32)
        targets = jnp.roll(tokens, -1, axis=1)
        with_aux = float(tfm.lm_loss(cfg, params, tokens, targets))
        no_aux = float(tfm.lm_loss(
            dataclasses.replace(cfg, moe_aux_weight=0.0), params, tokens,
            targets))
        _, aux = tfm.apply(cfg, params, tokens, train=True, return_aux=True)
        assert np.isclose(with_aux - no_aux, 0.5 * float(aux), atol=1e-6)

    def test_apply_uses_dispatch_under_mesh(self):
        """Full model equivalence in TRAIN mode (dispatch active): apply()
        must agree between mesh (GSPMD dp/sp/tp over 8 devices) and single
        device — routing is deterministic either way."""
        cfg = tfm.TransformerConfig(vocab_size=31, d_model=16, n_heads=4,
                                    n_layers=1, d_ff=32, n_experts=4,
                                    max_len=32)
        mesh = make_mesh((2, 2, 2), ("data", "seq", "model"),
                         devices=_all_devices(8))
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        tokens = jnp.asarray(
            np.random.default_rng(3).integers(0, cfg.vocab_size, (4, 16)),
            jnp.int32)
        single = tfm.apply(cfg, params, tokens, train=True)
        sharded = jax.jit(lambda p, t: tfm.apply(
            cfg, p, t, mesh=mesh, train=True))(params, tokens)
        np.testing.assert_allclose(np.asarray(sharded), np.asarray(single),
                                   atol=2e-5)

    @pytest.mark.slow  # ~10s; the dense-oracle dispatch parities
    # above keep MoE routing covered in tier-1
    def test_inference_apply_is_dense_and_matches_decode_contract(self):
        """apply()'s inference default must be batch-composition-independent
        (dense MoE, no drops): scoring one sequence alone equals scoring it
        co-batched — the property generation.decode_step relies on."""
        cfg = tfm.TransformerConfig(vocab_size=31, d_model=16, n_heads=4,
                                    n_layers=1, d_ff=32, n_experts=4,
                                    max_len=32)
        params = tfm.init_params(cfg, jax.random.PRNGKey(1))
        rng = np.random.default_rng(4)
        tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 10)),
                             jnp.int32)
        batched = np.asarray(tfm.apply(cfg, params, tokens))[0]
        alone = np.asarray(tfm.apply(cfg, params, tokens[:1]))[0]
        np.testing.assert_allclose(batched, alone, atol=1e-5)


def _gather(tree):
    return jax.tree_util.tree_map(np.asarray, tree)


def _single_device_adam_steps(cfg, tokens, targets, lr, n_steps, seed):
    from deeplearning4j_tpu.ops.updaters import (
        UpdaterConfig, apply_updates, make_updater)

    transform = make_updater(UpdaterConfig(
        updater="adam", learning_rate=lr, epsilon=1e-8))
    params = tfm.init_params(cfg, jax.random.PRNGKey(seed))
    state = transform.init(params)
    losses = []
    for _ in range(n_steps):
        loss, grads = jax.value_and_grad(
            lambda p: tfm.lm_loss(cfg, p, tokens, targets))(params)
        updates, state = transform.update(grads, state, params)
        params = apply_updates(params, updates)
        losses.append(float(loss))
    return params, losses


@pytest.mark.slow  # ~38s pair: each compiles a full mesh trainer AND its
# single-device Adam reference.  The SGD-reference equivalence for the
# same trainers (TestHybridParallelTrainer / TestPipelineParallelTrainer)
# stays in tier-1; this adds the Adam-state-sharding axis.
class TestTrainerUpdaters:
    """updater='adam' on the mesh trainers must match single-device Adam
    step for step (the optimizer state shards/replicates with its
    params)."""

    def test_hybrid_adam_matches_single_device(self):
        cfg = tfm.TransformerConfig(vocab_size=41, d_model=16, n_heads=4,
                                    n_layers=1, d_ff=32, max_len=16)
        mesh = make_mesh((2, 2, 2), ("data", "seq", "model"),
                         devices=_all_devices(8))
        rng = np.random.default_rng(5)
        tokens = rng.integers(0, cfg.vocab_size, (4, 8))
        targets = rng.integers(0, cfg.vocab_size, (4, 8))
        tr = HybridParallelTrainer(cfg, mesh, lr=0.01, seed=3,
                                   updater="adam")
        losses = [tr.fit_batch(tokens, targets) for _ in range(3)]
        ref_p, ref_l = _single_device_adam_steps(
            cfg, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(targets, jnp.int32), 0.01, 3, seed=3)
        np.testing.assert_allclose(losses, ref_l, atol=1e-4)
        for a, b in zip(jax.tree_util.tree_leaves(_gather(tr.params)),
                        jax.tree_util.tree_leaves(_gather(ref_p))):
            np.testing.assert_allclose(a, b, atol=5e-4)

    def test_pipeline_adam_matches_single_device(self):
        cfg = tfm.TransformerConfig(vocab_size=41, d_model=16, n_heads=4,
                                    n_layers=4, d_ff=32, max_len=16)
        mesh = make_mesh((2, 4), ("data", "stage"), devices=_all_devices(8))
        rng = np.random.default_rng(6)
        tokens = rng.integers(0, cfg.vocab_size, (8, 8))
        targets = rng.integers(0, cfg.vocab_size, (8, 8))
        tr = PipelineParallelTrainer(cfg, mesh, n_microbatches=2, lr=0.01,
                                     seed=4, updater="adam")
        losses = [tr.fit_batch(tokens, targets) for _ in range(3)]
        ref_p, ref_l = _single_device_adam_steps(
            cfg, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(targets, jnp.int32), 0.01, 3, seed=4)
        np.testing.assert_allclose(losses, ref_l, atol=1e-4)
        np.testing.assert_allclose(
            np.asarray(tr.io_params["embed"]),
            np.asarray(ref_p["embed"]), atol=5e-4)
        got_w1 = np.asarray(tr.stage_params["mlp"]["w1"]).reshape(
            cfg.n_layers, cfg.d_model, cfg.d_ff)
        want_w1 = np.stack([np.asarray(l["mlp"]["w1"])
                            for l in ref_p["layers"]])
        np.testing.assert_allclose(got_w1, want_w1, atol=5e-4)


def _single_device_steps(cfg, tokens, targets, lr, n_steps, seed):
    params = tfm.init_params(cfg, jax.random.PRNGKey(seed))
    losses = []
    for _ in range(n_steps):
        loss, grads = jax.value_and_grad(
            lambda p: tfm.lm_loss(cfg, p, tokens, targets))(params)
        params = _sgd_tree(params, grads, lr)
        losses.append(float(loss))
    return params, losses


class TestHybridParallelTrainer:
    # the MoE variant (~23s) rides the slow lane: expert dispatch
    # equivalence is pinned by TestMoEDispatch's dense-oracle tests in
    # tier-1, and the dense hybrid A/B stays here (tier-1 870s budget)
    @pytest.mark.parametrize("n_experts", [
        0, pytest.param(4, marks=pytest.mark.slow)])
    def test_matches_single_device(self, n_experts):
        cfg = tfm.TransformerConfig(
            vocab_size=61, d_model=16, n_heads=4, n_layers=2, d_ff=32,
            n_experts=n_experts, max_len=32)
        mesh = make_mesh((2, 2, 2), ("data", "seq", "model"),
                         devices=_all_devices(8))
        rng = np.random.default_rng(2)
        b, s = 4, 16
        tokens = rng.integers(0, cfg.vocab_size, (b, s))
        targets = rng.integers(0, cfg.vocab_size, (b, s))

        trainer = HybridParallelTrainer(cfg, mesh, lr=0.05, seed=9)
        losses = [trainer.fit_batch(tokens, targets) for _ in range(3)]

        ref_params, ref_losses = _single_device_steps(
            cfg, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(targets, jnp.int32), 0.05, 3, seed=9)

        np.testing.assert_allclose(losses, ref_losses, atol=1e-4)
        got = _gather(trainer.params)
        want = _gather(ref_params)
        flat_g = jax.tree_util.tree_leaves(got)
        flat_w = jax.tree_util.tree_leaves(want)
        for a, b_ in zip(flat_g, flat_w):
            np.testing.assert_allclose(a, b_, atol=5e-4)

    def test_seq_2_first_step_is_one_device_lm_loss(self):
        """The trainer deals each row zigzag over the two `seq` chips and
        gathers the learned positions in the same order: loss and first
        gradients (read off one SGD step) are the one-device `lm_loss`'s
        on the batch as given, `pos` among them and in natural order."""
        cfg = tfm.TransformerConfig(vocab_size=53, d_model=16, n_heads=2,
                                    n_layers=2, d_ff=32, max_len=24)
        mesh = make_mesh((1, 2, 1), ("data", "seq", "model"),
                         devices=_all_devices(2))
        rng = np.random.default_rng(12)
        tokens = rng.integers(0, cfg.vocab_size, (2, 16))
        targets = rng.integers(0, cfg.vocab_size, (2, 16))
        params = tfm.init_params(cfg, jax.random.PRNGKey(5))
        lr = 0.5
        trainer = HybridParallelTrainer(cfg, mesh, lr=lr, params=params)
        loss = trainer.fit_batch(tokens, targets)
        want_loss, want = jax.value_and_grad(lambda p: tfm.lm_loss(
            cfg, p, jnp.asarray(tokens), jnp.asarray(targets)))(params)
        np.testing.assert_allclose(loss, float(want_loss), atol=1e-5)
        after = trainer.export_params()
        got = jax.tree_util.tree_map(
            lambda a, b: (np.asarray(a) - b) / lr, params, after)
        for (path, g), w in zip(
                jax.tree_util.tree_flatten_with_path(got)[0],
                jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(g, np.asarray(w), atol=2e-5,
                                       err_msg=str(path))
        # a row of `pos` moves by its own position's gradient, and the
        # rows past the batch's length not at all
        assert np.abs(np.asarray(want["pos"])[:16]).min(axis=1).max() > 0
        np.testing.assert_allclose(
            after["pos"], np.asarray(params["pos"] - lr * want["pos"]),
            atol=1e-5)
        np.testing.assert_array_equal(after["pos"][16:],
                                      np.asarray(params["pos"])[16:])

    def test_apply_and_lm_loss_deal_for_themselves_on_a_seq_mesh(self):
        """Off the trainer the batch arrives in natural order: `lm_loss`
        deals it, `apply` deals it and puts the logits back."""
        cfg = tfm.TransformerConfig(vocab_size=53, d_model=16, n_heads=2,
                                    n_layers=1, d_ff=32, max_len=16)
        mesh = make_mesh((1, 4, 1), ("data", "seq", "model"),
                         devices=_all_devices(4))
        rng = np.random.default_rng(13)
        tokens = jnp.asarray(rng.integers(0, 53, (2, 16)), jnp.int32)
        targets = jnp.asarray(rng.integers(0, 53, (2, 16)), jnp.int32)
        params = tfm.init_params(cfg, jax.random.PRNGKey(6))
        np.testing.assert_allclose(
            np.asarray(jax.jit(lambda p, t: tfm.apply(cfg, p, t, mesh))(
                params, tokens)),
            np.asarray(tfm.apply(cfg, params, tokens)), atol=2e-5)
        np.testing.assert_allclose(
            float(jax.jit(lambda p: tfm.lm_loss(
                cfg, p, tokens, targets, mesh))(params)),
            float(tfm.lm_loss(cfg, params, tokens, targets)), atol=1e-5)
        assert tfm.seq_order(mesh, tfm.MeshAxes(), 16, causal=False) is None
        assert tfm.seq_order(None, tfm.MeshAxes(), 16) is None

    def test_length_the_ring_cannot_deal_raises(self):
        cfg = tfm.TransformerConfig(vocab_size=31, d_model=16, n_heads=2,
                                    n_layers=1, d_ff=32, max_len=16)
        mesh = make_mesh((1, 2, 1), ("data", "seq", "model"),
                         devices=_all_devices(2))
        trainer = HybridParallelTrainer(cfg, mesh)
        with pytest.raises(ValueError, match=r"2 chips.*4 chunks.*6"):
            trainer.fit_batch(np.zeros((2, 6), np.int32),
                              np.zeros((2, 6), np.int32))

    @pytest.mark.slow  # ~6s; the single-device A/B above is the
    # stronger hybrid-trainer gate and stays in tier-1
    def test_loss_decreases(self):
        cfg = tfm.TransformerConfig(vocab_size=31, d_model=16, n_heads=2,
                                    n_layers=1, d_ff=32, max_len=16)
        mesh = make_mesh((2, 2, 2), ("data", "seq", "model"),
                         devices=_all_devices(8))
        rng = np.random.default_rng(3)
        tokens = rng.integers(0, cfg.vocab_size, (4, 8))
        targets = np.roll(tokens, -1, axis=1)
        trainer = HybridParallelTrainer(cfg, mesh, lr=0.1)
        losses = [trainer.fit_batch(tokens, targets) for _ in range(10)]
        assert losses[-1] < losses[0]


class TestFlagshipTrainingPath:
    """GPT-2-small-class ingredients (VERDICT r4 #2): weight tying,
    per-block remat, gradient accumulation — each must change memory/
    params, never the math."""

    def _cfg(self, **kw):
        base = dict(vocab_size=64, d_model=32, n_heads=4, n_layers=2,
                    d_ff=64, max_len=32)
        base.update(kw)
        return tfm.TransformerConfig(**base)

    def test_tied_embeddings_drop_head_and_match_manual_tie(self):
        cfg = self._cfg(tie_embeddings=True)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        assert "head" not in params
        n_untied = sum(
            int(np.prod(np.shape(x))) for x in jax.tree_util.tree_leaves(
                tfm.init_params(self._cfg(), jax.random.PRNGKey(0))))
        n_tied = sum(int(np.prod(np.shape(x)))
                     for x in jax.tree_util.tree_leaves(params))
        assert n_untied - n_tied == cfg.d_model * cfg.vocab_size
        tokens = jnp.asarray(
            np.random.default_rng(0).integers(0, 64, (2, 8)), jnp.int32)
        got = tfm.apply(cfg, params, tokens)
        manual = dict(params, head=params["embed"].T)
        want = tfm.apply(self._cfg(), manual, tokens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-6)
        # decode path resolves the tied head too
        from deeplearning4j_tpu.parallel.generation import (
            decode_step, init_cache)
        cache = init_cache(cfg, 2)
        logits, _ = decode_step(cfg, params, cache, tokens[:, 0])
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(got)[:, 0], atol=2e-4)
        # tied init must keep initial logits at head scale: loss ~ ln V,
        # not ln V + O(sqrt(d)) (the tied-embedding scale trap)
        targets = jnp.roll(tokens, -1, axis=1)
        loss0 = float(tfm.lm_loss(cfg, params, tokens, targets))
        assert loss0 < 2.0 * np.log(cfg.vocab_size), loss0

    # the plain-attention case is ~13s; grad-accumulation equivalence
    # keeps the flagship training path covered in tier-1.  With the flash
    # kernels (interpreted here) the block's checkpoint saves their output
    # and row statistics and does not recompute them.
    @pytest.mark.parametrize("flash,experts", [
        pytest.param("0", 0, marks=pytest.mark.slow, id="plain-dense"),
        pytest.param("1", 0, id="flash-dense"),
        pytest.param("1", 4, id="flash-moe"),
    ])
    def test_remat_is_numerically_transparent(self, monkeypatch, flash,
                                              experts):
        monkeypatch.setenv("DL4J_TPU_FLASH", flash)
        tokens = jnp.asarray(
            np.random.default_rng(1).integers(0, 64, (2, 8)), jnp.int32)
        targets = jnp.roll(tokens, -1, axis=1)
        plain = self._cfg(n_experts=experts)
        rematted = self._cfg(n_experts=experts, remat=True)
        p = tfm.init_params(plain, jax.random.PRNGKey(1))
        for train in (False, True):
            base = tfm.apply(plain, p, tokens, train=train)
            rem = tfm.apply(rematted, p, tokens, train=train)
            np.testing.assert_allclose(np.asarray(rem), np.asarray(base),
                                       atol=1e-6)
        g0, g1 = (jax.grad(lambda q, cfg=cfg: tfm.lm_loss(
            cfg, q, tokens, targets))(p) for cfg in (plain, rematted))
        for a, b in zip(jax.tree_util.tree_leaves(g0),
                        jax.tree_util.tree_leaves(g1)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6)

    @pytest.mark.parametrize("updater", ["sgd", "adam"])
    def test_grad_accumulation_matches_full_batch(self, updater):
        from deeplearning4j_tpu.parallel.hybrid import make_accum_train_step

        cfg = self._cfg(tie_embeddings=True, remat=True)
        rng = np.random.default_rng(2)
        tokens = jnp.asarray(rng.integers(0, 64, (8, 16)), jnp.int32)
        targets = jnp.roll(tokens, -1, axis=1)
        p0 = tfm.init_params(cfg, jax.random.PRNGKey(2))

        def run(accum):
            step, init = make_accum_train_step(cfg, lr=0.1, accum=accum,
                                               updater=updater)
            p = jax.tree_util.tree_map(jnp.copy, p0)
            return step(p, init(p), tokens, targets)

        p_full, _, l_full = run(1)
        p_acc, _, l_acc = run(4)
        np.testing.assert_allclose(float(l_acc), float(l_full), atol=1e-5)
        # 5e-5: scan-vs-single-sum float reduction order, amplified by
        # adam's rsqrt on near-zero second moments
        for a, b in zip(jax.tree_util.tree_leaves(p_acc),
                        jax.tree_util.tree_leaves(p_full)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5)


class TestGPipeMemoryHygiene:
    """VERDICT r3 #5: microbatches must NOT be replicated to every stage.
    The new gpipe_apply takes each stage's blocked [K=ceil(M/P), mb] share
    and banks only its share of outputs; this test pins both the
    equivalence to the replicated formulation and the per-device memory
    reduction (via XLA's compiled memory analysis)."""

    @staticmethod
    def _replicated_gpipe(stage_fn, stage_params, x_microbatches, axis_name):
        """The round-3 formulation: full [M, mb] input replicated to every
        stage, full [M, mb] output buffer on every stage.  Kept here as
        the equivalence + memory oracle."""
        n_stages = jax.lax.psum(1, axis_name)
        stage = jax.lax.axis_index(axis_name)
        m = x_microbatches.shape[0]
        local_params = jax.tree_util.tree_map(lambda a: a[0], stage_params)
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        act_shape = x_microbatches.shape[1:]

        def tick(carry, t):
            incoming, outputs = carry
            mb = jax.lax.dynamic_index_in_dim(
                x_microbatches, jnp.clip(t, 0, m - 1), axis=0,
                keepdims=False)
            x_in = jnp.where(stage == 0, mb, incoming)
            y = stage_fn(local_params, x_in)
            out_idx = t - (n_stages - 1)
            valid = jnp.logical_and(stage == n_stages - 1, out_idx >= 0)
            outputs = jax.lax.cond(
                valid,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.clip(out_idx, 0, m - 1), axis=0),
                lambda o: o, outputs)
            nxt = jax.lax.ppermute(y, axis_name, perm)
            return (nxt, outputs), None

        init = (jnp.zeros(act_shape, x_microbatches.dtype),
                jnp.zeros((m,) + act_shape, x_microbatches.dtype))
        (_, outputs), _ = jax.lax.scan(
            tick, init, jnp.arange(m + n_stages - 1))
        return jax.lax.psum(
            jnp.where(stage == n_stages - 1, 1.0, 0.0) * outputs, axis_name)

    def _build(self, p, m, mbb, f):
        from deeplearning4j_tpu.parallel.pipeline import gpipe_apply

        mesh = make_mesh((p,), ("stage",), devices=_all_devices(p))
        rng = np.random.default_rng(0)
        w = jnp.asarray(rng.standard_normal((p, 1, f, f)),
                        jnp.float32) / np.sqrt(f)
        x = jnp.asarray(rng.standard_normal((m, mbb, f)), jnp.float32)
        stage_fn = lambda pp, a: jnp.tanh(a @ pp[0])  # noqa: E731
        new_f = jax.jit(shard_map(
            lambda sp, xl: gpipe_apply(stage_fn, sp, xl, "stage", m),
            mesh=mesh, in_specs=(P("stage"), P("stage")),
            out_specs=P("stage")))
        old_f = jax.jit(shard_map(
            lambda sp, xf: self._replicated_gpipe(
                stage_fn, sp, xf, "stage")[None],
            mesh=mesh, in_specs=(P("stage"), P()), out_specs=P("stage")))
        return w, x, new_f, old_f

    @pytest.mark.parametrize("m", [8, 6])  # m=6/P=4: mixed real+padding
    def test_matches_replicated_formulation(self, m):
        p = 4
        w, x, new_f, old_f = self._build(p=p, m=m, mbb=4, f=64)
        if m % p:  # pad the sharded input to K*P slots (trainer contract)
            k = -(-m // p)
            xp = jnp.pad(x, ((0, k * p - m), (0, 0), (0, 0)))
            got = np.asarray(new_f(w, xp))[:m]
        else:
            got = np.asarray(new_f(w, x))
        want = np.asarray(old_f(w, x))[0]
        np.testing.assert_allclose(got, want, atol=1e-5)

    def test_stage_remat_cuts_backward_memory_without_changing_grads(self):
        """remat_stage (default) must stash only tick inputs for the
        backward scan: same gradients, smaller compiled temp memory than
        remat_stage=False."""
        from deeplearning4j_tpu.parallel.pipeline import gpipe_apply

        p, m, mbb, f = 4, 8, 8, 128
        mesh = make_mesh((p,), ("stage",), devices=_all_devices(p))
        rng = np.random.default_rng(1)
        w = jnp.asarray(rng.standard_normal((p, 1, f, f)),
                        jnp.float32) / np.sqrt(f)
        x = jnp.asarray(rng.standard_normal((m, mbb, f)), jnp.float32)
        stage_fn = lambda pp, a: jnp.tanh(a @ pp[0])  # noqa: E731

        def make(remat):
            def loss(sp, xl):
                y = gpipe_apply(stage_fn, sp, xl, "stage", m,
                                remat_stage=remat)
                return jax.lax.psum(jnp.sum(y ** 2), "stage")

            return jax.jit(shard_map(
                jax.grad(loss), mesh=mesh,
                in_specs=(P("stage"), P("stage")), out_specs=P("stage")))

        g_remat = make(True)
        g_plain = make(False)
        np.testing.assert_allclose(np.asarray(g_remat(w, x)),
                                   np.asarray(g_plain(w, x)), atol=1e-5)
        t_remat = g_remat.lower(w, x).compile().memory_analysis(
        ).temp_size_in_bytes
        t_plain = g_plain.lower(w, x).compile().memory_analysis(
        ).temp_size_in_bytes
        assert t_remat < t_plain, (t_remat, t_plain)

    def test_per_stage_memory_is_sharded_not_replicated(self):
        p, m, mbb, f = 4, 8, 4, 64
        w, x, new_f, old_f = self._build(p, m, mbb, f)
        new_st = new_f.lower(w, x).compile().memory_analysis()
        old_st = old_f.lower(w, x).compile().memory_analysis()
        param_bytes = w.nbytes // p  # identical on both sides
        data_new = (new_st.argument_size_in_bytes - param_bytes
                    + new_st.temp_size_in_bytes
                    + new_st.output_size_in_bytes)
        data_old = (old_st.argument_size_in_bytes - param_bytes
                    + old_st.temp_size_in_bytes
                    + old_st.output_size_in_bytes)
        # input share is exactly 1/P of the replicated input...
        mb_bytes = x.nbytes // m
        assert (new_st.argument_size_in_bytes - param_bytes
                == (m // p) * mb_bytes)
        assert old_st.argument_size_in_bytes - param_bytes == m * mb_bytes
        # ...and total per-device data memory (args + temps + outputs)
        # drops well below the replicated formulation's.
        assert data_new < 0.6 * data_old, (data_new, data_old)


class TestPipelineParallelTrainer:
    # untied (~21s) rides the slow lane; the TIED config stays in
    # tier-1 — it is the flagship gpt2_small shape and additionally
    # proves the stage-psum on the doubly-contributed embed leaf
    @pytest.mark.parametrize("tied", [
        pytest.param(False, marks=pytest.mark.slow), True])
    def test_matches_single_device(self, tied):
        """Untied AND tied (GPT-2-style) configs: under tying the embed
        leaf receives two gradient contributions (lookup + lm-head
        projection), each computed on a stage's disjoint microbatch
        share, so this also proves the stage-psum accumulates the tied
        leaf correctly (the flagship gpt2_small config ties)."""
        cfg = tfm.TransformerConfig(
            vocab_size=41, d_model=16, n_heads=4, n_layers=4, d_ff=32,
            max_len=16, tie_embeddings=tied)
        mesh = make_mesh((2, 4), ("data", "stage"),
                         devices=_all_devices(8))
        rng = np.random.default_rng(4)
        b, s = 8, 8
        tokens = rng.integers(0, cfg.vocab_size, (b, s))
        targets = rng.integers(0, cfg.vocab_size, (b, s))

        trainer = PipelineParallelTrainer(cfg, mesh, n_microbatches=2,
                                          lr=0.05, seed=11)
        losses = [trainer.fit_batch(tokens, targets) for _ in range(3)]

        ref_params, ref_losses = _single_device_steps(
            cfg, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(targets, jnp.int32), 0.05, 3, seed=11)

        np.testing.assert_allclose(losses, ref_losses, atol=1e-4)
        # compare io params (stage params are re-stacked; spot-check embed)
        np.testing.assert_allclose(
            np.asarray(trainer.io_params["embed"]),
            np.asarray(ref_params["embed"]), atol=5e-4)
        if tied:
            assert "head" not in trainer.io_params
        else:
            np.testing.assert_allclose(
                np.asarray(trainer.io_params["head"]),
                np.asarray(ref_params["head"]), atol=5e-4)
        # and the stage-sharded blocks round-trip to the layer stack
        got_w1 = np.asarray(trainer.stage_params["mlp"]["w1"]).reshape(
            cfg.n_layers, cfg.d_model, cfg.d_ff)
        want_w1 = np.stack([np.asarray(l["mlp"]["w1"])
                            for l in ref_params["layers"]])
        np.testing.assert_allclose(got_w1, want_w1, atol=5e-4)


@pytest.mark.slow  # ~16s mesh bf16 A/B; the precision plane's own
# mixed-parity suite (tests/test_precision.py) stays in tier-1
def test_bf16_compute_keeps_f32_master_params():
    """Mixed-precision contract for the hybrid trainers: with a bf16
    config the parameters live (and update) in float32 — a pure-bf16
    `w - lr*g` rounds away small updates and training silently stalls."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.parallel import make_mesh
    from deeplearning4j_tpu.parallel import transformer as tfm
    from deeplearning4j_tpu.parallel.hybrid import (
        HybridParallelTrainer,
        PipelineParallelTrainer,
    )

    rng = np.random.default_rng(0)
    cfg = tfm.TransformerConfig(vocab_size=32, d_model=16, n_heads=4,
                                n_layers=2, d_ff=32, max_len=16,
                                dtype="bfloat16")
    mesh3 = make_mesh((2, 1, 1), ("data", "seq", "model"),
                      devices=jax.devices()[:2])
    tr = HybridParallelTrainer(cfg, mesh3, lr=0.05)
    toks = rng.integers(0, 32, (4, 8))
    before = jax.tree_util.tree_leaves(tr.params)[0]
    assert before.dtype == jnp.float32
    loss = tr.fit_batch(toks, rng.integers(0, 32, (4, 8)))
    assert np.isfinite(loss)
    assert all(a.dtype == jnp.float32 or not jnp.issubdtype(
        a.dtype, jnp.floating)
        for a in jax.tree_util.tree_leaves(tr.params))

    mesh2 = make_mesh((2, 2), ("data", "stage"), devices=jax.devices()[:4])
    pipe = PipelineParallelTrainer(cfg, mesh2, n_microbatches=2, lr=0.05)
    loss = pipe.fit_batch(rng.integers(0, 32, (4, 8)),
                          rng.integers(0, 32, (4, 8)))
    assert np.isfinite(loss)
    assert all(a.dtype == jnp.float32 or not jnp.issubdtype(
        a.dtype, jnp.floating)
        for a in jax.tree_util.tree_leaves(
            (pipe.stage_params, pipe.io_params)))


class TestFlagshipPresets:
    """Param-count sanity for the GPT-2-class presets via jax.eval_shape
    (counts shapes without materializing 355M/774M floats)."""

    @pytest.mark.parametrize("maker,lo,hi", [
        ("gpt2_small", 120e6, 130e6),
        ("gpt2_medium", 345e6, 365e6),
        ("gpt2_large", 760e6, 790e6),
    ])
    def test_param_counts(self, maker, lo, hi):
        import numpy as _np

        from deeplearning4j_tpu.parallel import transformer as tfm

        cfg = getattr(tfm, maker)(max_len=64)
        shapes = jax.eval_shape(
            lambda k: tfm.init_params(cfg, k), jax.random.PRNGKey(0))
        n = sum(int(_np.prod(l.shape))
                for l in jax.tree_util.tree_leaves(shapes))
        assert lo <= n <= hi, (maker, n)
        assert cfg.tie_embeddings and cfg.remat

    def test_cli_accepts_new_presets(self):
        from deeplearning4j_tpu.cli import build_parser

        p = build_parser()
        for preset in ("gpt2-small", "gpt2-medium", "gpt2-large"):
            args = p.parse_args(["lm", "-preset", preset])
            assert args.preset == preset
