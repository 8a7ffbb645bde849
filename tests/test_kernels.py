"""Pallas flash-attention kernel tests — interpret mode on CPU; the same
kernel compiles on TPU. Gold check: match dense attention exactly."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.parallel import kernels
from deeplearning4j_tpu.parallel.kernels import (
    FlashBlockError,
    _blocks,
    _bwd_block,
    _dense_grads,
    _plan,
    _vmem_need,
    flash_attention,
    flash_enabled,
)
from deeplearning4j_tpu.parallel.ring_attention import attention


def _qkv(b=2, s=16, h=2, d=8, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
                 for _ in range(3))


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_forward_matches_dense(self, causal):
        q, k, v = _qkv()
        want = attention(q, k, v, causal=causal)
        got = flash_attention(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-6)

    def test_non_pow2_seq_len(self):
        q, k, v = _qkv(s=24)
        want = attention(q, k, v, causal=True)
        got = flash_attention(q, k, v, True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-6)

    def test_grads_match_dense(self):
        q, k, v = _qkv(s=8)

        def f(fn):
            return jax.grad(
                lambda q, k, v: jnp.sum(fn(q, k, v) ** 2),
                argnums=(0, 1, 2))(q, k, v)

        got = f(lambda q, k, v: flash_attention(q, k, v, True))
        want = f(lambda q, k, v: attention(q, k, v, causal=True))
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)

    def test_bf16_inputs_forward_and_grads(self):
        """bf16 q/k/v — the dtype the TPU bench rows actually run.  The
        operands enter every matmul as bf16 (p and dS too), products
        accumulate in f32 and the outputs are stored bf16, so the kernel
        should track the f32 oracle to bf16 resolution (~1e-2)."""
        q32, k32, v32 = _qkv(s=32, d=16, seed=3)
        q, k, v = (x.astype(jnp.bfloat16) for x in (q32, k32, v32))
        out = flash_attention(q, k, v, True)
        assert out.dtype == jnp.bfloat16
        want = attention(q32, k32, v32, causal=True)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want), atol=2e-2)
        grads = jax.grad(
            lambda q, k, v: jnp.sum(
                flash_attention(q, k, v, True).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        ref = jax.grad(
            lambda q, k, v: jnp.sum(attention(q, k, v, causal=True) ** 2),
            argnums=(0, 1, 2))(q32, k32, v32)
        for a, b in zip(grads, ref):
            assert a.dtype == jnp.bfloat16
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b), atol=0.15, rtol=0.1)

    @pytest.mark.parametrize("causal,s", [(True, 64), (False, 64),
                                          (True, 24), (False, 40)])
    def test_fused_backward_matches_dense(self, causal, s):
        """The FlashAttention-2 bwd kernels vs autodiff through dense
        attention, at sizes that exercise multi-block loops and the causal
        block-skip bounds."""
        q, k, v = _qkv(s=s, seed=3)

        def f(fn):
            return jax.grad(
                lambda q, k, v: jnp.sum(fn(q, k, v) ** 2),
                argnums=(0, 1, 2))(q, k, v)

        got = f(lambda q, k, v: flash_attention(q, k, v, causal))
        want = f(lambda q, k, v: attention(q, k, v, causal=causal))
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-5)

    def test_fused_backward_equals_dense_recompute_path(self, monkeypatch):
        """DL4J_TPU_FLASH_BWD=0 selects the dense-recompute VJP; both
        backwards must agree."""
        q, k, v = _qkv(s=32, seed=5)

        def g():
            return jax.grad(lambda q, k, v: jnp.sum(
                flash_attention(q, k, v, True) * 0.5), (0, 1, 2))(q, k, v)

        fused = g()
        monkeypatch.setenv("DL4J_TPU_FLASH_BWD", "0")
        dense = g()
        for a, b in zip(fused, dense):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-6)

    def test_blocks_are_divisors_largest_first(self):
        assert _blocks(256, 128)[0] == 128
        assert _blocks(24, 128)[0] == 24
        assert _blocks(100, 128)[0] == 100
        assert _blocks(384, 128) == [128, 96, 64, 48, 32, 24, 16, 12, 8, 6,
                                     4, 3, 2, 1]

    def test_flash_enabled_env_override(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_FLASH", "1")
        assert flash_enabled()
        monkeypatch.setenv("DL4J_TPU_FLASH", "0")
        assert not flash_enabled()

    def test_transformer_uses_flash_when_forced(self, monkeypatch):
        from deeplearning4j_tpu.parallel import transformer as tfm

        cfg = tfm.TransformerConfig(vocab_size=17, d_model=16, n_heads=2,
                                    n_layers=1, d_ff=32, max_len=16)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        tokens = jnp.asarray(
            np.random.default_rng(0).integers(0, 17, (2, 8)), jnp.int32)
        monkeypatch.setenv("DL4J_TPU_FLASH", "0")
        dense_logits = tfm.apply(cfg, params, tokens)
        monkeypatch.setenv("DL4J_TPU_FLASH", "1")
        flash_logits = tfm.apply(cfg, params, tokens)
        np.testing.assert_allclose(np.asarray(flash_logits),
                                   np.asarray(dense_logits), atol=1e-4)

    @pytest.mark.slow  # ~13s full-transformer integration; the
    # kernel-level flash/ring parities above stay in tier-1
    def test_meshed_transformer_flash_ring_matches_plain_ring(
            self, monkeypatch):
        """With a seq-sharded mesh, forcing flash selects the Pallas ring
        path; loss and grads must match the plain-jnp ring."""
        from deeplearning4j_tpu.parallel import make_mesh
        from deeplearning4j_tpu.parallel import transformer as tfm

        mesh = make_mesh((1, 2, 1), ("data", "seq", "model"),
                         devices=jax.devices()[:2])
        cfg = tfm.TransformerConfig(vocab_size=17, d_model=16, n_heads=2,
                                    n_layers=1, d_ff=32, max_len=16)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        tokens = jnp.asarray(rng.integers(0, 17, (2, 8)), jnp.int32)
        targets = jnp.asarray(rng.integers(0, 17, (2, 8)), jnp.int32)

        def loss_and_grad():
            return jax.value_and_grad(
                lambda p: tfm.lm_loss(cfg, p, tokens, targets, mesh))(params)

        monkeypatch.setenv("DL4J_TPU_FLASH", "0")
        l0, g0 = loss_and_grad()
        monkeypatch.setenv("DL4J_TPU_FLASH", "1")
        l1, g1 = loss_and_grad()
        np.testing.assert_allclose(float(l1), float(l0), atol=1e-5)
        for a, b in zip(jax.tree_util.tree_leaves(g1),
                        jax.tree_util.tree_leaves(g0)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4)

    @pytest.mark.parametrize("seq_chips", [1, 2], ids=["one-device", "ring"])
    def test_remat_does_not_run_the_forward_kernel_again(self, monkeypatch,
                                                         seq_chips):
        """A block's checkpoint saves the forward kernel's output and row
        statistics (`kernels.SAVED_NAMES`), so the gradient of a rematted
        model launches the kernels of a plain one: a forward, a dK/dV and
        a dQ for every block pair of the ring, a layer."""
        from deeplearning4j_tpu.parallel import make_mesh
        from deeplearning4j_tpu.parallel import transformer as tfm

        monkeypatch.setenv("DL4J_TPU_FLASH", "1")
        mesh = None if seq_chips == 1 else make_mesh(
            (1, seq_chips, 1), ("data", "seq", "model"),
            devices=jax.devices()[:seq_chips])
        tokens = jnp.asarray(
            np.random.default_rng(0).integers(0, 17, (2, 8)), jnp.int32)

        def launches(jaxpr):
            return sum(
                (e.primitive.name == "pallas_call")
                + sum(launches(j) for j in jax.core.jaxprs_in_params(e.params))
                for e in jaxpr.eqns)

        def grad_launches(remat):
            cfg = tfm.TransformerConfig(
                vocab_size=17, d_model=16, n_heads=2, n_layers=2, d_ff=32,
                max_len=16, remat=remat)
            params = tfm.init_params(cfg, jax.random.PRNGKey(0))
            return launches(jax.make_jaxpr(jax.grad(
                lambda p: tfm.lm_loss(cfg, p, tokens, tokens, mesh)))(
                    params).jaxpr)

        assert grad_launches(True) == grad_launches(False) \
            == 2 * 3 * seq_chips


# ---------------------------------------------------------------------------
# One parity test over what the kernels adapt to (ISSUE 27): operand dtype,
# mask, and the block structure — one block, several owner blocks, several
# tiles a chunk, several chunks a head (the clamped index maps), owner and
# tile sizes that do not divide each other, and head sizes 64 and 128.

# name: (S, head size, DL4J_TPU_FLASH_BQ, DL4J_TPU_FLASH_BK, scoped VMEM)
_PARITY_SHAPES = {
    "one_block": (8, 8, None, None, None),
    "owner_blocks": (64, 8, 16, 64, None),           # 4 owners | 4 tiles
    "tiles": (64, 8, 16, 8, None),                   # 8 / 4 tiles a chunk
    "chunks": (64, 8, 8, 16, 1),                     # a tile a chunk
    "odd_blocks": (120, 8, 40, 24, None),            # S 1000 -> 40's kind
    "odd_chunks": (120, 8, 24, 40, 1),
    "d64": (32, 64, None, None, None),               # half a lane tile
    "d128": (32, 128, None, None, None),
}


def _set_blocks(monkeypatch, bq, bk, vmem):
    for side, val in (("Q", bq), ("K", bk)):
        if val is None:
            monkeypatch.delenv(f"DL4J_TPU_FLASH_B{side}", raising=False)
        else:
            monkeypatch.setenv(f"DL4J_TPU_FLASH_B{side}", str(val))
    if vmem is not None:
        monkeypatch.setattr(kernels, "_DEFAULT_SCOPED_VMEM", vmem)


def _tolerances(dtype):
    """(forward atol, gradient atol, gradient rtol): f32 at the values the
    suite has always held it to; bf16 at its resolution, 2^-8 of values
    up to 3 forward and up to 8 in a gradient, against the f32 oracle on
    the same rounded inputs."""
    if dtype == jnp.float32:
        return 2e-6, 2e-5, 0.0
    return 2e-2, 6e-2, 2e-2


@pytest.mark.parametrize("shape", list(_PARITY_SHAPES))
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_flash_parity_forward_and_all_gradients(shape, causal, dtype,
                                                monkeypatch):
    s, d, bq, bk, vmem = _PARITY_SHAPES[shape]
    _set_blocks(monkeypatch, bq, bk, vmem)
    q, k, v = (x.astype(dtype) for x in _qkv(b=1, s=s, h=2, d=d, seed=s + d))
    w = _qkv(b=1, s=s, h=2, d=d, seed=1)[0]
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    fwd_atol, atol, rtol = _tolerances(dtype)

    got = flash_attention(q, k, v, causal)
    assert got.dtype == dtype
    want = attention(*f32, causal=causal)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), atol=fwd_atol)

    grads = jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, causal).astype(jnp.float32) * w),
        (0, 1, 2))(q, k, v)
    ref = jax.grad(lambda q, k, v: jnp.sum(
        attention(q, k, v, causal=causal) * w), (0, 1, 2))(*f32)
    for a, b in zip(grads, ref):
        assert a.dtype == dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b), atol=atol, rtol=rtol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("blocks", [(None, None, None), (8, 16, 1)],
                         ids=["derived", "tiles_and_chunks"])
def test_bwd_block_takes_the_rings_global_stats(dtype, blocks, monkeypatch):
    """The ring's case: the second of two sequence shards holds q rows
    S/2..S and meets k/v block 0 whole (non-causal) and block 1 on the
    diagonal (causal), each through `_bwd_block` with the GLOBAL lse and
    delta of its q rows; the pieces add up to dense attention's grads."""
    _set_blocks(monkeypatch, *blocks)
    s, h, d = 64, 2, 8
    half = s // 2
    q, k, v = (x.astype(dtype) for x in _qkv(b=1, s=s, h=h, d=d, seed=9))
    g = _qkv(b=1, s=s, h=h, d=d, seed=10)[0].astype(dtype)
    g = g.at[:, :half].set(0)                  # only shard 1's rows count
    f32 = [x.astype(jnp.float32) for x in (q, k, v, g)]
    want_dq, want_dk, want_dv = _dense_grads(*f32[:3], True, f32[3])

    scores = jnp.einsum("bqhd,bkhd->bhqk", f32[0], f32[1]) / d ** 0.5
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -1e30)
    lse = jax.nn.logsumexp(scores, axis=-1)[0, :, half:]       # [H, S/2]
    out = attention(*f32[:3], causal=True)
    delta = jnp.sum(out * f32[3], -1)[0, half:].T              # [H, S/2]

    q1, g1 = q[:, half:], g[:, half:]
    dq0, dk0, dv0 = _bwd_block(q1, k[:, :half], v[:, :half], g1, lse, delta,
                               False, True)
    dq1, dk1, dv1 = _bwd_block(q1, k[:, half:], v[:, half:], g1, lse, delta,
                               True, True)
    _, atol, rtol = _tolerances(dtype)
    for got, want in (
            (dq0.astype(jnp.float32) + dq1.astype(jnp.float32),
             want_dq[:, half:]),
            (dk0, want_dk[:, :half]), (dk1, want_dk[:, half:]),
            (dv0, want_dv[:, :half]), (dv1, want_dv[:, half:])):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want), atol=atol, rtol=rtol)


@pytest.mark.parametrize("itemsize", [2, 4])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("s", [512, 1000, 1024, 4096, 16384])
def test_block_plan_divides_tiles_and_fits(s, d, itemsize):
    """The block derivation alone, as a compiled call makes it: every size
    divides S and is a multiple of 8, the streamed chunk is whole tiles,
    and the reckoned VMEM is inside the limit handed to the compiler —
    the default scoped budget unless the smallest legal blocks pass it."""
    for kind in ("fwd", "dkv", "dq"):
        bo, ts, bs, limit = _plan(kind, s, d, itemsize, interpret=False)
        for b in (bo, ts, bs):
            assert s % b == 0 and b % 8 == 0, (kind, bo, ts, bs)
        assert bs % ts == 0
        need = _vmem_need(kind, d, itemsize, bo, ts, bs)
        assert need <= limit, (kind, bo, ts, bs, need, limit)
        assert limit == kernels._DEFAULT_SCOPED_VMEM or bs == ts
        assert limit <= 64 << 20


@pytest.mark.parametrize("s", [100, 1001])
def test_block_plan_refuses_a_length_with_no_sublane_tile(s):
    for kind in ("fwd", "dkv", "dq"):
        with pytest.raises(FlashBlockError, match=f"sequence length {s}"):
            _plan(kind, s, 64, 2, interpret=False)
    assert _plan("fwd", s, 64, 2, interpret=True)[0] > 0   # the interpreter


# ---------------------------------------------------------------------------
# Paged-attention kernel (ISSUE-18): the fused block-table walk vs. the
# gather oracle, plus the dtype-aware mask constant it rides on.

from deeplearning4j_tpu.parallel.generation import (  # noqa: E402
    _paged_attn,
    _write_fed_rows,
    init_paged_cache,
    kv_rows_by_kernel,
    paged_forward,
    pool_layout,
    spec_verify_step,
)
from deeplearning4j_tpu.parallel.kernels import mask_value  # noqa: E402
from deeplearning4j_tpu.parallel import paged_kernel as pk  # noqa: E402
from deeplearning4j_tpu.parallel.paged_kernel import (  # noqa: E402
    _pages_per_block,
    _writer_blocks,
    paged_flash_attention,
    resolve_paged_kernel,
    write_kv_rows,
)


def _paged_state(b, c, h, kd, ps, mp, pos, seed=0, dtype=jnp.float32):
    """Random page-pool state: pool big enough for every lane's live
    pages to be DISTINCT physical pages; block tables cover each lane
    through pos+C-1 and point at the null page past it."""
    rng = np.random.default_rng(seed)
    pages = 1 + b * mp
    q = jnp.asarray(rng.standard_normal((b, c, h, kd)), dtype)
    kp = jnp.asarray(rng.standard_normal((pages, ps, h, kd)), dtype)
    vp = jnp.asarray(rng.standard_normal((pages, ps, h, kd)), dtype)
    table = np.zeros((b, mp), np.int32)
    for i in range(b):
        need = min(mp, (int(pos[i]) + c - 1) // ps + 1)
        table[i, :need] = 1 + i * mp + np.arange(need)
    return q, kp, vp, jnp.asarray(table), jnp.asarray(pos, jnp.int32)


def _gather_oracle(q, kp, vp, table, pos):
    """The `_paged_attn` gather path's attention math, verbatim: full
    MP*ps history buffer + masked softmax."""
    b, c, h, kd = q.shape
    pages, ps = kp.shape[:2]
    mp = table.shape[1]
    gidx = (table[:, :, None] * ps
            + jnp.arange(ps)[None, None, :]).reshape(b, mp * ps)
    hk = kp.reshape(pages * ps, h, kd)[gidx]
    hv = vp.reshape(pages * ps, h, kd)[gidx]
    s = jnp.einsum("bqhk,bshk->bqhs", q, hk) / jnp.sqrt(
        jnp.asarray(kd, q.dtype))
    wpos = pos[:, None] + jnp.arange(c)[None, :]
    causal = jnp.arange(mp * ps)[None, None, :] <= wpos[:, :, None]
    s = jnp.where(causal[:, :, None, :], s, mask_value(s.dtype))
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqhs,bshk->bqhk", w, hv)


def _assert_fed_columns_match(got, want, n_feed, atol=1e-5):
    for i in range(got.shape[0]):
        nf = int(n_feed[i])
        if nf:
            np.testing.assert_allclose(np.asarray(got)[i, :nf],
                                       np.asarray(want)[i, :nf],
                                       atol=atol)


@pytest.mark.paged_kernel
class TestPagedFlashAttention:
    """Kernel-vs-gather parity at the attention level: the kernel must
    reproduce the oracle's masked softmax at every FED column (padding
    columns are never consumed by any caller)."""

    def test_c1_decode_ragged_positions(self):
        """C=1 decode with lanes at a page boundary, mid-page, the last
        row of a page, and deep history — the decode dispatch shape."""
        ps, mp = 4, 8
        pos = np.array([0, 5, 3, 23], np.int32)
        q, kp, vp, table, posj = _paged_state(4, 1, 2, 8, ps, mp, pos)
        nf = jnp.ones((4,), jnp.int32)
        got = paged_flash_attention(q, kp, vp, table, posj, nf)
        want = _gather_oracle(q, kp, vp, table, posj)
        _assert_fed_columns_match(got, want, nf)

    def test_chunk_straddles_page_boundary(self):
        """C>1 chunked feed whose write window crosses a page edge:
        intra-chunk causal masking must match the oracle column by
        column (the chunked-prefill / verify dispatch shape)."""
        ps, mp, c = 4, 6, 5
        pos = np.array([2, 3, 7], np.int32)     # straddle 1 and 2 pages
        q, kp, vp, table, posj = _paged_state(3, c, 2, 8, ps, mp, pos,
                                              seed=1)
        nf = jnp.full((3,), c, jnp.int32)
        got = paged_flash_attention(q, kp, vp, table, posj, nf)
        want = _gather_oracle(q, kp, vp, table, posj)
        _assert_fed_columns_match(got, want, nf)

    def test_ragged_n_feed(self):
        """Lanes feeding fewer than C columns (mixed chunk tails): every
        fed column exact; padding columns are unconsumed by contract."""
        ps, mp, c = 4, 6, 4
        pos = np.array([9, 1, 14, 0], np.int32)
        q, kp, vp, table, posj = _paged_state(4, c, 2, 8, ps, mp, pos,
                                              seed=2)
        nf = jnp.asarray([4, 2, 1, 3], jnp.int32)
        got = paged_flash_attention(q, kp, vp, table, posj, nf)
        want = _gather_oracle(q, kp, vp, table, posj)
        _assert_fed_columns_match(got, want, nf)

    def test_null_page_lane(self):
        """An inactive lane (all-null table, pos=0, n_feed=0) rides the
        dispatch without a page visit: zeros out (no column of it is
        consumed; `paged_decode_step` samples its column 0 for a lane
        the scheduler ignores), and the live lanes around it are
        untouched by its presence."""
        ps, mp, c = 4, 4, 2
        pos = np.array([0, 6], np.int32)
        q, kp, vp, table, posj = _paged_state(2, c, 2, 8, ps, mp, pos,
                                              seed=3)
        table = table.at[0].set(0)              # lane 0: nothing live
        nf = jnp.asarray([0, 2], jnp.int32)
        got = paged_flash_attention(q, kp, vp, table, posj, nf)
        want = _gather_oracle(q, kp, vp, table, posj)
        assert not np.asarray(got)[0].any()
        _assert_fed_columns_match(got, want, nf)

    def test_property_random_shapes(self):
        """Property-style sweep: random (ps, mp, B, C, H, K, pos,
        n_feed) draws — the kernel tracks the oracle at every fed
        column on every draw."""
        rng = np.random.default_rng(7)
        for case in range(8):
            ps = int(rng.choice([2, 4, 8]))
            mp = int(rng.integers(2, 7))
            b = int(rng.integers(1, 4))
            c = int(rng.integers(1, 5))
            h = int(rng.choice([1, 2]))
            kd = int(rng.choice([4, 8]))
            hi = max(1, ps * mp - c)
            pos = rng.integers(0, hi, (b,)).astype(np.int32)
            q, kp, vp, table, posj = _paged_state(
                b, c, h, kd, ps, mp, pos, seed=100 + case)
            nf = jnp.asarray(rng.integers(0, c + 1, (b,)), jnp.int32)
            got = paged_flash_attention(q, kp, vp, table, posj, nf)
            want = _gather_oracle(q, kp, vp, table, posj)
            _assert_fed_columns_match(got, want, nf)

    def test_bf16_pool(self):
        """bf16 pool + queries (the TPU serving dtype): kernel output
        is bf16 and tracks the f32 oracle to bf16 resolution."""
        ps, mp, c = 4, 4, 2
        pos = np.array([5, 9], np.int32)
        q, kp, vp, table, posj = _paged_state(2, c, 2, 8, ps, mp, pos,
                                              seed=4)
        nf = jnp.full((2,), c, jnp.int32)
        got = paged_flash_attention(q.astype(jnp.bfloat16),
                                    kp.astype(jnp.bfloat16),
                                    vp.astype(jnp.bfloat16),
                                    table, posj, nf)
        assert got.dtype == jnp.bfloat16
        want = _gather_oracle(q, kp, vp, table, posj)
        _assert_fed_columns_match(got.astype(jnp.float32), want, nf,
                                  atol=2e-2)


# The page walk inside the kernel's body (ISSUE 29): every case runs on a
# stacked pool whose dead table entries are out of range and whose pages
# that no live entry of the layer names hold NaN (the other layers' pages
# among them), so a dead page read, a wrong layer, or a buffer row that no
# DMA wrote but a matmul multiplied, shows as a NaN or a fault.  Lanes are
# (pos, n_feed); `_WALK_G` is the kernel's own pages per block at the
# cases' shapes.
_WALK_PS, _WALK_MP = 16, 12
_WALK_G = _pages_per_block(_WALK_PS, 16, 4, _WALK_MP, True)


def _walk_history(pages):
    """One lane whose history is `pages` pages long, ending mid-page."""
    return [(pages * _WALK_PS - 7, 1)]


_WALK_CASES = {
    # name: (lanes [(pos, n_feed)], width, layers, layer, dtype)
    "dead_entries_and_nan_pool": (
        [(0, 1), (37, 1), (5, 1), (150, 1)], 1, 2, 1, "float32"),
    "dead_entries_and_nan_pool_bf16": (
        [(0, 8), (37, 3), (150, 8)], 8, 2, 0, "bfloat16"),
    "idle_lanes_between_busy": (
        [(0, 0), (21, 4), (0, 0), (0, 0), (130, 2), (0, 0)],
        4, 1, 0, "float32"),
    "history_1_page": (_walk_history(1), 1, 1, 0, "float32"),
    "history_g_minus_1_pages": (
        _walk_history(_WALK_G - 1), 1, 1, 0, "float32"),
    "history_g_pages": (_walk_history(_WALK_G), 1, 1, 0, "float32"),
    "history_g_plus_1_pages": (
        _walk_history(_WALK_G + 1), 1, 1, 0, "float32"),
    "history_max_pages": (_walk_history(_WALK_MP), 1, 1, 0, "float32"),
    "pos_mod_ps_0": ([(3 * _WALK_PS, 1), (0, 1)], 1, 1, 0, "float32"),
    "pos_mod_ps_1": ([(3 * _WALK_PS + 1, 1), (1, 1)], 1, 1, 0, "float32"),
    "pos_mod_ps_last": ([(4 * _WALK_PS - 1, 1), (_WALK_PS - 1, 1)],
                        1, 1, 0, "float32"),
    "width_1": ([(9, 1), (140, 1)], 1, 1, 0, "float32"),
    "width_5_verify_feed": (
        [(12, 5), (139, 5), (30, 1)], 5, 1, 0, "float32"),
    "width_8": ([(12, 8), (120, 8)], 8, 1, 0, "float32"),
    "ragged_n_feed_wide_round": (
        [(10, 8), (15, 1), (126, 3), (0, 0), (64, 7)], 8, 1, 0, "float32"),
    "layer_first": ([(9, 2), (140, 3)], 3, 3, 0, "float32"),
    "layer_last": ([(9, 2), (140, 3)], 3, 3, 2, "float32"),
}


def _walk_pool(name, lanes, c, ps, mp, h, kd, row, n_layers, layer, dtype):
    """A walk case's operands: queries `[B, C, h, kd]`, a stacked pool
    `[L, P, ps, row]` as it should read (`clean`) and as the kernel gets it
    (`dirty`: NaN in every page no live entry of `layer` names), and the
    block tables to match (dead entries 0, or out of range)."""
    b = len(lanes)
    pos = np.array([p for p, _ in lanes], np.int32)
    nf = np.array([f for _, f in lanes], np.int32)
    rng = np.random.default_rng(len(name))
    pages = 1 + b * mp
    shape = (n_layers, pages, ps, row)
    q = jnp.asarray(rng.standard_normal((b, c, h, kd)), dtype)
    clean = [rng.standard_normal(shape).astype(np.float32) for _ in "kv"]
    if dtype == "bfloat16":     # the oracle sees what the pool holds
        clean = [np.asarray(jnp.asarray(a, dtype), np.float32) for a in clean]
    clean_table = np.zeros((b, mp), np.int32)
    dirty_table = np.full((b, mp), 1 << 20, np.int32)
    live = np.zeros((pages,), bool)
    for i in range(b):
        if nf[i]:
            n = (pos[i] + nf[i] - 1) // ps + 1
            assert n <= mp
            ids = 1 + i * mp + rng.permutation(mp)[:n]
            clean_table[i, :n] = dirty_table[i, :n] = ids
            live[ids] = True
    dirty = [np.full(shape, np.nan, np.float32) for _ in "kv"]
    for d, a in zip(dirty, clean):
        d[layer, live] = a[layer, live]
    return (q, [jnp.asarray(a[layer]) for a in clean],
            [jnp.asarray(d, dtype) for d in dirty], jnp.asarray(clean_table),
            jnp.asarray(dirty_table), jnp.asarray(pos), jnp.asarray(nf))


def _assert_walk_matches(got, want, nf, dtype):
    got = np.asarray(got.astype(jnp.float32))
    assert np.isfinite(got).all()
    assert not got[np.asarray(nf) == 0].any()   # an idle lane: zeros
    _assert_fed_columns_match(got, want, nf,
                              atol=2e-2 if dtype == "bfloat16" else 1e-5)


@pytest.mark.paged_kernel
@pytest.mark.parametrize("case", list(_WALK_CASES))
def test_page_walk_reads_live_pages_only(case):
    lanes, c, n_layers, layer, dtype = _WALK_CASES[case]
    assert 1 < _WALK_G - 1 and _WALK_G + 1 < _WALK_MP  # five histories
    ps, mp, h, kd = _WALK_PS, _WALK_MP, 2, 8
    q, clean, dirty, clean_table, dirty_table, pos, nf = _walk_pool(
        case, lanes, c, ps, mp, h, kd, h * kd, n_layers, layer, dtype)
    got = paged_flash_attention(q, *dirty, dirty_table, pos, nf, layer=layer)
    want = _gather_oracle(q.astype(jnp.float32),
                          *(a.reshape(-1, ps, h, kd) for a in clean),
                          clean_table, pos)
    _assert_walk_matches(got, want, nf, dtype)


@pytest.mark.paged_kernel
@pytest.mark.parametrize("shape, want", [
    # (ps, H*K, itemsize, max_pages, interpret) -> pages a block
    ((16, 1280, 2, 64, False), 8),      # the serve cells: 128 keys
    ((32, 1280, 2, 32, False), 4),
    ((128, 1280, 2, 8, False), 1),      # a page is a block by itself
    ((16, 1280, 2, 3, False), 3),       # never wider than the table
    ((8, 1280, 4, 128, False), 16),
    ((4, 16, 4, 6, False), 1),          # compiled: no DMA inside a tile
    ((4, 16, 4, 6, True), 6),           # the interpreter takes any page
    ((16, 16384, 2, 64, False), 2),     # the buffers stay inside VMEM
    # the grouped kernel's rows, `Hkv * K` wide
    ((16, 512, 2, 128, False), 8),      # SDAR-30B-A3B: 4 K/V heads of 128
    ((128, 1024, 2, 192, False), 1),    # Solar-Open2: the page is a block
])
def test_pages_per_block_comes_from_the_shapes(shape, want):
    assert _pages_per_block(*shape) == want


# The grouped kernel's walk (ISSUE 43): fewer K/V heads than query heads,
# or the block mask, several pages a block.  The same stacked pool as
# above: dead table entries out of range, NaN in every page that no live
# entry of the layer names.  Lanes are (pos, n_feed); `_GROUPED_G` is the
# kernel's own pages a block at the cases' shapes.
_GROUPED_PS, _GROUPED_MP = 16, 18
_GROUPED_H, _GROUPED_HKV, _GROUPED_KD = 4, 2, 16
_GROUPED_G = _pages_per_block(_GROUPED_PS, _GROUPED_HKV * _GROUPED_KD, 4,
                              _GROUPED_MP, True)


def _grouped_history(pages, feed=1):
    """One lane whose history is `pages` pages long, ending mid-page at
    the end of a block of 4: its last fed row is row 11 of the page."""
    return [(pages * _GROUPED_PS - 4 - feed, feed)]


_GROUPED_WALK_CASES = {
    # name: (lanes [(pos, n_feed)], width, block, layers, layer, dtype)
    **{f"history_{name}_pages": (_grouped_history(n), 1, 1, 1, 0, "float32")
       for name, n in (("1", 1), ("g_minus_1", _GROUPED_G - 1),
                       ("g", _GROUPED_G), ("g_plus_1", _GROUPED_G + 1),
                       ("2g_plus_1", 2 * _GROUPED_G + 1))},
    **{f"block_4_history_{name}_pages": (
        _grouped_history(n, 4), 4, 4, 1, 0, "float32")
       for name, n in (("1", 1), ("g_minus_1", _GROUPED_G - 1),
                       ("g", _GROUPED_G), ("g_plus_1", _GROUPED_G + 1),
                       ("2g_plus_1", 2 * _GROUPED_G + 1))},
    "width_1_dead_entries_and_nan_pool": (
        [(0, 1), (37, 1), (5, 1), (150, 1)], 1, 1, 2, 1, "float32"),
    "width_4_causal": ([(12, 4), (139, 3), (30, 1)], 4, 1, 1, 0, "float32"),
    "width_16_causal_ragged": (
        [(10, 16), (15, 1), (126, 3), (0, 0), (200, 9)], 16, 1, 1, 0,
        "float32"),
    "width_16_block_4_on_a_boundary": (
        [(128, 16), (0, 16), (268, 4), (16, 8)], 16, 4, 1, 0, "float32"),
    "width_16_block_4_off_a_boundary": (
        [(130, 16), (1, 6), (267, 4), (15, 3)], 16, 4, 1, 0, "float32"),
    "width_4_block_4_off_a_boundary": (
        [(126, 4), (3, 2), (255, 4)], 4, 4, 1, 0, "float32"),
    "idle_lanes_between_busy": (
        [(0, 0), (21, 4), (0, 0), (0, 0), (130, 2), (0, 0)],
        4, 4, 1, 0, "float32"),
    "query_blocks_of_8_width_40": (
        [(100, 40), (3, 17), (0, 0), (240, 33)], 40, 1, 1, 0, "float32"),
    "layer_last_of_3": ([(9, 2), (140, 4)], 4, 4, 3, 2, "float32"),
    "bf16_width_1": ([(0, 1), (37, 1), (150, 1)], 1, 1, 2, 0, "bfloat16"),
    "bf16_width_16_block_4": (
        [(128, 16), (0, 0), (4, 8), (264, 4)], 16, 4, 2, 1, "bfloat16"),
}


def _grouped_oracle(q, kp, vp, table, pos, nf, block):
    """`generation._grouped_paged_attn`'s gather path over one layer's
    pages `[P, ps, Hkv, K]`: query head j reads K/V head `j // G`; the
    column at absolute position p sees the rows
    `< min(pos + n_feed, (p // block + 1) * block)`."""
    b, c, h, kd = q.shape
    pages, ps, hkv, _ = kp.shape
    mp = table.shape[1]
    gidx = (table[:, :, None] * ps
            + jnp.arange(ps)[None, None, :]).reshape(b, mp * ps)
    hk = kp.reshape(pages * ps, hkv, kd)[gidx]
    hv = vp.reshape(pages * ps, hkv, kd)[gidx]
    sc = jnp.einsum("bcngk,bsnk->bcngs", q.reshape(b, c, hkv, h // hkv, kd),
                    hk) * kd ** -0.5
    wpos = pos[:, None] + jnp.arange(c)[None, :]
    sees = jnp.minimum((wpos // block + 1) * block, (pos + nf)[:, None]) - 1
    seen = jnp.arange(mp * ps)[None, None, :] <= sees[:, :, None]
    sc = jnp.where(seen[:, :, None, None, :], sc, mask_value(sc.dtype))
    return jnp.einsum("bcngs,bsnk->bcngk", jax.nn.softmax(sc, axis=-1),
                      hv).reshape(b, c, h, kd)


@pytest.mark.paged_kernel
@pytest.mark.parametrize("case", list(_GROUPED_WALK_CASES))
def test_grouped_page_walk_reads_live_pages_only(case):
    lanes, c, block, n_layers, layer, dtype = _GROUPED_WALK_CASES[case]
    assert 1 < _GROUPED_G - 1 and 2 * _GROUPED_G + 1 < _GROUPED_MP
    ps, mp = _GROUPED_PS, _GROUPED_MP
    h, hkv, kd = _GROUPED_H, _GROUPED_HKV, _GROUPED_KD
    q, clean, dirty, clean_table, dirty_table, pos, nf = _walk_pool(
        case, lanes, c, ps, mp, h, kd, hkv * kd, n_layers, layer, dtype)
    got = paged_flash_attention(q, *dirty, dirty_table, pos, nf, layer=layer,
                                block=block)
    want = _grouped_oracle(q.astype(jnp.float32),
                           *(a.reshape(-1, ps, hkv, kd) for a in clean),
                           clean_table, pos, nf, block)
    assert got.shape == q.shape and got.dtype == q.dtype
    _assert_walk_matches(got, want, nf, dtype)


@pytest.mark.paged_kernel
class TestPagedKernelFullStack:
    """Parity through the REAL transformer stack: `paged_forward` and
    `spec_verify_step` with paged_kernel on vs. off — the exact
    programs `make_paged_step`/`make_spec_step` jit."""

    def _cfg(self, max_len=32):
        from deeplearning4j_tpu.parallel import transformer as tfm

        cfg = tfm.TransformerConfig(vocab_size=50, d_model=16,
                                    n_heads=2, n_layers=2, d_ff=32,
                                    max_len=max_len)
        return cfg, tfm.init_params(cfg, jax.random.PRNGKey(0))

    def _state(self, cfg, b, ps, seed=0):
        from deeplearning4j_tpu.parallel.generation import pages_per_seq

        mp = pages_per_seq(cfg, ps)
        pages = 1 + b * mp
        cache = init_paged_cache(cfg, pages, ps)
        rng = np.random.default_rng(seed)
        cache = {
            "k": jnp.asarray(rng.standard_normal(cache["k"].shape),
                             cache["k"].dtype),
            "v": jnp.asarray(rng.standard_normal(cache["v"].shape),
                             cache["v"].dtype)}
        table = np.zeros((b, mp), np.int32)
        for i in range(b):
            table[i] = 1 + i * mp + np.arange(mp)
        return cache, jnp.asarray(table), mp

    def test_paged_forward_decode_and_chunk(self):
        cfg, params = self._cfg()
        for c, pos, nf, seed in [
            (1, [0, 7, 13], [1, 1, 1], 0),        # decode dispatch
            (4, [0, 6, 11], [4, 3, 2], 1),        # chunked prefill
        ]:
            b = len(pos)
            cache, table, _ = self._state(cfg, b, ps=4, seed=seed)
            pos = jnp.asarray(pos, jnp.int32)
            nf = jnp.asarray(nf, jnp.int32)
            toks = jnp.asarray(
                np.random.default_rng(seed).integers(
                    0, cfg.vocab_size, (b, c)), jnp.int32)
            lo, co = paged_forward(cfg, params, dict(cache), table, pos,
                                   nf, toks, paged_kernel=False)
            lk, ck = paged_forward(cfg, params, dict(cache), table, pos,
                                   nf, toks, paged_kernel=True)
            _assert_fed_columns_match(lk, lo, np.asarray(nf), atol=1e-5)
            # the kernel's program writes by the row writer at either
            # width, the oracle by the scatter (`kv_rows_by_kernel`);
            # deeper layers' writes inherit the previous layer's rounding,
            # so tolerance not equality.  The null page is the scatter's
            # alone to write
            assert kv_rows_by_kernel(True, 2, 4, 16)
            np.testing.assert_allclose(np.asarray(ck["k"])[:, 1:],
                                       np.asarray(co["k"])[:, 1:], atol=1e-5)
            np.testing.assert_allclose(np.asarray(ck["v"])[:, 1:],
                                       np.asarray(co["v"])[:, 1:], atol=1e-5)

    def test_spec_verify_parity(self):
        """The speculative verify dispatch: bonus logits AND per-lane
        accepted counts agree between kernel and oracle."""
        cfg, params = self._cfg()
        b, w = 3, 4
        cache, table, _ = self._state(cfg, b, ps=4, seed=5)
        pos = jnp.asarray([3, 9, 0], jnp.int32)
        nf = jnp.asarray([4, 3, 1], jnp.int32)     # verify, verify, decode
        nd = jnp.asarray([3, 2, 0], jnp.int32)
        toks = jnp.asarray(np.random.default_rng(5).integers(
            0, cfg.vocab_size, (b, w)), jnp.int32)
        bo, ao, _ = spec_verify_step(cfg, params, dict(cache), table,
                                     pos, nf, nd, toks,
                                     paged_kernel=False)
        bk, ak, _ = spec_verify_step(cfg, params, dict(cache), table,
                                     pos, nf, nd, toks,
                                     paged_kernel=True)
        np.testing.assert_allclose(np.asarray(bk), np.asarray(bo),
                                   atol=1e-5)
        np.testing.assert_array_equal(np.asarray(ak), np.asarray(ao))

    def test_layer_level_paged_attn_switch(self):
        """`_paged_attn` itself: the two switch positions write the same
        rows (the scatter, the row writer) and agree at fed columns (C=1
        and C=3)."""
        cfg, params = self._cfg()
        layer = params["layers"][0]["attn"]
        for c, seed in [(1, 0), (3, 1)]:
            b, ps, mp, h, kd = 2, 4, 8, cfg.n_heads, cfg.head_dim
            rng = np.random.default_rng(seed)
            x = jnp.asarray(rng.standard_normal((b, c, cfg.d_model)),
                            jnp.float32)
            _, kp, vp, table, pos = _paged_state(
                b, c, h, kd, ps, mp, np.array([5, 2], np.int32),
                seed=seed)
            nf = jnp.full((b,), c, jnp.int32)
            # the layer works on the stacked pool [L, P, ps, H*K]; its
            # pages are layer 1 of 2 here (layer 0 must stay untouched)
            kp = jnp.stack([kp[::-1], kp]).reshape(2, -1, ps, h * kd)
            vp = jnp.stack([vp[::-1], vp]).reshape(2, -1, ps, h * kd)
            oo, ko, vo = _paged_attn(layer, x, kp, vp, 1, table, pos, nf,
                                     paged_kernel=False)
            ok, kk, vk = _paged_attn(layer, x, kp, vp, 1, table, pos, nf,
                                     paged_kernel=True)
            assert ko.shape == kp.shape
            np.testing.assert_array_equal(np.asarray(ko[0]),
                                          np.asarray(kp[0]))
            np.testing.assert_allclose(np.asarray(ok), np.asarray(oo),
                                       atol=1e-5)
            np.testing.assert_array_equal(np.asarray(kk), np.asarray(ko))
            np.testing.assert_array_equal(np.asarray(vk), np.asarray(vo))


# The row writer (ISSUE 41): the fed K/V rows of a dispatch into the pools
# where they lie, against the `.at[].set` scatter it stands in for,
# bit-equal over BOTH pools of every layer except the written layer's null
# page (the scatter's padding goes there; the writer never touches it).
# Every case holds, in one batch: an idle lane (n_feed 0), a lane that
# feeds one column, lanes that feed the whole width, one of them ending on
# the table's last position, a run that straddles a page boundary, pages
# handed out in a shuffled order, and a second idle lane whose table and
# unaligned position point into the very group the one-column lane writes
# (a lane that feeds nothing must not fetch that group and send it back
# stale); the layer written is the middle one of three.
_WRITER_CASES = {
    # name: (C, ps, dtype, row, what the case adds)
    **{f"c{c}_ps{ps}": (c, ps, "float32", 16, None)
       for c in (1, 8, 16) for ps in (4, 8, 16)},
    "bf16_rows": (8, 16, "bfloat16", 128, None),
    "odd_width_5": (5, 8, "float32", 16, None),
    "two_column_blocks": (24, 8, "float32", 16, "columns"),
    "a_lane_a_grid_step": (8, 16, "float32", 32, "lanes"),
}


@pytest.mark.paged_kernel
@pytest.mark.parametrize("case", list(_WRITER_CASES))
def test_row_writer_matches_the_scatter(case, monkeypatch):
    c, ps, dtype, row, twist = _WRITER_CASES[case]
    # (a twisted case has shapes no other call has: the jitted call is
    # cached by shapes, and its blocks are worked out when it is traced)
    if twist == "columns":      # the width in blocks of 8 columns
        monkeypatch.setattr(pk, "_WRITER_COLUMNS", 8)
    if twist == "lanes":        # no room for two lanes in a grid step
        monkeypatch.setattr(pk, "_WRITER_VMEM", 1)
    n_layers, layer, b = 3, 1, 7
    mp = max(4, -(-3 * c // ps) + 1)
    pages = 1 + b * mp
    gr, cb, lb, _, _ = _writer_blocks(b, c, ps, row, 4)
    assert (cb, lb) == {"columns": (8, b), "lanes": (c, 1)}.get(
        twist, (c, b))
    rng = np.random.default_rng(c * 100 + ps)
    table = np.stack([1 + i * mp + rng.permutation(mp) for i in range(b)])
    table[0] = 0                                    # the idle lane
    table[6] = table[1]                             # idle, on lane 1's pages
    last = mp * ps - c                              # ends on the last page
    straddle = max(ps - 1, 0)                       # crosses into page 1
    pos = np.array([0, 3, last, straddle, ps, 2 * ps + 1, 2], np.int32)
    nf = np.array([0, 1, c, c, c, max(c - 2, 1), 0], np.int32)
    assert pos[3] // ps != (pos[3] + max(c, 2) - 1) // ps or c == 1
    shape = (n_layers, pages, ps, row)
    ck = jnp.asarray(rng.standard_normal(shape), dtype)
    cv = jnp.asarray(rng.standard_normal(shape), dtype)
    k = jnp.asarray(rng.standard_normal((b, c, 2, row // 2)), dtype)
    v = jnp.asarray(rng.standard_normal((b, c, 2, row // 2)), dtype)
    args = (jnp.asarray(table, jnp.int32), jnp.asarray(pos),
            jnp.asarray(nf))

    got_k, got_v = write_kv_rows(ck, cv, k, v, *args, layer)
    want_k, want_v = _write_fed_rows((ck, cv), (k, v), layer, *args,
                                     paged_kernel=False)
    for got, want, old, new in ((got_k, want_k, ck, k),
                                (got_v, want_v, cv, v)):
        got, want = np.asarray(got), np.array(want)
        assert got.dtype == want.dtype and got.shape == shape
        # the null page: the writer leaves it as it was
        np.testing.assert_array_equal(got[layer, 0],
                                      np.asarray(old)[layer, 0])
        want[layer, 0] = got[layer, 0]
        np.testing.assert_array_equal(got, want)
        # and the rows did land: lane 2's last column at the last position
        np.testing.assert_array_equal(
            got[layer, table[2, mp - 1], ps - 1],
            np.asarray(new).reshape(b, c, row)[2, c - 1])


@pytest.mark.paged_kernel
class TestMaskValueAndPolicy:
    """The dtype-aware mask constant (satellite: the hardcoded -1e30
    overflowed fp16 to -inf and NaN-poisoned fully masked rows) and the
    paged_kernel switch-resolution policy."""

    def test_mask_value_finite_in_every_float_dtype(self):
        for dt in (jnp.float32, jnp.bfloat16, jnp.float16):
            mv = mask_value(dt)
            assert mv.dtype == jnp.dtype(dt)
            assert np.isfinite(np.asarray(mv, np.float32))
        # the old constant is exactly the fp16 failure being fixed
        assert np.isinf(np.float16(-1e30))

    def test_fp16_fully_masked_row_stays_finite(self):
        s = jnp.zeros((2, 4), jnp.float16)
        masked = jnp.where(jnp.zeros((2, 4), bool), s,
                           mask_value(s.dtype))
        w = jax.nn.softmax(masked, axis=-1)
        assert np.isfinite(np.asarray(w, np.float32)).all()
        # the -1e30 path NaNs: softmax over a row of -inf
        bad = jnp.where(jnp.zeros((2, 4), bool), s, jnp.float16(-1e30))
        assert np.isnan(np.asarray(
            jax.nn.softmax(bad, axis=-1), np.float32)).all()

    @pytest.mark.parametrize("case, want", [
        # (paged_kernel, pools, ps, row, compiled) -> the writer?
        ((True, 2, 16, 1280, True), True),      # the serve cells' pools
        ((True, 2, 128, 1024, True), True),     # Solar-Open2's
        ((True, 1, 128, 640, True), False),     # the one latent pool
        ((False, 2, 16, 1280, True), False),    # the oracle's program
        ((True, 2, 4, 1280, True), False),      # a page under a tile
        ((True, 2, 16, 96, True), False),       # a row under a lane tile
        ((True, 2, 4, 16, False), True),        # the interpreter takes any
    ])
    def test_write_path_comes_from_the_program_s_shapes(self, case, want,
                                                        monkeypatch):
        """`kv_rows_by_kernel`: the row writer iff the paged kernel runs
        and the pools are a K and a V pool of whole tiles, whatever the
        feed width; `kv_write_path` is the same rule asked of a
        configuration, for `stats()["kv"]["write_path"]`."""
        from deeplearning4j_tpu.parallel import transformer as tfm
        from deeplearning4j_tpu.parallel.generation import kv_write_path

        on, pools, ps, row, compiled = case
        monkeypatch.setattr(pk, "_resolve_interpret",
                            lambda interpret: not compiled)
        assert kv_rows_by_kernel(on, pools, ps, row) is want
        if pools == 2:
            cfg = tfm.TransformerConfig(vocab_size=50, d_model=row,
                                        n_heads=2, n_layers=1, d_ff=32,
                                        max_len=32)
        else:
            cfg = tfm.deepseek_v2(layers=2, experts_held=(0, 40), vocab=64,
                                  max_len=256)
            assert pool_layout(cfg).row == row
        assert kv_write_path(cfg, ps, on) == (
            "kernel" if want else "scatter")

    def test_resolve_paged_kernel(self, monkeypatch):
        """An explicit bool is the oracle seam; `None` is the platform
        rule and nothing else: the kernel iff the backend is a TPU,
        whatever the environment says."""
        assert resolve_paged_kernel(True) is True
        assert resolve_paged_kernel(False) is False
        monkeypatch.setenv("DL4J_TPU_PAGED_KERNEL", "1")
        assert resolve_paged_kernel(None) is False    # a CPU, here
        for backend, want in (("tpu", True), ("cpu", False)):
            monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
            assert resolve_paged_kernel(None) is want
