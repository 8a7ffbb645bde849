"""Pallas TPU kernels for the hot ops.

The framework's device tier is XLA; Pallas covers the spots where manual
VMEM scheduling beats the fusion XLA picks (SURVEY §7 "Native components":
attention is the FLOP/HBM-critical op of the transformer flagship).

`flash_attention(q, k, v, causal)` — fused online-softmax attention:
one Q block resident in VMEM while K/V stream through, running (m, l, acc)
accumulators — O(S) memory instead of materializing the [S, S] score
matrix in HBM. The forward also emits the per-row logsumexp; the backward
is the FlashAttention-2 scheme: two fused kernels (dK/dV with K-block
resident and Q/dO streaming, dQ with Q-block resident and K/V streaming)
that recompute P = exp(S - lse) blockwise, so training memory stays O(S)
too. Causal blocks that are fully masked are skipped via dynamic loop
bounds. Set DL4J_TPU_FLASH_BWD=0 to fall back to the dense-recompute
backward (kept for A/B benchmarking).

Off-TPU (tests, CPU meshes) the same kernel runs in Pallas interpret mode,
so numerics are validated everywhere the suite runs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def mask_value(dtype) -> jax.Array:
    """Finite large-negative mask constant for `dtype` softmax scores.

    The hardcoded ``-1e30`` the masked-softmax paths used overflows to
    ``-inf`` in fp16 (max ~6.5e4), so a fully masked row becomes
    ``softmax(-inf - (-inf)) = NaN`` and poisons every downstream read.
    ``finfo.min / 2`` is representable in every float dtype and still
    underflows to exactly 0 through ``exp(s - max)``, so masked
    positions contribute nothing while fully-masked rows stay finite.
    """
    return jnp.asarray(jnp.finfo(jnp.dtype(dtype)).min / 2, dtype)

# Softmax row-stats (lse, delta) cross the pallas_call boundary in
# LANE-REPLICATED form [B*H, S, REP]: Mosaic tiles VMEM blocks (8, 128)
# over the last two dims, so a compact [B*H, S] array can never be
# blocked per-(batch*head) row — the size-1 sublane dim is illegal.
# Replicating each scalar across the 128 lanes keeps every stat block
# (bq, 128)-shaped and sublane-aligned with the [bq, bk] score tiles it
# corrects, so the kernels never transpose.  (Same layout the TPU
# flash-attention literature uses for its l/m residuals.)
REP = 128


def flash_enabled() -> bool:
    """Policy for the transformer's single-device attention path: the
    Pallas kernel on TPU by default; opt in/out anywhere with
    DL4J_TPU_FLASH=1/0."""
    import os

    flag = os.environ.get("DL4J_TPU_FLASH")
    if flag is not None:
        return flag.lower() in ("1", "true", "yes")
    return jax.default_backend() == "tpu"


class FlashBlockError(ValueError):
    """The sequence length admits no block the TPU compiler can tile."""


def _pick_block(s: int, target: int = None, kind: str = "q",
                interpret: bool = True) -> int:
    """Largest divisor of s that is <= target (block sizes must tile S).
    Mosaic tiles the sublane dim in rows of 8 and must prove every
    in-kernel row offset aligned, so a COMPILED call (``interpret=False``)
    takes only multiples of 8 and raises `FlashBlockError` at trace time
    when S has none, instead of handing Mosaic a shape it refuses —
    S=1000 gets 40, S=100 or S=1001 the error.  The interpreter takes
    any divisor.  Tunable per-axis via DL4J_TPU_FLASH_BQ /
    DL4J_TPU_FLASH_BK (the VMEM residency/occupancy trade-off differs
    per chip generation)."""
    import os

    if target is None:
        env = os.environ.get(f"DL4J_TPU_FLASH_B{kind.upper()}")
        target = 128
        if env:
            if int(env) <= 0:
                raise ValueError(
                    f"DL4J_TPU_FLASH_B{kind.upper()}={env}: block size "
                    f"target must be a positive integer")
            target = int(env)
    divisors = [b for b in range(min(s, target), 0, -1) if s % b == 0]
    if interpret:
        return divisors[0]
    tiled = [b for b in divisors if b % 8 == 0]
    if not tiled:
        raise FlashBlockError(
            f"flash attention: sequence length {s} has no block <= "
            f"{target} that divides it and is a multiple of 8 (the TPU "
            f"sublane tile); pad the sequence to a multiple of 8")
    return tiled[0]


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal, bq,
                 bk, n_kv_blocks):
    """Grid program: one (batch*head, q_block) pair.

    q_ref [bq, d]; k_ref/v_ref [s, d] (whole sequence for this bh);
    o_ref [bq, d]; lse_ref [bq, REP] (lane-replicated logsumexp of the
    scaled scores, consumed by the fused backward).

    All row stats are kept 2-D [bq, 1] (keepdims reductions) so every
    intermediate is a sublane vector Mosaic can tile.
    """
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale       # [bq, d]
    d = q.shape[-1]

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)

    def body(j, carry):
        m, l, acc = carry                                 # [bq,1]x2,[bq,d]
        rows = pl.ds(pl.multiple_of(j * bk, bk), bk)
        k_blk = k_ref[0, rows, :].astype(jnp.float32)
        v_blk = v_ref[0, rows, :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bq, bk]
        if causal:
            k_pos = j * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        blk_m = jnp.max(s, axis=1, keepdims=True)         # [bq, 1]
        new_m = jnp.maximum(m, blk_m)
        p = jnp.exp(s - new_m)
        if causal:
            p = jnp.where(q_pos >= k_pos, p, 0.0)
        scale_old = jnp.exp(m - new_m)
        l = l * scale_old + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * scale_old + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [bq, d]
        return new_m, l, acc

    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)
    # Causal: kv blocks past this q block are fully masked — skip them.
    n_blocks = jnp.minimum(
        n_kv_blocks, (qi * bq + bq + bk - 1) // bk) if causal else n_kv_blocks
    m, l, acc = jax.lax.fori_loop(0, n_blocks, body, (m0, l0, acc0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    lse_ref[...] = jnp.broadcast_to(
        m + jnp.log(jnp.maximum(l, 1e-30)), (bq, REP))


def _fold(x, b, s, h, d):
    """[B,S,H,D] -> [B*H, S, D]"""
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _unfold(x, b, s, h, d):
    return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)


# Mosaic's default scoped-VMEM budget on the chips this package targets.
_DEFAULT_SCOPED_VMEM = 16 << 20


def _resident_kv_vmem(s: int, d: int, itemsize: int):
    """Scoped-VMEM limit for the forward, which keeps one head's whole
    K and V resident: two operands, double-buffered, lanes padded to
    128.  None (the compiler's default) while that fits with 4 MiB to
    spare for the q/o/lse blocks and the score tiles; past it — S=16384
    at d=64 needs 16 MiB for K/V alone — the limit is raised to what
    the shape needs."""
    need = 2 * 2 * s * max(d, 128) * itemsize + (4 << 20)
    return None if need <= _DEFAULT_SCOPED_VMEM else need


def _flash_forward(q, k, v, causal: bool, interpret: bool):
    """Returns (out [B,S,H,D], lse [B*H, S]).

    The kernel emits lse lane-replicated [B*H, S, REP] (see REP above);
    the compact [B*H, S] view handed to callers (ring attention, the
    fused backward's residuals) is lane 0.
    """
    b, s, h, d = q.shape
    bq = _pick_block(s, kind="q", interpret=interpret)
    bk = _pick_block(s, kind="k", interpret=interpret)
    n_kv_blocks = s // bk
    scale = 1.0 / (d ** 0.5)

    qf, kf, vf = (_fold(x, b, s, h, d) for x in (q, k, v))

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, bq=bq, bk=bk,
        n_kv_blocks=n_kv_blocks)
    out, lse_rep = pl.pallas_call(
        kernel,
        grid=(b * h, s // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, s, d), lambda bh, qi: (bh, 0, 0)),
            pl.BlockSpec((1, s, d), lambda bh, qi: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((None, bq, REP), lambda bh, qi: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, s, REP), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_resident_kv_vmem(s, d, k.dtype.itemsize)),
        interpret=interpret,
    )(qf, kf, vf)
    return _unfold(out, b, s, h, d), lse_rep[..., 0]


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *,
                scale, causal, bq, bk, n_q_blocks):
    """Grid program: (batch*head, kv_block, q_block), q innermost.

    The K/V block is revisited across the inner q steps while Q/dO and
    the row stats stream through as (bq, ·) blocks — every block is
    DMA-sized by the grid, so VMEM use is independent of S.  dK/dV
    accumulate in f32 VMEM scratch (persistent across the sequential
    inner steps) and flush once on the last q step.
    """
    j, i = pl.program_id(1), pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    # Causal: this (q, kv) block pair touches the triangle iff the last
    # q position reaches the first k position.
    live = (i * bq + bq - 1 >= j * bk) if causal else True

    @pl.when(live)
    def _compute():
        k_blk = k_ref[0].astype(jnp.float32)              # [bk, d]
        v_blk = v_ref[0].astype(jnp.float32)
        q_blk = q_ref[0].astype(jnp.float32)              # [bq, d]
        do_blk = do_ref[0].astype(jnp.float32)
        lse_blk = lse_ref[:, :1]                          # [bq, 1]
        delta_blk = delta_ref[:, :1]
        s = jax.lax.dot_general(
            q_blk, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [bq, bk]
        if causal:
            q_pos = i * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            k_pos = j * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse_blk)                          # [bq, bk]
        dv_acc[...] += jax.lax.dot_general(
            p, do_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [bk, d]
        dp = jax.lax.dot_general(
            do_blk, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # [bq, bk]
        ds = p * (dp - delta_blk)
        dk_acc[...] += jax.lax.dot_general(
            ds, q_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [bk, d]

    @pl.when(i == n_q_blocks - 1)
    def _flush():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_acc, *, scale, causal, bq, bk, n_kv_blocks):
    """Grid program: (batch*head, q_block, kv_block), kv innermost; the
    Q block is revisited while K/V stream through.  Same scratch-
    accumulate-flush scheme as _dkv_kernel."""
    qi, jb = pl.program_id(1), pl.program_id(2)

    @pl.when(jb == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    live = (jb * bk <= qi * bq + bq - 1) if causal else True

    @pl.when(live)
    def _compute():
        q_blk = q_ref[0].astype(jnp.float32)              # [bq, d]
        do_blk = do_ref[0].astype(jnp.float32)
        lse_blk = lse_ref[:, :1]                          # [bq, 1]
        delta_blk = delta_ref[:, :1]
        k_blk = k_ref[0].astype(jnp.float32)              # [bk, d]
        v_blk = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q_blk, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [bq, bk]
        if causal:
            q_pos = qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            k_pos = jb * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse_blk)
        dp = jax.lax.dot_general(
            do_blk, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # [bq, bk]
        ds = p * (dp - delta_blk)
        dq_acc[...] += jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [bq, d]

    @pl.when(jb == n_kv_blocks - 1)
    def _flush():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _flash_backward(q, k, v, o, lse, g, causal: bool, interpret: bool):
    b, s, h, d = q.shape
    of = _fold(o, b, s, h, d)
    gf = _fold(g, b, s, h, d)
    # delta_i = sum_d dO_i * O_i — the softmax-jacobian row correction
    # (FlashAttention-2 eq. 4); cheap elementwise, XLA fuses it.
    delta = jnp.sum(gf.astype(jnp.float32) * of.astype(jnp.float32), axis=-1)
    return _bwd_block(q, k, v, g, lse, delta, causal, interpret)


def _bwd_block(q, k, v, g, lse, delta, causal: bool, interpret: bool):
    """(dq, dk, dv) for one attention block given the Q-side row stats.

    q/k/v/g: [B,S,H,D]; lse/delta: [B*H, S] float32.  Used both by the
    single-device VJP and (per ring step, with the GLOBAL lse/delta) by
    ring attention's distributed backward.
    """
    b, s, h, d = q.shape
    bq = _pick_block(s, kind="q", interpret=interpret)
    bk = _pick_block(s, kind="k", interpret=interpret)
    scale = 1.0 / (d ** 0.5)

    qf, kf, vf, gf = (_fold(x, b, s, h, d) for x in (q, k, v, g))
    # Lane-replicate the compact row stats for the kernels (see REP).
    lse_rep = jnp.broadcast_to(lse[:, :, None], (b * h, s, REP))
    delta_rep = jnp.broadcast_to(delta[:, :, None], (b * h, s, REP))

    dkf, dvf = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal, bq=bq,
                          bk=bk, n_q_blocks=s // bq),
        grid=(b * h, s // bk, s // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, j, i: (bh, i, 0)),    # q
            pl.BlockSpec((1, bk, d), lambda bh, j, i: (bh, j, 0)),    # k
            pl.BlockSpec((1, bk, d), lambda bh, j, i: (bh, j, 0)),    # v
            pl.BlockSpec((1, bq, d), lambda bh, j, i: (bh, i, 0)),    # do
            pl.BlockSpec((None, bq, REP), lambda bh, j, i: (bh, i, 0)),
            pl.BlockSpec((None, bq, REP), lambda bh, j, i: (bh, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda bh, j, i: (bh, j, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, j, i: (bh, j, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((b * h, s, d), k.dtype),
                   jax.ShapeDtypeStruct((b * h, s, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        # Inner q dim is sequential (scratch accumulation); outer two are
        # independent, letting Mosaic pipeline/parallelize them.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf, gf, lse_rep, delta_rep)

    dqf = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal, bq=bq,
                          bk=bk, n_kv_blocks=s // bk),
        grid=(b * h, s // bq, s // bk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, jb: (bh, qi, 0)),  # q
            pl.BlockSpec((1, bk, d), lambda bh, qi, jb: (bh, jb, 0)),  # k
            pl.BlockSpec((1, bk, d), lambda bh, qi, jb: (bh, jb, 0)),  # v
            pl.BlockSpec((1, bq, d), lambda bh, qi, jb: (bh, qi, 0)),  # do
            pl.BlockSpec((None, bq, REP), lambda bh, qi, jb: (bh, qi, 0)),
            pl.BlockSpec((None, bq, REP), lambda bh, qi, jb: (bh, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, qi, jb: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf, gf, lse_rep, delta_rep)

    return tuple(_unfold(x, b, s, h, d) for x in (dqf, dkf, dvf))


def _dense_grads(q, k, v, causal, g):
    """Standard attention backward in plain jnp (dense recompute)."""
    d = q.shape[-1]
    scale = 1.0 / (d ** 0.5)
    s = jnp.einsum("bqhd,bkhd->bqhk", q, k) * scale
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((sq, sk), bool))
        s = jnp.where(mask[None, :, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    dv = jnp.einsum("bqhk,bqhd->bkhd", p, g)
    dp = jnp.einsum("bqhd,bkhd->bqhk", g, v)
    ds = p * (dp - jnp.sum(p * dp, axis=-1, keepdims=True))
    dq = jnp.einsum("bqhk,bkhd->bqhd", ds, k) * scale
    dk = jnp.einsum("bqhk,bqhd->bkhd", ds, q) * scale
    return dq, dk, dv


def _flash_bwd_enabled() -> bool:
    import os

    return os.environ.get("DL4J_TPU_FLASH_BWD", "1").lower() in (
        "1", "true", "yes")


def _resolve_interpret(interpret):
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, causal: bool = True,
                    interpret: bool | None = None):
    """Fused attention [B,S,H,D] -> [B,S,H,D]. interpret=None auto-detects
    (compiled on TPU, interpreter elsewhere)."""
    out, _ = _flash_forward(q, k, v, causal, _resolve_interpret(interpret))
    return out


def _fa_fwd(q, k, v, causal, interpret):
    out, lse = _flash_forward(q, k, v, causal, _resolve_interpret(interpret))
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, interpret, residuals, g):
    q, k, v, o, lse = residuals
    if not _flash_bwd_enabled():
        return _dense_grads(q, k, v, causal, g)
    return _flash_backward(q, k, v, o, lse, g, causal,
                           _resolve_interpret(interpret))


flash_attention.defvjp(_fa_fwd, _fa_bwd)
