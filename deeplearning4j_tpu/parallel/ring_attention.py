"""Ring attention: exact attention over a sequence sharded across devices.

Out-of-reference extension (SURVEY §5 "long-context: absent" — the 2015
reference loops an LSTM over time on one device, `GravesLSTM.java:108`).
For the TPU framework long context is first-class: the sequence dimension
is sharded over a mesh axis, each device holds a Q/K/V block, and K/V
blocks rotate around the ring via `lax.ppermute` while a running
flash-attention-style (m, l, o) accumulator keeps the softmax exact —
O(S/P) memory per device, compute overlapping communication on ICI.

Two inner-block engines:
- `ring_attention` — plain-jnp blockwise softmax (reference formulation,
  autodiff backward; materializes [S/P, S/P] scores per block).
- `ring_flash_attention` — the Pallas flash kernels per block with a
  custom distributed VJP: the backward is a SECOND ring pass that rotates
  (K, V, dK, dV) while each device folds in its local Q/dO contribution
  using the saved global logsumexp — O(S/P) memory end to end, forward
  AND backward.

Pattern follows the public blockwise/ring attention formulation (Liu et al.
ring attention; PAPERS.md) — no reference code involved.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def _block_attn(q, k, v, mask):
    """One Q-block vs one KV-block. q:[B,Sq,H,D] k,v:[B,Sk,H,D]
    mask:[Sq,Sk] bool (True = attend). Returns (scores-max m:[B,Sq,H],
    sumexp l:[B,Sq,H], out o:[B,Sq,H,D])."""
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bqhk", q, k,
                   precision=lax.Precision.HIGHEST) / jnp.sqrt(
                       jnp.asarray(d, q.dtype))
    s = jnp.where(mask[None, :, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    # rows with no attendable key: exp(NEG_INF - NEG_INF) = 1 per key —
    # mask them back out so l counts only real keys.
    p = jnp.where(mask[None, :, None, :], p, 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bqhk,bkhd->bqhd", p, v,
                   precision=lax.Precision.HIGHEST)
    return m, l, o


def attention(q, k, v, causal: bool = True):
    """Plain single-device attention [B,S,H,D] — the unsharded baseline."""
    sq, sk = q.shape[1], k.shape[1]
    if causal:
        mask = jnp.tril(jnp.ones((sq, sk), bool))
    else:
        mask = jnp.ones((sq, sk), bool)
    m, l, o = _block_attn(q, k, v, mask)
    return o / jnp.maximum(l, 1e-30)[..., None]


def ring_attention(q, k, v, axis_name: Optional[str], causal: bool = True):
    """Attention with the S dimension sharded over `axis_name`.

    Call inside shard_map: q/k/v are the LOCAL blocks [B, S_local, H, D].
    Requires equal S_local per device. axis_name=None falls back to the
    dense single-device path.
    """
    if axis_name is None:
        return attention(q, k, v, causal)

    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    s_local = q.shape[1]
    b, _, h, dh = q.shape

    # positions are global: block i covers [i*s_local, (i+1)*s_local)
    q_pos = my_idx * s_local + jnp.arange(s_local)

    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def body(carry, _):
        kv, kv_idx, m, l, o = carry
        k_blk, v_blk = kv
        k_pos = kv_idx * s_local + jnp.arange(s_local)
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]
        else:
            mask = jnp.ones((s_local, s_local), bool)
        bm, bl, bo = _block_attn(q, k_blk, v_blk, mask)
        new_m = jnp.maximum(m, bm)
        # rescale both accumulators onto the new max
        scale_old = jnp.exp(m - new_m)
        scale_new = jnp.exp(bm - new_m)
        l = l * scale_old + bl * scale_new
        o = o * scale_old[..., None] + bo * scale_new[..., None]
        # rotate KV around the ring (overlaps with next block's compute)
        k_nxt = lax.ppermute(k_blk, axis_name, perm)
        v_nxt = lax.ppermute(v_blk, axis_name, perm)
        kv_idx = lax.ppermute(kv_idx, axis_name, perm)
        return ((k_nxt, v_nxt), kv_idx, new_m, l, o), None

    init = (
        (k, v),
        my_idx,
        jnp.full((b, s_local, h), NEG_INF, q.dtype),
        jnp.zeros((b, s_local, h), q.dtype),
        jnp.zeros((b, s_local, h, dh), q.dtype),
    )
    (_, _, _, l, o), _ = lax.scan(body, init, None, length=axis_size)
    return o / jnp.maximum(l, 1e-30)[..., None]


# ---------------------------------------------------------------------------
# Pallas-backed ring attention with distributed backward
# ---------------------------------------------------------------------------

def _fold_rows(x):
    """[B,S,H] -> [B*H, S] (the row-stat layout the kernels consume)."""
    return x.transpose(0, 2, 1).reshape(-1, x.shape[1])


def _flash_block_fwd(q, k, v, causal, interpret):
    """One q-block vs one kv-block through the Pallas forward.
    Returns (o [B,S,H,D] normalized, lse [B,S,H] float32)."""
    from deeplearning4j_tpu.parallel import kernels as _k

    o, lse = _k._flash_forward(q, k, v, causal, interpret)
    b, s, h, _ = q.shape
    return o, lse.reshape(b, h, s).transpose(0, 2, 1)


def _flash_block_bwd(q, k, v, g, lse, delta, causal, interpret):
    """(dq, dk, dv) for one block pair; lse/delta are the GLOBAL Q-side
    row stats [B,S,H]."""
    from deeplearning4j_tpu.parallel import kernels as _k

    return _k._bwd_block(q, k, v, g, _fold_rows(lse), _fold_rows(delta),
                         causal, interpret)


def _ring_cases(causal, my_idx, kv_idx):
    """0 = fully masked (skip), 1 = diagonal (causal mask), 2 = full."""
    if not causal:
        return jnp.int32(2)
    return jnp.sign(my_idx - kv_idx).astype(jnp.int32) + 1


def _ring_flash_fwd_pass(q, k, v, axis_name, causal, interpret):
    axis_size = lax.psum(1, axis_name)
    # Non-causal rings never branch on block position, so don't emit
    # axis_index at all: the partition-id HLO it lowers to is rejected by
    # the SPMD partitioner when XLA keeps the shard_map body outlined
    # (observed on CPU meshes), and an unused carry doesn't DCE it.
    my_idx = lax.axis_index(axis_name) if causal else jnp.int32(0)
    b, s_local, h, _ = q.shape
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def body(carry, _):
        k_blk, v_blk, kv_idx, o, lse = carry

        def skip(_):
            return jnp.zeros_like(q), jnp.full_like(lse, NEG_INF)

        def diag(_):
            return _flash_block_fwd(q, k_blk, v_blk, True, interpret)

        def full(_):
            return _flash_block_fwd(q, k_blk, v_blk, False, interpret)

        if causal:
            bo, blse = lax.switch(_ring_cases(causal, my_idx, kv_idx),
                                  [skip, diag, full], None)
        else:
            bo, blse = full(None)
        # lse-weighted combine of normalized outputs (numerically stable:
        # weights are exp of non-positive numbers).
        new_lse = jnp.logaddexp(lse, blse)
        w_old = jnp.exp(lse - new_lse)
        w_new = jnp.exp(blse - new_lse)
        o = o * w_old[..., None] + bo * w_new[..., None]   # f32 carry
        k_n = lax.ppermute(k_blk, axis_name, perm)
        v_n = lax.ppermute(v_blk, axis_name, perm)
        i_n = lax.ppermute(kv_idx, axis_name, perm)
        return (k_n, v_n, i_n, o, new_lse), None

    # the combine weights are f32, so the running output is too: a
    # q.dtype carry would change type across the scan for bf16 inputs
    init = (k, v, my_idx, jnp.zeros(q.shape, jnp.float32),
            jnp.full((b, s_local, h), NEG_INF, jnp.float32))
    (_, _, _, o, lse), _ = lax.scan(body, init, None, length=axis_size)
    return o.astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def ring_flash_attention(q, k, v, axis_name: Optional[str],
                         causal: bool = True,
                         interpret: bool | None = None):
    """Ring attention with the Pallas flash kernels as the inner block.

    Call inside shard_map with q/k/v the LOCAL sequence blocks
    [B, S_local, H, D]. axis_name=None falls back to the single-device
    flash kernel.
    """
    from deeplearning4j_tpu.parallel import kernels as _k

    if axis_name is None:
        return _k.flash_attention(q, k, v, causal, interpret)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    out, _ = _ring_flash_fwd_pass(q, k, v, axis_name, causal, interpret)
    return out


def _rfa_fwd(q, k, v, axis_name, causal, interpret):
    from deeplearning4j_tpu.parallel import kernels as _k

    if axis_name is None:
        out, lse = _k._flash_forward(q, k, v, causal,
                                     _k._resolve_interpret(interpret))
        b, s, h, _ = q.shape
        return out, (q, k, v, out, lse.reshape(b, h, s).transpose(0, 2, 1))
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    out, lse = _ring_flash_fwd_pass(q, k, v, axis_name, causal, interpret)
    return out, (q, k, v, out, lse)


def _rfa_bwd(axis_name, causal, interpret, residuals, g):
    q, k, v, o, lse = residuals
    if axis_name is None:
        from deeplearning4j_tpu.parallel import kernels as _k

        return _k._flash_backward(q, k, v, o, _fold_rows(lse), g, causal,
                                  _k._resolve_interpret(interpret))
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    # Global softmax-jacobian row correction, once per backward.
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)

    def body(carry, _):
        k_blk, v_blk, dk_blk, dv_blk, kv_idx, dq = carry
        zeros = (jnp.zeros_like(q), jnp.zeros_like(k_blk),
                 jnp.zeros_like(v_blk))

        def skip(_):
            return zeros

        def diag(_):
            return _flash_block_bwd(q, k_blk, v_blk, g, lse, delta, True,
                                    interpret)

        def full(_):
            return _flash_block_bwd(q, k_blk, v_blk, g, lse, delta, False,
                                    interpret)

        dqc, dkc, dvc = lax.switch(_ring_cases(causal, my_idx, kv_idx),
                                   [skip, diag, full], None)
        # dq accumulates locally; dK/dV accumulate ON the rotating block,
        # so after a full circle each block carries every device's
        # contribution and is back home.
        dq = dq + dqc
        dk_blk = dk_blk + dkc
        dv_blk = dv_blk + dvc
        rot = lambda x: lax.ppermute(x, axis_name, perm)  # noqa: E731
        return (rot(k_blk), rot(v_blk), rot(dk_blk), rot(dv_blk),
                rot(kv_idx), dq), None

    init = (k, v, jnp.zeros_like(k), jnp.zeros_like(v), my_idx,
            jnp.zeros_like(q))
    (_, _, dk, dv, _, dq), _ = lax.scan(body, init, None, length=axis_size)
    return dq, dk, dv


ring_flash_attention.defvjp(_rfa_fwd, _rfa_bwd)
