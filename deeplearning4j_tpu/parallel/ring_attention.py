"""Ring attention: exact attention over a sequence sharded across devices.

Out-of-reference extension (SURVEY §5 "long-context: absent" — the 2015
reference loops an LSTM over time on one device, `GravesLSTM.java:108`).
For the TPU framework long context is first-class: the sequence dimension
is sharded over a mesh axis, each device holds a Q/K/V block, and K/V
blocks rotate around the ring via `lax.ppermute` while a running
flash-attention-style accumulator keeps the softmax exact — O(S/P) memory
per device, compute overlapping communication on ICI.

The schedule is static.  The axis size is known when the ring is traced,
so its steps are a Python loop: at step `s` a chip holds the block of chip
`(its index - s) mod n` and works that out itself.  Nothing but K/V (and,
in the backward, dK/dV) travels, and the permute that feeds step `s + 1`
does not wait for step `s`'s kernel.

A CAUSAL ring over n > 1 chips wants the sequence dealt zigzag
(`zigzag_order`: 2n chunks, chip i holds chunks i and 2n-1-i; Megatron-LM
context parallelism, zhuzilin/ring-flash-attention), so that every chip
does the same work.  A contiguous deal gives chip 0 one block of live
scores and chip n-1 n of them.  Dealt zigzag, with lo/hi a block's early
and late chunk:

- step 0, a chip's own K/V: one causal call on its whole block (lo < hi,
  so the block's own mask is the causal one);
- step s >= 1, holding chip j's block: q hi sees k lo whole, whoever j is;
  and if j came before (j < i), q lo sees k lo whole, else q hi sees k hi
  whole.  Every other pair of chunks is masked whole and is not computed.
  So a remote step is two chunk-by-chunk calls without a mask, run as ONE
  kernel call with the two stacked on the batch axis (`_half` names the
  chunk that varies), and a chip does 1 + (n-1)/2 blocks of work whatever
  its index.

Whoever calls a causal ring deals the sequence (the trainer does, where it
places the batch: `transformer.seq_order`).  A non-causal ring has no
uneven work and no cases: its layout stays contiguous.

Two inner-block engines:
- `ring_attention` — plain-jnp blockwise softmax (reference formulation,
  autodiff backward; materializes [S/P, S/P] scores per block, masked by
  the positions the layout gives).
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
import numpy as np
from jax import lax

NEG_INF = -1e30


def zigzag_order(n: int, seq_len: int) -> np.ndarray:
    """order[c] = the position in the sequence of column c of a row dealt
    for a causal ring over n chips: the sequence cut into 2n chunks, chip i
    (columns [i, i+1) * seq_len/n) holding chunks i and 2n-1-i, in that
    order.  Deal with `x[:, order]`, undo with `x[:, np.argsort(order)]`."""
    if seq_len % (2 * n):
        raise ValueError(
            f"a causal ring over {n} chips deals the sequence in {2 * n} "
            f"chunks: sequence length {seq_len} is not a multiple of "
            f"{2 * n}")
    chunks = np.arange(seq_len, dtype=np.int32).reshape(2 * n, -1)
    return np.concatenate([chunks[[i, 2 * n - 1 - i]].reshape(-1)
                           for i in range(n)])


def _half(idx, step):
    """Remote step `step` of a zigzag ring on chip `idx` (an int, or the
    traced `axis_index`): the chunk h of which the chip's own q half h sees
    the held block's k half h whole.  0 (early) where the held block comes
    from a chip before this one, 1 (late) where from one after."""
    return (idx < step) * 1


def zigzag_schedule(n: int, idx: int) -> list:
    """What chip `idx` of a zigzag ring over n computes, step by step, as
    (q chunk, k chunk, causal) in the 2n chunks' own numbers: the schedule
    the traced ring follows, in plain integers for the tests."""
    lo, hi = idx, 2 * n - 1 - idx
    steps = [[(lo, lo, True), (hi, lo, False), (hi, hi, True)]]
    for s in range(1, n):
        j = (idx - s) % n
        mine, held = (lo, hi), (j, 2 * n - 1 - j)
        h = _half(idx, s)
        steps.append([(mine[h], held[h], False), (hi, held[0], False)])
    return steps


def _ring_perm(axis_size):
    return [(i, (i + 1) % axis_size) for i in range(axis_size)]


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

    Call inside shard_map: q/k/v are the LOCAL blocks [B, S_local, H, D],
    dealt zigzag (`zigzag_order`) where causal.  Requires equal S_local per
    device. axis_name=None falls back to the dense single-device path.
    """
    if axis_name is None:
        return attention(q, k, v, causal)

    axis_size = lax.psum(1, axis_name)
    b, s_local, h, dh = q.shape
    perm = _ring_perm(axis_size)

    if causal:
        my_idx = lax.axis_index(axis_name)
        within = jnp.arange(s_local) % (s_local // 2)
        late = jnp.arange(s_local) >= s_local // 2

        def positions(idx):
            """Of the rows of chip idx's block: chunks idx and 2n-1-idx."""
            if axis_size == 1:
                return jnp.arange(s_local)
            chunk = jnp.where(late, 2 * axis_size - 1 - idx, idx)
            return chunk * (s_local // 2) + within

        q_pos = positions(my_idx)
    m = jnp.full((b, s_local, h), NEG_INF, q.dtype)
    l = jnp.zeros((b, s_local, h), q.dtype)
    o = jnp.zeros((b, s_local, h, dh), q.dtype)
    for step in range(axis_size):
        with jax.named_scope("ring:remote" if step else "ring:local"):
            if step:
                # rotate KV around the ring (no kernel stands before it)
                k = lax.ppermute(k, axis_name, perm)
                v = lax.ppermute(v, axis_name, perm)
            if causal:
                k_pos = positions((my_idx - step) % axis_size)
                mask = q_pos[:, None] >= k_pos[None, :]
            else:
                mask = jnp.ones((s_local, s_local), bool)
            bm, bl, bo = _block_attn(q, k, v, mask)
            new_m = jnp.maximum(m, bm)
            # rescale both accumulators onto the new max
            scale_old = jnp.exp(m - new_m)
            scale_new = jnp.exp(bm - new_m)
            l = l * scale_old + bl * scale_new
            o = o * scale_old[..., None] + bo * scale_new[..., None]
            m = new_m
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


def _halves(x):
    """[B, S_local, ...] -> [B, 2, S_local/2, ...]: a block's two chunks."""
    return x.reshape(x.shape[0], 2, x.shape[1] // 2, *x.shape[2:])


def _pair(x, h, fixed):
    """The operand of a remote step's one kernel call: the block x itself
    where the ring has no mask (h None); else [2B, S_local/2, ...], chunk
    h of x (h traced) stacked on chunk `fixed` of it."""
    if h is None:
        return x
    xh = _halves(x)
    return jnp.concatenate(
        [lax.dynamic_index_in_dim(xh, h, 1, keepdims=False), xh[:, fixed]])


def _unpair(y, h, fixed, fill=0.0):
    """What such a call gives, as blocks [B, S_local, ...]: y itself (h
    None); else its two results, each on its own chunk of a block that
    holds `fill` elsewhere."""
    if h is None:
        return [y]
    b = y.shape[0] // 2
    chunk = jnp.arange(2).reshape((1, 2) + (1,) * (y.ndim - 1))
    parts = [jnp.where(chunk == at, part[:, None], fill)
             for at, part in ((h, y[:b]), (fixed, y[b:]))]
    return [p.reshape(b, -1, *y.shape[2:]) for p in parts]


def _merge(o, lse, bo, blse):
    """lse-weighted combine of normalized outputs (numerically stable:
    weights are exp of non-positive numbers); o is the f32 carry.  Rows a
    block did not compute come with blse = NEG_INF and weigh nothing."""
    new_lse = jnp.logaddexp(lse, blse)
    w_old = jnp.exp(lse - new_lse)
    w_new = jnp.exp(blse - new_lse)
    return o * w_old[..., None] + bo * w_new[..., None], new_lse


def _ring_flash_fwd_pass(q, k, v, axis_name, causal, interpret):
    axis_size = lax.psum(1, axis_name)
    perm = _ring_perm(axis_size)
    with jax.named_scope("ring:local"):
        o, lse = _flash_block_fwd(q, k, v, causal, interpret)
        # the combine weights are f32, so the running output is too
        o = o.astype(jnp.float32)
    # Non-causal rings never branch on block position, so don't emit
    # axis_index at all: the partition-id HLO it lowers to is rejected by
    # the SPMD partitioner when XLA keeps the shard_map body outlined
    # (observed on CPU meshes), and an unused value doesn't DCE it.
    my_idx = lax.axis_index(axis_name) if causal and axis_size > 1 else None
    for step in range(1, axis_size):
        with jax.named_scope("ring:remote"):
            k = lax.ppermute(k, axis_name, perm)
            v = lax.ppermute(v, axis_name, perm)
            h = _half(my_idx, step) if causal else None
            bo, blse = _flash_block_fwd(_pair(q, h, 1), _pair(k, h, 0),
                                        _pair(v, h, 0), False, interpret)
            for part, plse in zip(_unpair(bo, h, 1),
                                  _unpair(blse, h, 1, NEG_INF)):
                o, lse = _merge(o, lse, part, plse)
    return o.astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def ring_flash_attention(q, k, v, axis_name: Optional[str],
                         causal: bool = True,
                         interpret: bool | None = None):
    """Ring attention with the Pallas flash kernels as the inner block.

    Call inside shard_map with q/k/v the LOCAL sequence blocks
    [B, S_local, H, D], dealt zigzag (`zigzag_order`) where causal.
    axis_name=None falls back to the single-device flash kernel.
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
        out, lse = _k._saved(out, lse.reshape(b, h, s).transpose(0, 2, 1))
        return out, (q, k, v, out, lse)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    # the merged output and statistics: saved, none of the ring's forward
    # kernels or hops is left in a checkpoint's recomputation
    out, lse = _k._saved(
        *_ring_flash_fwd_pass(q, k, v, axis_name, causal, interpret))
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
    perm = _ring_perm(axis_size)
    rot = lambda x: lax.ppermute(x, axis_name, perm)  # noqa: E731
    # Global softmax-jacobian row correction, once per backward.
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    # dq accumulates locally; dK/dV accumulate ON the rotating block, so
    # after a full circle each block carries every device's contribution
    # and is back home.  K and V themselves stop one hop short.
    with jax.named_scope("ring:local"):
        dq, dk, dv = _flash_block_bwd(q, k, v, g, lse, delta, causal,
                                      interpret)
    my_idx = lax.axis_index(axis_name) if causal and axis_size > 1 else None
    for step in range(1, axis_size):
        with jax.named_scope("ring:remote"):
            k, v, dk, dv = rot(k), rot(v), rot(dk), rot(dv)
            h = _half(my_idx, step) if causal else None
            dqc, dkc, dvc = _flash_block_bwd(
                _pair(q, h, 1), _pair(k, h, 0), _pair(v, h, 0),
                _pair(g, h, 1), _pair(lse, h, 1), _pair(delta, h, 1),
                False, interpret)
            dq = sum(_unpair(dqc, h, 1), dq)
            dk = sum(_unpair(dkc, h, 0), dk)
            dv = sum(_unpair(dvc, h, 0), dv)
    if axis_size > 1:
        with jax.named_scope("ring:remote"):
            dk, dv = rot(dk), rot(dv)
    return dq, dk, dv


ring_flash_attention.defvjp(_rfa_fwd, _rfa_bwd)
