"""Pallas TPU kernels for the hot ops.

The framework's device tier is XLA; Pallas covers the spots where manual
VMEM scheduling beats the fusion XLA picks (SURVEY §7 "Native components":
attention is the FLOP/HBM-critical op of the transformer flagship).

`flash_attention(q, k, v, causal)` — fused online-softmax attention, O(S)
memory instead of the [S, S] score matrix in HBM, forward and backward
(the FlashAttention-2 scheme: a dK/dV kernel and a dQ kernel that
recompute P = exp(S - lse) tile by tile).  What the three kernels share:

- **Operands in the inputs' dtype.**  No block is cast up before a dot:
  q, k, v, dO enter the MXU as they are, `p` and `dS` in the value dtype,
  every product accumulates in f32.  bf16 inputs run the MXU's one-pass
  bf16 mode (a bf16 x bf16 product is exact in f32, so the scores are the
  ones an f32 cast gave); f32 inputs compute in f32 as before.  The
  softmax itself (max, exp, sums, the 1/sqrt(d) scale unless it is a power
  of two) is f32 on the score tile.
- **One block program.**  A grid step OWNS a block of rows (q rows in the
  forward and dQ, k rows in dK/dV) and accumulates for them in f32 scratch
  while the other side streams through: in chunks by the innermost grid
  axis (the whole sequence where it fits VMEM, so it is fetched once a
  head), and inside a chunk in tiles by a loop whose bounds follow the
  causal limit.  Tiles under the diagonal run without the mask, tiles on
  it with it, tiles past it not at all, and a chunk past it names the
  last live one in its index map, so a dead block costs no DMA.  `_plan`
  derives owner, tile and chunk from (S, d, itemsize) and the scoped-VMEM
  budget.
- **Row stats that stay compact.**  lse and delta are [B*H, S] f32 to
  every caller (ring attention hands the GLOBAL ones to `_bwd_block`) and
  reach the kernels as a free 4-D view whose blocks are rows (see REP
  below); nothing [B*H, S, 128] is written or re-read.

Set DL4J_TPU_FLASH_BWD=0 to fall back to the dense-recompute backward
(the oracle the tests hold the kernels to).

Off-TPU (tests, CPU meshes) the same kernels run in Pallas interpret mode,
so numerics are validated everywhere the suite runs.
"""

from __future__ import annotations

import functools
import math

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

# Softmax row-stats (lse, delta) cross the pallas_call boundary COMPACT:
# callers see [B*H, S] float32, the kernels a 4-D view of it,
# [B*H, S/t, 1, t] (a reshape of 256 KB at the train cell's size), whose
# blocks (.., 1, t) are rows with the positions on the lanes — legal for
# every t (the last two block dims equal the array's) and picked per tile
# by an index on an untiled leading dim.  No [B*H, S, 128] copy is written
# or re-read.  The dK/dV kernel works on TRANSPOSED score tiles
# [k rows, q columns], so a row of stats broadcasts down the sublanes as
# it is.  The forward and the dQ kernel need their q block's stats down
# the sublanes: they keep them in VMEM replicated over REP lanes (a
# [rows, 1] column would cost a cross-lane permute per sublane group every
# time it meets a score tile; REP-wide it meets the tile vreg for vreg,
# `_lanes`) and change between row and column once a q block
# (`_col_to_row`, `_row_to_col`).  The paged kernel's running max and sum
# are REP-replicated scratch too.
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


def _blocks(s: int, target: int, interpret: bool = True) -> list:
    """Divisors of s that are <= target, largest first (block sizes must
    tile S).  Mosaic tiles the sublane dim in rows of 8 and must prove
    every in-kernel row offset aligned, so a COMPILED call
    (``interpret=False``) takes only multiples of 8 and raises
    `FlashBlockError` at trace time when S has none, instead of handing
    Mosaic a shape it refuses — S=1000 under a target of 128 gets 40 first,
    S=100 or S=1001 the error.  The interpreter takes any divisor."""
    divisors = [b for b in range(min(s, target), 0, -1) if s % b == 0]
    if interpret:
        return divisors
    tiled = [b for b in divisors if b % 8 == 0]
    if not tiled:
        raise FlashBlockError(
            f"flash attention: sequence length {s} has no block <= "
            f"{target} that divides it and is a multiple of 8 (the TPU "
            f"sublane tile); pad the sequence to a multiple of 8")
    return tiled


# Mosaic's default scoped-VMEM budget on the chips this package targets:
# what a call's blocks are sized to.  Only a sequence whose smallest
# legal blocks do not fit it gets a larger limit.
_DEFAULT_SCOPED_VMEM = 16 << 20
# A grid step, and every pass of a kernel's tile loop, has a fixed cost
# (the MXU's fill and drain, the forward's cross-lane row maxima: 0.4-0.6
# us a 256-row tile on a v5e) that only rows x columns amortise, while the
# f32 score tile and its companions are the kernel's temporaries: the
# OWNER block (the rows a grid step accumulates for: q rows in the forward
# and dQ, k rows in dK/dV) and the TILE of the streamed side are each the
# largest divisor of S of at most this many positions.  Measured on a v5e
# at [64,1024,64] and [80,512,64] bf16 (PERF.md, PR 27): 512 x 512 is at
# or within 4 % of the best of {128..1024}^2 for all three kernels, causal
# or not, although half of a causal diagonal tile is masked away.
_OWNER_ROWS = 512
_TILE_COLS = 512


def _buf(rows: int, cols: int, itemsize: int) -> int:
    """VMEM bytes of one [rows, cols] buffer: lanes padded to 128, rows to
    the dtype's sublane tile (8 rows of 32 bits, 16 of 16)."""
    sub = 8 * max(1, 4 // itemsize)
    return -(-rows // sub) * sub * -(-cols // 128) * 128 * itemsize


def _vmem_need(kind: str, d: int, itemsize: int, bo: int, ts: int,
               bs: int) -> int:
    """Reckoned scoped VMEM of one grid step of kernel `kind`: the owner's
    blocks and the streamed chunk double-buffered, the f32 accumulators,
    the stats, and the [bo, ts] temporaries (f32 scores / probabilities /
    dP / dS and the two operand-dtype copies that enter the matmuls)."""
    owner_ops = {"fwd": 2, "dkv": 4, "dq": 3}[kind]     # q,o | k,v,dk,dv | q,do,dq
    need = 2 * owner_ops * _buf(bo, d, itemsize)
    need += 2 * 2 * _buf(bs, d, itemsize)               # streamed k,v | q,do
    need += {"fwd": 1, "dkv": 2, "dq": 1}[kind] * _buf(bo, d, 4)
    if kind == "dkv":       # streamed stats: rows [1, ts], 8 sublanes each
        need += 2 * 2 * (bs // ts) * _buf(1, ts, 4)
    else:                   # the owner's stats row, and its two columns
        need += 2 * 2 * _buf(1, bo, 4) + 2 * _buf(bo, REP, 4)
    tiles = 2 if kind == "fwd" else 4
    need += tiles * _buf(bo, ts, 4) + 2 * _buf(bo, ts, itemsize)
    return need


def _plan(kind: str, s: int, d: int, itemsize: int, interpret: bool):
    """(owner rows, tile, streamed chunk, vmem limit) for kernel `kind`
    ("fwd", "dkv", "dq") on a sequence of s positions.

    Every size is a divisor of s (a multiple of 8 when compiled).  Owner
    and tile are the largest of at most `_OWNER_ROWS` / `_TILE_COLS`
    whose `_vmem_need` fits the default scoped budget (the tile gives way
    first); the streamed chunk is the largest multiple of the tile that
    still fits — the whole sequence where it does, so the streamed side is
    fetched once a head.  Only when the smallest legal blocks pass the
    budget is the limit handed to the compiler raised to what they need.
    DL4J_TPU_FLASH_BQ / DL4J_TPU_FLASH_BK cap the q-side / k-side size
    (owner or tile, by kernel) for a block search."""
    import os

    def target(side, default):
        env = os.environ.get(f"DL4J_TPU_FLASH_B{side}")
        if not env:
            return default
        if int(env) <= 0:
            raise ValueError(
                f"DL4J_TPU_FLASH_B{side}={env}: block size target must "
                f"be a positive integer")
        return int(env)

    def fits(bo, ts, bs):
        return _vmem_need(kind, d, itemsize, bo, ts, bs) <= \
            _DEFAULT_SCOPED_VMEM

    owner_side, tile_side = ("K", "Q") if kind == "dkv" else ("Q", "K")
    owners = _blocks(s, target(owner_side, _OWNER_ROWS), interpret)
    tiles = _blocks(s, target(tile_side, _TILE_COLS), interpret)
    bo, ts = next(((o, t) for o in owners for t in tiles if fits(o, t, t)),
                  (owners[-1], tiles[-1]))
    bs = next((c for c in range(s, ts, -ts)
               if s % c == 0 and fits(bo, ts, c)), ts)
    limit = max(_DEFAULT_SCOPED_VMEM,
                _vmem_need(kind, d, itemsize, bo, ts, bs))
    return bo, ts, bs, limit


def _scaled(x, scale):
    """(x, scale) or (x * scale, None): where `scale` is a power of two
    (head size 16, 64, 256) the resident operand takes it, exactly in any
    float dtype, and the f32 score tiles are spared a multiply an
    element; otherwise the f32 scores are scaled after the dot."""
    if math.frexp(scale)[0] == 0.5:
        return (x * scale).astype(x.dtype), None
    return x, scale


def _scores(a, b, scale=None):
    """a [m, d] . b [n, d]^T -> [m, n] f32: operands as they are (bf16
    products are exact in f32), accumulated in f32; `scale`, if any, on
    the f32 result."""
    s = jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    return s if scale is None else s * scale


def _mm(a, b):
    """a [m, k] . b [k, n] -> [m, n] f32; `a` enters in b's dtype."""
    return jax.lax.dot_general(a.astype(b.dtype), b,
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _stat_lanes(n: int) -> int:
    """Lanes a per-row stat is kept replicated over beside tiles (or in
    chunks) of n positions: a full lane tile where that divides n, so that
    stat and score tile meet vreg for vreg; else n itself."""
    return REP if n % REP == 0 else n


def _lanes(x, n: int):
    """x [rows, w] with every lane of a row the same -> [rows, n].  Whole
    copies side by side are the same vregs again: no cross-lane work,
    where broadcasting a [rows, 1] column costs a permute a sublane
    group."""
    rows, w = x.shape
    if n == w:
        return x
    if n < w:
        return x[:, :n]
    if n % w == 0:
        return jnp.concatenate([x] * (n // w), axis=1)
    return jnp.broadcast_to(x[:, :1], (rows, n))


def _eye(c: int):
    return (jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (c, c), 1))


def _col_to_row(col):
    """[n, w] f32, lane-replicated -> [1, n]: each chunk of rows picked
    off the diagonal of its square (a sum of one value and zeros: exact)."""
    n = col.shape[0]
    c = _stat_lanes(n)
    eye = _eye(c)
    return jnp.concatenate(
        [jnp.sum(jnp.where(eye, _lanes(col[i:i + c], c), 0.0), axis=0,
                 keepdims=True)
         for i in range(0, n, c)], axis=1)


def _row_to_col(row_ref, w: int):
    """The row [1, n] f32 of a stats block ref [1, 1, 1, n] -> [n, w],
    lane-replicated, the same way (each chunk is loaded off the ref:
    Mosaic broadcasts a loaded row down the sublanes, not a lane-offset
    slice of a value)."""
    n = row_ref.shape[-1]
    c = _stat_lanes(n)
    eye = _eye(c)
    return jnp.concatenate(
        [jnp.broadcast_to(
            jnp.sum(jnp.where(eye, row_ref[0, 0, :, i:i + c], 0.0), axis=1,
                    keepdims=True), (c, w))
         for i in range(0, n, c)], axis=0)


def _causal_mask(s, q0, k0, q_axis: int):
    """Score tile s with q positions from q0 along `q_axis` and k
    positions from k0 along the other: NEG_INF where k is past q."""
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    return jnp.where(q_pos >= k_pos, s, NEG_INF)


def _tile_loops(causal, n_t, body, carry, open_tiles, cut_tiles):
    """Run `body(masked)(a, carry)` over the chunk's tiles a in [0, n_t):
    all of them without the mask when not causal; else the range
    `open_tiles`, where every score is live, without it, then the range
    `cut_tiles`, which the diagonal crosses, with it, and the rest (no
    live score) not at all."""
    if not causal:
        return jax.lax.fori_loop(0, n_t, body(False), carry)
    carry = jax.lax.fori_loop(*open_tiles, body(False), carry)
    return jax.lax.fori_loop(*cut_tiles, body(True), carry)


def _tiles_under_q_block(i, bo, ts, t0, n_t):
    """(open, cut) ranges of the k tiles of the chunk that starts at tile
    t0, for q block i (forward and dQ): tiles wholly at or under the
    diagonal need no mask; those past the block's last row are not
    visited.  Open tiles come first, so every row's first tile holds a
    live score."""
    open_end = jnp.clip((i * bo + 1) // ts - t0, 0, n_t)
    live_end = jnp.clip(pl.cdiv((i + 1) * bo, ts) - t0, 0, n_t)
    return (0, open_end), (open_end, live_end)


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_acc, l_acc, o_acc,
                 *, scale, causal, bo, ts, n_t):
    """Grid program: (batch*head, q block, k/v chunk), chunk innermost.

    q_ref/o_ref [1, bo, d]; k_ref/v_ref [1, bs, d], the chunk, walked in
    tiles of ts rows by a loop whose bounds follow the causal limit;
    lse_ref [1, 1, 1, bo], the logsumexp of the scaled scores as a row.
    Running max, sum and output live in f32 scratch across chunks, max
    and sum replicated over `_stat_lanes(ts)` lanes.
    """
    i, c = pl.program_id(1), pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        m_acc[...] = jnp.full_like(m_acc, NEG_INF)
        l_acc[...] = jnp.zeros_like(l_acc)
        o_acc[...] = jnp.zeros_like(o_acc)

    q, s_scale = _scaled(q_ref[0], scale)                 # [bo, d]
    d = q.shape[1]
    t0 = c * n_t                                          # chunk's 1st tile
    # A head narrower than its lane tile leaves lanes of the P.V product
    # free: ones beside V make them P's row sums, on the MXU and already
    # rescaled with the accumulator, where a sum over lanes costs a
    # cross-lane reduction a sublane group in every tile.
    ones = jnp.ones((ts, -d % REP), v_ref.dtype) if d % REP else None

    def body(masked):
        def tile(a, carry):
            m, l, acc = carry                     # [bo,w]x2, [bo,d(+free)]
            rows = pl.ds(pl.multiple_of(a * ts, ts), ts)
            k, v = k_ref[0, rows, :], v_ref[0, rows, :]
            s = _scores(q, k, s_scale)                    # [bo, ts] f32
            if masked:
                s = _causal_mask(s, i * bo, (t0 + a) * ts, 0)
            new_m = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            # every row's first tile holds a live score (tiles run from
            # column 0 up), so new_m is finite and a masked score's
            # exp(NEG_INF - new_m) is exactly 0
            p = jnp.exp(s - _lanes(new_m, ts))
            alpha = jnp.exp(m - new_m)
            if ones is None:
                l = l * alpha + jnp.sum(p, axis=1, keepdims=True)
            else:
                v = jnp.concatenate([v, ones], axis=1)
            acc = acc * _lanes(alpha, acc.shape[1]) + _mm(p, v)
            return new_m, l, acc
        return tile

    m, l, acc = _tile_loops(
        causal, n_t, body, (m_acc[...], l_acc[...], o_acc[...]),
        *_tiles_under_q_block(i, bo, ts, t0, n_t))
    m_acc[...], l_acc[...], o_acc[...] = m, l, acc

    @pl.when(c == pl.num_programs(2) - 1)
    def _flush():
        row_sum = l if ones is None else jnp.broadcast_to(
            acc[:, d:d + 1], l.shape)
        l_safe = jnp.maximum(row_sum, 1e-30)
        o_ref[0] = (acc[:, :d] * _lanes(1.0 / l_safe, d)).astype(o_ref.dtype)
        lse_ref[0, 0] = _col_to_row(m + jnp.log(l_safe))


def _fold(x, b, s, h, d):
    """[B,S,H,D] -> [B*H, S, D]"""
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _unfold(x, b, s, h, d):
    return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def _params(limit):
    # the innermost (chunk) axis is sequential: scratch accumulates over
    # it; the outer two are independent
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=limit)


def _flash_forward(q, k, v, causal: bool, interpret: bool):
    """Returns (out [B,S,H,D], lse [B*H, S] float32)."""
    b, s, h, d = q.shape
    bo, ts, bs, limit = _plan("fwd", s, d, q.dtype.itemsize, interpret)
    scale = 1.0 / (d ** 0.5)
    qf, kf, vf = (_fold(x, b, s, h, d) for x in (q, k, v))

    def chunk(bh, i, c):
        # a chunk past the causal limit names the last live one: no DMA
        if causal:
            c = jnp.minimum(c, ((i + 1) * bo - 1) // bs)
        return bh, c, 0

    out, lse = pl.pallas_call(
        functools.partial(_attn_kernel, scale=scale, causal=causal, bo=bo,
                          ts=ts, n_t=bs // ts),
        grid=(b * h, s // bo, s // bs),
        in_specs=[
            pl.BlockSpec((1, bo, d), lambda bh, i, c: (bh, i, 0)),
            pl.BlockSpec((1, bs, d), chunk),
            pl.BlockSpec((1, bs, d), chunk),
        ],
        out_specs=[
            pl.BlockSpec((1, bo, d), lambda bh, i, c: (bh, i, 0)),
            pl.BlockSpec((1, 1, 1, bo), lambda bh, i, c: (bh, i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, s // bo, 1, bo), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bo, _stat_lanes(ts)), jnp.float32),
                        pltpu.VMEM((bo, _stat_lanes(ts)), jnp.float32),
                        pltpu.VMEM((bo, d + -d % REP), jnp.float32)],
        compiler_params=_params(limit),
        interpret=interpret,
    )(qf, kf, vf)
    return _unfold(out, b, s, h, d), lse.reshape(b * h, s)


def _dkv_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *,
                scale, causal, bo, ts, n_t):
    """Grid program: (batch*head, k/v block, q chunk), chunk innermost.

    The K/V block [1, bo, d] stays while Q/dO chunks [1, bs, d] and their
    stats [1, n_t, 1, ts] stream through in tiles of ts q positions.  The
    score tile is TRANSPOSED, [k rows, q columns]: the stats broadcast
    down it as the rows they are, and all four matmuls stream the k rows
    through the MXU with no transpose.  dK/dV accumulate in f32 scratch
    and flush on the last chunk.
    """
    j, c = pl.program_id(1), pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    k, v = k_ref[0], v_ref[0]                             # [bo, d]
    k_s, s_scale = _scaled(k, scale)
    t0 = c * n_t

    def body(masked):
        def tile(a, carry):
            dk, dv = carry                                # [bo, d] f32
            rows = pl.ds(pl.multiple_of(a * ts, ts), ts)
            q, do = q_ref[0, rows, :], do_ref[0, rows, :]   # [ts, d]
            s_t = _scores(k_s, q, s_scale)                # [bo, ts] f32
            if masked:
                s_t = _causal_mask(s_t, (t0 + a) * ts, j * bo, 1)
            p_t = jnp.exp(s_t - lse_ref[0, a])            # rows [1, ts]
            dv = dv + _mm(p_t, do)
            dp_t = _scores(v, do)
            ds_t = p_t * (dp_t - delta_ref[0, a])
            dk = dk + _mm(ds_t, q)
            return dk, dv
        return tile

    # q tiles wholly before the k block see none of it; those the
    # diagonal crosses are masked; the rest are live throughout
    live_from = jnp.clip((j * bo) // ts - t0, 0, n_t)
    open_from = jnp.clip(pl.cdiv((j + 1) * bo - 1, ts) - t0, 0, n_t)
    dk, dv = _tile_loops(causal, n_t, body, (dk_acc[...], dv_acc[...]),
                         (open_from, n_t), (live_from, open_from))
    dk_acc[...], dv_acc[...] = dk, dv

    @pl.when(c == pl.num_programs(2) - 1)
    def _flush():
        dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv.astype(dv_ref.dtype)


def _dq_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref, dq_ref,
               dq_acc, lse_col, delta_col, *, scale, causal, bo, ts, n_t):
    """Grid program: (batch*head, q block, k/v chunk), chunk innermost;
    the Q/dO block stays while K/V stream through, as in the forward.
    The block's stats arrive as rows [1, 1, 1, bo] and are turned into
    lane-replicated columns [bo, `_stat_lanes(ts)`] once, on the first
    chunk."""
    i, c = pl.program_id(1), pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        lse_col[...] = _row_to_col(lse_ref, lse_col.shape[1])
        delta_col[...] = _row_to_col(delta_ref, delta_col.shape[1])

    do = do_ref[0]                                        # [bo, d]
    q, s_scale = _scaled(q_ref[0], scale)
    lse, delta = lse_col[...], delta_col[...]             # [bo, w]
    t0 = c * n_t

    def body(masked):
        def tile(a, dq):
            rows = pl.ds(pl.multiple_of(a * ts, ts), ts)
            k, v = k_ref[0, rows, :], v_ref[0, rows, :]   # [ts, d]
            s = _scores(q, k, s_scale)                    # [bo, ts] f32
            if masked:
                s = _causal_mask(s, i * bo, (t0 + a) * ts, 0)
            p = jnp.exp(s - _lanes(lse, ts))
            ds = p * (_scores(do, v) - _lanes(delta, ts))
            return dq + _mm(ds, k)                        # [bo, d]
        return tile

    dq = _tile_loops(causal, n_t, body, dq_acc[...],
                     *_tiles_under_q_block(i, bo, ts, t0, n_t))
    dq_acc[...] = dq

    @pl.when(c == pl.num_programs(2) - 1)
    def _flush():
        dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _flash_backward(q, k, v, o, lse, g, causal: bool, interpret: bool):
    b, s, h, d = q.shape
    # delta_i = sum_d dO_i * O_i — the softmax-jacobian row correction
    # (FlashAttention-2 eq. 4); elementwise on the unfolded operands, XLA
    # fuses it, and only the [B,S,H] result is folded.
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = delta.transpose(0, 2, 1).reshape(b * h, s)
    return _bwd_block(q, k, v, g, lse, delta, causal, interpret)


def _bwd_block(q, k, v, g, lse, delta, causal: bool, interpret: bool):
    """(dq, dk, dv) for one attention block given the Q-side row stats.

    q/k/v/g: [B,S,H,D]; lse/delta: [B*H, S] float32.  Used both by the
    single-device VJP and (per ring step, with the GLOBAL lse/delta) by
    ring attention's distributed backward.
    """
    b, s, h, d = q.shape
    scale = 1.0 / (d ** 0.5)
    qf, kf, vf, gf = (_fold(x, b, s, h, d) for x in (q, k, v, g))

    bo, ts, bs, limit = _plan("dkv", s, d, q.dtype.itemsize, interpret)

    def q_chunk(bh, j, c):
        # a q chunk wholly before the k block names the first live one
        if causal:
            c = jnp.maximum(c, (j * bo) // bs)
        return bh, c, 0

    def q_stats(bh, j, c):
        return q_chunk(bh, j, c) + (0,)

    rows = (b * h, s // ts, 1, ts)
    dkf, dvf = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal, bo=bo,
                          ts=ts, n_t=bs // ts),
        grid=(b * h, s // bo, s // bs),
        in_specs=[
            pl.BlockSpec((1, bs, d), q_chunk),                        # q
            pl.BlockSpec((1, bs, d), q_chunk),                        # do
            pl.BlockSpec((1, bs // ts, 1, ts), q_stats),              # lse
            pl.BlockSpec((1, bs // ts, 1, ts), q_stats),              # delta
            pl.BlockSpec((1, bo, d), lambda bh, j, c: (bh, j, 0)),    # k
            pl.BlockSpec((1, bo, d), lambda bh, j, c: (bh, j, 0)),    # v
        ],
        out_specs=[
            pl.BlockSpec((1, bo, d), lambda bh, j, c: (bh, j, 0)),
            pl.BlockSpec((1, bo, d), lambda bh, j, c: (bh, j, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((b * h, s, d), k.dtype),
                   jax.ShapeDtypeStruct((b * h, s, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bo, d), jnp.float32),
                        pltpu.VMEM((bo, d), jnp.float32)],
        compiler_params=_params(limit),
        interpret=interpret,
    )(qf, gf, lse.reshape(rows), delta.reshape(rows), kf, vf)

    bo, ts, bs, limit = _plan("dq", s, d, q.dtype.itemsize, interpret)

    def kv_chunk(bh, i, c):
        if causal:
            c = jnp.minimum(c, ((i + 1) * bo - 1) // bs)
        return bh, c, 0

    rows = (b * h, s // bo, 1, bo)
    dqf = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal, bo=bo,
                          ts=ts, n_t=bs // ts),
        grid=(b * h, s // bo, s // bs),
        in_specs=[
            pl.BlockSpec((1, bo, d), lambda bh, i, c: (bh, i, 0)),    # q
            pl.BlockSpec((1, bo, d), lambda bh, i, c: (bh, i, 0)),    # do
            pl.BlockSpec((1, 1, 1, bo), lambda bh, i, c: (bh, i, 0, 0)),
            pl.BlockSpec((1, 1, 1, bo), lambda bh, i, c: (bh, i, 0, 0)),
            pl.BlockSpec((1, bs, d), kv_chunk),                       # k
            pl.BlockSpec((1, bs, d), kv_chunk),                       # v
        ],
        out_specs=pl.BlockSpec((1, bo, d), lambda bh, i, c: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bo, d), jnp.float32),
                        pltpu.VMEM((bo, _stat_lanes(ts)), jnp.float32),
                        pltpu.VMEM((bo, _stat_lanes(ts)), jnp.float32)],
        compiler_params=_params(limit),
        interpret=interpret,
    )(qf, gf, lse.reshape(rows), delta.reshape(rows), kf, vf)

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


# What a block's `jax.checkpoint` keeps of its attention (transformer.
# `_apply_dealt`): the forward kernel's two outputs, so that the backward
# goes to dK/dV and dQ without running the forward kernel again.
SAVED_NAMES = ("attn_out", "attn_lse")


def _saved(out, lse):
    """`out` and `lse` under `SAVED_NAMES`; an identity off a checkpoint.
    (Imported here and not at the top: a Mosaic body carries the line
    numbers of everything above it, and those key the compile cache.)"""
    from jax.ad_checkpoint import checkpoint_name

    return (checkpoint_name(out, SAVED_NAMES[0]),
            checkpoint_name(lse, SAVED_NAMES[1]))


def _fa_fwd(q, k, v, causal, interpret):
    out, lse = _saved(*_flash_forward(q, k, v, causal,
                                      _resolve_interpret(interpret)))
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, interpret, residuals, g):
    q, k, v, o, lse = residuals
    if not _flash_bwd_enabled():
        return _dense_grads(q, k, v, causal, g)
    return _flash_backward(q, k, v, o, lse, g, causal,
                           _resolve_interpret(interpret))


flash_attention.defvjp(_fa_fwd, _fa_bwd)
