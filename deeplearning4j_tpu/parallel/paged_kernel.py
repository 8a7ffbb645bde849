"""Pallas paged-attention decode kernel: the block-table walk fused
into flash attention (ROADMAP item 6, kernel plane round 2).

The gather oracle in ``generation._paged_attn`` pays a full-history
bandwidth tax per layer per dispatch: it materializes every lane's
logical history as a contiguous ``[B, MP*ps, H, K]`` buffer
(``hk, hv = fk[gidx]``) before running dense masked softmax — ``MP*ps``
rows of HBM traffic per lane whether the lane holds 3 live pages or 30.
``paged_flash_attention`` removes the buffer entirely: the kernel takes
the page pool ``[P, ps, H, K]``, the per-lane block table ``[B, MP]``,
``pos`` and ``n_feed`` directly, prefetches the page ids as scalars
(``pltpu.PrefetchScalarGridSpec``) so the BlockSpec index maps can
resolve *physical* page addresses before each grid step's DMA, and
streams K/V one page at a time through a FlashAttention-style online
softmax accumulator (PAPERS.md 2205.14135; fused-epilogue discipline
per 1808.05567).  Pages past a lane's frontier — beyond-``pos`` pages,
which is where every null/unallocated block-table entry lives — are
skipped: their grid steps clamp the index map onto the lane's last live
page (no new DMA) and ``pl.when`` guards out the compute, so both
bandwidth and FLOPs scale with *live* pages, not ``MP*ps``.

Chunked feeds (C > 1: chunked prefill and the speculative verify
dispatch) ride the same kernel: query column ``c`` sits at write
position ``pos + c`` and the in-kernel mask admits keys at
``t <= pos + c`` — bitwise the same causal semantics as the oracle's
masked softmax, including intra-chunk attention (the chunk's own k/v
were scattered into the pool before the kernel runs).

Like ``kernels.flash_attention``, ``interpret=None`` auto-detects:
compiled on TPU, Pallas interpret mode elsewhere — so the tier-1 parity
sweep (tests/test_kernels.py, ``paged_kernel`` marker) exercises the
real kernel everywhere the suite runs.  Whether the *serving* paths use
the kernel at all is the separate ``paged_kernel_enabled()`` policy
below, mirroring ``flash_enabled()``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.parallel.kernels import (
    REP,
    _resolve_interpret,
    mask_value,
)


def paged_kernel_enabled() -> bool:
    """Policy for the paged decode/prefill/verify dispatches: the fused
    block-table kernel on TPU by default, the gather oracle elsewhere;
    opt in/out anywhere with DL4J_TPU_PAGED_KERNEL=1/0.  (Parity tests
    opt IN on CPU — the kernel then runs in interpret mode.)"""
    import os

    flag = os.environ.get("DL4J_TPU_PAGED_KERNEL")
    if flag is not None:
        return flag.lower() in ("1", "true", "yes")
    return jax.default_backend() == "tpu"


def resolve_paged_kernel(paged_kernel) -> bool:
    """Normalize the ``paged_kernel=`` switch BEFORE it reaches any
    compile-ladder cache key: ``None`` resolves through the policy
    above, anything else coerces to bool — so auto-detect and an
    explicit matching flag hit the SAME cached program."""
    if paged_kernel is None:
        return paged_kernel_enabled()
    return bool(paged_kernel)


def _paged_attn_kernel(table_ref, pos_ref, nf_ref, q_ref, k_ref, v_ref,
                       o_ref, m_acc, l_acc, acc, *, scale, ps, c, mp,
                       neg):
    """Grid program: one (lane, logical_page) pair, the page dimension
    sequential (online-softmax accumulation in VMEM scratch).

    table_ref/pos_ref/nf_ref are the scalar-prefetch operands — already
    resident when the body runs, and consumed by the K/V index maps to
    turn logical page ``lp`` into a physical pool address.  q_ref
    ``[C, H, K]`` is revisited across the page steps; k_ref/v_ref
    ``[ps, H, K]`` is ALL heads of this lane's page ``lp`` (or a clamped
    repeat of its last live page on dead steps — same block index, so
    the pipeline issues no new DMA).  Taking every head of a page per
    step is what makes the blocks legal for Mosaic: the last two block
    dims are the full ``(H, K)`` of the pool, where a one-head
    ``(1, K)`` slice of a 12-row sublane dim is neither tile-aligned
    nor full.

    The body stays in the pool's own ``[., H, K]`` layout — heads on
    sublanes, head_dim on lanes — and only ever indexes LEADING dims
    (query column, page row): scores are a lane reduction of ``q * k``
    and the value mix a leading-dim reduction of ``p * v``, so there is
    no per-head slice, transpose or matmul for the compiler to refuse.
    Row stats live lane-replicated ``[C, H, REP]`` (see kernels.REP).
    """
    b, lp = pl.program_id(0), pl.program_id(1)
    h = q_ref.shape[1]

    @pl.when(lp == 0)
    def _init():
        m_acc[...] = jnp.full_like(m_acc, neg)
        l_acc[...] = jnp.zeros_like(l_acc)
        acc[...] = jnp.zeros_like(acc)

    # The lane's frontier: its last written position this dispatch.
    # Pages strictly past it are fully masked — skip them (this is also
    # where every null block-table entry of a live lane lives).
    wmax = pos_ref[b] + jnp.maximum(nf_ref[b], 1) - 1

    @pl.when(lp * ps <= wmax)
    def _page():
        k_blk = k_ref[...].astype(jnp.float32)              # [ps, H, K]
        v_blk = v_ref[...].astype(jnp.float32)
        # key t = lp*ps + row is visible to query column ci iff
        # t <= pos + ci — the oracle's causal mask, intra-chunk included
        t = lp * ps + jax.lax.broadcasted_iota(jnp.int32, (ps, h, 1), 0)
        for ci in range(c):
            q = q_ref[ci].astype(jnp.float32) * scale       # [H, K]
            s = jnp.sum(q[None] * k_blk, axis=-1,
                        keepdims=True)                      # [ps, H, 1]
            live = t <= pos_ref[b] + ci
            s = jnp.where(live, s, neg)
            m = m_acc[ci][:, :1]                            # [H, 1]
            new_m = jnp.maximum(m, jnp.max(s, axis=0))
            p = jnp.where(live, jnp.exp(s - new_m[None]), 0.0)
            scale_old = jnp.exp(m - new_m)
            new_l = l_acc[ci][:, :1] * scale_old + jnp.sum(p, axis=0)
            acc[ci] = acc[ci] * scale_old + jnp.sum(p * v_blk, axis=0)
            m_acc[ci] = jnp.broadcast_to(new_m, (h, REP))
            l_acc[ci] = jnp.broadcast_to(new_l, (h, REP))

    @pl.when(lp == mp - 1)
    def _flush():
        l = l_acc[...][:, :, :1]                            # [C, H, 1]
        o_ref[...] = (acc[...] / jnp.maximum(l, 1e-30)).astype(
            o_ref.dtype)


def paged_flash_attention(q, k_pages, v_pages, table, pos, n_feed=None,
                          interpret: bool | None = None) -> jax.Array:
    """Fused block-table paged attention.

    q: [B, C, H, K] queries (C = feed width; decode dispatches use 1);
    k_pages/v_pages: [P, ps, H, K] page pool AFTER this dispatch's
    scatter (the chunk's own k/v are already in their pages);
    table: [B, MP] int32 physical page ids per logical page;
    pos: [B] int32 start positions; n_feed: [B] int32 real columns
    (None = every column fed).  Returns [B, C, H, K] in q.dtype.

    Matches the gather oracle exactly at every column ``< n_feed``;
    padding columns (never consumed — `paged_decode_step` indexes
    column ``n_feed - 1``, the verify step at most that) attend only
    through the lane's frontier page rather than the oracle's full
    ``pos + c`` horizon.
    """
    b, c, h, kd = q.shape
    ps = k_pages.shape[1]
    mp = table.shape[1]
    table = jnp.asarray(table, jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    n_feed = (jnp.full((b,), c, jnp.int32) if n_feed is None
              else jnp.asarray(n_feed, jnp.int32))
    scale = 1.0 / (kd ** 0.5)
    neg = float(jnp.finfo(jnp.float32).min / 2)

    def _lane_map(bi, lp, tbl, pos_, nf):
        return (bi, 0, 0, 0)

    def _page_map(bi, lp, tbl, pos_, nf):
        # Clamp dead grid steps onto the lane's last live logical page:
        # the repeated block index means the pipeline re-uses the
        # already-resident page instead of DMAing a dead one.
        wmax = pos_[bi] + jnp.maximum(nf[bi], 1) - 1
        live_lp = jnp.minimum(lp, wmax // ps)
        return (tbl[bi, live_lp], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, mp),
        in_specs=[
            pl.BlockSpec((None, c, h, kd), _lane_map),
            pl.BlockSpec((None, ps, h, kd), _page_map),
            pl.BlockSpec((None, ps, h, kd), _page_map),
        ],
        out_specs=pl.BlockSpec((None, c, h, kd), _lane_map),
        scratch_shapes=[
            pltpu.VMEM((c, h, REP), jnp.float32),   # running max
            pltpu.VMEM((c, h, REP), jnp.float32),   # running denominator
            pltpu.VMEM((c, h, kd), jnp.float32),    # output accumulator
        ],
    )
    kernel = functools.partial(_paged_attn_kernel, scale=scale, ps=ps,
                               c=c, mp=mp, neg=neg)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, c, h, kd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_resolve_interpret(interpret),
    )(table, pos, n_feed, q, k_pages, v_pages)


def paged_hbm_bytes(n_layers: int, lanes: int, live_pages: int,
                    max_pages: int, page_size: int, n_heads: int,
                    head_dim: int, itemsize: int,
                    kernel: bool) -> int:
    """Modeled K/V HBM bytes one decode dispatch reads (the cost model
    in docs/performance.md): the gather path touches every block-table
    row — ``MP * ps`` pool rows per lane per layer — while the kernel
    reads only the lane's live pages.  Both read k AND v (the factor
    2); q/output/params traffic is identical across the paths and
    excluded."""
    rows = (live_pages if kernel else max_pages) * page_size
    return 2 * n_layers * lanes * rows * n_heads * head_dim * itemsize
