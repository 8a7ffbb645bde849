"""Pallas paged-attention decode kernel: the block-table walk fused
into flash attention (ROADMAP item 6, kernel plane round 2).

The gather oracle in ``generation._paged_attn`` pays a full-history
bandwidth tax per layer per dispatch: it materializes every lane's
logical history as a contiguous ``[B, MP*ps, H, K]`` buffer
(``fk[gidx]``) before running dense masked softmax — ``MP*ps``
rows of HBM traffic per lane whether the lane holds 3 live pages or 30.
``paged_flash_attention`` removes the buffer entirely: the kernel takes
the serving pool as it lies on the device, ``[L, P, ps, H*K]``, a
``layer``, the per-lane block table ``[B, MP]``, ``pos`` and
``n_feed`` directly.  The table and the two vectors are prefetched as
scalars (``pltpu.PrefetchScalarGridSpec``); the pool stays in HBM
(``memory_space=pl.ANY``).  The grid is one step a LANE, and the lane's
block table is walked inside the body: a ``fori_loop`` over the lane's
live pages, several pages a block, each page ``layer*P + table[b, i]``
fetched by its own DMA into a two-slot VMEM buffer (the next block in
flight under this block's matmuls) and streamed through a
FlashAttention-style online softmax accumulator (PAPERS.md 2205.14135;
fused-epilogue discipline per 1808.05567).  Pages past a lane's
frontier, beyond-``pos`` pages, which is where every null/unallocated
block-table entry lives, are never visited, and a lane that feeds
nothing takes no trip at all: bandwidth, FLOPs AND time scale with
*live* pages, not ``MP*ps`` (a grid over ``(lanes, max_pages)`` paid
0.22 us for every dead step: 7.9 of a chat round's 11.4 ms, PERF.md
section 6, PR 29).

The pool's rows are lane-dense (all heads of a position side by side:
16 rows by 1,280 lanes is an exact bf16 tile for GPT-2-large), which is
the one layout the step's write (the `.at[].set` scatter, or the row
writer at the end of this file: `write_kv_rows`, one call a layer that
fetches, fills and sends back the 8-row groups the fed rows lie in), the
resident buffer and this kernel agree on: no call slices the pool or
asks the compiler to relay it, so the step updates it in place (PERF.md
section 4).  The
price is that a head is no longer a dim of the block: the per-head
reduction is a block-diagonal matmul on the MXU, one 128-lane tile
(``128 // K`` heads) at a time (see ``_paged_attn_kernel``).  A pool
with fewer K/V heads than the queries have, or a block mask, takes
``_grouped_attn_kernel`` through the same entry point: the same walk,
as many pages a block by the same rule of the shapes
(``_pages_per_block``: eight pages of 16 rows, one of 128), a plain
matmul a K/V head.

Chunked feeds (C > 1: chunked prefill and the speculative verify
dispatch) ride the same kernel: query column ``c`` sits at write
position ``pos + c`` and the in-kernel mask admits keys at
``t <= pos + c`` — bitwise the same causal semantics as the oracle's
masked softmax, including intra-chunk attention (the chunk's own k/v
were written into the pool, by the scatter or by the row writer, before
the kernel runs).

Like ``kernels.flash_attention``, ``interpret=None`` auto-detects:
compiled on TPU, Pallas interpret mode elsewhere — so the tier-1 parity
sweep (tests/test_kernels.py, ``paged_kernel`` marker) exercises the
real kernel everywhere the suite runs.  Whether the *serving* paths use
the kernel at all is the separate ``paged_kernel_enabled()`` rule
below: the platform decides.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.parallel.kernels import (
    _DEFAULT_SCOPED_VMEM,
    REP,
    _buf,
    _resolve_interpret,
)


def paged_kernel_enabled() -> bool:
    """The rule for the paged decode/prefill/verify dispatches: the
    fused block-table kernel on a TPU, the gather oracle elsewhere (on
    a CPU the kernel would run in the Pallas interpreter)."""
    return jax.default_backend() == "tpu"


def resolve_paged_kernel(paged_kernel) -> bool:
    """``generation``'s ``paged_kernel=`` keyword as a bool BEFORE it
    reaches a compile cache key: ``None`` takes the rule above, anything
    else coerces — so the platform's choice and an explicit matching
    flag hit the SAME cached program."""
    if paged_kernel is None:
        return paged_kernel_enabled()
    return bool(paged_kernel)


# the masked score: finite in f32 (a fully masked row must not NaN)
_NEG = float(jnp.finfo(jnp.float32).min) / 2


def _lane_tile(hkd: int, kd: int) -> int:
    """Lanes of one kernel tile: a full 128-lane vreg column holding
    ``128 // kd`` whole heads where the shapes allow it (kd 64 -> head
    pairs), otherwise the whole ``H*K`` row as one tile (toy shapes and
    head sizes that do not divide 128)."""
    return 128 if hkd % 128 == 0 and 128 % kd == 0 else hkd


def _dot_f32(a, b, dims):
    """``a`` (f32) times a pool block ``b`` (bf16 or f32) with f32
    accumulation, at f32 fidelity whatever the MXU's native pass is: a
    bf16 block takes two bf16 passes (``a`` split into its bf16 head and
    the bf16 of what is left: 16 bits of mantissa, and products with
    bf16 are exact), an f32 block one ``HIGHEST`` matmul."""
    dn = (dims, ((), ()))
    if b.dtype == jnp.bfloat16:
        hi = a.astype(jnp.bfloat16)
        lo = (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)
        return (jax.lax.dot_general(hi, b, dn,
                                    preferred_element_type=jnp.float32)
                + jax.lax.dot_general(lo, b, dn,
                                      preferred_element_type=jnp.float32))
    return jax.lax.dot_general(a, b, dn,
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


# Keys a block of the page walk holds: one 128-lane tile of scores.  A
# page of 16 keys alone leaves the MXU's latches and the DMA's issue and
# wait as all of a visit: on a v5e at GPT-2-large's row a page costs
# 1.67 us walked one a block, 0.85 at two, 0.24 at four, 0.17 at eight
# and 0.15 at sixteen, where a lane's tail block already fetches more
# spare slots than pages (PERF.md section 6, PR 29).  The grouped kernel
# at SDAR's row (4 K/V heads of 128, 128 lanes x 16 columns, 26 live
# pages a lane): 1.63, 0.78, 0.46, 0.27 and 0.23 (PR 43): a `[128, 16]`
# tile of scores fills the 16 vregs a `[128, 128]` one does, so a block
# costs 1.5-1.9 us whatever it holds up to a tile, and 2.9 at two tiles.
_KEYS = 128


def _pages_per_block(ps: int, hkd: int, itemsize: int, mp: int,
                     interpret: bool) -> int:
    """Pages `G` one block of the walk fetches and attends over at once:
    as many as make `_KEYS` keys, within the table's width and with the
    K and V buffers (two slots each) inside a quarter of the scoped VMEM
    `kernels._plan` sizes its blocks to.  Mosaic tiles a VMEM buffer's
    rows in eights, so a compiled call whose page is not whole tiles
    walks a page a block: the page then fills a buffer slot whole and
    no DMA lands inside a tile."""
    if not interpret and ps % 8:
        return 1
    fit = (_DEFAULT_SCOPED_VMEM // 4) // (4 * _buf(ps, hkd, itemsize))
    return max(1, min(_KEYS // ps, mp, fit))


def walk_plan(ps: int, row: int, itemsize: int, mp: int, width: int,
              heads: int, head_dim: int, block: int = 1,
              latent: bool = False) -> tuple:
    """(pages a block, fed columns a query block) of the walk the kernel
    these shapes are sent to takes, for whoever counts the blocks of a
    round's walk (`serving/lm.py`): the latent kernel walks a page a
    block; a pool row `[heads * head_dim]` without a block mask is the
    full-heads kernel's (every column of a lane in one grid step), any
    other the grouped kernel's, by `paged_flash_attention`'s own rule."""
    if latent:
        return 1, _query_block(width, heads)
    gp = _pages_per_block(ps, row, itemsize, mp, _resolve_interpret(None))
    grouped = block > 1 or row != heads * head_dim
    return gp, _grouped_query_block(width) if grouped else width


def _paged_attn_kernel(table_ref, pos_ref, nf_ref, q_ref, k_ref, v_ref,
                       o_ref, kbuf, vbuf, sem, qt, m_acc, l_acc, acc, *,
                       scale, ps, c, cp, kd, tw, gp, neg):
    """Grid program: one lane.  The lane's live pages are walked INSIDE
    the body, `gp` pages a block, so a dispatch pays for the pages it
    holds and nothing for the table's width or an idle lane.

    table_ref/pos_ref/nf_ref are the scalar-prefetch operands, resident
    when the body runs.  q_ref ``[CP, H*K]`` is the lane's C fed columns
    padded to whole sublane tiles.  k_ref/v_ref are the WHOLE pool
    ``[L*P, ps, H*K]`` left in HBM (``memory_space=pl.ANY``): page
    ``table[b, i]`` (the table is already offset to the layer) is
    fetched by its own DMA into rows ``[j*ps, (j+1)*ps)`` of a buffer
    slot ``[gp*ps, H*K]``, two slots deep, the next block's DMAs in
    flight under this block's matmuls.  Rows are lane-dense: all heads
    of a position side by side, the pool's own layout in HBM (no
    relayout on either side of the call).

    The lane holds ``n = (pos + n_feed - 1) // ps + 1`` live pages
    (every null block-table entry of a live lane lies past them) and
    takes ``ceil(n / gp)`` trips.  The tail block's spare slots fetch
    the lane's LAST live page again: every row a matmul reads was
    written by a DMA from a live page, so a masked key's ``p`` of
    exactly 0 never meets what VMEM happened to hold (0 x NaN is NaN on
    the MXU), and no dead table entry is ever dereferenced.  A lane
    with ``n_feed == 0`` reads nothing and writes zeros: no column of
    it is consumed.

    The per-head reduction runs on the MXU, one ``tw``-lane tile (``g``
    whole heads; a head pair at K=64) at a time.  The queries of a tile
    are laid out block-diagonally in ``qt``: row ``gi*CP + ci`` holds
    column ``ci``'s query with every lane outside head ``gi`` zeroed, so
    ``qt[j] @ k_tile.T`` is the ``[g*CP, gp*ps]`` score block of those
    heads (the zeros drop the other heads' lanes from the contraction),
    the softmax statistics are plain row statistics, and ``p @ v_tile``
    gives each row its head's value mix in that head's own lanes (the
    other lanes hold a mix that is masked away at the flush).  Every
    slice is a whole tile: static multiples of ``tw`` lanes and of
    ``CP`` sublanes.
    Row stats live lane-replicated ``[., g*CP, REP]`` (see kernels.REP).
    """
    b = pl.program_id(0)
    nt = qt.shape[0]
    g = tw // kd
    rows = g * cp
    keys = gp * ps
    pos, nf = pos_ref[b], nf_ref[b]
    # the lane's live pages: through its last written position
    n = jnp.where(nf > 0, (pos + nf - 1) // ps + 1, 0)

    def copies(blk, slot):
        out = []
        for j in range(gp):
            page = table_ref[b, jnp.minimum(blk * gp + j, n - 1)]
            dst = pl.ds(j * ps, ps)
            out += [pltpu.make_async_copy(k_ref.at[page],
                                          kbuf.at[slot, dst], sem.at[0, slot]),
                    pltpu.make_async_copy(v_ref.at[page],
                                          vbuf.at[slot, dst], sem.at[1, slot])]
        return out

    @pl.when(n == 0)
    def _idle():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n > 0)
    def _busy():
        for dma in copies(0, 0):
            dma.start()
        m_acc[...] = jnp.full_like(m_acc, neg)
        l_acc[...] = jnp.zeros_like(l_acc)
        acc[...] = jnp.zeros_like(acc)
        # lanes of tile-local head gi, as [CP, tw] masks
        lane = jax.lax.broadcasted_iota(jnp.int32, (cp, tw), 1)
        masks = [(lane >= gi * kd) & (lane < (gi + 1) * kd)
                 for gi in range(g)]
        for j in range(nt):
            qj = q_ref[:, j * tw:(j + 1) * tw].astype(jnp.float32)
            qt[j] = jnp.concatenate(
                [jnp.where(o, qj, 0.0) for o in masks], axis=0
            ).astype(qt.dtype)
        # key t = blk*keys + column is visible to the query column ci of
        # row gi*CP + ci iff t <= pos + ci: the oracle's causal mask,
        # intra-chunk included
        r = jax.lax.broadcasted_iota(jnp.int32, (rows, keys), 0)
        ci = r
        for gi in range(1, g):
            ci = jnp.where(r >= gi * cp, r - gi * cp, ci)
        horizon = pos + ci - jax.lax.broadcasted_iota(
            jnp.int32, (rows, keys), 1)

        def block(blk, carry):
            slot = jax.lax.rem(blk, 2)

            @pl.when((blk + 1) * gp < n)
            def _next():
                for dma in copies(blk + 1, 1 - slot):
                    dma.start()

            for dma in copies(blk, slot):
                dma.wait()
            live = blk * keys <= horizon
            for j in range(nt):
                k_blk = kbuf[slot, :, j * tw:(j + 1) * tw].astype(qt.dtype)
                v_blk = vbuf[slot, :, j * tw:(j + 1) * tw].astype(qt.dtype)
                s = jax.lax.dot_general(
                    qt[j], k_blk, (((1,), (1,)), ((), ())),
                    precision=(None if qt.dtype == jnp.bfloat16
                               else jax.lax.Precision.HIGHEST),
                    preferred_element_type=jnp.float32) * scale  # [rows, keys]
                s = jnp.where(live, s, neg)
                m = m_acc[j][:, :1]                             # [rows, 1]
                new_m = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
                p = jnp.where(live, jnp.exp(s - new_m), 0.0)
                scale_old = jnp.exp(m - new_m)
                new_l = (l_acc[j][:, :1] * scale_old
                         + jnp.sum(p, axis=1, keepdims=True))
                acc[j] = (acc[j] * scale_old
                          + _dot_f32(p, v_blk, ((1,), (0,))))
                m_acc[j] = jnp.broadcast_to(new_m, (rows, REP))
                l_acc[j] = jnp.broadcast_to(new_l, (rows, REP))
            return carry

        jax.lax.fori_loop(0, (n + gp - 1) // gp, block, 0)
        for j in range(nt):
            o = acc[j] / jnp.maximum(l_acc[j][:, :1], 1e-30)  # [rows, tw]
            out = jnp.zeros((cp, tw), jnp.float32)
            for gi in range(g):
                out = jnp.where(masks[gi], o[gi * cp:(gi + 1) * cp], out)
            for col in range(c):
                o_ref[col, :, j * tw:(j + 1) * tw] = (
                    out[col:col + 1].astype(o_ref.dtype))


def paged_flash_attention(q, k_pages, v_pages, table, pos, n_feed=None,
                          interpret: bool | None = None,
                          layer: int | None = None,
                          block: int = 1) -> jax.Array:
    """Fused block-table paged attention.

    q: [B, C, H, K] queries (C = feed width; decode dispatches use 1);
    k_pages/v_pages: the page pool AFTER this dispatch's write, scatter
    or row writer (the chunk's own k/v are already in their pages), in
    one of two forms:
    with ``layer`` the serving pool as it lies on the device,
    ``[L, P, ps, H*K]``, of which the kernel reads layer ``layer``'s
    pages; with ``layer=None`` one layer's pages ``[P, ps, H, K]``
    (reshaped at the boundary);
    table: [B, MP] int32 physical page ids per logical page;
    pos: [B] int32 start positions; n_feed: [B] int32 real columns
    (None = every column fed).  Returns [B, C, H, K] in q.dtype.

    The layer reaches the kernel through the block table: the pool is
    viewed as ``[L*P, ps, H*K]`` (leading dims merged, no data moves)
    and the table offset by ``layer*P``, so the call has no operand but
    the table, the queries and the pool itself, needs no slice of the
    pool, and is the SAME call for every layer — one trace and one
    lowering of the kernel a step program instead of one a layer
    (`_paged_call` is jitted; the step's warm-up is mostly that Python).

    Matches the gather oracle exactly at every column ``< n_feed``;
    padding columns (never consumed — `paged_decode_step` indexes
    column ``n_feed - 1``, the verify step at most that) attend only
    through the lane's frontier page rather than the oracle's full
    ``pos + c`` horizon, and a lane with ``n_feed == 0`` comes back as
    zeros.
    """
    b, c, h, kd = q.shape
    hkd = h * kd
    table = jnp.asarray(table, jnp.int32)
    if block > 1 or (k_pages.shape[2] != h if layer is None
                     else k_pages.shape[3] != hkd):
        # fewer K/V heads than query heads, or the block mask (`block`,
        # see `_grouped_attn_kernel`): the grouped kernel below
        return _grouped_paged_attention(q, k_pages, v_pages, table, pos,
                                        n_feed, interpret, layer, block)
    if layer is None:
        ps = k_pages.shape[1]
    else:
        ps = k_pages.shape[2]
        table = table + layer * k_pages.shape[1]
    k_pages = k_pages.reshape(-1, ps, hkd)
    v_pages = v_pages.reshape(-1, ps, hkd)
    n_feed = (jnp.full((b,), c, jnp.int32) if n_feed is None
              else jnp.asarray(n_feed, jnp.int32))
    cp = -(-c // 8) * 8                       # whole f32 sublane tiles
    qf = jnp.pad(q.reshape(b, c, hkd), ((0, 0), (0, cp - c), (0, 0)))
    out = _paged_call(table, jnp.asarray(pos, jnp.int32), n_feed, qf,
                      k_pages, v_pages, c=c, kd=kd,
                      interpret=_resolve_interpret(interpret))
    return out.reshape(b, c, h, kd)


@functools.partial(jax.jit, static_argnames=("c", "kd", "interpret"))
def _paged_call(table, pos, n_feed, qf, k_pages, v_pages, *, c, kd,
                interpret):
    """The pallas_call: qf [B, CP, H*K] (C real columns), k_pages/v_pages
    [pages, ps, H*K], table [B, MP] -> [B, C, 1, H*K]."""
    b, cp, hkd = qf.shape
    ps = k_pages.shape[1]
    mp = table.shape[1]
    scale = 1.0 / (kd ** 0.5)
    tw = _lane_tile(hkd, kd)
    nt, g = hkd // tw, tw // kd
    # the MXU's operand dtype: the pool's own if that is bf16, else f32
    md = jnp.bfloat16 if k_pages.dtype == jnp.bfloat16 else jnp.float32

    gp = _pages_per_block(ps, hkd, k_pages.dtype.itemsize, mp, interpret)

    def _lane_map(bi, tbl, pos_, nf):
        return (bi, 0, 0)

    def _out_map(bi, tbl, pos_, nf):
        return (bi, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((None, cp, hkd), _lane_map),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((None, c, 1, hkd), _out_map),
        scratch_shapes=[
            pltpu.VMEM((2, gp * ps, hkd), k_pages.dtype),  # K blocks
            pltpu.VMEM((2, gp * ps, hkd), v_pages.dtype),  # V blocks
            pltpu.SemaphoreType.DMA((2, 2)),               # [K|V, slot]
            pltpu.VMEM((nt, g * cp, tw), md),           # block-diagonal q
            pltpu.VMEM((nt, g * cp, REP), jnp.float32),  # running max
            pltpu.VMEM((nt, g * cp, REP), jnp.float32),  # running denom
            pltpu.VMEM((nt, g * cp, tw), jnp.float32),   # output accum
        ],
    )
    kernel = functools.partial(_paged_attn_kernel, scale=scale, ps=ps,
                               c=c, cp=cp, kd=kd, tw=tw, gp=gp, neg=_NEG)
    # The result stays 4-D with the feed width second, [B, C, 1, H*K],
    # and the block table the call's first operand: the benchmark's
    # trace readers find the kernel, and the width, by that signature.
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, c, 1, hkd), qf.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(table, pos, n_feed, qf, k_pages, v_pages)


# ---------------------------------------------------------------------------
# Grouped queries (fewer K/V heads than query heads)
#
# A pool row is `[Hkv * K]`: with K = 128 a K/V head is one 128-lane tile
# of the page, and the `G = H / Hkv` query heads that read it (times the
# fed columns of a query block) are the ROWS of one query block against
# that tile, so a page fetched once serves `G` heads: a plain matmul a
# K/V head and no block-diagonal layout.  The walk is `_paged_attn_kernel`'s:
# the lane's live pages inside the body, `_pages_per_block` pages a block
# (eight pages of 16 positions, or one of 128: a 128-lane tile of scores
# either way), each page fetched by its own DMA, the next block's DMAs
# under this block's matmuls.  Walked a page a block, a page of 16 rows
# cost a DMA wait, an MXU fill and a rescale of the accumulator a K/V head
# for 16 keys: 1.4-1.6 us a visit on a v5e at SDAR's row of 512 lanes, 0.27
# at eight a block (the readings are beside `_KEYS`).


def _grouped_attn_kernel(table_ref, pos_ref, nf_ref, q_ref, k_ref, v_ref,
                         o_ref, kbuf, vbuf, sem, m_acc, l_acc, acc, out, *,
                         scale, ps, cq, g, hkv, kd, gp, neg, block=1):
    """Grid program (lane b, query block j).  q_ref `[Hkv, cq*G, K]`: for
    a K/V head its `G` query heads of `cq` fed columns, row `ci*G + gi`;
    k_ref/v_ref the whole pool `[L*P, ps, Hkv*K]` in HBM; o_ref
    `[cq', H, K]` (the block's real columns).  Key `t` is visible to
    column `ci` iff `t <= pos + ci`; under a block mask (`block` B > 1:
    causal between blocks of B positions dealt by absolute position,
    bidirectional inside one) the column at absolute position
    `p = pos + ci` sees the rows `t < min(pos + n_feed, (p // B + 1) * B)`,
    which for B = 1 is the causal rule of every fed column and the code
    B = 1 traces is the causal one as it was.  A block past the lane's
    fed columns reads nothing and writes zeros.

    The query block's `n` live pages are walked `gp` a block, as
    `_paged_attn_kernel` walks its lane's: page `blk*gp + j` lands in rows
    `[j*ps, (j+1)*ps)` of a buffer slot `[gp*ps, Hkv*K]` by its own DMA,
    all of a block's DMAs started before any is waited for and the next
    block's in flight under this block's matmuls; a K/V head's step is
    `[rows, K] x [K, gp*ps]`, one exponent, `_dot_f32` and one rescale of
    its accumulator.  The tail block's spare slots fetch the LAST live
    page again (every row a matmul reads was written by a DMA from a live
    page: 0 x NaN is NaN on the MXU; no dead table entry is dereferenced);
    the doubled keys lie past every column's `sees` and are masked.  With
    `gp == 1` (a page of 128 rows) a block is the page and the slot."""
    b, j = pl.program_id(0), pl.program_id(1)
    rows = cq * g
    keys = gp * ps
    nf = nf_ref[b]
    first = j * cq
    last = pos_ref[b] + jnp.minimum(first + cq, nf) - 1
    if block > 1:       # through the end of the last column's block
        last = jnp.minimum((last // block + 1) * block,
                           pos_ref[b] + nf) - 1
    n = jnp.where(first < nf, last // ps + 1, 0)

    def copies(blk, slot):
        if gp == 1:     # the page is the block: one DMA a pool, no spare
            page = table_ref[b, blk]
            return [pltpu.make_async_copy(k_ref.at[page], kbuf.at[slot],
                                          sem.at[0, slot]),
                    pltpu.make_async_copy(v_ref.at[page], vbuf.at[slot],
                                          sem.at[1, slot])]
        out = []
        for pj in range(gp):
            page = table_ref[b, jnp.minimum(blk * gp + pj, n - 1)]
            dst = pl.ds(pj * ps, ps)
            out += [pltpu.make_async_copy(k_ref.at[page],
                                          kbuf.at[slot, dst], sem.at[0, slot]),
                    pltpu.make_async_copy(v_ref.at[page],
                                          vbuf.at[slot, dst], sem.at[1, slot])]
        return out

    @pl.when(n == 0)
    def _idle():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n > 0)
    def _busy():
        for dma in copies(0, 0):
            dma.start()
        m_acc[...] = jnp.full_like(m_acc, neg)
        l_acc[...] = jnp.zeros_like(l_acc)
        acc[...] = jnp.zeros_like(acc)
        ci = jax.lax.broadcasted_iota(jnp.int32, (rows, keys), 0) // g
        sees = pos_ref[b] + first + ci      # the last row a column sees
        if block > 1:
            sees = jnp.minimum((sees // block + 1) * block,
                               pos_ref[b] + nf) - 1
        # key t = blk*keys + column is live for a row iff t <= sees
        horizon = sees - jax.lax.broadcasted_iota(jnp.int32, (rows, keys), 1)
        exact = (None if q_ref.dtype == jnp.bfloat16
                 else jax.lax.Precision.HIGHEST)

        def block_step(blk, carry):
            slot = jax.lax.rem(blk, 2)

            @pl.when((blk + 1) * gp < n)
            def _next():
                for dma in copies(blk + 1, 1 - slot):
                    dma.start()

            for dma in copies(blk, slot):
                dma.wait()
            live = blk * keys <= horizon
            for nh in range(hkv):
                k_blk = kbuf[slot, :, nh * kd:(nh + 1) * kd]
                v_blk = vbuf[slot, :, nh * kd:(nh + 1) * kd]
                s = jax.lax.dot_general(
                    q_ref[nh], k_blk.astype(q_ref.dtype),
                    (((1,), (1,)), ((), ())), precision=exact,
                    preferred_element_type=jnp.float32) * scale
                s = jnp.where(live, s, neg)
                m = m_acc[nh][:, :1]
                new_m = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
                p = jnp.where(live, jnp.exp(s - new_m), 0.0)
                scale_old = jnp.exp(m - new_m)
                new_l = (l_acc[nh][:, :1] * scale_old
                         + jnp.sum(p, axis=1, keepdims=True))
                acc[nh] = (acc[nh] * scale_old
                           + _dot_f32(p, v_blk, ((1,), (0,))))
                m_acc[nh] = jnp.broadcast_to(new_m, (rows, REP))
                l_acc[nh] = jnp.broadcast_to(new_l, (rows, REP))
            return carry

        jax.lax.fori_loop(0, pl.cdiv(n, gp), block_step, 0)
        for nh in range(hkv):
            o = acc[nh] / jnp.maximum(l_acc[nh][:, :1], 1e-30)
            out[:, nh * g:(nh + 1) * g, :] = o.reshape(cq, g, kd)
        o_ref[...] = out[:o_ref.shape[0]].astype(o_ref.dtype)


def _grouped_query_block(c: int) -> int:
    """Fed columns a query block of the grouped kernel: the width in
    whole sublane tiles, at most 32 (256 query rows a K/V head at G = 8,
    and the block's buffers about 7 MB of VMEM)."""
    cp = -(-c // 8) * 8
    cq = min(cp, 32)
    while cp % cq:
        cq -= 8
    return cq


def _grouped_paged_attention(q, k_pages, v_pages, table, pos, n_feed,
                             interpret, layer, block: int = 1):
    """`paged_flash_attention` for `H` query heads over `Hkv < H` K/V
    heads: same operands, same result `[B, C, H, K]`."""
    b, c, h, kd = q.shape
    if layer is None:
        ps, hkv = k_pages.shape[1], k_pages.shape[2]
    else:
        ps, hkv = k_pages.shape[2], k_pages.shape[3] // kd
        table = table + layer * k_pages.shape[1]
    g = h // hkv
    k_pages = k_pages.reshape(-1, ps, hkv * kd)
    v_pages = v_pages.reshape(-1, ps, hkv * kd)
    n_feed = (jnp.full((b,), c, jnp.int32) if n_feed is None
              else jnp.asarray(n_feed, jnp.int32))
    cq = _grouped_query_block(c)
    cp = -(-c // cq) * cq
    if cp != c and cp != cq:
        raise ValueError(f"a feed of {c} columns is neither one query "
                         f"block nor whole blocks of {cq}")
    # rows of a K/V head's query block: column-major, its G heads inside
    qg = jnp.pad(q, ((0, 0), (0, cp - c), (0, 0), (0, 0)))
    qg = qg.reshape(b, cp, hkv, g, kd).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(b, hkv, cp * g, kd)
    return _grouped_call(table, jnp.asarray(pos, jnp.int32), n_feed, qg,
                         k_pages, v_pages, c=c, cq=cq, g=g,
                         interpret=_resolve_interpret(interpret),
                         block=int(block))


@functools.partial(jax.jit,
                   static_argnames=("c", "cq", "g", "interpret", "block"))
def _grouped_call(table, pos, n_feed, qg, k_pages, v_pages, *, c, cq, g,
                  interpret, block=1):
    b, hkv, _, kd = qg.shape
    ps = k_pages.shape[1]
    rows = cq * g
    gp = _pages_per_block(ps, hkv * kd, k_pages.dtype.itemsize,
                          table.shape[1], interpret)

    def _q_map(bi, ji, tbl, pos_, nf):
        return (bi, 0, ji, 0)

    def _o_map(bi, ji, tbl, pos_, nf):
        return (bi, ji, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, -(-c // cq)),
        in_specs=[pl.BlockSpec((None, hkv, rows, kd), _q_map),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((None, min(cq, c), hkv * g, kd), _o_map),
        scratch_shapes=[
            pltpu.VMEM((2, gp * ps, hkv * kd), k_pages.dtype),  # K blocks
            pltpu.VMEM((2, gp * ps, hkv * kd), v_pages.dtype),  # V blocks
            pltpu.SemaphoreType.DMA((2, 2)),             # [K|V, slot]
            pltpu.VMEM((hkv, rows, REP), jnp.float32),   # running max
            pltpu.VMEM((hkv, rows, REP), jnp.float32),   # running denom
            pltpu.VMEM((hkv, rows, kd), jnp.float32),    # accumulator
            pltpu.VMEM((cq, hkv * g, kd), jnp.float32),  # heads in order
        ],
    )
    kernel = functools.partial(
        _grouped_attn_kernel, scale=1.0 / (kd ** 0.5), ps=ps, cq=cq, g=g,
        hkv=hkv, kd=kd, gp=gp, neg=_NEG, block=block)
    # as for `_paged_call`: the block table first, the result 4-D with the
    # feed width second; the trace's readers find the kernel by that
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, c, hkv * g, kd), qg.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="grouped_paged_attention",
    )(table, pos, n_feed, qg, k_pages, v_pages)


# ---------------------------------------------------------------------------
# Latent pages (multi-head latent attention, absorbed form)
#
# A latent pool row is `[c_kv | k_rope]`: one row a token and layer for
# ALL heads, key and value at once (the value is the row's first
# `v_width` lanes).  Every query head of a lane reads the same page, so
# the kernel's work per cached byte is `2 * H` matmul rows and not one:
# 128 heads put it at the v5e's ridge where `_paged_attn_kernel` is
# bound by bytes alone.  The block table is walked INSIDE the body as in
# `_paged_attn_kernel`, a `fori_loop` over the lane's live pages with
# the next page's DMA in flight under the current page's matmuls, so a
# dispatch costs its live pages and nothing for the table's width.  The
# two walks differ in their block: a latent page of 128 positions is a
# block by itself and all heads read it, a `[ps, H*K]` page of 16 is
# one of several and each 128-lane tile of it belongs to a head pair.


def _latent_attn_kernel(table_ref, pos_ref, nf_ref, q_ref, pool_ref, o_ref,
                        buf, sem, acc, *, scale, ps, cq, h, vw, neg):
    """Grid program (lane b, query block j): `cq` fed columns times `h`
    heads as `cq*h` query rows `[., R]` against the lane's pages
    `[ps, R]`, streamed HBM -> VMEM two buffers deep.  Key `t` is
    visible to column `ci` iff `t <= pos + ci` (the oracle's mask,
    intra-chunk included).  A block past the lane's fed columns reads
    nothing and writes zeros."""
    b, j = pl.program_id(0), pl.program_id(1)
    rows = cq * h
    nf = jnp.maximum(nf_ref[b], 1)
    first = j * cq
    last = pos_ref[b] + jnp.minimum(first + cq, nf) - 1
    n = jnp.where(first < nf, last // ps + 1, 0)

    def fetch(i, slot):
        return pltpu.make_async_copy(
            pool_ref.at[table_ref[b, i]], buf.at[slot], sem.at[slot])

    @pl.when(n > 0)
    def _first():
        fetch(0, 0).start()

    q = q_ref[...].reshape(rows, q_ref.shape[-1])
    acc[...] = jnp.zeros_like(acc)
    ci = jax.lax.broadcasted_iota(jnp.int32, (rows, ps), 0) // h
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, ps), 1)
    horizon = pos_ref[b] + first + ci
    exact = None if q.dtype == jnp.bfloat16 else jax.lax.Precision.HIGHEST

    def page_step(i, carry):
        m, l = carry
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < n)
        def _next():
            fetch(i + 1, 1 - slot).start()

        fetch(i, slot).wait()
        page = buf[slot]                                     # [ps, R]
        s = jax.lax.dot_general(
            q, page, (((1,), (1,)), ((), ())), precision=exact,
            preferred_element_type=jnp.float32) * scale      # [rows, ps]
        live = i * ps + col <= horizon
        s = jnp.where(live, s, neg)
        new_m = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(live, jnp.exp(s - new_m), 0.0)
        alpha = jnp.exp(m - new_m)
        acc[...] = acc[...] * alpha + jax.lax.dot_general(
            p.astype(page.dtype), page[:, :vw], (((1,), (0,)), ((), ())),
            precision=exact, preferred_element_type=jnp.float32)
        return new_m, l * alpha + jnp.sum(p, axis=1, keepdims=True)

    _, l = jax.lax.fori_loop(
        0, n, page_step, (jnp.full((rows, 1), neg, jnp.float32),
                          jnp.zeros((rows, 1), jnp.float32)))
    o_ref[...] = (acc[...] / jnp.maximum(l, 1e-30)).reshape(
        cq, h, vw).astype(o_ref.dtype)


def latent_paged_attention(q, pool, table, pos, n_feed=None, *, layer: int,
                           v_width: int, scale: float,
                           interpret: bool | None = None) -> jax.Array:
    """Absorbed latent attention over the block table.

    q: [B, C, H, R] queries already in the pool row's space
    (`[q_nope W_uk^T | q_rope]`, R = latent rank + rotary width);
    pool: the latent pool `[L, P, ps, R]` AFTER this dispatch's scatter;
    table [B, MP], pos [B], n_feed [B] as for `paged_flash_attention`.
    Returns the value mix in latent space `[B, C, H, v_width]` (q.dtype),
    to be taken through `W_uv` by the caller.  One kernel serves width 1
    (128 query rows a lane) and the wide rounds (blocks of 8 columns,
    1,024 rows): the mask is the same and only the block of queries held
    in VMEM differs."""
    b, c, h, r = q.shape
    ps = pool.shape[2]
    table = jnp.asarray(table, jnp.int32) + layer * pool.shape[1]
    n_feed = (jnp.full((b,), c, jnp.int32) if n_feed is None
              else jnp.asarray(n_feed, jnp.int32))
    return _latent_call(table, jnp.asarray(pos, jnp.int32), n_feed, q,
                        pool.reshape(-1, ps, r), vw=int(v_width),
                        scale=float(scale),
                        interpret=_resolve_interpret(interpret))


def _query_block(c: int, h: int) -> int:
    """Fed columns per query block: as many as give about 1,024 query
    rows (the MXU's rows are then full and the accumulator is 2 MB),
    dividing the width."""
    cq = max(1, min(c, 1024 // max(h, 1)))
    while c % cq:
        cq -= 1
    return cq


@functools.partial(jax.jit, static_argnames=("vw", "scale", "interpret"))
def _latent_call(table, pos, n_feed, q, pool, *, vw, scale, interpret):
    b, c, h, r = q.shape
    ps = pool.shape[1]
    cq = _query_block(c, h)

    def _q_map(bi, ji, tbl, pos_, nf):
        return (bi, ji, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, c // cq),
        in_specs=[pl.BlockSpec((None, cq, h, r), _q_map),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((None, cq, h, vw), _q_map),
        scratch_shapes=[pltpu.VMEM((2, ps, r), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.VMEM((cq * h, vw), jnp.float32)],
    )
    kernel = functools.partial(_latent_attn_kernel, scale=scale, ps=ps,
                               cq=cq, h=h, vw=vw, neg=_NEG)
    # as for `_paged_call`: the block table first, the result 4-D with the
    # feed width second; the trace's readers find the kernel by that
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, c, h, vw), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="latent_paged_attention",
    )(table, pos, n_feed, q, pool)


# ---------------------------------------------------------------------------
# The row writer: a dispatch's fed K/V rows into the pool where it lies
#
# The step programs' other way to write is an XLA scatter of `B*C` rows a
# pool and layer, which a TPU runs a row at a time (0.16 us a row of 2,560
# bytes, padding rows sent to the null page included: 1.4 ms of a 4.6 ms
# wide round of GPT-2-large; PERF.md section 5).  Here one call a layer
# takes BOTH pools as they lie, `[L*P, ps, row]` left in HBM and aliased to
# the call's outputs, and writes only the real rows: lane `b`'s positions
# `pos[b] .. pos[b] + n_feed[b] - 1`.  Mosaic starts a DMA only on a tile of
# 8 rows (a bf16 row is half a 32-bit sublane, and a slice it cannot prove
# aligned is refused), so the unit is the 8-row GROUP of a page: every group
# a lane's fed range touches is fetched, the new rows are placed in it, and
# it is sent back: whole tiles both ways, every fetch in flight before the
# first is waited for (each on a semaphore of its own: a wait answers for
# that group and no other), and every send before the first is waited for.
# Padding columns and lanes that feed nothing touch nothing (the null page
# is never written), and a written page is never a shared one (the radix
# tree shares full prompt pages only), so no two lanes of a call meet in a
# group.

_GROUP = 8          # rows of one tile of the pool: where a DMA may start
_WRITER_COLUMNS = 128   # fed columns one grid step takes, at most
_WRITER_VMEM = 8 << 20  # what a grid step's blocks and buffers may hold


def row_writer_takes(ps: int, row: int) -> bool:
    """Whether the pool's shapes are the writer's: compiled, whole tiles
    (a page of whole 8-row groups, a row of whole 128-lane tiles); the
    interpreter takes any."""
    return _resolve_interpret(None) or (ps % _GROUP == 0 and row % 128 == 0)


def _writer_blocks(b: int, c: int, ps: int, row: int, itemsize: int):
    """(rows a group, fed columns a grid step, lanes a grid step, groups a
    lane and step, staging rows) from the shapes: the whole width a step up
    to `_WRITER_COLUMNS`, and as many lanes as fit `_WRITER_VMEM` (all 16
    of a GPT-2-large wide round: their DMAs are then in flight together)."""
    gr = _GROUP if ps % _GROUP == 0 else ps
    cb = min(c, _WRITER_COLUMNS)
    groups = (cb + gr - 2) // gr + 1
    stage = (cb + gr - 1) // gr * gr + 2 * gr
    lane = 2 * (2 * _buf(cb, row, itemsize) + groups * _buf(gr, row, itemsize)
                + _buf(stage, row, 4))
    lb = max(d for d in range(1, b + 1)
             if b % d == 0 and (d == 1 or d * lane <= _WRITER_VMEM))
    return gr, cb, lb, groups, stage


def _row_writer_kernel(table_ref, pos_ref, nf_ref, knew, vnew, kin, vin,
                       kout, vout, kbuf, vbuf, kstage, vstage, fetched, sent,
                       *, ps, gr, cb, lb):
    """Grid program (lane block jl, column block jc): lanes
    `[jl*lb, (jl+1)*lb)`, their fed columns `[jc*cb, (jc+1)*cb)`.

    knew/vnew `[lb, cb, row]` are the step's new rows in VMEM; kin/vin are
    the pools, which kout/vout alias: the body reads and writes kout/vout.
    A lane's columns of this step sit at positions `p .. p + n - 1`
    (`p = pos + jc*cb`, `n` of them fed); group `i` of them holds the
    positions `t0 .. t0 + gr - 1`, `t0 = (p // gr + i) * gr`, rows
    `t0 % ps ..` of page `table[b, t0 // ps]` (the table is offset to the
    layer), and is live while `n > 0` and `t0 < p + n`.  Its row `r` takes
    column `t0 - p + r` where that is a fed column and keeps what it held
    elsewhere.  The columns reach the rows' places through a float32
    staging copy of the block (exact for a bf16 pool) read at the aligned
    pair of groups that holds the window, and a sublane roll."""
    del kin, vin
    jl, jc = pl.program_id(0), pl.program_id(1)
    groups, row = kbuf.shape[1], kbuf.shape[-1]
    mp = table_ref.shape[1]
    pools = ((knew, kout, kbuf, kstage, 0), (vnew, vout, vbuf, vstage, 1))

    def each(fn):
        """`fn(l, i, page, off, c0, n)` for every live group of the step."""
        def lane(l, carry):
            b = jl * lb + l
            p = pos_ref[b] + jc * cb
            n = jnp.clip(nf_ref[b] - jc * cb, 0, cb)

            def group(i, carry):
                t0 = (p // gr + i) * gr

                @pl.when((n > 0) & (t0 < p + n))
                def _():
                    fn(l, i, table_ref[b, jnp.minimum(t0 // ps, mp - 1)],
                       pl.multiple_of(t0 % ps, gr), t0 - p, n)
                return carry

            return jax.lax.fori_loop(0, groups, group, carry)

        jax.lax.fori_loop(0, lb, lane, 0)

    # A group's fetch has its own semaphore: `place` reads the group as
    # soon as ITS copy has landed, whatever order the others complete in.
    # The sends of a pool share one: nothing reads what they wrote before
    # `wait_sends` has waited once for each, and they are all one size.
    def fetch(out, buf, s, l, i, page, off):
        return pltpu.make_async_copy(out.at[page, pl.ds(off, gr)],
                                     buf.at[l, i], fetched.at[s, l, i])

    def send(out, buf, s, l, i, page, off):
        return pltpu.make_async_copy(buf.at[l, i],
                                     out.at[page, pl.ds(off, gr)],
                                     sent.at[s])

    def start_fetches(l, i, page, off, c0, n):
        for _, out, buf, _, s in pools:
            fetch(out, buf, s, l, i, page, off).start()

    each(start_fetches)
    for new, _, _, stage, _ in pools:      # under the fetches in flight
        stage[:, gr:gr + cb, :] = new[...].astype(stage.dtype)

    def place(l, i, page, off, c0, n):
        col = jax.lax.broadcasted_iota(jnp.int32, (gr, row), 0) + c0
        fed = (col >= 0) & (col < n)
        # the window's columns c0 .. c0 + gr - 1 are staging rows
        # c0 + gr ..: inside the aligned pair of groups at `at`
        at = pl.multiple_of((c0 + gr) // gr * gr, gr)
        for _, out, buf, stage, s in pools:
            fetch(out, buf, s, l, i, page, off).wait()
            pair = stage[l, pl.ds(at, 2 * gr), :]
            win = pltpu.roll(pair, (2 * gr - (c0 + gr - at)) % (2 * gr),
                             0)[:gr]
            buf[l, i] = jnp.where(fed, win, buf[l, i].astype(stage.dtype)
                                  ).astype(buf.dtype)
            send(out, buf, s, l, i, page, off).start()

    each(place)

    def wait_sends(l, i, page, off, c0, n):
        for _, out, buf, _, s in pools:
            send(out, buf, s, l, i, page, off).wait()

    each(wait_sends)


def write_kv_rows(k_pool, v_pool, k_new, v_new, table, pos, n_feed,
                  layer: int):
    """The fed rows of one dispatch and layer into both pools.

    k_pool/v_pool: the serving pools `[L, P, ps, row]`; k_new/v_new
    `[B, C, ...]` with `row` values a column; table `[B, MP]`, pos `[B]`,
    n_feed `[B]` as for `paged_flash_attention`.  Lane b's column `j <
    n_feed[b]` becomes row `(pos[b] + j) % ps` of page
    `table[b, (pos[b] + j) // ps]` of layer `layer`; nothing else of the
    pools changes (the scatter it stands in for also writes its padding
    to the null page).  -> (k_pool, v_pool), the same buffers where the
    caller donates them.  The layer reaches the kernel through the block
    table, as in `paged_flash_attention`: one trace and one lowering a
    step program."""
    n_layers, pages, ps, row = k_pool.shape
    b, c = k_new.shape[:2]
    table = jnp.asarray(table, jnp.int32) + layer * pages
    k_flat, v_flat = _row_writer_call(
        table, jnp.asarray(pos, jnp.int32), jnp.asarray(n_feed, jnp.int32),
        k_new.reshape(b, c, row), v_new.reshape(b, c, row),
        k_pool.reshape(-1, ps, row), v_pool.reshape(-1, ps, row),
        interpret=_resolve_interpret(None))
    return k_flat.reshape(k_pool.shape), v_flat.reshape(v_pool.shape)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _row_writer_call(table, pos, n_feed, k_new, v_new, k_pages, v_pages, *,
                     interpret):
    """The pallas_call: k_new/v_new [B, C, row], k_pages/v_pages
    [pages, ps, row] -> the two pools, aliased."""
    b, c, row = k_new.shape
    ps = k_pages.shape[1]
    gr, cb, lb, groups, stage = _writer_blocks(
        b, c, ps, row, k_pages.dtype.itemsize)
    cp = -(-c // cb) * cb
    k_new = jnp.pad(k_new, ((0, 0), (0, cp - c), (0, 0)))
    v_new = jnp.pad(v_new, ((0, 0), (0, cp - c), (0, 0)))

    def _new_map(jl, jc, tbl, pos_, nf):
        return (jl, jc, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b // lb, cp // cb),
        in_specs=[pl.BlockSpec((lb, cb, row), _new_map),
                  pl.BlockSpec((lb, cb, row), _new_map),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=[
            pltpu.VMEM((lb, groups, gr, row), k_pages.dtype),  # K groups
            pltpu.VMEM((lb, groups, gr, row), v_pages.dtype),  # V groups
            pltpu.VMEM((lb, stage, row), jnp.float32),         # new K rows
            pltpu.VMEM((lb, stage, row), jnp.float32),         # new V rows
            pltpu.SemaphoreType.DMA((2, lb, groups)),  # fetches: a group's
            pltpu.SemaphoreType.DMA((2,)),             # sends: a pool's
        ],
    )
    kernel = functools.partial(_row_writer_kernel, ps=ps, gr=gr, cb=cb,
                               lb=lb)
    # The result is the two pools, 3-D: nothing here answers to the
    # signature the trace's readers find the attention kernels by.  A
    # column block's groups are read after the block before it has
    # written them, so the grid is sequential.
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
                   jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype)],
        input_output_aliases={5: 0, 6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="kv_row_writer",
    )(table, pos, n_feed, k_new, v_new, k_pages, v_pages)
