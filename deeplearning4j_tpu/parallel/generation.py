"""KV-cached autoregressive decoding for the TransformerLM.

The TPU-idiomatic inference path: one jitted ``decode_step`` whose shapes
never change (the KV cache is a fixed [B, max_len, H, K] buffer updated
with ``lax.dynamic_update_slice``), driven by ``lax.scan`` — so the whole
generation loop is a single XLA program, no per-token retrace, no O(S²)
recompute per emitted token.

The 2015 reference has no generative inference at all; this backs the
framework's LM story (including weights imported from HF GPT-2 via
`runtime.model_import.import_hf_gpt2`, whose optional attention biases are
honored here).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.parallel import kda
from deeplearning4j_tpu.parallel.kernels import mask_value
from deeplearning4j_tpu.parallel.paged_kernel import (
    latent_paged_attention,
    paged_flash_attention,
    resolve_paged_kernel,
    row_writer_takes,
    write_kv_rows,
)
from deeplearning4j_tpu.parallel.transformer import (
    TransformerConfig,
    UnsupportedLayerKind,
    attn_gated,
    block,
    embed_tokens,
    feed_forward,
    latent_proj,
    latent_softmax_scale,
    lm_head,
    norm,
    normed_rotated,
    out_proj,
    qkv_proj,
    require_classic,
)


def init_cache(cfg: TransformerConfig, batch: int) -> dict:
    """Fixed-shape KV cache: one [B, max_len, H, K] pair per layer."""
    require_classic(cfg, "the dense KV cache (generate / beam_search)")
    dt = jnp.dtype(cfg.dtype)
    shape = (batch, cfg.max_len, cfg.n_heads, cfg.head_dim)
    return {
        "k": jnp.zeros((cfg.n_layers,) + shape, dt),
        "v": jnp.zeros((cfg.n_layers,) + shape, dt),
        "pos": jnp.zeros((), jnp.int32),
    }


def _cached_attn(p, x, layer_k, layer_v, pos):
    """Single-position attention against the cache.

    x: [B, 1, d]; layer_k/v: [B, max_len, H, K] with positions < pos
    filled; returns (out [B,1,d], new_k, new_v).
    """
    q, k, v = qkv_proj(p, x)
    layer_k = lax.dynamic_update_slice(layer_k, k, (0, pos, 0, 0))
    layer_v = lax.dynamic_update_slice(layer_v, v, (0, pos, 0, 0))
    d = q.shape[-1]
    s = jnp.einsum("bqhk,bshk->bqhs", q, layer_k) / jnp.sqrt(
        jnp.asarray(d, q.dtype))
    valid = jnp.arange(layer_k.shape[1]) <= pos          # [max_len]
    s = jnp.where(valid[None, None, None, :], s, mask_value(s.dtype))
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bqhs,bshk->bqhk", w, layer_v)
    return out_proj(p, o), layer_k, layer_v


def decode_step(cfg: TransformerConfig, params: dict, cache: dict,
                token: jax.Array) -> Tuple[jax.Array, dict]:
    """token: [B] int32 at position cache['pos'] -> (logits [B,V], cache)."""
    pos = cache["pos"]
    x = params["embed"][token][:, None, :] + lax.dynamic_slice_in_dim(
        params["pos"], pos, 1, axis=0)[None]
    ks, vs = [], []
    for i, layer in enumerate(params["layers"]):
        def attend(p, h, i=i):
            a, nk, nv = _cached_attn(p, h, cache["k"][i], cache["v"][i], pos)
            ks.append(nk)
            vs.append(nv)
            return a

        # experts at inference are dropless (`transformer._moe`): the
        # same function `apply` computes, so cache path == full recompute
        x = block(cfg, layer, x, attend)
    x = norm(cfg, params["ln_f"], x)
    logits = jnp.einsum("bsd,dv->bsv", x, lm_head(params))[:, 0]
    new_cache = {"k": jnp.stack(ks), "v": jnp.stack(vs), "pos": pos + 1}
    return logits, new_cache


def _filter_top_k(logits, top_k: int):
    """Keep the top_k largest logits per row; mask the rest."""
    kth = lax.top_k(logits, top_k)[0][..., -1:]
    return jnp.where(logits < kth, -1e30, logits)


def _filter_top_p(logits, top_p):
    """Nucleus filtering: keep the smallest set of tokens whose
    cumulative probability reaches top_p (the argmax always survives)."""
    sorted_l = jnp.flip(jnp.sort(logits, axis=-1), axis=-1)
    probs = jax.nn.softmax(sorted_l, axis=-1)
    # token kept iff the mass BEFORE it is still below top_p
    keep = (jnp.cumsum(probs, axis=-1) - probs) < top_p
    cutoff = jnp.min(jnp.where(keep, sorted_l, jnp.inf), axis=-1,
                     keepdims=True)
    return jnp.where(logits < cutoff, -1e30, logits)


def _prefill(cfg, params, prompt):
    """Run the prompt through the decoder: (filled cache, last logits)."""
    cache = init_cache(cfg, prompt.shape[0])

    def body(cache, tok):
        logits, cache = decode_step(cfg, params, cache, tok)
        return cache, logits

    cache, logits = lax.scan(body, cache, prompt.T)
    return cache, logits[-1]                              # [B, V]


@functools.lru_cache(maxsize=32)
def _compiled_run(cfg: TransformerConfig, batch: int, max_new_tokens: int,
                  sampled: bool, top_k: int, top_p: float):
    """One jitted program per (config, batch, length, mode) — stable across
    generate() calls so repeated generation never retraces."""

    @jax.jit
    def run(params, prompt, rng, temperature):
        cache, last = _prefill(cfg, params, prompt)

        def pick(logits, key):
            if not sampled:
                return jnp.argmax(logits, axis=-1)
            logits = logits.astype(jnp.float32) / temperature
            if 0 < top_k < logits.shape[-1]:
                logits = _filter_top_k(logits, top_k)
            if top_p < 1.0:
                logits = _filter_top_p(logits, top_p)
            return jax.random.categorical(key, logits)

        def step(carry, key):
            cache, last_logits = carry
            tok = pick(last_logits, key).astype(jnp.int32)
            logits, cache = decode_step(cfg, params, cache, tok)
            return (cache, logits), tok

        keys = jax.random.split(rng, max_new_tokens)
        (_, _), toks = lax.scan(step, (cache, last), keys)
        return toks.T                                     # [B, new]

    return run


def _validate_prompt(cfg, prompt, max_new_tokens):
    """Shared generate()/beam_search() prompt checks -> [B, P] int32."""
    prompt = jnp.asarray(prompt, jnp.int32)
    _, plen = prompt.shape
    if plen < 1:
        raise ValueError("prompt must contain at least one token "
                         "(the first sampled token conditions on it)")
    if plen + max_new_tokens > cfg.max_len:
        raise ValueError(f"prompt({plen}) + new({max_new_tokens}) exceeds "
                         f"max_len({cfg.max_len})")
    return prompt


def generate(cfg: TransformerConfig, params: dict, prompt,
             max_new_tokens: int, temperature: float = 0.0,
             rng: Optional[jax.Array] = None, top_k: int = 0,
             top_p: float = 1.0) -> jax.Array:
    """prompt: [B, P] int -> [B, P + max_new_tokens] int32.

    temperature 0 = greedy; otherwise softmax sampling (rng required),
    optionally truncated to the top_k most likely tokens and/or the
    top_p nucleus.  The prefill and every decode step run inside ONE
    jitted lax.scan, compiled once per (config, batch, length, mode).
    """
    prompt = _validate_prompt(cfg, prompt, max_new_tokens)
    batch = prompt.shape[0]
    if temperature > 0 and rng is None:
        raise ValueError("sampling (temperature>0) requires rng")
    if top_k < 0:
        raise ValueError(f"top_k must be >= 0, got {top_k}")
    if not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if rng is None:
        rng = jax.random.PRNGKey(0)
    sampled = temperature > 0
    # Greedy never reads top_k/top_p — normalize them out of the cache
    # key so varying them cannot retrace or churn identical programs.
    run = _compiled_run(cfg, batch, max_new_tokens, sampled,
                        int(top_k) if sampled else 0,
                        float(top_p) if sampled else 1.0)
    new = run(params, prompt, rng,
              jnp.asarray(max(temperature, 1e-6), jnp.float32))
    return jnp.concatenate([prompt, new], axis=1)


# ---------------------------------------------------------------------------
# Paged slot decode (block-table paged KV for the continuous LM pool)
#
# `generate()` above runs ONE request (or one fixed batch) to completion:
# every row shares a single scalar position.  A serving process wants the
# opposite shape: a fixed pool of decode lanes, each at its OWN position —
# finished sequences free their lane and queued prompts join mid-flight
# (serving/lm.py drives the loop).  Its KV state is ONE fixed pool of
# `[layers, pages, page_size, H*K]` rows plus a per-slot page list
# (`[slots, max_pages]` int32 block table) carried through the jitted
# step: a lane's logical position `t` of layer `i` lives at
# `pool[i, table[slot, t // page_size], t % page_size]`, so device capacity
# is sum-of-actual-lengths, pages are refcount-shared between lanes with
# a common prompt prefix (radix cache, `serving/paged.py`), and a prompt
# can feed up to `chunk` tokens per dispatch (chunked prefill) without a
# shape change.  Page 0 is the reserved NULL page: masked lanes and
# padding columns write there, and unallocated block-table entries point
# there — its contents are garbage by design and every read of it is
# masked.  One jitted program per (config, pages, page_size, chunk).
#
# Heads and head size share the pool's LAST axis (`H*K`, 1280 lanes for
# GPT-2-large): a page is `page_size` rows of full 128-lane tiles, so the
# buffer as it rests on the device, the rows the step writes into it
# (`_write_fed_rows`: an XLA scatter, or the row writer's DMAs of whole
# 8-row groups) and the blocks the paged kernel reads have one physical
# layout.  That is what lets the donated buffers be updated in place:
# with `(H, K)` as the minor dims the TPU compiler pads `(20, 64)` to
# `(24, 128)` for the kernel, lays the scatter's rows out a third way,
# and converts between the three by copying the pool (PERF.md section 4).


def pages_per_seq(cfg: TransformerConfig, page_size: int) -> int:
    """Block-table width: logical pages needed for one max_len lane."""
    return -(-int(cfg.max_len) // int(page_size))


@dataclasses.dataclass(frozen=True)
class PoolLayout:
    """What the paged pool keeps a token and layer, the ONE place every
    reader takes it from (the step programs, `serving/lm.py`'s stats,
    gather/install shapes and warm-up, `serving/transfer.py`,
    `serving/hibernate.py`): `pools` arrays `[L, P, ps, heads * width]`,
    named `names`; a row leaves the pool (shipping, swap, hibernation)
    as `[heads, width]`."""

    names: Tuple[str, ...]
    heads: int
    width: int

    @property
    def row(self) -> int:
        return self.heads * self.width


def pool_layout(cfg: TransformerConfig) -> PoolLayout:
    """Full heads: a key pool and a value pool, a row `[Hkv, K]` (the
    K/V heads: all of them unless the queries are grouped).  Latent
    attention: ONE pool, a row `[c_kv | k_rope]` for all heads, key and
    value at once (576 values for DeepSeek-V2), held in whole 128-lane
    tiles (640 lanes: the device pads the minor dim to them whatever is
    declared, and the kernel's DMA takes whole tiles), the tail zero."""
    if cfg.latent is None:
        return PoolLayout(("k", "v"), cfg.n_kv_heads, cfg.head_dim)
    values = cfg.latent.row_values
    return PoolLayout(("kv",), 1,
                      -(-values // 128) * 128 if values > 128 else values)


def pool_layers(cfg: TransformerConfig) -> Tuple[Optional[int], ...]:
    """Layer -> its index in the paged pool: the pool is as deep as the
    model has FULL layers, and a recurrent layer has no pages (None)."""
    out, n = [], 0
    for kind in cfg.mixer_kinds():
        out.append(n if kind == "full" else None)
        n += kind == "full"
    return tuple(out)


def pool_depth(cfg: TransformerConfig) -> int:
    """How many layers have pages: the FULL ones."""
    return cfg.mixer_kinds().count("full")


def pool_token_bytes(cfg: TransformerConfig) -> int:
    """Bytes the pool holds a cached token, all layers that have pages."""
    lay = pool_layout(cfg)
    return (len(lay.names) * pool_depth(cfg) * lay.row
            * jnp.dtype(cfg.dtype).itemsize)


@dataclasses.dataclass(frozen=True)
class StateLayout:
    """What a recurrent configuration keeps a SEQUENCE (not a token):
    `state [n, rows, H, K, V]` float32 and `tail [n, rows, (taps - 1) *
    H * (2K + V)]` (the convolutions' last inputs, one lane-dense vector
    a row: with `[taps - 1, channels]` as the minor dims the TPU tiles 3
    rows as 4 or 8 and converts the whole pool between the two around
    every scatter, 2.9 ms a round at 257 rows; my chip run, PR 38), `n`
    the recurrent layers, addressed by a row id a lane; row 0 is the null
    row idle lanes carry.  Live lanes and snapshots are rows of the same
    arrays."""

    names: Tuple[str, ...]
    depth: int
    state: Tuple[int, ...]      # a row of `state`, one layer
    tail: Tuple[int, ...]       # a row of `tail`, one layer


def state_layout(cfg: TransformerConfig) -> Optional[StateLayout]:
    """None where no layer is recurrent."""
    if not cfg.recurrent:
        return None
    la = cfg.linear
    return StateLayout(
        ("state", "tail"), cfg.mixer_kinds().count("kda"),
        (la.heads, la.k_dim, la.v_dim),
        ((la.conv_taps - 1) * la.heads * (2 * la.k_dim + la.v_dim),))


def state_row_bytes(cfg: TransformerConfig) -> int:
    """Bytes one state row holds, all recurrent layers: a live lane's or
    a snapshot's (0 where no layer is recurrent)."""
    lay = state_layout(cfg)
    if lay is None:
        return 0
    return lay.depth * (4 * math.prod(lay.state)
                        + jnp.dtype(cfg.dtype).itemsize
                        * math.prod(lay.tail))


def pool_names(cfg: TransformerConfig) -> Tuple[str, ...]:
    """Every array the step programs carry, donated, in call order: the
    paged pools, then a recurrent configuration's state and tail."""
    lay = state_layout(cfg)
    return pool_layout(cfg).names + (lay.names if lay is not None else ())


def init_state_pool(cfg: TransformerConfig, rows: int) -> dict:
    """`{"state", "tail"}` of `rows` rows (row 0 the null row), zero.  The
    rows are rounded up to whole 16-row tiles: `tail [n, rows, W]` is then
    the same bytes as `[n * rows, W]`, the form its rows are gathered and
    written in, and no copy of the pool stands between the two."""
    lay = state_layout(cfg)
    rows = -(-int(rows) // 16) * 16
    return {"state": jnp.zeros((lay.depth, rows) + lay.state, jnp.float32),
            "tail": jnp.zeros((lay.depth, rows) + lay.tail,
                              jnp.dtype(cfg.dtype))}


def init_paged_cache(cfg: TransformerConfig, pages: int,
                     page_size: int) -> dict:
    """The paged pool, `{name: [L, pages, page_size, row]}` by
    `pool_layout` (page 0 reserved as the null page): `k` and `v` with
    each position one lane-dense row of all heads, or the one latent
    pool `kv`."""
    dt = jnp.dtype(cfg.dtype)
    lay = pool_layout(cfg)
    shape = (pool_depth(cfg), int(pages), int(page_size), lay.row)
    return {name: jnp.zeros(shape, dt) for name in lay.names}


def _fed_rows(table, pos, n_feed, c: int, pages: int, ps: int, layer: int):
    """Where a dispatch's scatter writes: the flat pool rows [B*C] of the
    fed columns.  Lane b's column j lands at position `pos[b] + j` of its
    own pages of layer `layer`, flat row `(layer*P + page)*ps + off` of
    the stacked pool; padding columns and inactive lanes write the
    layer's null page 0."""
    mp = table.shape[1]
    j = jnp.arange(c)[None, :]                            # [1, C]
    wpos = pos[:, None] + j                               # [B, C] write pos
    real = j < n_feed[:, None]                            # [B, C]
    lpage = jnp.minimum(wpos // ps, mp - 1)               # logical page
    page = jnp.take_along_axis(table, lpage, axis=1)      # physical page
    page = jnp.where(real, page, 0)                       # padding -> null
    off = jnp.where(real, wpos % ps, 0)
    base = layer * pages                                  # this layer's pages
    return ((base + page) * ps + off).reshape(-1)


def kv_rows_by_kernel(paged_kernel: bool, n_pools: int, ps: int,
                      row: int) -> bool:
    """Which form a step program's write of its fed rows takes
    (`_write_fed_rows`), from what the program being built sees: the row
    writer (`paged_kernel.write_kv_rows`) where the paged kernel runs (a
    TPU, or `paged_kernel=True`), the pools are a K and a V pool, and
    they are whole tiles; the `.at[].set` scatter elsewhere: the oracle,
    and the one latent pool.  Every feed width alike: at width 1 too the
    writer is the faster of the two (PERF.md section 5)."""
    return bool(paged_kernel) and n_pools == 2 and row_writer_takes(ps, row)


def kv_write_path(cfg: TransformerConfig, page_size: int,
                  paged_kernel: bool | None = None) -> str:
    """"kernel" or "scatter": `kv_rows_by_kernel` for `cfg`'s pools, what
    `stats()["kv"]["write_path"]` reports of each step program."""
    lay = pool_layout(cfg)
    return ("kernel" if kv_rows_by_kernel(
        resolve_paged_kernel(paged_kernel), len(lay.names), int(page_size),
        lay.row) else "scatter")


def _write_fed_rows(pools: tuple, rows: tuple, layer: int, table, pos,
                    n_feed, paged_kernel: bool) -> tuple:
    """The fed tokens' rows (`rows`, each `[B, C, ...]`) written into
    layer `layer` of the stacked pools (`pools`, each `[L, P, ps, row]`:
    k and v, or the one latent pool), in one of two forms
    (`kv_rows_by_kernel`): the row writer, one call for both pools that
    touches the real rows' 8-row groups and nothing else, or the scatter
    at flat rows `_fed_rows`, whose padding goes to the null page.
    -> the pools."""
    _, pages, ps, row = pools[0].shape
    c = rows[0].shape[1]
    with jax.named_scope("kv:write"):
        if kv_rows_by_kernel(paged_kernel, len(pools), ps, row):
            return write_kv_rows(*pools, *rows, table, pos, n_feed, layer)
        idx = _fed_rows(table, pos, n_feed, c, pages, ps, layer)
        return tuple(
            pool.reshape(-1, row).at[idx].set(new.reshape(-1, row)
                                              ).reshape(pool.shape)
            for pool, new in zip(pools, rows))


def _paged_attn(p, x, cache_k, cache_v, layer: int, table, pos, n_feed,
                paged_kernel: bool = False):
    """Block-table paged attention for layer `layer` (a Python int).

    x: [B, C, d] (C = prefill chunk width; decode dispatches use C=1);
    cache_k/v: the WHOLE stacked pool [L, P, ps, H*K]; table: [B, MP]
    int32 page ids; pos: [B] start positions; n_feed: [B] real columns
    this dispatch.  Returns (out [B, C, d], cache_k, cache_v): the same
    stacked buffers with this layer's fed rows written.

    Each lane's fed tokens' k/v rows are written into its OWN pages of
    this layer (`_write_fed_rows`: by the row writer, which touches the
    real rows alone, or by the scatter, whose padding columns and
    inactive lanes write the layer's null page 0), row `off` of page
    `layer*P + page` of the stacked buffer — no per-layer slice is taken
    and nothing is restacked, so under `donate_argnums` the pool is
    updated where it lies.  Then the lane attends over its logical
    history.  Two history paths follow that write:

    - ``paged_kernel=False`` — the gather ORACLE: materialize the full
      ``[B, MP*ps, H, K]`` history through the block table and run
      `_cached_attn`'s masked softmax over it, each lane at its own
      position; masked positions contribute exact zeros.  Kept as the
      parity reference (and guarded against
      re-growth by dl4jlint PGD301 — this is the baselined occurrence).
    - ``paged_kernel=True`` — `paged_flash_attention` walks the block
      table INSIDE the kernel's body, one grid step a lane: the
      stacked pool stays in HBM and each live page `[ps, H*K]` is
      fetched by its own DMA through the table offset to this layer's
      pages, several pages a block: no contiguous history buffer,
      beyond-``pos`` pages never visited, so HBM traffic and time
      scale with live pages instead of ``MP*ps``.  Identical math at
      every fed column (padding columns are never consumed; a lane
      with ``n_feed == 0`` reads nothing and comes back as zeros).
    """
    q, k, v = qkv_proj(p, x)                              # [B, C, H, K]
    b, c, h, kd = q.shape
    _, pages, ps, _ = cache_k.shape
    mp = table.shape[1]
    cache_k, cache_v = _write_fed_rows((cache_k, cache_v), (k, v), layer,
                                       table, pos, n_feed, paged_kernel)
    if paged_kernel:
        o = paged_flash_attention(q, cache_k, cache_v, table, pos, n_feed,
                                  layer=layer)
        return out_proj(p, o), cache_k, cache_v
    # gather each lane's logical history: [B, S, H, K], S = MP * ps
    gidx = ((layer * pages + table)[:, :, None] * ps
            + jnp.arange(ps)[None, None, :]).reshape(b, mp * ps)
    fk, fv = cache_k.reshape(-1, h * kd), cache_v.reshape(-1, h * kd)
    hist_k = fk[gidx].reshape(b, mp * ps, h, kd)
    hist_v = fv[gidx].reshape(b, mp * ps, h, kd)
    wpos = pos[:, None] + jnp.arange(c)[None, :]          # [B, C] write pos
    s = jnp.einsum("bqhk,bshk->bqhs", q, hist_k) / jnp.sqrt(
        jnp.asarray(kd, q.dtype))
    causal = jnp.arange(mp * ps)[None, None, :] <= wpos[:, :, None]
    s = jnp.where(causal[:, :, None, :], s,
                  mask_value(s.dtype))                    # [B, C, H, S]
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bqhs,bshk->bqhk", w, hist_v)
    return out_proj(p, o), cache_k, cache_v


def _grouped_paged_attn(p, x, cache_k, cache_v, layer: int, table, pos,
                        n_feed, paged_kernel: bool = False, *,
                        cfg: TransformerConfig):
    """`_paged_attn` on the grouped-query path (`cfg.grouped`): the K/V
    heads may be fewer than the query heads (query head j reads K/V head
    `j // G`) and the output gated: the pool's row is `[Hkv * K]`, the
    write is the same, the kernel is `paged_flash_attention`'s grouped
    form and the oracle gathers the history as `[B, S, Hkv, K]`.  Scores
    in float32.  q and the fed k are normed and rotated at their
    absolute positions where the configuration says so
    (`normed_rotated`), and the pool keeps the ROTATED keys.  Under a
    block mask (`cfg.block_length` B > 1) the fed column at absolute
    position p sees the rows `< min(pos + n_feed, (p // B + 1) * B)`:
    one rule for a prefill chunk of whole blocks, a denoise round and a
    commit pass; B = 1 is the causal rule and the causal program."""
    with jax.named_scope("attn:gqa"):
        q, k, v = qkv_proj(p, x)              # [B,C,H,K], [B,C,Hkv,K]
        b, c, h, kd = q.shape
        hkv = k.shape[2]
        _, pages, ps, _ = cache_k.shape
        mp = table.shape[1]
        q, k = normed_rotated(cfg, p, q, k,
                              pos[:, None] + jnp.arange(c)[None, :])
        cache_k, cache_v = _write_fed_rows((cache_k, cache_v), (k, v), layer,
                                           table, pos, n_feed, paged_kernel)
        blk = cfg.block_length
        if paged_kernel:
            o = paged_flash_attention(q, cache_k, cache_v, table, pos,
                                      n_feed, layer=layer, block=blk)
        else:
            gidx = ((layer * pages + table)[:, :, None] * ps
                    + jnp.arange(ps)[None, None, :]).reshape(b, mp * ps)
            # the gather ORACLE of the grouped path (parity reference)
            fk = cache_k.reshape(-1, hkv * kd)
            fv = cache_v.reshape(-1, hkv * kd)
            hist_k = fk[gidx].reshape(b, mp * ps, hkv, kd)  # noqa: PGD301 — oracle
            hist_v = fv[gidx].reshape(b, mp * ps, hkv, kd)  # noqa: PGD301 — oracle
            wpos = pos[:, None] + jnp.arange(c)[None, :]
            qg = q.reshape(b, c, hkv, h // hkv, kd)
            sc = jnp.einsum("bcngk,bsnk->bcngs", qg, hist_k).astype(
                jnp.float32) * kd ** -0.5
            sees = wpos                     # the last row a column sees
            if blk > 1:
                sees = jnp.minimum((wpos // blk + 1) * blk,
                                   (pos + n_feed)[:, None]) - 1
            seen = jnp.arange(mp * ps)[None, None, :] <= sees[:, :, None]
            sc = jnp.where(seen[:, :, None, None, :], sc,
                           mask_value(sc.dtype))
            w = jax.nn.softmax(sc, axis=-1).astype(x.dtype)
            o = jnp.einsum("bcngs,bsnk->bcngk", w, hist_v
                           ).reshape(b, c, h, kd)
        return out_proj(p, attn_gated(p, x, o)), cache_k, cache_v


def _kda_paged(cfg: TransformerConfig, p, x, state, tail, layer: int,
               rows, n_feed, kernel: bool):
    """A KDA layer in a step program: lane b's state and tail are row
    `rows[b]` of recurrent layer `layer` of the pools `[n, R, ...]`, read
    and written where they lie (the pools are donated).  An idle lane
    carries the null row 0 and writes it back as it was.
    -> (out [B, C, d], state, tail)."""
    n, r = state.shape[:2]
    at = layer * r + rows
    flat_s = state.reshape((n * r,) + state.shape[2:])
    flat_t = tail.reshape((n * r,) + tail.shape[2:])
    taps, lanes = cfg.linear.conv_taps - 1, len(rows)
    # A tail row is one 147 KB vector.  It is read and written a lane at a
    # time by dynamic slices, which the compiler updates in place; as a
    # gather and a scatter of `[lanes, W]` it split the pool by taps and
    # passed over all of it several times a round (2.2 ms of a 6.4 ms
    # width-1 round at 771 rows; my chip run, PR 38).
    old_t = jnp.stack([lax.dynamic_index_in_dim(flat_t, at[b], keepdims=False)
                       for b in range(lanes)])
    out, new_s, new_t = kda.attend(
        cfg, p, x, flat_s[at], old_t.reshape(lanes, taps, -1), n_feed,
        kernel)
    new_t = new_t.reshape(lanes, -1)
    for b in range(lanes):
        flat_t = lax.dynamic_update_index_in_dim(flat_t, new_t[b], at[b], 0)
    return (out, flat_s.at[at].set(new_s).reshape(state.shape),
            flat_t.reshape(tail.shape))


def _latent_paged_attn(cfg: TransformerConfig, p, x, pool, layer: int,
                       table, pos, n_feed, paged_kernel: bool = False):
    """`_paged_attn` for latent attention, in the ABSORBED form (an
    identity, not an approximation): the fed tokens' rows
    `[c_kv | k_rope]` (normed, rotated) are scattered into the one pool
    `[L, P, ps, R]`; the queries are taken into the row's space,
    `q_abs_h = [q_nope_h W_uk_h^T | q_rope_h]`, so a score is
    `q_abs_h . row` and every head reads the SAME row; the value mix is
    formed in latent space and leaves through `W_uv_h`.  Wide rounds and
    width 1 alike: the history is never up-projected.  With
    `paged_kernel` the block table is walked by
    `latent_paged_attention`; without, the gather oracle.
    -> (out [B, C, d], pool)."""
    la = cfg.latent
    b, c, _ = x.shape
    _, pages, ps, r = pool.shape
    with jax.named_scope("attn:latent"):
        wpos = pos[:, None] + jnp.arange(c)[None, :]
        q_nope, q_rope, c_kv, k_rope = latent_proj(cfg, p, x, wpos)
        def to_row(a):      # the tail of a row's 128-lane tiles, zero
            return jnp.pad(a, [(0, 0)] * (a.ndim - 1)
                           + [(0, r - la.row_values)])

        row = to_row(jnp.concatenate([c_kv, k_rope], axis=-1))
        pool, = _write_fed_rows((pool,), (row,), layer, table, pos, n_feed,
                                paged_kernel)
        w_uk = p["wukv"][:, :, :la.nope_dim]                  # [rank, H, nope]
        w_uv = p["wukv"][:, :, la.nope_dim:]                  # [rank, H, v]
        q_lat = jnp.einsum("bchk,rhk->bchr", q_nope, w_uk)
        q_abs = to_row(jnp.concatenate([q_lat, q_rope], axis=-1))
        scale = latent_softmax_scale(cfg)
        if paged_kernel:
            o_lat = latent_paged_attention(
                q_abs, pool, table, pos, n_feed, layer=layer,
                v_width=la.kv_rank, scale=scale)
        else:
            mp = table.shape[1]
            gidx = ((layer * pages + table)[:, :, None] * ps
                    + jnp.arange(ps)[None, None, :]).reshape(b, mp * ps)
            hist = pool.reshape(-1, r)[gidx]                  # [B, S, R]
            sc = jnp.einsum("bchr,bsr->bchs", q_abs, hist
                            ).astype(jnp.float32) * scale
            seen = jnp.arange(mp * ps)[None, None, :] <= wpos[:, :, None]
            sc = jnp.where(seen[:, :, None, :], sc, mask_value(sc.dtype))
            o_lat = jnp.einsum("bchs,bsr->bchr",
                               jax.nn.softmax(sc, axis=-1).astype(x.dtype),
                               hist[..., :la.kv_rank])
        o = jnp.einsum("bchr,rhk->bchk", o_lat, w_uv)
        return jnp.einsum("bchk,hkd->bcd", o, p["wo"]), pool


def _paged_hidden(cfg: TransformerConfig, params: dict, cache: dict,
                  table: jax.Array, pos: jax.Array, n_feed: jax.Array,
                  tokens: jax.Array, paged_kernel: bool = False,
                  loads: Optional[list] = None,
                  rows: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, dict]:
    """tokens: [B, C] int32, lane b feeding its first n_feed[b] columns
    at positions pos[b].. -> (the last layer's output [B, C, d] at EVERY
    fed column, before the final norm and the head, cache with the fed
    rows scattered into the page pool).

    Every family's layer is `transformer.block`; what differs is the
    attention it is handed (`_paged_attn` over the k and v pools,
    `_latent_paged_attn` over the one latent pool).  Identical math to
    `decode_step` per position — the chunk's own writes land in the
    pool before the gather, so intra-chunk causal attention rides the
    same masked-softmax path as the history.  The stacked pool
    `[L, P, ps, row]` is carried from layer to layer, each writing its
    own rows into it; what comes back is that buffer, not a stack of
    per-layer copies.  `loads` collects each `RoutedExperts` layer's
    load counts.  A recurrent configuration's `cache` also holds `state`
    and `tail` (`state_layout`), lane b's in row `rows[b]`; its full
    layers have the pool's layers `pool_layers(cfg)`."""
    c = tokens.shape[1]
    wpos = pos[:, None] + jnp.arange(c)[None, :]
    pidx = jnp.minimum(wpos, cfg.max_len - 1)             # clip padding
    x = embed_tokens(cfg, params, tokens, pidx)           # [B, C, d]
    pools = dict(cache)
    ffn = None
    if cfg.experts is not None:
        fed = jnp.arange(c)[None, :] < n_feed[:, None]

        def ffn(layer, h):
            return feed_forward(cfg, layer, h, fed, loads)

    mixers, paged_at = cfg.mixer_kinds(), pool_layers(cfg)
    grouped = cfg.grouped
    for i, layer in enumerate(params["layers"]):
        def attend(p, h, i=i):
            if mixers[i] == "kda":
                a, pools["state"], pools["tail"] = _kda_paged(
                    cfg, p, h, pools["state"], pools["tail"],
                    mixers[:i].count("kda"), rows, n_feed, paged_kernel)
            elif cfg.latent is not None:
                a, pools["kv"] = _latent_paged_attn(
                    cfg, p, h, pools["kv"], paged_at[i], table, pos, n_feed,
                    paged_kernel=paged_kernel)
            elif grouped:
                a, pools["k"], pools["v"] = _grouped_paged_attn(
                    p, h, pools["k"], pools["v"], paged_at[i], table, pos,
                    n_feed, paged_kernel=paged_kernel, cfg=cfg)
            else:
                a, pools["k"], pools["v"] = _paged_attn(
                    p, h, pools["k"], pools["v"], paged_at[i], table, pos,
                    n_feed, paged_kernel=paged_kernel)
            return a

        x = block(cfg, layer, x, attend, ffn)
    return x, pools


def _head(cfg: TransformerConfig, params: dict, x: jax.Array) -> jax.Array:
    """Final norm and head: [B, C, d] -> logits [B, C, V]."""
    return jnp.einsum("bcd,dv->bcv", norm(cfg, params["ln_f"], x),
                      lm_head(params))


def _last_fed(a: jax.Array, n_feed: jax.Array) -> jax.Array:
    """[B, C, ...] -> [B, ...] at each lane's last fed column."""
    return jnp.take_along_axis(
        a, jnp.maximum(n_feed - 1, 0)[:, None, None], axis=1)[:, 0]


def paged_forward(cfg: TransformerConfig, params: dict, cache: dict,
                  table: jax.Array, pos: jax.Array, n_feed: jax.Array,
                  tokens: jax.Array, paged_kernel: bool = False,
                  loads: Optional[list] = None,
                  rows: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, dict]:
    """`_paged_hidden` under the head: logits [B, C, V] at EVERY fed
    column, what the speculative verify step consumes
    (`make_spec_step`): column j scores the token that should FOLLOW fed
    token j."""
    x, pools = _paged_hidden(cfg, params, cache, table, pos, n_feed,
                             tokens, paged_kernel=paged_kernel, loads=loads,
                             rows=rows)
    return _head(cfg, params, x), pools


def expert_load(cfg: TransformerConfig, loads: list) -> jax.Array:
    """A round's expert load as int32 [3], what the step programs of a
    `RoutedExperts` configuration append to the sampled tokens: routed
    pairs that fell on experts held here (all layers), pairs that fell
    on absent ones, and 1000 x the largest share any layer gave one held
    expert over the mean share (1000 = even)."""
    held = sum(ld[0] for ld in loads)
    absent = sum(ld[1] for ld in loads)
    peak = jnp.max(jnp.stack([
        ld[2] * (1000 * cfg.experts.n_held) // jnp.maximum(ld[0], 1)
        for ld in loads]))
    return jnp.stack([held, absent, peak]).astype(jnp.int32)


def paged_decode_step(cfg: TransformerConfig, params: dict, cache: dict,
                      table: jax.Array, pos: jax.Array, n_feed: jax.Array,
                      tokens: jax.Array, paged_kernel: bool = False,
                      loads: Optional[list] = None,
                      rows: Optional[jax.Array] = None
                      ) -> Tuple[jax.Array, dict]:
    """Logits at each lane's LAST fed column (-> [B, V]) — the
    chunked-prefill/decode entry point.  The new families take the
    column before the head.  GPT-2's layer still pays the head at every
    column of a wide round and keeps one: its step programs lower to the
    HLO they had, and taking the column first there is a `perf_opt`'s,
    with its pairs (PERF.md section 7)."""
    x, cache = _paged_hidden(cfg, params, cache, table, pos, n_feed,
                             tokens, paged_kernel=paged_kernel, loads=loads,
                             rows=rows)
    if cfg.classic:
        return _last_fed(_head(cfg, params, x), n_feed), cache
    return _head(cfg, params, _last_fed(x, n_feed)[:, None])[:, 0], cache


def _pooled(cfg: TransformerConfig, run):
    """`jax.jit` of `run(params, pools: tuple, *rest)` as
    `step(params, *pools, *rest)`, the pools donated: two for full
    heads (`k`, `v`: the call the serving plane has always made), one
    for latent rows; a recurrent configuration's state and tail after
    them (`pool_names`)."""
    n = len(pool_names(cfg))

    def step(params, *args):
        return run(params, args[:n], *args[n:])

    return jax.jit(step, donate_argnums=tuple(range(1, 1 + n)))


def _sample(logits, temperature, seeds, counts):
    """The device-side per-slot sampling automaton: greedy rows take the
    argmax, sampled rows draw from `fold_in(PRNGKey(seed), count)`."""
    greedy = jnp.argmax(logits, axis=-1)
    keys = jax.vmap(lambda s, c: jax.random.fold_in(
        jax.random.PRNGKey(s), c))(seeds, counts)
    temp = jnp.maximum(temperature, 1e-6)[:, None]
    sampled = jax.vmap(jax.random.categorical)(keys, logits / temp)
    return jnp.where(temperature > 0, sampled, greedy).astype(jnp.int32)


@functools.lru_cache(maxsize=16)
def _compiled_paged_step(cfg: TransformerConfig, pages: int,
                         page_size: int, chunk: int,
                         paged_kernel: bool = False):
    """One jitted paged program per (config, pages, page_size, chunk):
    the pool shape and block-table width are baked in, the pool buffers
    `[L, P, ps, row]` are donated and come back as the SAME buffers with
    `B*C` rows a layer written (`memory_analysis().alias_size_in_bytes`
    is the pool's bytes; tests/test_paged_inplace.py holds it there),
    and sampling is the device-side per-slot automaton `_sample`
    (greedy/temperature, fold_in(seed, count)): deterministic per
    request regardless of how requests interleave across dispatches.
    `paged_kernel` arrives pre-resolved to a bool (see
    `resolve_paged_kernel`) so the platform's choice and an explicit
    matching flag share ONE cache entry.  A
    `RoutedExperts` configuration's program returns `[B + 3]` int32: the
    sampled tokens and then `expert_load`, in the one array the host
    already waits for.  A recurrent configuration's program carries
    `state` and `tail` after the paged pools and takes the lanes' state
    rows `[B]` as its last argument."""
    names = pool_names(cfg)

    def run(params, pools, table, pos, n_feed, tokens, temperature, seeds,
            counts, rows=None):
        loads = [] if cfg.experts is not None else None
        logits, cache = paged_decode_step(
            cfg, params, dict(zip(names, pools)), table, pos, n_feed,
            tokens, paged_kernel=paged_kernel, loads=loads, rows=rows)
        nxt = _sample(logits.astype(jnp.float32), temperature, seeds,
                      counts)
        if loads:
            nxt = jnp.concatenate([nxt, expert_load(cfg, loads)])
        return (nxt,) + tuple(cache[n] for n in names)

    return _pooled(cfg, run)


def make_paged_step(cfg: TransformerConfig, pages: int, page_size: int,
                    chunk: int, paged_kernel: bool | None = None):
    """Compiled paged-step entry for `serving.lm.ContinuousLMServer`:
    fn(params, k, v, table [B, MP], pos [B], n_feed [B], tokens [B, C],
    temperature [B], seeds [B], counts [B]) -> (next_token [B], k, v).

    `paged_kernel=None` takes the platform's rule (fused block-table
    kernel on TPU, gather oracle elsewhere); a bool is the oracle seam
    of tests and tools."""
    return _compiled_paged_step(cfg, int(pages), int(page_size),
                                int(chunk),
                                resolve_paged_kernel(paged_kernel))


def require_causal(cfg: TransformerConfig, who: str) -> None:
    """Raise `UnsupportedLayerKind` where `cfg` is a block model: the
    gate of every path whose round is "one lane, one new token"."""
    if cfg.block_length > 1:
        raise UnsupportedLayerKind(
            f"{who} commits a token a lane and round; this model "
            f"generates blocks of {cfg.block_length} positions by "
            f"unmasking (make_block_step)")


# ---------------------------------------------------------------------------
# The block round (generation by diffusion over blocks)
#
# A block model (`cfg.block_length` B > 1) generates a block of B positions
# by unmasking it over a few forwards: a decode lane feeds its current block
# at `pos .. pos + B - 1`, masked columns as `cfg.mask_token`, known ones as
# their token, against its committed history; the logits at a column predict
# THAT column's token.  `pos` does not move over these denoise rounds, so the
# provisional K/V rows they write are overwritten by the next round and are
# read by nothing else; when no column is masked one more feed of the same
# columns (the commit pass) writes the K/V of the block's final tokens, and
# `pos += B`.  The unmasking choice runs in the program, so a round has the
# one host sync it always had.


def block_unmask(logits, tokens, known, quota, tau, mask_token: int):
    """One denoise step's choice.  logits [L, B, V] float32 at a lane's B
    block columns, tokens [L, B] the block as fed, known [L, B] bool,
    quota [L] int32, tau [L] float32.  At every masked column the best
    token `t` (the mask id's logit left out) and its confidence
    `c = softmax(logits)[t]` (over the whole vocabulary); unmasked are the
    `quota` masked columns of highest `c` (ties to the lower position) and
    every masked column with `c > tau`: the static schedule gives
    `quota = B / steps` and a `tau` no confidence reaches, the dynamic one
    `quota = 1` (its fallback) and its threshold.
    -> (tokens [L, B] after the step, known [L, B] after it)."""
    width = tokens.shape[1]
    drop = jnp.arange(logits.shape[-1]) == mask_token
    best = jnp.argmax(jnp.where(drop, -jnp.inf, logits), axis=-1)
    top = jnp.take_along_axis(logits, best[..., None], axis=-1)[..., 0]
    conf = jnp.exp(top - jax.nn.logsumexp(logits, axis=-1))    # [L, B]
    conf = jnp.where(known, -1.0, conf)
    # a column's rank among its lane's: how many stand before it
    i, j = jnp.arange(width)[:, None], jnp.arange(width)[None, :]
    before = ((conf[:, None, :] > conf[:, :, None])
              | ((conf[:, None, :] == conf[:, :, None]) & (j < i)))
    rank = jnp.sum(before, axis=-1)                            # [L, B]
    take = ~known & ((rank < quota[:, None]) | (conf > tau[:, None]))
    return (jnp.where(take, best.astype(tokens.dtype), tokens),
            known | take)


@functools.lru_cache(maxsize=16)
def _compiled_block_step(cfg: TransformerConfig, pages: int,
                         page_size: int, chunk: int,
                         paged_kernel: bool = False):
    """`_compiled_paged_step` for a block model, one program per width:
    `chunk` is B (the narrow program, `[slots, B]`) or the prefill chunk
    (whole blocks).  A decode lane feeds its block in the first B columns
    (`n_feed = B`), a prefill lane whole blocks of its prompt; the head,
    the float32 softmax and the unmasking choice run over the first B
    columns of every lane and a prefill lane's are ignored by the host.
    Returns ONE int32 array `[2 * slots * B (+ 3)]`: the blocks after this
    round's unmasking, their known flags, and a `RoutedExperts`
    configuration's `expert_load`.  `held` is such an array, the round
    before's, and a lane with `carry` set feeds the block and the flags it
    holds there in the place of `tokens[:, :B]` and `known`: the block of a
    lane whose last round the host has not read yet never leaves the
    device, so the next round is dispatched while that one runs."""
    names = pool_names(cfg)
    blk = cfg.block_length

    def run(params, pools, table, pos, n_feed, tokens, known, quota, tau,
            held, carry):
        cells = tokens.shape[0] * blk
        kept = (carry != 0)[:, None]
        tokens = tokens.at[:, :blk].set(jnp.where(
            kept, held[:cells].reshape(-1, blk), tokens[:, :blk]))
        known = jnp.where(kept, held[cells:2 * cells].reshape(-1, blk),
                          known)
        loads = [] if cfg.experts is not None else None
        x, cache = _paged_hidden(
            cfg, params, dict(zip(names, pools)), table, pos, n_feed,
            tokens, paged_kernel=paged_kernel, loads=loads)
        with jax.named_scope("blocks:unmask"):
            logits = _head(cfg, params, x[:, :blk]).astype(jnp.float32)
            new, now = block_unmask(logits, tokens[:, :blk], known != 0,
                                    quota, tau, cfg.mask_token)
        out = jnp.concatenate([new.reshape(-1).astype(jnp.int32),
                               now.reshape(-1).astype(jnp.int32)])
        if loads:
            out = jnp.concatenate([out, expert_load(cfg, loads)])
        return (out,) + tuple(cache[n] for n in names)

    return _pooled(cfg, run)


def make_block_step(cfg: TransformerConfig, pages: int, page_size: int,
                    chunk: int, paged_kernel: bool | None = None):
    """Compiled block-round entry for `serving.lm.ContinuousLMServer`:
    fn(params, k, v, table [S, MP], pos [S], n_feed [S], tokens [S, C],
    known [S, B] int32, quota [S] int32, tau [S] float32,
    held [2 * S * B (+ 3)] int32, carry [S] int32)
    -> (blocks and flags [2 * S * B (+ 3)] int32, k, v)."""
    if cfg.block_length < 2:
        raise ValueError("a causal model's round is make_paged_step's")
    if chunk % cfg.block_length or page_size % cfg.block_length:
        raise ValueError(
            f"a feed of {chunk} columns / a page of {page_size} rows does "
            f"not hold whole blocks of {cfg.block_length}")
    return _compiled_block_step(cfg, int(pages), int(page_size),
                                int(chunk),
                                resolve_paged_kernel(paged_kernel))


# ---------------------------------------------------------------------------
# Speculative verify (multi-token decode on the chunked-feed path)
#
# `paged_decode_step` already scores a [B, C] token chunk per lane in
# ONE wide dispatch — built for chunked prefill, where every fed token
# is ground truth.  Speculative decoding generalizes the same program
# shape to DECODE: a cheap drafter (serving/draft.py) proposes up to
# `draft_len` tokens per lane, the target model scores
# [last_committed, d_1..d_k] in one wide dispatch, and the accept rule
# runs IN-JIT — the longest draft prefix where the target's greedy
# argmax agrees, plus the target's own next token at the divergence
# point (the "bonus" token).  Greedy output is byte-identical to
# 1-token decode by construction: emitted token i is always
# argmax(target | committed history), whether it arrived as an accepted
# draft or as the bonus.  Rollback is free on the paged pool: rejected
# columns wrote k/v into the lane's OWN future pages (or the null
# page), positions the causal mask already hides — the host just
# advances `pos` by 1 + accepted instead of by n_feed, a pointer move,
# never a copy.  The step returns per-lane accepted counts so the host
# syncs ONCE per round, not per token.


def spec_verify_step(cfg: TransformerConfig, params: dict, cache: dict,
                     table: jax.Array, pos: jax.Array, n_feed: jax.Array,
                     n_draft: jax.Array, tokens: jax.Array,
                     paged_kernel: bool = False,
                     loads: Optional[list] = None
                     ) -> Tuple[jax.Array, jax.Array, dict]:
    """tokens: [B, W] int32; lane b feeds its first n_feed[b] columns.
    Two lane shapes are supported, and the accept mask assumes them:
    a VERIFY lane feeds exactly one committed token followed by its
    drafts — [last_committed, d_1..d_k] with n_feed = k+1 and
    n_draft = k — and a TEACHER-FORCED lane (prefill chunk, plain
    decode, or padding) feeds any n_feed with n_draft = 0.  Shapes
    with more than one committed token ahead of drafts
    (n_feed > n_draft + 1 with n_draft > 0) are NOT supported: the
    draft window is hardwired to columns 1..n_draft.

    -> (bonus_logits [B, V] at each lane's divergence column,
        accepted [B] int32 draft tokens accepted, cache).

    Draft d_i is accepted iff every earlier draft was AND the target's
    greedy argmax after consuming through column i-1 equals d_i; the
    bonus logits are the target's distribution at the column AFTER the
    last accepted token — exactly the logits 1-token decode would have
    produced there, so greedy parity is byte-exact and a sampled lane
    (n_draft = 0) sees precisely its last-fed column."""
    logits, cache = paged_forward(cfg, params, cache, table, pos, n_feed,
                                  tokens, paged_kernel=paged_kernel,
                                  loads=loads)
    logits = logits.astype(jnp.float32)                    # [B, W, V]
    pred = jnp.argmax(logits, axis=-1).astype(jnp.int32)   # [B, W]
    w = tokens.shape[1]
    # column j in [1, W): draft position j is live iff j <= n_draft
    live = jnp.arange(1, w)[None, :] <= n_draft[:, None]   # [B, W-1]
    ok = (pred[:, :-1] == tokens[:, 1:]) & live
    # length of the initial all-True run = accepted draft count
    accepted = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)
    # divergence column: the last committed feed column (n_feed-1-n_draft)
    # advanced by the accepted run; == n_feed-1 when n_draft == 0
    bonus_col = jnp.clip(n_feed - 1 - n_draft + accepted, 0, w - 1)
    blog = jnp.take_along_axis(
        logits, bonus_col[:, None, None], axis=1)[:, 0]    # [B, V]
    return blog, accepted.astype(jnp.int32), cache


@functools.lru_cache(maxsize=16)
def _compiled_spec_step(cfg: TransformerConfig, pages: int,
                        page_size: int, width: int,
                        paged_kernel: bool = False):
    """One jitted speculative-verify program per (config, pages,
    page_size, width): forward + in-jit accept/rollback + the SAME
    per-slot sampling automaton as `_compiled_paged_step` applied at
    the bonus column, so a sampled lane riding this wide dispatch with
    n_draft = 0 samples byte-identically to the 1-wide program (and a
    `RoutedExperts` configuration's tokens carry `expert_load` the same
    way)."""
    names = pool_layout(cfg).names

    def run(params, pools, table, pos, n_feed, n_draft, tokens,
            temperature, seeds, counts):
        loads = [] if cfg.experts is not None else None
        blog, accepted, cache = spec_verify_step(
            cfg, params, dict(zip(names, pools)), table, pos, n_feed,
            n_draft, tokens, paged_kernel=paged_kernel, loads=loads)
        nxt = _sample(blog, temperature, seeds, counts)
        if loads:
            nxt = jnp.concatenate([nxt, expert_load(cfg, loads)])
        return (nxt, accepted) + tuple(cache[n] for n in names)

    return _pooled(cfg, run)


def require_stateless(cfg: TransformerConfig, who: str) -> None:
    """Raise `UnsupportedLayerKind` where `cfg` has a recurrent layer:
    the gate of every path that moves or rewinds a lane by its pages
    alone (a lane of such a model is its pages AND its state row)."""
    if cfg.recurrent:
        raise UnsupportedLayerKind(
            f"{who} handles a lane as its K/V pages; this configuration "
            f"has recurrent layers ({cfg.mixer_kinds().count('kda')} "
            f"\"kda\") whose state it would neither move nor roll back")


def make_spec_step(cfg: TransformerConfig, pages: int, page_size: int,
                   width: int, paged_kernel: bool | None = None):
    """Compiled speculative-verify entry for the LM pool:
    fn(params, k, v, table [B, MP], pos [B], n_feed [B], n_draft [B],
    tokens [B, W], temperature [B], seeds [B], counts [B])
    -> (bonus_token [B], accepted [B], k, v).  `paged_kernel=None`
    auto-resolves exactly as in `make_paged_step`.  Refused for a
    recurrent configuration: a rejected draft has already moved the
    state."""
    require_stateless(cfg, "speculative decoding (make_spec_step)")
    require_causal(cfg, "speculative decoding (make_spec_step)")
    return _compiled_spec_step(cfg, int(pages), int(page_size),
                               int(width),
                               resolve_paged_kernel(paged_kernel))


@functools.lru_cache(maxsize=16)
def _compiled_page_copy(cfg: TransformerConfig, pages: int,
                        page_size: int):
    """Copy-on-write primitive: duplicate ONE page (all layers, every
    pool) inside the donated pool.  Host-side admission calls this once
    per divergence page — a request whose prompt shares a cached prefix
    that ends mid-page copies that page and overwrites from the
    divergence offset, instead of re-prefilling the whole page."""

    def copy(pools, src, dst):
        def dup(buf):
            page = lax.dynamic_slice_in_dim(buf, src, 1, axis=1)
            return lax.dynamic_update_slice_in_dim(buf, page, dst, axis=1)

        return tuple(dup(buf) for buf in pools)

    n = len(pool_layout(cfg).names)
    return jax.jit(lambda *a: copy(a[:n], *a[n:]),
                   donate_argnums=tuple(range(n)))


def make_page_copy(cfg: TransformerConfig, pages: int, page_size: int):
    """Compiled page-copy entry: fn(*pools, src, dst) -> pools."""
    return _compiled_page_copy(cfg, int(pages), int(page_size))


@functools.lru_cache(maxsize=16)
def _compiled_page_gather(cfg: TransformerConfig, pages: int,
                          page_size: int):
    """Export half of KV page shipping (serving/transfer.py): gather a
    lane's pages OUT of the pool by block-table row, fixed shape so the
    whole disaggregated serving lifetime runs one compiled program.  The
    pool is NOT donated — the exporting lane keeps serving from it (and
    the radix tree keeps the prefix for local reuse)."""
    lay = pool_layout(cfg)

    def gather(*args):
        # table_row: [MP] int32 physical page ids; entries past the
        # shipped count point at the null page and the host slices them
        # off before serialization.  A stack leaves in the shipped
        # [L, MP, ps, heads, width] form: a reshape of the small stack,
        # not of the pool
        table_row = args[-1]

        def pick(buf):
            got = buf[:, table_row]
            return got.reshape(got.shape[:3] + (lay.heads, lay.width))

        return tuple(pick(buf) for buf in args[:-1])

    return jax.jit(gather)


def make_page_gather(cfg: TransformerConfig, pages: int, page_size: int):
    """Compiled page-gather entry: fn(*pools, table_row [MP]) ->
    one page stack [L, MP, ps, heads, width] a pool."""
    require_stateless(cfg, "page export (make_page_gather)")
    return _compiled_page_gather(cfg, int(pages), int(page_size))


@functools.lru_cache(maxsize=16)
def _compiled_page_install(cfg: TransformerConfig, pages: int,
                           page_size: int):
    """Import half of KV page shipping: batched page install on top of
    the `make_page_copy` idea — scatter shipped [L, MP, ps, heads, width]
    page stacks INTO the donated pools at the block-table row's physical
    ids, all pages in ONE dispatch.  Rows past `n` land on the reserved
    null page (whose contents are garbage by design), so the program
    shape never depends on how many pages actually shipped."""
    n_pools = len(pool_layout(cfg).names)

    def install(*args):
        pools, stacks = args[:n_pools], args[n_pools:2 * n_pools]
        table_row, n = args[2 * n_pools:]
        mp = table_row.shape[0]
        dst = jnp.where(jnp.arange(mp) < n, table_row, 0)

        def put(buf, stack):
            # the shipped stack folded to the pool's rows
            return buf.at[:, dst].set(stack.reshape(stack.shape[:3] + (-1,)))

        return tuple(put(b, st) for b, st in zip(pools, stacks))

    return jax.jit(install, donate_argnums=tuple(range(n_pools)))


def make_page_install(cfg: TransformerConfig, pages: int, page_size: int):
    """Compiled page-install entry: fn(*pools, *stacks, table_row [MP],
    n) -> pools."""
    require_stateless(cfg, "page import (make_page_install)")
    return _compiled_page_install(cfg, int(pages), int(page_size))


@functools.lru_cache(maxsize=16)
def _compiled_state_copy(cfg: TransformerConfig, n: int):
    """Saving and restoring a recurrent lane are ONE program, as
    `make_page_copy` is for pages: row `dst[i]` of every recurrent layer
    of `state` and `tail` becomes row `src[i]` (or zero where `src[i] < 0`:
    a lane that starts from nothing), `n` copies a dispatch, the pools
    donated.  A spare entry is `(0, 0)`, the null row onto itself."""

    def state_copy(state, tail, src, dst):
        with jax.named_scope("state:copy"):
            def move(buf):
                got = buf[:, jnp.maximum(src, 0)]
                keep = (src >= 0).reshape((1, n) + (1,) * (buf.ndim - 2))
                return buf.at[:, dst].set(jnp.where(keep, got, 0))

            return move(state), move(tail)

    # the benchmark's reader finds the program by this function's name
    return jax.jit(state_copy, donate_argnums=(0, 1))


def make_state_copy(cfg: TransformerConfig, n: int):
    """Compiled state-row copy entry: fn(state, tail, src [n], dst [n])
    -> (state, tail)."""
    if state_layout(cfg) is None:
        raise ValueError("this configuration keeps no recurrent state")
    return _compiled_state_copy(cfg, int(n))


# ---------------------------------------------------------------------------
# Beam search (extension: the reference has no generative inference at all)

@functools.lru_cache(maxsize=16)
def _compiled_beam_run(cfg: TransformerConfig, batch: int, k: int,
                       max_new_tokens: int):
    """One jitted beam-search program per (config, batch, beams, length)."""

    @jax.jit
    def run(params, prompt):
        # Prefill once per INPUT row, then tile the cache to the beams.
        cache, logits = _prefill(cfg, params, prompt)
        last = jax.nn.log_softmax(logits.astype(jnp.float32))  # [B, V]

        def tile(a):  # [L, B, ...] -> [L, B*k, ...] beams contiguous per row
            return jnp.repeat(a, k, axis=1)

        cache = {"k": tile(cache["k"]), "v": tile(cache["v"]),
                 "pos": cache["pos"]}
        v = last.shape[-1]
        # Seed: only beam 0 live per row, so step 1 picks k DISTINCT tokens.
        scores = jnp.where(jnp.arange(k) == 0, 0.0, -1e30)  # [k]
        scores = jnp.tile(scores, (batch, 1))               # [B, k]
        logp = jnp.repeat(last, k, axis=0)                  # [B*k, V]
        toks0 = jnp.zeros((batch * k, max_new_tokens), jnp.int32)

        def step(carry, _):
            cache, scores, logp, toks, t = carry
            total = scores[:, :, None] + logp.reshape(batch, k, v)
            flat = total.reshape(batch, k * v)
            top_scores, top_idx = lax.top_k(flat, k)        # [B, k]
            parent = top_idx // v                           # beam index
            token = (top_idx % v).astype(jnp.int32)
            # Gather parent beams' caches and emitted-token histories.
            row = jnp.arange(batch)[:, None] * k + parent   # [B, k] flat idx
            flat_row = row.reshape(-1)
            cache = {"k": cache["k"][:, flat_row],
                     "v": cache["v"][:, flat_row], "pos": cache["pos"]}
            toks = toks[flat_row].at[:, t].set(token.reshape(-1))
            logits, cache = decode_step(cfg, params, cache,
                                        token.reshape(-1))
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            return (cache, top_scores, logp, toks, t + 1), None

        (cache, scores, logp, toks, _), _ = lax.scan(
            step, (cache, scores, logp, toks0, jnp.zeros((), jnp.int32)),
            None, length=max_new_tokens)
        best = jnp.argmax(scores, axis=1)                   # [B]
        toks = toks.reshape(batch, k, max_new_tokens)
        return toks[jnp.arange(batch), best], scores[jnp.arange(batch), best]

    return run


def beam_search(cfg: TransformerConfig, params: dict, prompt,
                max_new_tokens: int, beam_size: int = 4):
    """Deterministic beam-search decoding over the KV-cached decoder.

    prompt [B, P] int -> (tokens [B, P + max_new_tokens] int32,
    summed log-prob scores [B] of the winning beams).  beam_size=1
    degenerates to greedy.  All beams decode exactly max_new_tokens
    tokens (no EOS handling), so every candidate has equal length and a
    GNMT-style length penalty would not change the ranking — none is
    offered.  The whole search — prefill, per-step top-k over
    (beam, token) pairs, parent cache gathers — runs inside ONE jitted
    lax.scan.
    """
    prompt = _validate_prompt(cfg, prompt, max_new_tokens)
    if beam_size < 1:
        raise ValueError(f"beam_size must be >= 1, got {beam_size}")
    run = _compiled_beam_run(cfg, prompt.shape[0], int(beam_size),
                             max_new_tokens)
    new, scores = run(params, prompt)
    return jnp.concatenate([prompt, new], axis=1), scores
