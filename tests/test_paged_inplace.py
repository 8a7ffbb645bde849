"""The serve step updates the KV pool in place (ISSUE 25).

The pool rests on the device as ``[L, P, ps, H*K]``: one lane-dense
layout that the step's write fills (in either of its forms: the
``.at[].set`` scatter of the oracle and of the latent pool, or the row
writer's DMAs of whole 8-row groups in a step program on a TPU; ISSUE 41)
and the paged kernel reads, so the donated buffers come back as
themselves.  These tests hold that:

- the new formulation (stacked pool carried layer to layer, rows written
  at ``(layer*P + page)*ps + off``) is bit-equal to the old one written
  out here (per-layer slice of an ``[L, P, ps, H, K]`` pool, scatter,
  ``jnp.stack``) on the oracle path;
- the kernel reading layer ``i`` of the stacked pool equals the 4-D call
  on layer ``i``'s pages and the gather oracle;
- page copy / gather / install keep their ``[L, MP, ps, H, K]`` contract;
- the lowered ``step`` aliases both pool arguments to its outputs, on the
  CPU and, compile-only, at GPT-2-large's width for a TPU v5e, where the
  step's temporaries must not hold a copy of the pool and the
  program's writes are the row writer's calls, which alias the pools too
  and do not answer to the signature the trace's readers find the
  attention kernel by.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.parallel import generation as gen
from deeplearning4j_tpu.parallel import paged_kernel as pk
from deeplearning4j_tpu.parallel import transformer as tfm
from deeplearning4j_tpu.parallel.kernels import mask_value


def _toy(n_layers=3, max_len=32):
    cfg = tfm.TransformerConfig(vocab_size=50, d_model=16, n_heads=2,
                                n_layers=n_layers, d_ff=32,
                                max_len=max_len)
    return cfg, tfm.init_params(cfg, jax.random.PRNGKey(0))


def _random_pool(cfg, pages, ps, seed):
    rng = np.random.default_rng(seed)
    cache = gen.init_paged_cache(cfg, pages, ps)
    return {n: jnp.asarray(rng.standard_normal(a.shape), a.dtype)
            for n, a in cache.items()}


def _parent_paged_forward(cfg, params, cache5, table, pos, n_feed, tokens):
    """`paged_forward` as it was before the pool changed shape, oracle
    path: `cache5` is ``[L, P, ps, H, K]``; every layer takes its slice,
    scatters into a flattened copy, gathers the history, and the slices
    are stacked at the end."""
    c = tokens.shape[1]
    wpos = pos[:, None] + jnp.arange(c)[None, :]
    x = (params["embed"][tokens]
         + params["pos"][jnp.minimum(wpos, cfg.max_len - 1)])
    ks, vs = [], []
    for i, layer in enumerate(params["layers"]):
        q, k, v = tfm.qkv_proj(layer["attn"],
                               tfm._layer_norm(layer["ln1"], x))
        b, _, h, kd = q.shape
        layer_k, layer_v = cache5["k"][i], cache5["v"][i]
        pages, ps = layer_k.shape[:2]
        mp = table.shape[1]
        real = jnp.arange(c)[None, :] < n_feed[:, None]
        lpage = jnp.minimum(wpos // ps, mp - 1)
        page = jnp.where(real, jnp.take_along_axis(table, lpage, axis=1), 0)
        off = jnp.where(real, wpos % ps, 0)
        idx = (page * ps + off).reshape(-1)
        fk = layer_k.reshape(pages * ps, h, kd).at[idx].set(
            k.reshape(b * c, h, kd))
        fv = layer_v.reshape(pages * ps, h, kd).at[idx].set(
            v.reshape(b * c, h, kd))
        gidx = (table[:, :, None] * ps
                + jnp.arange(ps)[None, None, :]).reshape(b, mp * ps)
        s = jnp.einsum("bqhk,bshk->bqhs", q, fk[gidx]) / jnp.sqrt(
            jnp.asarray(kd, q.dtype))
        causal = jnp.arange(mp * ps)[None, None, :] <= wpos[:, :, None]
        s = jnp.where(causal[:, :, None, :], s, mask_value(s.dtype))
        o = jnp.einsum("bqhs,bshk->bqhk", jax.nn.softmax(s, axis=-1),
                       fv[gidx])
        x = x + tfm.out_proj(layer["attn"], o)
        x = x + tfm._mlp(layer["mlp"], tfm._layer_norm(layer["ln2"], x))
        ks.append(fk.reshape(pages, ps, h, kd))
        vs.append(fv.reshape(pages, ps, h, kd))
    x = tfm._layer_norm(params["ln_f"], x)
    logits = jnp.einsum("bcd,dv->bcv", x, tfm.lm_head(params))
    return logits, {"k": jnp.stack(ks), "v": jnp.stack(vs)}


@pytest.mark.parametrize("width", [1, 8])
def test_paged_forward_bit_equal_to_slice_scatter_stack(width):
    """Oracle path, 3 layers: logits and pool bit-equal to the parent's
    formulation, with a lane that feeds fewer columns than the width (a
    padding column, which writes the null page) and an idle lane."""
    cfg, params = _toy()
    ps, b = 4, 4
    mp = gen.pages_per_seq(cfg, ps)
    pages = 1 + b * mp
    cache = _random_pool(cfg, pages, ps, seed=width)
    rng = np.random.default_rng(width)
    table = np.stack([1 + i * mp + rng.permutation(mp) for i in range(b)])
    table[3] = 0                                  # the idle lane
    table = jnp.asarray(table, jnp.int32)
    pos = jnp.asarray([0, 6, 13, 0], jnp.int32)
    n_feed = jnp.asarray([width, max(width - 3, 1), 1, 0], jnp.int32)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, width)),
                         jnp.int32)
    h, kd = cfg.n_heads, cfg.head_dim
    cache5 = {n: a.reshape(a.shape[:3] + (h, kd)) for n, a in cache.items()}

    got_logits, got = jax.jit(
        lambda p, ch: gen.paged_forward(cfg, p, ch, table, pos, n_feed,
                                        tokens, paged_kernel=False)
    )(params, cache)
    want_logits, want = jax.jit(
        lambda p, ch: _parent_paged_forward(cfg, p, ch, table, pos,
                                            n_feed, tokens)
    )(params, cache5)

    assert got["k"].shape == (cfg.n_layers, pages, ps, h * kd)
    np.testing.assert_array_equal(np.asarray(got_logits),
                                  np.asarray(want_logits))
    for n in ("k", "v"):
        np.testing.assert_array_equal(
            np.asarray(got[n]), np.asarray(want[n]).reshape(got[n].shape))
    # something was written, and only where the table says
    assert not np.array_equal(np.asarray(got["k"]), np.asarray(cache["k"]))


@pytest.mark.paged_kernel
@pytest.mark.parametrize("layer", [0, 1, 2])
def test_kernel_reads_its_layer_of_the_stacked_pool(layer):
    """`paged_flash_attention(..., layer=i)` on ``[L, P, ps, H*K]`` equals
    the 4-D call on layer i's pages ``[P, ps, H, K]`` and the gather
    oracle, at tests/test_kernels.py's tolerance."""
    from test_kernels import (
        _assert_fed_columns_match,
        _gather_oracle,
        _paged_state,
    )

    n_layers, b, c, h, kd, ps, mp = 3, 3, 4, 2, 8, 4, 6
    pos = np.array([2, 9, 14], np.int32)
    rng = np.random.default_rng(11)
    q, kp, _, table, posj = _paged_state(b, c, h, kd, ps, mp, pos, seed=5)
    pool_k = jnp.asarray(rng.standard_normal((n_layers,) + kp.shape),
                         jnp.float32)
    pool_v = jnp.asarray(rng.standard_normal((n_layers,) + kp.shape),
                         jnp.float32)
    nf = jnp.asarray([4, 2, 3], jnp.int32)

    def flat(a):
        return a.reshape(a.shape[:3] + (h * kd,))

    got = pk.paged_flash_attention(q, flat(pool_k), flat(pool_v), table,
                                   posj, nf, layer=layer)
    four_d = pk.paged_flash_attention(q, pool_k[layer], pool_v[layer],
                                      table, posj, nf)
    want = _gather_oracle(q, pool_k[layer], pool_v[layer], table, posj)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(four_d))
    _assert_fed_columns_match(got, want, nf)
    other = _gather_oracle(q, pool_k[(layer + 1) % n_layers],
                           pool_v[(layer + 1) % n_layers], table, posj)
    assert not np.allclose(np.asarray(got)[0, 0], np.asarray(other)[0, 0],
                           atol=1e-3)


def test_page_copy_gather_install_keep_their_contract():
    """Copy, gather and install round-trip a page through the
    ``[L, P, ps, H*K]`` pool; the page stack that crosses the program's
    edge is still ``[L, MP, ps, H, K]`` (serving/transfer.py's format)."""
    cfg, _ = _toy()
    ps, b = 4, 2
    mp = gen.pages_per_seq(cfg, ps)
    pages = 1 + b * mp
    h, kd, n_layers = cfg.n_heads, cfg.head_dim, cfg.n_layers
    cache = _random_pool(cfg, pages, ps, seed=3)
    k0, v0 = np.asarray(cache["k"]), np.asarray(cache["v"])

    copy = gen.make_page_copy(cfg, pages, ps)
    k, v = copy(cache["k"], cache["v"], np.int32(3), np.int32(7))
    assert k.shape == (n_layers, pages, ps, h * kd)
    np.testing.assert_array_equal(np.asarray(k)[:, 7], k0[:, 3])
    np.testing.assert_array_equal(np.asarray(v)[:, 7], v0[:, 3])
    np.testing.assert_array_equal(np.asarray(k)[:, :7], k0[:, :7])

    gather = gen.make_page_gather(cfg, pages, ps)
    row = jnp.asarray(np.r_[[7, 2, 5], np.zeros(mp - 3)], jnp.int32)
    pages_k, pages_v = gather(k, v, row)
    assert pages_k.shape == (n_layers, mp, ps, h, kd)
    np.testing.assert_array_equal(
        np.asarray(pages_k)[:, :3],
        np.asarray(k)[:, [7, 2, 5]].reshape(n_layers, 3, ps, h, kd))

    # install what was gathered into a fresh pool at other page ids: the
    # first two pages land, the rest of the row goes to the null page
    fresh = gen.init_paged_cache(cfg, pages, ps)
    install = gen.make_page_install(cfg, pages, ps)
    dst = jnp.asarray(np.r_[[4, 9, 11], np.zeros(mp - 3)], jnp.int32)
    k2, v2 = install(fresh["k"], fresh["v"], pages_k, pages_v, dst,
                     np.int32(2))
    assert k2.shape == (n_layers, pages, ps, h * kd)
    np.testing.assert_array_equal(np.asarray(k2)[:, 4], np.asarray(k)[:, 7])
    np.testing.assert_array_equal(np.asarray(v2)[:, 9], np.asarray(v)[:, 2])
    assert not np.asarray(k2)[:, 11].any()        # past n: not installed
    back_k, _ = gather(k2, v2, dst)
    np.testing.assert_array_equal(np.asarray(back_k)[:, :2],
                                  np.asarray(pages_k)[:, :2])


def _step_args(cfg, lanes, width, ps, sds):
    mp = gen.pages_per_seq(cfg, ps)
    pages = lanes * mp + 1
    pool = jax.eval_shape(lambda: gen.init_paged_cache(cfg, pages, ps))["k"]
    pool = sds(pool.shape, pool.dtype)

    def i32(*s):
        return sds(s, np.int32)

    return pages, pool, (pool, pool, i32(lanes, mp), i32(lanes), i32(lanes),
                         i32(lanes, width), sds((lanes,), np.float32),
                         i32(lanes), i32(lanes))


@pytest.mark.parametrize("width", [1, 8])
def test_lowered_step_aliases_both_pool_arguments(width):
    """The jitted `step` donates k and v and its outputs 1 and 2 alias
    them: by the lowered text, and by the compiled program's alias bytes
    (the pool's, exactly)."""
    cfg, params = _toy()
    ps, lanes = 4, 3
    pages, pool, args = _step_args(cfg, lanes, width, ps,
                                   jax.ShapeDtypeStruct)
    step = gen.make_paged_step(cfg, pages, ps, width, paged_kernel=False)
    lowered = step.lower(params, *args)
    assert sorted(re.findall(r"tf\.aliasing_output = (\d+)",
                             lowered.as_text())) == ["1", "2"]
    pool_bytes = 2 * int(np.prod(pool.shape)) * pool.dtype.itemsize
    assert (lowered.compile().memory_analysis().alias_size_in_bytes
            == pool_bytes)


# ---- compile-only for a TPU v5e that is described and not attached -------
# (`on-chip-measurement` guide, section 2: the topology is described inside
# a fixture, never at import, and everything that needs it lives in this one
# file.)

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means: no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.paged_kernel
@pytest.mark.parametrize("width", [1, 8])
def test_v5e_step_holds_no_copy_of_the_pool(one_chip, width, monkeypatch):
    """GPT-2-large's width (20 heads x 64, bf16, page 16, 16 lanes) cut to
    two layers, compiled for a v5e with the Mosaic kernel: both pool
    arguments are aliased whole and the step's temporaries stay far under
    one copy of the pool (a 5-D ``[L, P, ps, H, K]`` pool compiles to two
    to three copies, PERF.md section 4).  The kernel's result keeps the
    signature the benchmark's trace readers match."""
    # the program asks `jax.default_backend()`, which is the CPU here
    monkeypatch.setattr(pk, "_resolve_interpret", lambda interpret: False)
    cfg = dataclasses.replace(tfm.gpt2_large(), n_layers=2,
                              dtype="bfloat16")
    ps, lanes = 16, 16

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: tfm.init_params(cfg, jax.random.PRNGKey(0))))
    pages, pool, args = _step_args(cfg, lanes, width, ps, sds)
    # an uncached build: the cached one belongs to the process's own runs
    step = gen._compiled_paged_step.__wrapped__(cfg, pages, ps, width, True)
    compiled = step.lower(params, *args).compile()
    ma = compiled.memory_analysis()
    pool_bytes = 2 * int(np.prod(pool.shape)) * pool.dtype.itemsize
    assert ma.alias_size_in_bytes == pool_bytes
    assert ma.temp_size_in_bytes < pool_bytes // 8
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    # the attention kernel, a call a layer, by what the trace's readers
    # find it by (`benchmark/readings.py:PAGED_KERNEL`): a 4-D bf16 result
    # with the feed width second, the block table its first operand
    reads = re.compile(r"= bf16\[\d+,(\d+),\d+,\d+\]\S* custom-call\(")
    attention = [ln for ln in calls if reads.search(ln)]
    assert len(attention) == cfg.n_layers
    for ln in attention:
        assert int(reads.search(ln).group(1)) == width, ln[:300]
        assert "operand_layout_constraints={s32[%d," % lanes in ln
    # the program's writes, at either width: the row writer, a call a
    # layer for both pools, its result the two pools (a tuple of 3-D
    # arrays, so the readers' pattern passes it by), each aliased to its
    # operand
    writers = [ln for ln in calls if "kv_row_writer" in ln]
    assert gen.kv_rows_by_kernel(True, 2, ps, 1280)
    assert len(writers) == cfg.n_layers
    assert len(calls) == len(attention) + len(writers)
    flat = r"bf16\[%d,%d,1280\]\S*" % (cfg.n_layers * pages, ps)
    for ln in writers:
        assert not reads.search(ln), ln[:300]
        assert re.search(r"= \(%s, %s\) custom-call\(" % (flat, flat),
                         ln), ln[:300]
        assert ("output_to_operand_aliasing={{0}: (5, {}), {1}: (6, {})}"
                in ln), ln[:300]
    # no copy and no restacking of a pool-sized buffer is left
    big = r"bf16\[%d,%d,%d,\d+(,\d+)?\]" % (cfg.n_layers, pages, ps)
    assert not [ln for ln in text.splitlines()
                if re.search(r"= %s\S* (copy|concatenate)\(" % big, ln)]


@pytest.mark.paged_kernel
@pytest.mark.parametrize("width", [1, 8])
def test_v5e_paged_kernel_compiles_and_keeps_its_signature(one_chip, width):
    """`_paged_call` at the serve cells' shape (16 lanes, 64 pages a lane,
    page 16, ``H*K`` 1,280, bf16, the whole 36-layer pool as the operand)
    lowers through Mosaic, and the custom call keeps what the benchmark's
    trace readers find it by (`benchmark/readings.py:PAGED_KERNEL`): the
    block table ``s32[16,64]`` its first operand, the result
    ``bf16[16,C,1,1280]`` with the feed width second.  The pool stays in
    HBM: no operand-sized temporary."""
    lanes, mp, ps, hkd, rows = 16, 64, 16, 1280, 36 * 1025

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((rows, ps, hkd), jnp.bfloat16)
    compiled = pk._paged_call.lower(
        sds((lanes, mp), np.int32), sds((lanes,), np.int32),
        sds((lanes,), np.int32), sds((lanes, 8, hkd), jnp.bfloat16),
        pool, pool, c=width, kd=64, interpret=False).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
    calls = [ln for ln in compiled.as_text().splitlines()
             if "tpu_custom_call" in ln]
    assert len(calls) == 1
    assert re.search(r"= bf16\[%d,%d,1,%d\]\S* custom-call\(" %
                     (lanes, width, hkd), calls[0]), calls[0][:300]
    assert ("operand_layout_constraints={s32[%d,%d]" % (lanes, mp)
            in calls[0]), calls[0][:600]


_WRITER_SHAPES = {
    # lanes, chunk, page, row, max pages a lane, pages of the whole pool
    "gpt2_large_serve": (16, 8, 16, 1280, 64, 36 * 1025),
    "solar_open2_serve": (4, 512, 128, 1024, 160, 641),
    # the width-1 programs of the same two
    "gpt2_large_decode": (16, 1, 16, 1280, 64, 36 * 1025),
    "solar_open2_decode": (4, 1, 128, 1024, 160, 641),
}


@pytest.mark.paged_kernel
@pytest.mark.parametrize("case", list(_WRITER_SHAPES))
def test_v5e_row_writer_compiles_and_aliases_its_pools(one_chip, case):
    """`_row_writer_call` at the serve cells' shape (16 lanes, chunk 8,
    page 16, row 1,280, the whole 36-layer pool) and at Solar-Open2's
    (4 lanes, chunk 512, page 128, row 1,024), and at width 1 of both
    (a group's fetch has a semaphore of its own: 16 x 2 to 4 x 17 a
    pool), lowers through Mosaic: ONE
    custom call whose result is the two pools, each aliased to its
    operand (the alias bytes are both pools', exactly), no temporary the
    size of a page run, and nothing of the attention kernels' signature:
    the trace's readers must not take it for a step program's kernel."""
    lanes, c, ps, row, mp, pages = _WRITER_SHAPES[case]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((pages, ps, row), jnp.bfloat16)
    new = sds((lanes, c, row), jnp.bfloat16)
    compiled = jax.jit(
        lambda *a: pk._row_writer_call(*a, interpret=False),
        donate_argnums=(5, 6)).lower(
        sds((lanes, mp), np.int32), sds((lanes,), np.int32),
        sds((lanes,), np.int32), new, new, pool, pool).compile()
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes == 2 * pages * ps * row * 2
    assert ma.temp_size_in_bytes < 1 << 20
    calls = [ln for ln in compiled.as_text().splitlines()
             if "tpu_custom_call" in ln]
    assert len(calls) == 1 and "kv_row_writer" in calls[0]
    flat = r"bf16\[%d,%d,%d\]\S*" % (pages, ps, row)
    assert re.search(r"= \(%s, %s\) custom-call\(" % (flat, flat),
                     calls[0]), calls[0][:300]
    assert not re.search(r"= bf16\[\d+,\d+,\d+,\d+\]\S* custom-call\(",
                         calls[0])
    assert ("output_to_operand_aliasing={{0}: (5, {}), {1}: (6, {})}"
            in calls[0])


@pytest.mark.paged_kernel
@pytest.mark.parametrize("width", [1, 512])
def test_v5e_latent_step_updates_its_one_pool_in_place(one_chip, width,
                                                       monkeypatch):
    """DeepSeek-V2's published widths (128 heads, ranks 1536 / 512, 64
    rotary, 40 held of 160 experts of 1536) at the benchmark's 8 lanes and
    page 128, cut to the dense layer and one expert layer and a small
    vocabulary, compiled for a v5e with the Mosaic kernel: ONE pool
    `[L, P, ps, 640]`, aliased whole; a kernel call a layer whose result
    `bf16[lanes, width, 128, 512]` the trace readers match; the experts'
    grouped matmuls as the compiler's `ragged-dot` calls."""
    monkeypatch.setattr(pk, "_resolve_interpret", lambda interpret: False)
    cfg = tfm.deepseek_v2(layers=2, experts_held=(0, 40), vocab=1024,
                          max_len=16384)
    ps, lanes, pages = 128, 8, 1 + 8 * 8

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: tfm.init_params(cfg, jax.random.PRNGKey(0))))
    pool = sds((2, pages, ps, 640), jnp.bfloat16)
    assert gen.pool_layout(cfg).row == 640
    mp = gen.pages_per_seq(cfg, ps)

    def i32(*s):
        return sds(s, np.int32)

    step = gen._compiled_paged_step.__wrapped__(cfg, pages, ps, width, True)
    compiled = step.lower(
        params, pool, i32(lanes, mp), i32(lanes), i32(lanes),
        i32(lanes, width), sds((lanes,), np.float32), i32(lanes),
        i32(lanes)).compile()
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes == int(np.prod(pool.shape)) * 2
    text = compiled.as_text()
    kernels = [ln for ln in text.splitlines()
               if "tpu_custom_call" in ln and "latent_paged_attention" in ln]
    assert len(kernels) == cfg.n_layers
    for ln in kernels:
        assert re.search(r"= bf16\[%d,%d,128,512\]\S* custom-call\("
                         % (lanes, width), ln), ln[:300]
        # the block table is the first operand
        assert "operand_layout_constraints={s32[%d,%d]" % (lanes, mp) in ln
    assert len(re.findall(r"%ragged-dot\S* = ", text)) >= 3


@pytest.mark.paged_kernel
@pytest.mark.parametrize("width", [4, 64])
def test_v5e_block_step_compiles_under_the_block_mask(one_chip, width,
                                                      monkeypatch):
    """SDAR-30B-A3B's published widths (32 query heads over 4 K/V heads of
    128, q/k norms, rotary, 128 experts of 768 all held, the whole
    vocabulary) at the benchmark's 32 lanes and page 16, cut to two layers,
    compiled for a v5e with the Mosaic kernel under the block mask: the K
    and V pools aliased whole; a grouped kernel call a layer whose result
    `bf16[lanes, width, 32, 128]` the trace readers match; the experts'
    grouped matmuls as `ragged-dot` calls; the step's tail under its
    scope, and one int32 array out."""
    monkeypatch.setattr(pk, "_resolve_interpret", lambda interpret: False)
    cfg = tfm.sdar_30b_a3b(layers=2, max_len=2048)
    ps, lanes, pages = 16, 32, 1 + 32 * 8

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: tfm.init_params(cfg, jax.random.PRNGKey(0))))
    pool = sds((2, pages, ps, 512), jnp.bfloat16)
    assert gen.pool_layout(cfg).row == 512
    mp = gen.pages_per_seq(cfg, ps)

    def i32(*s):
        return sds(s, np.int32)

    step = gen._compiled_block_step.__wrapped__(cfg, pages, ps, width, True)
    compiled = step.lower(
        params, pool, pool, i32(lanes, mp), i32(lanes), i32(lanes),
        i32(lanes, width), i32(lanes, 4), i32(lanes),
        sds((lanes,), np.float32), i32(2 * lanes * 4 + 3),
        i32(lanes)).compile()
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes == 2 * int(np.prod(pool.shape)) * 2
    text = compiled.as_text()
    kernels = [ln for ln in text.splitlines()
               if "tpu_custom_call" in ln and "grouped_paged_attention" in ln]
    assert len(kernels) == cfg.n_layers
    for ln in kernels:
        assert re.search(r"= bf16\[%d,%d,32,128\]\S* custom-call\("
                         % (lanes, width), ln), ln[:300]
        assert "operand_layout_constraints={s32[%d,%d]" % (lanes, mp) in ln
    assert len(re.findall(r"%ragged-dot\S* = ", text)) >= 3 * cfg.n_layers
    assert "blocks:unmask" in text and "attn:rope" in text
    out = [ln for ln in text.splitlines() if "ROOT" in ln and "tuple(" in ln]
    assert any("s32[%d]" % (2 * lanes * 4 + 3) in ln for ln in out)


_GROUPED_SHAPES = {
    # lanes, width, heads, K/V heads, page, table width, pool pages, block
    # mask, pages a block of the walk
    "sdar_block_round": (128, 16, 32, 4, 16, 128, 6 * 27501, 4, 8),
    "sdar_narrow_round": (128, 4, 32, 4, 16, 128, 6 * 27501, 4, 8),
    "solar_open2_decode": (4, 1, 64, 8, 128, 160, 641, 1, 1),
    "solar_open2_chunk": (4, 512, 64, 8, 128, 160, 641, 1, 1),
}


@pytest.mark.paged_kernel
@pytest.mark.parametrize("case", list(_GROUPED_SHAPES))
def test_v5e_grouped_kernel_compiles_at_its_pages_a_block(one_chip, case):
    """`_grouped_call` at the published shapes (ISSUE 43): SDAR-30B-A3B's
    `[128, 16, 32, 128]` over 4 K/V heads of 128, pages of 16 rows walked
    eight a block under the block mask, and Solar-Open2's `[4, C, 64, 128]`
    over 8, a page of 128 rows a block, lower through Mosaic (the
    interpreter takes DMAs and slices that Mosaic refuses), keep what the
    trace's readers find the kernel by (`benchmark/readings.py`'s
    `PAGED_KERNEL`: the block table the first operand, the result 4-D with
    the feed width second) and hold no copy of the pool."""
    lanes, c, h, hkv, ps, mp, pages, block, gp = _GROUPED_SHAPES[case]
    kd, g = 128, h // hkv
    assert pk._pages_per_block(ps, hkv * kd, 2, mp, False) == gp

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cq = pk._grouped_query_block(c)
    cp = -(-c // cq) * cq
    pool = sds((pages, ps, hkv * kd), jnp.bfloat16)
    compiled = pk._grouped_call.lower(
        sds((lanes, mp), np.int32), sds((lanes,), np.int32),
        sds((lanes,), np.int32), sds((lanes, hkv, cp * g, kd), jnp.bfloat16),
        pool, pool, c=c, cq=cq, g=g, interpret=False, block=block).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
    calls = [ln for ln in compiled.as_text().splitlines()
             if "tpu_custom_call" in ln]
    assert len(calls) == 1 and "grouped_paged_attention" in calls[0]
    assert re.search(r"= bf16\[%d,%d,%d,%d\]\S* custom-call\(" %
                     (lanes, c, h, kd), calls[0]), calls[0][:300]
    assert ("operand_layout_constraints={s32[%d,%d]" % (lanes, mp)
            in calls[0]), calls[0][:600]


# The flash attention kernels at the shapes the benchmark's train cells and
# the suite run them, compiled by the real Mosaic compiler (ISSUE 27): the
# interpreter takes blocks, slices and layouts that Mosaic refuses.
_FLASH_SHAPES = {
    "train_cell": ((4, 1024, 16, 64), jnp.bfloat16, True),
    "ring_diagonal": ((8, 512, 10, 64), jnp.bfloat16, True),
    "ring_full_block": ((8, 512, 10, 64), jnp.bfloat16, False),
    "odd_length": ((2, 1000, 4, 64), jnp.bfloat16, True),
    "long_context": ((1, 16384, 2, 64), jnp.bfloat16, True),
    "f32_inputs": ((1, 4096, 4, 64), jnp.float32, True),
    "head_size_128": ((1, 2048, 4, 128), jnp.bfloat16, True),
}


@pytest.mark.parametrize("case", list(_FLASH_SHAPES))
def test_v5e_flash_kernels_compile_and_keep_their_signature(one_chip, case):
    """Forward and both backward kernels lower for a v5e; their results are
    what the benchmark's trace readers match (`custom-call`s on 3-D
    ``[B*H, S, d]`` in the input dtype: one result and the row stats
    forward, two for dK/dV, one for dQ), and no lane-replicated
    ``f32[B*H, S, 128]`` copy of the row stats is left in the program."""
    from deeplearning4j_tpu.parallel.kernels import flash_attention

    shape, dtype, causal = _FLASH_SHAPES[case]
    b, s, h, d = shape
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    grad = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal, False).astype(jnp.float32)), (0, 1, 2)))
    text = grad.lower(x, x, x).compile().as_text()
    name = {jnp.bfloat16: "bf16", jnp.float32: "f32"}[dtype]
    one = r"%s\[%d,%d,%d\]\S*" % (name, b * h, s, d)
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 3, calls
    assert len([ln for ln in calls
                if re.search(r"= \(%s, f32\[\d+,\d+,1,\d+\]" % one, ln)]) == 1
    assert len([ln for ln in calls
                if re.search(r"= \(%s, %s\) " % (one, one), ln)]) == 1
    assert len([ln for ln in calls
                if re.search(r"= %s custom-call\(" % one, ln)]) == 1
    if d != 128:        # an f32 [.., S, 128] there is the operands' own shape
        assert not re.search(r"f32\[%d,%d,128\]" % (b * h, s), text)
