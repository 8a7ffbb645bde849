"""DeepSeek-V2 in plain `jax.numpy`, float32, matmul precision "highest":
the yardstick the served tokens are held to.

Written from the published description (arXiv:2405.04434 and the public
`modeling_deepseek`); it imports nothing of the program and takes nothing
the program has made: the weights are the benchmark's
(`benchmark/adapters/deepseek_v2.py`), in the layout they are handed to the
program in, and are only read (kept in the precision they came in and taken
to float32 where they are used, a block at a time: whole in float32 they
would not fit beside themselves).

A layer, per token `x`, `h` heads:

- latent attention, NOT absorbed: `c_q = RMSNorm(x W_dq)`,
  `[q_nope | q_rope]_h = c_q W_uq`; `[c_kv | k_rope] = x W_dkv`,
  `c_kv <- RMSNorm(c_kv)`; `q_rope`, `k_rope` rotated (one `k_rope` for all
  heads); `[k_nope | v]_h = c_kv W_ukv`; `score_h = (q_nope_h . k_nope_h +
  q_rope_h . k_rope) * (nope + rope)^-0.5 * m^2`, `m = 0.1 * mscale_all_dim *
  ln(factor) + 1`; causal softmax; `out = concat_h(sum p v_h) W_o`.  The
  rotary is YaRN's: inverse frequencies blended between interpolated
  (`/ factor`) and unchanged by the linear ramp between the dimensions that
  `beta_fast` and `beta_slow` give at the original context; cos and sin
  carry `mscale / mscale_all_dim` = 1;
- layer 0: `SwiGLU(x) = (silu(x W_gate) * x W_up) W_down`;
- every other layer: `s = softmax(x W_g)` over all published experts (float32);
  a group's score is its largest `s`; the `topk_group` best of `n_group`
  groups stand; the `top_k` largest `s` among them; `y = SwiGLU_shared(x) +
  sum_i scale * s_i * SwiGLU_i(x)`, not renormalised; dense-masked (every
  held expert on every token, the weight zero where it was not chosen);
- pre-norm residuals, a final RMSNorm, an untied head.

Departures from the source, each because the configuration states one chip's
share of a deployment (`benchmark/configs/deepseek-v2-serve-ep4.json`):

- HELD EXPERTS ONLY.  The router's width is what `W_g` has (160); the
  expert weights handed over are those of experts `held_lo ..
  held_lo + n_held` (0-39 as the benchmark runs it) and only their part of
  the sum is formed.  What the absent experts would have added is left out,
  as in the program; nothing stands in for it.
- SLICED VOCABULARY.  The embedding and the head have the rows handed over
  (25,600), and the logits are over those.
- SERVED CONTEXT.  Positions run to the length of `tokens` (16,384); the
  YaRN factor and original context stay the published ones.
- The rotary pairs are `(i, i + rope/2)` (the source de-interleaves
  `(2i, 2i+1)` to that order before rotating; with weights from a seed the
  two are the same model).

The published numbers that no array's shape gives are the module's
constants below (`n_group`, `topk_group`, `top_k`, `routed_scaling_factor`,
`rope_theta`, `rope_scaling`); the sizes come from the weights' shapes.

`quant` is the control, as in `reference/gpt2.py`: every linear layer's two
operands rounded to 8 bits (`int8` | `fp8`, `_bf16` after it rounds every
intermediate to bfloat16 too).  `correct` has to come out false for it.
Two planted faults run through the same door at full precision, to show
that the limit sees the routed experts at all (`FAULTS`: `no_routed` leaves
their sum out, `routed_unscaled` combines without the factor 16).
"""

from __future__ import annotations

import functools
import math

N_GROUP, TOPK_GROUP, TOP_K, ROUTED_SCALE = 8, 3, 6, 16.0
ROPE_THETA = 10000.0
ROPE_SCALING = {"factor": 40.0, "original_max_position_embeddings": 4096,
                "beta_fast": 32.0, "beta_slow": 1.0, "mscale": 0.707,
                "mscale_all_dim": 0.707}
FAULTS = ("no_routed", "routed_unscaled")
QUERY_BLOCK = 256       # query rows scored at once
HEAD_BLOCK = 32         # heads up-projected at once


def stack(params):
    """The benchmark's weights as the reference reads them: as they are.
    (GPT-2's reference stacks its layers in float32 here; these are taken
    to float32 a block at a time where they are used.)"""
    return params


# -- the control's roundings (as reference/gpt2.py) -------------------------

def _fake_int8(x, axis):
    import jax.numpy as jnp

    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.round(x / scale) * scale


def _fake_fp8(x, axis):
    import jax.numpy as jnp

    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale


def _kept(x, quant):
    import jax.numpy as jnp

    if quant is not None and quant.endswith("_bf16"):
        return x.astype(jnp.bfloat16).astype(x.dtype)
    return x


def _linear(x, w, quant):
    """x [..., n] times w [n, m], both float32."""
    import jax.numpy as jnp

    w = w.astype(jnp.float32)
    operands = (quant or "").removesuffix("_bf16")
    if operands == "int8":
        x, w = _fake_int8(x, -1), _fake_int8(_kept(w, quant), 0)
    elif operands == "fp8":
        x, w = _fake_fp8(x, -1), _fake_fp8(_kept(w, quant), 0)
    elif quant is not None and quant not in FAULTS:
        raise ValueError(f"unknown control precision {quant!r}")
    return _kept(jnp.matmul(x, w), quant)


# -- the layer ---------------------------------------------------------------

def _rms_norm(p, x, eps):
    import jax.numpy as jnp

    return (x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps)) * p["scale"].astype(jnp.float32)


def yarn_inv_freq(dim, theta=ROPE_THETA, scaling=None):
    """The `dim / 2` inverse frequencies of the YaRN rotary, as a list of
    Python floats (written out; `tests` hold the program to it by hand)."""
    sc = scaling or ROPE_SCALING
    orig = sc["original_max_position_embeddings"]

    def turns_at(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(turns_at(sc["beta_fast"])), 0)
    high = min(math.ceil(turns_at(sc["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    out = []
    for i in range(dim // 2):
        extra = 1.0 / theta ** (2 * i / dim)
        inter = extra / sc["factor"]
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(inter * ramp + extra * (1.0 - ramp))
    return out


def _mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def _rotate(x, positions, scaling=None):
    """x [S, ..., rope] rotated at `positions` [S]: pairs (i, i + rope/2)."""
    import jax.numpy as jnp

    sc = scaling or ROPE_SCALING
    rope = x.shape[-1]
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        yarn_inv_freq(rope, scaling=sc), jnp.float32)
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (rope // 2,))
    m = (_mscale(sc["factor"], sc["mscale"])
         / _mscale(sc["factor"], sc["mscale_all_dim"]))
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    a, b = x[..., :rope // 2], x[..., rope // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _block_of(n, most):
    b = min(n, most)
    while n % b:
        b -= 1
    return b


def attention(p, x, eps, quant=None):
    """Latent attention, not absorbed, on one sequence x [S, d]."""
    import jax
    import jax.numpy as jnp

    s, d = x.shape
    q_rank, h, qk = p["wuq"].shape
    kv_rank = p["kv_norm"]["scale"].shape[0]
    rope = p["wdkv"].shape[1] - kv_rank
    nope = qk - rope
    v_dim = p["wukv"].shape[2] - nope
    pos = jnp.arange(s)
    c_q = _kept(_rms_norm(p["q_norm"], _linear(x, p["wdq"], quant), eps),
                quant)
    down = _linear(x, p["wdkv"], quant)
    c_kv = _kept(_rms_norm(p["kv_norm"], down[:, :kv_rank], eps), quant)
    k_rope = _rotate(down[:, kv_rank:], pos)                  # [S, rope]
    scale = qk ** -0.5 * _mscale(ROPE_SCALING["factor"],
                                 ROPE_SCALING["mscale_all_dim"]) ** 2
    qb, hb = _block_of(s, QUERY_BLOCK), _block_of(h, HEAD_BLOCK)

    def heads(h0):      # a block of heads: keys and values up-projected
        w = jax.lax.dynamic_slice_in_dim(p["wukv"], h0, hb, axis=1)
        kv = _linear(c_kv, w.reshape(kv_rank, hb * (nope + v_dim)), quant
                     ).reshape(s, hb, nope + v_dim)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        w = jax.lax.dynamic_slice_in_dim(p["wuq"], h0, hb, axis=1)
        q_h = _linear(c_q, w.reshape(q_rank, hb * qk), quant
                      ).reshape(s, hb, qk)
        q_h = jnp.concatenate(
            [q_h[..., :nope], _rotate(q_h[..., nope:], pos)], -1)

        def rows(r0):   # a block of query rows against every key
            q_r = jax.lax.dynamic_slice_in_dim(q_h, r0, qb, axis=0)
            sc = (jnp.einsum("shk,thk->hst", q_r[..., :nope], k_nope)
                  + jnp.einsum("shk,tk->hst", q_r[..., nope:], k_rope)
                  ) * scale
            seen = (r0 + jnp.arange(qb))[:, None] >= pos[None, :]
            sc = jnp.where(seen[None], sc, -jnp.inf)
            return jnp.einsum("hst,thk->shk", jax.nn.softmax(sc, -1), v)

        out = jax.lax.map(rows, jnp.arange(0, s, qb))         # [S/qb, qb, ..]
        return out.reshape(s, hb, v_dim)

    mix = jax.lax.map(heads, jnp.arange(0, h, hb))            # [H/hb, S, hb, v]
    mix = _kept(jnp.moveaxis(mix, 0, 1).reshape(s, h * v_dim), quant)
    return _linear(mix, p["wo"].reshape(h * v_dim, d), quant)


def swiglu(p, x, quant=None):
    import jax

    return _linear(_kept(jax.nn.silu(_linear(x, p["wg"], quant))
                         * _linear(x, p["wu"], quant), quant),
                   p["wd"], quant)


def route(scores, n_group=N_GROUP, topk_group=TOPK_GROUP, top_k=TOP_K,
          scale=ROUTED_SCALE):
    """scores [S, E] -> combine weights [S, E]: `scale * s` at the `top_k`
    best experts of the `topk_group` best groups, 0 elsewhere."""
    import jax
    import jax.numpy as jnp

    s, e = scores.shape
    best = jnp.max(scores.reshape(s, n_group, e // n_group), axis=-1)
    _, kept = jax.lax.top_k(best, topk_group)
    stands = jnp.zeros((s, n_group), bool).at[
        jnp.arange(s)[:, None], kept].set(True)
    standing = jnp.where(jnp.repeat(stands, e // n_group, axis=1),
                         scores, 0.0)
    w, idx = jax.lax.top_k(standing, top_k)
    return jnp.zeros_like(scores).at[jnp.arange(s)[:, None], idx].set(
        w * scale)


def expert_layer(p, x, quant=None, held_lo=0, shared=True, **routing):
    """The expert layer on x [S, d]: the router over all published experts,
    the experts whose weights are here (`held_lo` is the first one's index)
    dense-masked, and the shared expert (`shared=False` leaves it out: the
    share test counts it once)."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.softmax(_linear(x, p["gate"], quant), axis=-1)
    n_held = p["wg"].shape[0]
    combine = jax.lax.dynamic_slice_in_dim(
        route(scores, **routing), held_lo, n_held, axis=1)    # [S, held]
    if quant in FAULTS:
        combine = combine * (0.0 if quant == "no_routed"
                             else 1.0 / ROUTED_SCALE)

    def one(acc, e):
        w = {k: p[k][e] for k in ("wg", "wu", "wd")}
        return acc + combine[:, e, None] * swiglu(w, x, quant), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(n_held))
    if shared:
        y = y + swiglu(p["shared"], x, quant)
    return y


@functools.lru_cache(maxsize=None)
def _programs(eps, quant):
    """The jitted pieces, one sequence at a time: a layer's attention half
    and its feed-forward half (one program for every expert layer), the
    embedding, the final norm with the positions picked out, the head."""
    import jax

    def under_highest(fn):
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)
        return jax.jit(run)

    def attn_half(layer, x):
        y = _kept(_rms_norm(layer["ln1"], x, eps), quant)
        return _kept(x + attention(layer["attn"], y, eps, quant), quant)

    def ffn_half(layer, x):
        y = _kept(_rms_norm(layer["ln2"], x, eps), quant)
        if "experts" in layer:
            return _kept(x + expert_layer(layer["experts"], y, quant), quant)
        return _kept(x + swiglu(layer["mlp"], y, quant), quant)

    def embed(table, tokens):
        import jax.numpy as jnp

        return _kept(table[tokens].astype(jnp.float32), quant)

    def pick(ln_f, x, cols):
        return _kept(_rms_norm(ln_f, x, eps), quant)[cols]

    def head(w, x):
        return _linear(x, w, quant)

    return {k: under_highest(f) for k, f in (
        ("attn", attn_half), ("ffn", ffn_half), ("embed", embed),
        ("pick", pick), ("head", head))}


def hidden(params, tokens, eps, quant=None):
    """One sequence tokens [S] -> the last layer's output [S, d], before
    the final norm."""
    run = _programs(float(eps), quant)
    x = run["embed"](params["embed"], tokens)
    for layer in params["layers"]:
        x = run["ffn"](layer, run["attn"](layer, x))
    return x


def logits(params, tokens, eps, quant=None):
    """tokens [B, S] -> logits [B, S, V]: every position (tests, tiny)."""
    import jax.numpy as jnp

    run = _programs(float(eps), quant)
    cols = jnp.arange(tokens.shape[1])
    return jnp.stack([
        run["head"](params["head"],
                    run["pick"](params["ln_f"],
                                hidden(params, row, eps, quant), cols))
        for row in jnp.asarray(tokens)])


def served_logits(stacked, tokens, rows, cols, eps, quant=None):
    """Logits [N, V] after positions (rows[i], cols[i]) of tokens [B, S]:
    one full causal forward a sequence, no cache.  Padding after a
    sequence's end cannot reach an earlier position."""
    import jax.numpy as jnp

    run = _programs(float(eps), quant)
    rows, cols = jnp.asarray(rows), jnp.asarray(cols)
    picked = None
    for k, row in enumerate(jnp.asarray(tokens)):
        got = run["pick"](stacked["ln_f"],
                          hidden(stacked, row, eps, quant), cols)
        picked = got if picked is None else jnp.where(
            (rows == k)[:, None], got, picked)
    return run["head"](stacked["head"], picked)
