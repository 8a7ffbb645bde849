"""Solar-Open2-250B in plain `jax.numpy`, float32, matmul precision
"highest": the yardstick the served tokens are held to.

Written from the published config (`solar_open2`) and, for its KDA layers,
from Kimi Linear (arXiv:2510.26692, whose layer the `kda_*` keys name); it
imports nothing of the program and takes nothing the program has made: the
weights are the benchmark's (`benchmark/adapters/solar_open2.py`), in the
layout they are handed to the program in, kept in the precision they came in
and taken to float32 where they are used.

No positions anywhere (`use_rope` false).  A layer, `h = RMSNorm(x)`:

- GROUPED-QUERY layer (layer i with `i % 4 == 0`): `q = h W_q` in
  `[64, 128]`, `k = h W_k`, `v = h W_v` in `[8, 128]`; query head `j` reads
  K/V head `j // 8`; scores `q . k / sqrt(128)`, causal softmax,
  `a = sum softmax v`; `out = (a * sigmoid(h W_g)) W_o`, the gate
  elementwise from a projection of its own (assumed: Qwen3-Next's form of
  gated attention).  Computed a K/V head and a block of query rows at a
  time, so that 24,576 positions fit.
- KDA layer (every other): `q~, k~, v~ = h W_q, h W_k, h W_v`, each through
  its own causal depthwise convolution of 4 taps and SiLU;
  `q_t = l2norm(q~_t) / sqrt(128)`, `k_t = l2norm(k~_t)` (an epsilon of 1e-6
  under the root, as the public kernels have it), `v_t = v~_t`, a head;
  `alpha_t = exp(-exp(A_log[h]) * softplus(W_f_up(W_f_down h_t) + dt_bias))`
  a channel, `beta_t = 2 * sigmoid(h_t W_b)` a head;
  `S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T`,
  `S_0 = 0`; `o_t = S_t^T q_t`; `out = (RMSNorm_head(o_t) *
  sigmoid(W_g_up(W_g_down h_t))) W_o`.  The recurrence is a `lax.scan` over
  positions: no chunking, no kernel, no cache.
- EXPERT layer (every layer): `s = sigmoid(h W_r)` over all published
  experts; the `TOP_K` experts with the largest `s + b` are chosen, `b` used
  for the choice only; `w_e = s_e / sum over the chosen of s` times
  `ROUTED_SCALE`; `y = sum over chosen w_e SwiGLU_e(h) + SwiGLU_shared(h)`;
  dense-masked (every held expert on every token, the weight zero where it
  was not chosen).
- pre-norm residuals, a final RMSNorm, an untied head.

Departures from the source, each because the configuration states one chip's
share of a deployment (`benchmark/configs/solar-open2-serve-ep8.json`):

- HELD EXPERTS ONLY.  The router's width is what `W_r` has (320); the expert
  weights handed over are those of experts `held_lo .. held_lo + n_held`
  (0-39 as the benchmark runs it) and only their part of the sum is formed,
  with the weights normalised over ALL chosen experts, held or not.  What
  the absent experts would have added is left out, as in the program.
- SLICED VOCABULARY.  The embedding and the head have the rows handed over
  (24,576), and the logits are over those.
- SERVED CONTEXT.  Positions run to the length of `tokens` (24,576).
- Which layers are grouped-query is read off the weights (a KDA layer has
  `a_log`), not counted: the benchmark holds layers 0-3 of 48.

The published numbers that no array's shape gives are the module's constants
(`TOP_K`, `ROUTED_SCALE`, `NEG_EIGVAL`); the sizes come from the weights.

`quant` is the control, as in `reference/gpt2.py`: every linear layer's two
operands rounded to 8 bits (`int8` | `fp8`, `_bf16` after it rounds every
intermediate to bfloat16 too).  `correct` has to come out false for it.
"""

from __future__ import annotations

import functools

TOP_K, ROUTED_SCALE = 8, 1.0
NEG_EIGVAL = True       # kda_allow_neg_eigval: beta in (0, 2)
L2_EPS = 1e-6
QUERY_BLOCK = 256       # query rows scored at once, a K/V head


def stack(params):
    """The benchmark's weights as the reference reads them: as they are."""
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
    elif quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return _kept(jnp.matmul(x, w), quant)


# -- the layer ---------------------------------------------------------------

def _f32(a):
    import jax.numpy as jnp

    return a.astype(jnp.float32)


def _rms_norm(p, x, eps):
    import jax.numpy as jnp

    return (x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps)) * _f32(p["scale"])


def _block_of(n, most):
    b = min(n, most)
    while n % b:
        b -= 1
    return b


def attention(p, x, quant=None):
    """The gated grouped-query layer on one sequence x [S, d]."""
    import jax
    import jax.numpy as jnp

    s, d = x.shape
    _, h, kd = p["wq"].shape
    hkv = p["wk"].shape[1]
    g = h // hkv
    q = _linear(x, p["wq"].reshape(d, h * kd), quant).reshape(s, hkv, g, kd)
    k = _linear(x, p["wk"].reshape(d, hkv * kd), quant).reshape(s, hkv, kd)
    v = _linear(x, p["wv"].reshape(d, hkv * kd), quant).reshape(s, hkv, kd)
    gate = jax.nn.sigmoid(_linear(x, p["wgate"].reshape(d, h * kd), quant))
    qb = _block_of(s, QUERY_BLOCK)
    pos = jnp.arange(s)

    def head(n):        # one K/V head and the g query heads that read it
        k_n, v_n = k[:, n], v[:, n]

        def rows(r0):   # a block of query rows against every key
            q_r = jax.lax.dynamic_slice_in_dim(q[:, n], r0, qb, axis=0)
            sc = jnp.einsum("sgk,tk->gst", q_r, k_n) * kd ** -0.5
            seen = (r0 + jnp.arange(qb))[:, None] >= pos[None, :]
            sc = jnp.where(seen[None], sc, -jnp.inf)
            return jnp.einsum("gst,tk->sgk", jax.nn.softmax(sc, -1), v_n)

        return jax.lax.map(rows, jnp.arange(0, s, qb)).reshape(s, g, kd)

    mix = jax.lax.map(head, jnp.arange(hkv))                # [hkv, S, g, K]
    mix = jnp.moveaxis(mix, 0, 1).reshape(s, h * kd)
    return _linear(_kept(mix * gate, quant), p["wo"].reshape(h * kd, d),
                   quant)


def short_conv(x, w):
    """Causal depthwise convolution: x [S, C], w [taps, C];
    `y_t = sum_j w[j] x_{t - taps + 1 + j}`, zeros before the start."""
    import jax.numpy as jnp

    taps, s = w.shape[0], x.shape[0]
    ext = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), x.dtype), x])
    return sum(w[j] * ext[j:j + s] for j in range(taps))


def delta_rule(q, k, v, log_alpha, beta):
    """The KDA recurrence, a position at a time from `S_0 = 0`:
    q, k, log_alpha [S, H, K], v [S, H, V], beta [S, H] -> o [S, H, V]."""
    import jax
    import jax.numpy as jnp

    def step(state, xs):
        q_t, k_t, v_t, la_t, b_t = xs
        state = jnp.exp(la_t)[..., None] * state            # Diag(alpha) S
        seen = jnp.einsum("hk,hkv->hv", k_t, state)         # k^T (alpha S)
        state = state + (b_t[:, None] * k_t)[..., None] * (
            v_t - seen)[:, None, :]
        return state, jnp.einsum("hk,hkv->hv", q_t, state)

    h, kd = q.shape[1:]
    state = jnp.zeros((h, kd, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(step, state, (q, k, v, log_alpha, beta))
    return o


def kda(p, x, eps, quant=None):
    """The KDA layer on one sequence x [S, d]."""
    import jax
    import jax.numpy as jnp

    s, d = x.shape
    _, h, kd = p["wq"].shape
    vd = p["wv"].shape[2]

    def l2norm(a):
        return a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + L2_EPS)

    def conv_in(w, taps):       # projection, convolution, SiLU
        n = w.shape[1] * w.shape[2]
        pre = _linear(x, w.reshape(d, n), quant)
        return _kept(jax.nn.silu(short_conv(
            pre, _f32(taps).reshape(taps.shape[0], n))), quant)

    q = l2norm(conv_in(p["wq"], p["conv_q"]).reshape(s, h, kd)) * kd ** -0.5
    k = l2norm(conv_in(p["wk"], p["conv_k"]).reshape(s, h, kd))
    v = conv_in(p["wv"], p["conv_v"]).reshape(s, h, vd)
    r = p["wf_down"].shape[1]
    f = _linear(_linear(x, p["wf_down"], quant),
                p["wf_up"].reshape(r, h * kd), quant).reshape(s, h, kd)
    log_alpha = -jnp.exp(_f32(p["a_log"]))[:, None] * jax.nn.softplus(
        f + _f32(p["dt_bias"]))
    beta = jax.nn.sigmoid(_linear(x, p["wb"], quant))
    if NEG_EIGVAL:
        beta = 2.0 * beta
    o = _kept(delta_rule(q, k, v, log_alpha, beta), quant)  # [S, H, V]
    o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    o = o * _f32(p["o_norm"]["scale"])
    gate = jax.nn.sigmoid(_linear(
        _linear(x, p["wg_down"], quant),
        p["wg_up"].reshape(r, h * vd), quant)).reshape(s, h, vd)
    return _linear(_kept((o * gate).reshape(s, h * vd), quant),
                   p["wo"].reshape(h * vd, d), quant)


def swiglu(p, x, quant=None):
    import jax

    return _linear(_kept(jax.nn.silu(_linear(x, p["wg"], quant))
                         * _linear(x, p["wu"], quant), quant),
                   p["wd"], quant)


def route(scores, bias, top_k=TOP_K, scale=ROUTED_SCALE):
    """scores [S, E] (sigmoid), bias [E] -> combine weights [S, E]: the
    `top_k` experts with the largest `scores + bias`, each weighted by its
    own score over the chosen scores' sum, times `scale`; 0 elsewhere."""
    import jax
    import jax.numpy as jnp

    s = scores.shape[0]
    _, idx = jax.lax.top_k(scores + bias, top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True) * scale
    return jnp.zeros_like(scores).at[jnp.arange(s)[:, None], idx].set(w)


def expert_layer(p, x, quant=None, held_lo=0, shared=True, **routing):
    """The expert layer on x [S, d]: the router over all published experts,
    the experts whose weights are here (`held_lo` is the first one's index)
    dense-masked, and the shared expert (`shared=False` leaves it out: the
    share test counts it once)."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.sigmoid(_linear(x, p["gate"], quant))
    n_held = p["wg"].shape[0]
    combine = jax.lax.dynamic_slice_in_dim(
        route(scores, _f32(p["bias"]), **routing), held_lo, n_held, axis=1)

    def one(acc, e):
        w = {k: p[k][e] for k in ("wg", "wu", "wd")}
        return acc + combine[:, e, None] * swiglu(w, x, quant), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(n_held))
    if shared:
        y = y + swiglu(p["shared"], x, quant)
    return y


@functools.lru_cache(maxsize=None)
def _programs(eps, quant):
    """The jitted pieces, one sequence at a time: a layer's mixer half (one
    program a kind) and its feed-forward half, the embedding, the final norm
    with the positions picked out, the head."""
    import jax

    def under_highest(fn):
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)
        return jax.jit(run)

    def mixer_half(layer, x):
        y = _kept(_rms_norm(layer["ln1"], x, eps), quant)
        p = layer["attn"]
        out = kda(p, y, eps, quant) if "a_log" in p else attention(p, y,
                                                                   quant)
        return _kept(x + out, quant)

    def ffn_half(layer, x):
        y = _kept(_rms_norm(layer["ln2"], x, eps), quant)
        return _kept(x + expert_layer(layer["experts"], y, quant), quant)

    def embed(table, tokens):
        return _kept(_f32(table[tokens]), quant)

    def pick(ln_f, x, cols):
        return _kept(_rms_norm(ln_f, x, eps), quant)[cols]

    def head(w, x):
        return _linear(x, w, quant)

    return {k: under_highest(f) for k, f in (
        ("mixer", mixer_half), ("ffn", ffn_half), ("embed", embed),
        ("pick", pick), ("head", head))}


def hidden(params, tokens, eps, quant=None):
    """One sequence tokens [S] -> the last layer's output [S, d], before
    the final norm."""
    run = _programs(float(eps), quant)
    x = run["embed"](params["embed"], tokens)
    for layer in params["layers"]:
        x = run["ffn"](layer, run["mixer"](layer, x))
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
