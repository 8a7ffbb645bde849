"""Kimi Delta Attention (KDA, arXiv:2510.26692): the gated delta-rule
layer whose cache is a state and not a history.

A head keeps a matrix `S [K, V]` (float32).  A token with key `k`
(unit length), value `v`, query `q`, per-channel decay `alpha` in
(0, 1)^K and write strength `beta`:

    S <- (I - beta k k^T) Diag(alpha) S + beta k v^T,     o = S^T q

so a sequence costs a fixed `H * K * V` values however long it is, plus
the `taps - 1` last inputs of the causal depthwise convolutions that q,
k and v pass through (`tail`).  `generation` keeps both in row-addressed
pools beside the paged K/V of the full layers and hands a lane's rows in
and out of `paged`; what `serving/paged.py` snapshots under the radix
tree are those rows.

Three forms of the recurrence compute one function:

- `scan_delta`: one token after another, `lax.scan`.  The ORACLE, what
  `paged_kernel=False` selects (as the gather oracle is for attention)
  and what the other two are tested against.
- `chunk_delta`: wide rounds, `CHUNK` positions at once.  Within a chunk
  with cumulative decay `G_t = prod_{s<=t} alpha_s` the pseudo-values
  `u_t = beta_t (v_t - k_t^T Diag(alpha_t) S_{t-1})` solve the unit
  lower-triangular system `(I + Diag(beta) A) U = Diag(beta) (V - K+ S_0)`,
  `A_ts = (k_t G_t) . (k_s / G_s)` for s < t, `K+ = K G` (the WY / UT
  transform with the decay folded in); then `o_t = (q_t G_t)^T S_0 +
  sum_{s<=t} (q_t G_t) . (k_s / G_s) u_s` and `S_C = Diag(G_C) S_0 +
  (K G_C / G)^T U`.  The quotients `G_t / G_s` are formed about the
  chunk's middle, `exp(g_t - g_m) * exp(g_m - g_s)`, so that a factor is
  at most `exp(32 |log alpha|)`: finite in float32 down to decays of 0.07
  a token.  Everything here is float32 at `HIGHEST`.
- `step_delta`: width 1, one rank-1 update a head.

A padding column (`fed` false) has `beta = 0` and `alpha = 1`: it moves
no state; an idle lane's row comes back as it went in.

On a v5e the compiler's own lowering of `chunk_delta` and `step_delta`
is what serves (PERF.md section 6, PR 38: measured there); the scopes
`kda:chunk` and `kda:step` mark them on a device trace, `attn:kda` the
projections, convolutions and gates around them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

CHUNK = 64
_HI = lax.Precision.HIGHEST
_L2_EPS = 1e-6


def _l2norm(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + _L2_EPS)


def conv_weights(p):
    """The three convolutions' taps side by side: [taps, H*(2K+V)]."""
    taps = p["conv_q"].shape[0]
    return jnp.concatenate([p[n].reshape(taps, -1)
                            for n in ("conv_q", "conv_k", "conv_v")], axis=1)


def project(cfg, p, x, tail, n_feed):
    """The normed input x [B, C, d] to what the recurrence consumes.
    `tail` [B, taps-1, H*(2K+V)] holds the convolutions' inputs at the
    `taps - 1` positions before this feed; lane b feeds its first
    `n_feed[b]` columns.  -> (q, k [B,C,H,K] float32, unit keys, queries
    scaled K^-0.5; v [B,C,H,V] float32; log_alpha [B,C,H,K] <= 0 and
    beta [B,C,H], both neutral at padding columns; the tail after the
    feed)."""
    la = cfg.linear
    b, c, _ = x.shape
    h, kd, vd, taps = la.heads, la.k_dim, la.v_dim, la.conv_taps
    pre = jnp.concatenate(
        [jnp.einsum("bsd,dhk->bshk", x, p[w]).reshape(b, c, -1)
         for w in ("wq", "wk", "wv")], axis=-1)
    ext = jnp.concatenate([tail.astype(pre.dtype), pre], axis=1)
    w = conv_weights(p).astype(jnp.float32)
    extf = ext.astype(jnp.float32)
    y = sum(w[j] * extf[:, j:j + c] for j in range(taps))
    y = jax.nn.silu(y)
    at = n_feed[:, None] + jnp.arange(taps - 1)[None, :]
    new_tail = jnp.take_along_axis(ext, at[:, :, None], axis=1)
    q = y[..., :h * kd].reshape(b, c, h, kd)
    k = y[..., h * kd:2 * h * kd].reshape(b, c, h, kd)
    v = y[..., 2 * h * kd:].reshape(b, c, h, vd)
    q = _l2norm(q) * kd ** -0.5
    k = _l2norm(k)
    f = jnp.einsum("bsr,rhk->bshk", x @ p["wf_down"], p["wf_up"])
    log_alpha = -jnp.exp(p["a_log"].astype(jnp.float32))[:, None] * (
        jax.nn.softplus(f.astype(jnp.float32)
                        + p["dt_bias"].astype(jnp.float32)))
    beta = jax.nn.sigmoid((x @ p["wb"]).astype(jnp.float32))
    if la.neg_eigval:
        beta = 2.0 * beta
    fed = jnp.arange(c)[None, :] < n_feed[:, None]
    log_alpha = jnp.where(fed[:, :, None, None], log_alpha, 0.0)
    beta = jnp.where(fed[:, :, None], beta, 0.0)
    return q, k, v, log_alpha, beta, new_tail.astype(tail.dtype)


def finish(cfg, p, x, o):
    """o [B,C,H,V] float32 -> [B,C,d]: a head's RMSNorm, the low-rank
    sigmoid gate, the output projection."""
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.norm_eps)
    o = o * p["o_norm"]["scale"].astype(jnp.float32)
    gate = jnp.einsum("bsr,rhv->bshv", x @ p["wg_down"], p["wg_up"])
    o = (o * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(x.dtype)
    return jnp.einsum("bshv,hvd->bsd", o, p["wo"])


def _one_step(state, q, k, v, log_alpha, beta):
    """One token a lane: state [B,H,K,V]; q, k, log_alpha [B,H,K];
    v [B,H,V]; beta [B,H] -> (state, o [B,H,V])."""
    decayed = jnp.exp(log_alpha)[..., None] * state
    seen = jnp.einsum("bhk,bhkv->bhv", k, decayed, precision=_HI)
    write = (beta[..., None] * k)[..., None] * (v - seen)[..., None, :]
    state = decayed + write
    return state, jnp.einsum("bhk,bhkv->bhv", q, state, precision=_HI)


def scan_delta(q, k, v, log_alpha, beta, state):
    """The recurrence a token at a time over [B, C, H, .]: the oracle.
    -> (o [B,C,H,V] float32, state [B,H,K,V])."""
    def step(s, xs):
        return _one_step(s, *xs)

    seq = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, log_alpha, beta))
    state, o = lax.scan(step, state, seq)
    return jnp.moveaxis(o, 0, 1), state


def step_delta(q, k, v, log_alpha, beta, state):
    """Width 1: q, k, v, log_alpha [B,1,H,.], beta [B,1,H]."""
    with jax.named_scope("kda:step"):
        state, o = _one_step(state, q[:, 0], k[:, 0], v[:, 0],
                             log_alpha[:, 0], beta[:, 0])
        return o[:, None], state


def chunk_delta(q, k, v, log_alpha, beta, state, chunk: int = CHUNK):
    """The recurrence `chunk` positions at once (module docstring) over
    [B, C, H, .], any C: the width is padded to whole chunks with neutral
    columns.  -> (o [B,C,H,V] float32, state [B,H,K,V])."""
    b, c, h, kd = q.shape
    n = -(-c // chunk)
    pad = n * chunk - c

    def chunks(a):      # [B, C, H, X] -> [B, H, n, chunk, X]
        a = jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        return jnp.moveaxis(
            a.reshape((b, n, chunk) + a.shape[2:]), 3, 1)

    with jax.named_scope("kda:chunk"):
        q, k, v, la = chunks(q), chunks(k), chunks(v), chunks(log_alpha)
        beta = chunks(beta[..., None])                    # [B,H,n,c,1]
        g = jnp.cumsum(la, axis=3)                        # through t
        mid = g[:, :, :, chunk // 2:chunk // 2 + 1]
        kb = k * jnp.exp(mid - g)                         # k_s / G_s
        tri = jnp.tril(jnp.ones((chunk, chunk), bool))

        def pairs(a):   # (a_t G_t) . (k_s / G_s), [.., t, s]
            return jnp.einsum("bhntk,bhnsk->bhnts", a * jnp.exp(g - mid),
                              kb, precision=_HI)

        a_mat = jnp.where(tri & ~jnp.eye(chunk, dtype=bool), pairs(k), 0.0)
        qk = jnp.where(tri, pairs(q), 0.0)
        system = jnp.eye(chunk, dtype=jnp.float32) + beta * a_mat
        rhs = jnp.concatenate([beta * k * jnp.exp(g), beta * v], axis=-1)
        sol = lax.linalg.triangular_solve(
            system, rhs, left_side=True, lower=True, unit_diagonal=True)
        w, uv = sol[..., :kd], sol[..., kd:]
        q_in = q * jnp.exp(g)                             # q_t G_t
        g_end = g[:, :, :, -1:]
        k_out = k * jnp.exp(g_end - g)                    # k_s G_C / G_s

        def one(s, xs):
            w_n, uv_n, q_n, qk_n, k_n, ge_n = xs
            u = uv_n - jnp.einsum("bhtk,bhkv->bhtv", w_n, s, precision=_HI)
            o = (jnp.einsum("bhtk,bhkv->bhtv", q_n, s, precision=_HI)
                 + jnp.einsum("bhts,bhsv->bhtv", qk_n, u, precision=_HI))
            s = (jnp.exp(ge_n[:, :, 0])[..., None] * s
                 + jnp.einsum("bhtk,bhtv->bhkv", k_n, u, precision=_HI))
            return s, o

        seq = tuple(jnp.moveaxis(a, 2, 0)
                    for a in (w, uv, q_in, qk, k_out, g_end))
        state, o = lax.scan(one, state, seq)              # o [n,B,H,c,V]
        o = jnp.moveaxis(o, 0, 2).reshape(b, h, n * chunk, -1)
        return jnp.moveaxis(o, 1, 2)[:, :c], state


def recurrence(q, k, v, log_alpha, beta, state, kernel: bool):
    """The form a step program takes: the oracle scan without `kernel`;
    with it the width-1 update or the chunked form by the width."""
    if not kernel:
        return scan_delta(q, k, v, log_alpha, beta, state)
    if q.shape[1] == 1:
        return step_delta(q, k, v, log_alpha, beta, state)
    return chunk_delta(q, k, v, log_alpha, beta, state)


def attend(cfg, p, x, state, tail, n_feed, kernel: bool):
    """A KDA layer's mixer on the normed x [B, C, d] from a lane's state
    [B,H,K,V] and tail.  -> (out [B,C,d], state, tail)."""
    with jax.named_scope("attn:kda"):
        q, k, v, log_alpha, beta, tail = project(cfg, p, x, tail, n_feed)
        o, state = recurrence(q, k, v, log_alpha, beta, state, kernel)
        return finish(cfg, p, x, o), state, tail


def whole_sequence(cfg, p, x):
    """The layer on whole sequences x [B, S, d] from an empty state, by
    the oracle scan: `transformer.apply`'s path."""
    la = cfg.linear
    b, s, _ = x.shape
    state = jnp.zeros((b, la.heads, la.k_dim, la.v_dim), jnp.float32)
    tail = jnp.zeros((b, la.conv_taps - 1,
                      la.heads * (2 * la.k_dim + la.v_dim)), x.dtype)
    out, _, _ = attend(cfg, p, x, state, tail,
                       jnp.full((b,), s, jnp.int32), kernel=False)
    return out
