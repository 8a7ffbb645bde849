"""SDAR-30B-A3B-Chat in plain `jax.numpy`, float32, matmul precision
"highest": the yardstick the served blocks are held to.

Written from the published config (`sdar_moe`, JetLM/SDAR-30B-A3B-Chat) and
the family's paper (arXiv:2510.06303); it imports nothing of the program and
takes nothing the program has made: the weights are the benchmark's
(`benchmark/adapters/sdar.py`), in the layout they are handed to the program
in, kept in the precision they came in and taken to float32 a layer (and an
expert) at a time where they are used, because 3.7 B parameters do not fit in
float32 beside the bfloat16 ones.  No kernel, no cache.

With `x` a position's hidden state and `B` the block length:

- layer: `h = x + Attn(RMSNorm(x))`, `y = h + MoE(RMSNorm(h))`; RMSNorm with
  a learned gain, statistics in float32; a final RMSNorm and an untied head;
  the embedding is not scaled;
- Attn: `q = W_q x` as `H` heads of `K`, `k = W_k x`, `v = W_v x` as `Hkv`
  heads, no biases; `q <- RMSNorm_K(q; g_q)`, `k <- RMSNorm_K(k; g_k)` (one
  gain vector for all query heads, one for all key heads); rotary over the
  full head at the position's ABSOLUTE index, the half-split `rotate_half`
  convention, `THETA`, no scaling; scores `q . k / sqrt(K)`; query head `i`
  reads K/V head `i // (H / Hkv)`; softmax in float32 over the positions the
  mask allows; `o = W_o concat(heads)`;
- MoE: `s = softmax(W_r x)` over all experts; the `TOP_K` largest; their
  weights divided by their sum; `sum_e w_e W_down,e (silu(W_gate,e x) *
  W_up,e x)`; dense-masked (every expert on every row, weight zero where it
  was not chosen), one expert at a time; no shared expert;
- mask: position `i` sees position `j` iff `j // B <= i // B`.

What the program's denoise rounds saw is computed in ONE forward a request,
in the shape of the family's training pass (`replay_rows`): the clean
sequence (the prompt, the final answer and what the last block held past the
answer's end), followed by one noisy copy of a generated block a denoise
step, the copies carrying their clean twins' positions.  A clean position
sees clean positions by the mask above; a noisy position of block `b`, step
`s` sees the clean positions of the blocks before `b` and the noisy
positions of its own `(b, s)`.  The state of block `b` at step `s` holds the
tokens unmasked before `s` and the mask id elsewhere; the logits at a
position predict THAT position's token (no shift).

Departures from the source, each because the configuration says so
(`benchmark/configs/sdar-30b-a3b-serve-6l.json`):

- DEPTH.  As many layers as the weights handed over have (6 of 48).
- SERVED CONTEXT.  Positions run as far as the rows handed over.
- q/k norms, the rotary convention, `B`, the mask id, unshifted logits and
  the tie rule are the file's `assumed`, as this module's constants and
  arguments.

`quant` is the control, as in `reference/gpt2.py`: every linear layer's two
operands rounded to 8 bits (`int8` | `fp8`, `_bf16` after it rounds every
intermediate to bfloat16 too).  `correct` has to come out false for it.
Plain `bf16` is not a control but the yardstick's own scale: this forward
with every operand and intermediate rounded to bfloat16 and sums in float32,
an implementation in the configuration's precision that shares nothing with
the program; what it reads is what the precision costs, whoever computes.
"""

from __future__ import annotations

import functools

import numpy as np

TOP_K = 8
THETA = 1e6
EPS = 1e-6


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

    if quant is not None and quant.endswith("bf16"):
        return x.astype(jnp.bfloat16).astype(x.dtype)
    return x


def _linear(x, w, quant):
    """x [..., n] times w [n, m], both float32."""
    import jax.numpy as jnp

    w = w.astype(jnp.float32)
    operands = (quant or "").removesuffix("bf16").removesuffix("_")
    if operands == "int8":
        x, w = _fake_int8(x, -1), _fake_int8(_kept(w, quant), 0)
    elif operands == "fp8":
        x, w = _fake_fp8(x, -1), _fake_fp8(_kept(w, quant), 0)
    elif operands or quant == "":
        raise ValueError(f"unknown control precision {quant!r}")
    else:           # None, or plain "bf16": both operands in bfloat16
        x, w = _kept(x, quant), _kept(w, quant)
    return _kept(jnp.matmul(x, w), quant)


# -- the layer ---------------------------------------------------------------

def _rms_norm(p, x, eps):
    import jax.numpy as jnp

    return (x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps)) * p["scale"].astype(jnp.float32)


def rotary(x, positions, theta=THETA):
    """x [T, heads, K] rotated at integer `positions` [T]: frequency i of
    K / 2 is `theta ** (-2 i / K)`, and the pair is `(x[i], x[i + K / 2])`
    (`rotate_half`)."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(p, x, positions, allowed, eps, quant=None):
    """The grouped-query layer on rows x [T, d] at `positions` [T]; row i
    attends to row j iff `allowed[i, j]`."""
    import jax
    import jax.numpy as jnp

    t, d = x.shape
    _, h, kd = p["wq"].shape
    hkv = p["wk"].shape[1]
    g = h // hkv
    q = _linear(x, p["wq"].reshape(d, h * kd), quant).reshape(t, h, kd)
    k = _linear(x, p["wk"].reshape(d, hkv * kd), quant).reshape(t, hkv, kd)
    v = _linear(x, p["wv"].reshape(d, hkv * kd), quant).reshape(t, hkv, kd)
    if "q_norm" in p:
        q = _rms_norm(p["q_norm"], q, eps)
        k = _rms_norm(p["k_norm"], k, eps)
    q = _kept(rotary(q, positions), quant).reshape(t, hkv, g, kd)
    k = _kept(rotary(k, positions), quant)

    def head(n):        # one K/V head and the g query heads that read it
        sc = jnp.einsum("sgk,tk->gst", q[:, n], k[:, n]) * kd ** -0.5
        sc = jnp.where(allowed[None], sc, -jnp.inf)
        return jnp.einsum("gst,tk->sgk", jax.nn.softmax(sc, -1), v[:, n])

    mix = jax.lax.map(head, jnp.arange(hkv))                # [hkv, T, g, K]
    mix = jnp.moveaxis(mix, 0, 1).reshape(t, h * kd)
    return _linear(_kept(mix, quant), p["wo"].reshape(h * kd, d), quant)


def swiglu(p, x, quant=None):
    import jax

    return _linear(_kept(jax.nn.silu(_linear(x, p["wg"], quant))
                         * _linear(x, p["wu"], quant), quant),
                   p["wd"], quant)


def route(scores, top_k=TOP_K):
    """scores [T, E] (softmax) -> combine weights [T, E]: the `top_k`
    largest, each over their sum (`norm_topk_prob`); 0 elsewhere."""
    import jax
    import jax.numpy as jnp

    w, idx = jax.lax.top_k(scores, top_k)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    return jnp.zeros_like(scores).at[
        jnp.arange(scores.shape[0])[:, None], idx].set(w)


def expert_layer(p, x, quant=None, top_k=TOP_K):
    """The expert layer on x [T, d], every expert on every row."""
    import jax
    import jax.numpy as jnp

    combine = route(jax.nn.softmax(_linear(x, p["gate"], quant), axis=-1),
                    min(top_k, p["gate"].shape[1]))

    def one(acc, e):
        w = {k: p[k][e] for k in ("wg", "wu", "wd")}
        return acc + combine[:, e, None] * swiglu(w, x, quant), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        jnp.arange(p["wg"].shape[0]))
    return y


def allowed_rows(blocks, copies):
    """Which row sees which, [T, T], from each row's block index and copy
    (0 clean, k > 0 the k-th noisy copy, < 0 padding): a clean row sees the
    clean rows of its own and earlier blocks; a noisy row the clean rows of
    EARLIER blocks and the rows of its own copy; padding itself."""
    import jax.numpy as jnp

    bi, bj = blocks[:, None], blocks[None, :]
    ci, cj = copies[:, None], copies[None, :]
    clean = (ci == 0) & (cj == 0) & (bj <= bi)
    noisy = (ci > 0) & (((cj == 0) & (bj < bi)) | (cj == ci))
    return clean | noisy | jnp.eye(blocks.shape[0], dtype=bool)


@functools.lru_cache(maxsize=None)
def _programs(eps, quant, top_k):
    """The jitted pieces, one request at a time: a layer's two halves, the
    embedding, the final norm with rows picked out, the head."""
    import jax

    def under_highest(fn):
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)
        return jax.jit(run)

    def mixer_half(layer, x, positions, blocks, copies):
        y = _kept(_rms_norm(layer["ln1"], x, eps), quant)
        out = attention(layer["attn"], y, positions,
                        allowed_rows(blocks, copies), eps, quant)
        return _kept(x + out, quant)

    def ffn_half(layer, x):
        y = _kept(_rms_norm(layer["ln2"], x, eps), quant)
        return _kept(x + expert_layer(layer["experts"], y, quant, top_k),
                     quant)

    def embed(table, tokens):
        import jax.numpy as jnp

        return _kept(table[tokens].astype(jnp.float32), quant)

    def pick(ln_f, x, rows):
        return _kept(_rms_norm(ln_f, x, eps), quant)[rows]

    def head(w, x):
        return _linear(x, w, quant)

    return {k: under_highest(f) for k, f in (
        ("mixer", mixer_half), ("ffn", ffn_half), ("embed", embed),
        ("pick", pick), ("head", head))}


def row_logits(params, tokens, positions, blocks, copies, rows, eps=EPS,
               quant=None, top_k=TOP_K):
    """One forward over rows `tokens` [T] at `positions`, each seeing what
    `allowed_rows(blocks, copies)` lets it -> logits [R, V] at `rows`."""
    import jax.numpy as jnp

    run = _programs(float(eps), quant, int(top_k))
    positions, blocks, copies = (jnp.asarray(a, jnp.int32) for a in
                                 (positions, blocks, copies))
    x = run["embed"](params["embed"], jnp.asarray(tokens, jnp.int32))
    for layer in params["layers"]:
        x = run["ffn"](layer, run["mixer"](layer, x, positions, blocks,
                                           copies))
    return run["head"](params["head"],
                       run["pick"](params["ln_f"], x,
                                   jnp.asarray(rows, jnp.int32)))


def logits(params, tokens, block, eps=EPS, quant=None, top_k=TOP_K):
    """tokens [S] -> logits [S, V] of the clean sequence alone under the
    block mask (`block` 1 is causal): tests, tiny."""
    s = len(tokens)
    pos = np.arange(s)
    return row_logits(params, tokens, pos, pos // block, np.zeros(s), pos,
                      eps, quant, top_k)


# -- what the denoise rounds saw ---------------------------------------------

def replay_rows(prompt, answer, steps, surplus, surplus_steps, block,
                mask_id, pad_to=None):
    """One request as rows for `row_logits`.  `steps[i]` is the denoise step
    (0-based, within its block) at which answer token i was unmasked;
    `surplus` / `surplus_steps` are what the answer's last block held past
    the answer's end.  -> dict of arrays [T] `tokens`, `positions`,
    `blocks`, `copies`, and `states`: a list of (first row of the copy,
    block's first position, step, known [B] bool before the step)."""
    plen = len(prompt)
    clean = list(prompt) + list(answer) + list(surplus)
    when = [-1] * plen + list(steps) + list(surplus_steps)
    if len(clean) % block:
        raise ValueError("prompt + answer + surplus is not whole blocks")
    tokens, positions = list(clean), list(range(len(clean)))
    blocks = [p // block for p in positions]
    copies = [0] * len(clean)
    states = []
    for first in range(plen // block * block, len(clean), block):
        cols = range(first, first + block)
        n_steps = max(when[p] for p in cols) + 1
        for s in range(n_steps):
            known = [when[p] < s for p in cols]
            states.append((len(tokens), first, s, np.array(known)))
            tokens += [clean[p] if kn else mask_id
                       for p, kn in zip(cols, known)]
            positions += list(cols)
            blocks += [first // block] * block
            copies += [len(states)] * block
    if pad_to is not None:
        pad = pad_to - len(tokens)
        if pad < 0:
            raise ValueError(f"{len(tokens)} rows do not fit {pad_to}")
        tokens += [0] * pad
        positions += [0] * pad
        blocks += [0] * pad
        copies += [-1] * pad
    return {"tokens": np.array(tokens, np.int32),
            "positions": np.array(positions, np.int32),
            "blocks": np.array(blocks, np.int32),
            "copies": np.array(copies, np.int32), "states": states}


def state_logits(params, rows, block, eps=EPS, quant=None, top_k=TOP_K,
                 states_pad=None):
    """logits [n_states, B, V] at every column of every noisy copy of
    `rows` (`replay_rows`); with `states_pad`, rows of state 0 again up to
    that many states (one shape for every request)."""
    at = [first + c for first, *_ in rows["states"] for c in range(block)]
    n = len(rows["states"])
    if states_pad is not None:
        at += at[:block] * (states_pad - n)
    out = row_logits(params, rows["tokens"], rows["positions"],
                     rows["blocks"], rows["copies"], at, eps, quant, top_k)
    return out.reshape(-1, block, out.shape[-1])[:n]


def choices(state_logits_, mask_id):
    """From logits [n, B, V]: at every column the best token with the mask
    id's logit left out, its logit, and its confidence `softmax(logits)[t]`
    over the whole vocabulary.  -> (token [n, B], logit [n, B], conf)."""
    import jax
    import jax.numpy as jnp

    drop = jnp.arange(state_logits_.shape[-1]) == mask_id
    shown = jnp.where(drop, -jnp.inf, state_logits_)
    best = jnp.argmax(shown, axis=-1)
    top = jnp.max(shown, axis=-1)
    conf = jnp.exp(top - jax.nn.logsumexp(state_logits_, axis=-1))
    return best, top, conf
