"""GPT-2 in plain `jax.numpy`, float32, matmul precision "highest": the
yardstick the served tokens and the train step are held to.

Written from the published description (Radford et al. 2019 and the public
`modeling_gpt2`): learned token and position embeddings; pre-LayerNorm
blocks (eps from the file) of causal multi-head attention with biases and a
tanh-GELU ("gelu_new") MLP, each added to the residual; a final LayerNorm;
logits through the transposed embedding.  No kernel, no cache, no batching
trick.  It imports nothing of the program and takes nothing the program has
made: the weights are the benchmark's (`benchmark/adapters/gpt2.py`), in the
layout they are handed to the program in, and are only read.

One block is written once and scanned over the stacked layers, so that the
whole model compiles in the time of one layer.  `quant` is the control, not
a mode of the benchmark: every linear layer's two operands are rounded to 8
bits, weights per output column and activations per row, either to integers
(`int8`, symmetric) or to float8 e4m3 (`fp8`): the precisions below the
bfloat16 the configurations state, and the step that would tempt a later PR.
With `_bf16` after it (`int8_bf16`) every intermediate is rounded to bfloat16
as well, as a program that computes in bfloat16 around 8-bit matmuls would
keep them; without, everything outside the operands stays float32.
`correct` has to come out false for the control (`PERF.md`, section 2).
"""

from __future__ import annotations

import functools

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
ROWS_PER_BLOCK = 4      # rows of a batch taken together, to bound memory


def stack(params):
    """The benchmark's weights with the layers stacked along a new first
    axis, in float32 (scanned over by `hidden`)."""
    import jax
    import jax.numpy as jnp

    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    out = {k: jax.tree_util.tree_map(f32, v) for k, v in params.items()
           if k != "layers"}
    out["layers"] = jax.tree_util.tree_map(
        lambda *leaves: jnp.stack([f32(a) for a in leaves]),
        *params["layers"])
    return out


def _fake_int8(x, axis):
    """x rounded to 255 levels of its largest magnitude along `axis`; the
    gradient passes straight through."""
    import jax
    import jax.numpy as jnp

    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    q = jnp.round(x / scale) * scale
    return x + jax.lax.stop_gradient(q - x)


def _fake_fp8(x, axis):
    """x rounded to float8 (e4m3: 3 bits of mantissa) after scaling its
    largest magnitude along `axis` to the format's largest, 448."""
    import jax
    import jax.numpy as jnp

    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale
    return x + jax.lax.stop_gradient(q - x)


def _kept(x, quant):
    """An intermediate as the control keeps it: rounded to bfloat16 and back
    where `quant` ends in `_bf16`, else untouched."""
    import jax.numpy as jnp

    if quant is not None and quant.endswith("_bf16"):
        return x.astype(jnp.bfloat16).astype(x.dtype)
    return x


def _linear(x, w, b, quant):
    """x [..., n] times w [n, m] plus b [m]."""
    import jax.numpy as jnp

    operands = (quant or "").removesuffix("_bf16")
    if operands == "int8":
        x, w = _fake_int8(x, -1), _fake_int8(_kept(w, quant), 0)
    elif operands == "fp8":
        x, w = _fake_fp8(x, -1), _fake_fp8(_kept(w, quant), 0)
    elif quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return _kept(jnp.matmul(x, w) + b, quant)


def _layer_norm(p, x, eps):
    import jax.numpy as jnp

    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _gelu_new(x):
    import jax.numpy as jnp

    return 0.5 * x * (1.0 + jnp.tanh(
        (2.0 / jnp.pi) ** 0.5 * (x + 0.044715 * x ** 3)))


def _block(layer, x, eps, quant):
    """One pre-LayerNorm GPT-2 block on x [B, S, d]."""
    import jax
    import jax.numpy as jnp

    a = layer["attn"]
    d, h, dh = a["wq"].shape
    s = x.shape[1]
    y = _kept(_layer_norm(layer["ln1"], x, eps), quant)
    q, k, v = (_linear(y, a[w].reshape(d, h * dh), a[b].reshape(h * dh),
                       quant).reshape(*y.shape[:2], h, dh)
               for w, b in (("wq", "bq"), ("wk", "bk"), ("wv", "bv")))
    scores = jnp.einsum("bshk,bthk->bhst", q, k) / dh ** 0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    mix = _kept(jnp.einsum("bhst,bthk->bshk",
                           jax.nn.softmax(scores, axis=-1), v), quant)
    x = _kept(x + _linear(mix.reshape(*y.shape[:2], h * dh),
                          a["wo"].reshape(h * dh, d), a["bo"], quant), quant)
    m = layer["mlp"]
    y = _kept(_layer_norm(layer["ln2"], x, eps), quant)
    y = _kept(_gelu_new(_linear(y, m["w1"], m["b1"], quant)), quant)
    return _kept(x + _linear(y, m["w2"], m["b2"], quant), quant)


def hidden(stacked, tokens, eps, quant=None, remat=False):
    """tokens [B, S] -> the final LayerNorm's output [B, S, d]."""
    import jax

    block = functools.partial(_block, eps=eps, quant=quant)
    if remat:
        block = jax.checkpoint(block)
    x = _kept(stacked["embed"][tokens] + stacked["pos"][:tokens.shape[1]],
              quant)
    x, _ = jax.lax.scan(lambda x, layer: (block(layer, x), None), x,
                        stacked["layers"])
    return _kept(_layer_norm(stacked["ln_f"], x, eps), quant)


def _head(stacked, x, quant):
    import jax.numpy as jnp

    return _linear(x, stacked["embed"].T,
                   jnp.zeros((), jnp.float32), quant)


@functools.lru_cache(maxsize=None)
def _served_logits(eps, quant):
    import jax

    @jax.jit
    def run(stacked, tokens, rows, cols):
        with jax.default_matmul_precision("highest"):
            x = hidden(stacked, tokens, eps, quant)
            return _head(stacked, x[rows, cols], quant)

    return run


def served_logits(stacked, tokens, rows, cols, eps, quant=None):
    """Logits [N, V] after positions (rows[i], cols[i]) of tokens [B, S]:
    one full causal forward, no cache.  Padding after a sequence's end
    cannot reach an earlier position."""
    return _served_logits(float(eps), quant)(stacked, tokens, rows, cols)


def loss(stacked, tokens, targets, eps, quant=None):
    """Mean next-token cross-entropy of tokens [B, S] against targets."""
    import jax
    import jax.numpy as jnp

    logits = _head(stacked, hidden(stacked, tokens, eps, quant, remat=True),
                   quant)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))


def _norms(stacked_tree):
    """Euclidean norms of a stacked tree: one per layer for a leaf under
    "layers", one for any other leaf."""
    import jax
    import jax.numpy as jnp

    out = {k: jax.tree_util.tree_map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a))), v)
        for k, v in stacked_tree.items() if k != "layers"}
    out["layers"] = jax.tree_util.tree_map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.reshape(a.shape[0], -1)),
                                   axis=-1)), stacked_tree["layers"])
    return out


def _named(norm_tree) -> dict:
    """{"layers/3/attn/wq": norm, "embed": norm, ...} from `_norms`: the
    names a leaf has in the layout the program is handed."""
    import jax
    import numpy as np

    out = {}
    for path, value in jax.tree_util.tree_leaves_with_path(norm_tree):
        keys = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
        value = np.asarray(value)
        if keys[0] == "layers":
            for i, x in enumerate(value):
                out["/".join(["layers", str(i)] + keys[1:])] = float(x)
        else:
            out["/".join(keys)] = float(value)
    return out


@functools.lru_cache(maxsize=None)
def _train_step(eps, lr, quant):
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(stacked, m, v, t, tokens, targets):
        with jax.default_matmul_precision("highest"):
            blocks = tokens.shape[0] // ROWS_PER_BLOCK
            tok = tokens.reshape(blocks, ROWS_PER_BLOCK, -1)
            tgt = targets.reshape(blocks, ROWS_PER_BLOCK, -1)

            def one(carry, xs):
                value, grad = jax.value_and_grad(loss)(stacked, xs[0], xs[1],
                                                       eps, quant)
                return (carry[0] + value / blocks, jax.tree_util.tree_map(
                    lambda a, g: a + g / blocks, carry[1], grad)), None

            zero = jax.tree_util.tree_map(jnp.zeros_like, stacked)
            (value, grad), _ = jax.lax.scan(one, (jnp.float32(0), zero),
                                            (tok, tgt))
            m = jax.tree_util.tree_map(
                lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g, m, grad)
            v = jax.tree_util.tree_map(
                lambda v, g: ADAM_B2 * v + (1 - ADAM_B2) * g * g, v, grad)
            stacked = jax.tree_util.tree_map(
                lambda p, m, v: p - lr * (m / (1 - ADAM_B1 ** t)) / (
                    jnp.sqrt(v / (1 - ADAM_B2 ** t)) + ADAM_EPS),
                stacked, m, v)
        return stacked, m, v, value, _norms(grad)

    return step


def train(params, batches, eps, lr, quant=None):
    """Adam (bias-corrected, b1 0.9, b2 0.999, eps 1e-8, no decay) from
    `params` over `batches` of (tokens, targets) [B, S], the gradient of the
    mean loss summed over blocks of rows.  Returns each step's loss, the
    first gradient's norm by leaf and the norm of each leaf's change over all
    the steps, both as {leaf name: norm}."""
    import jax
    import jax.numpy as jnp

    start = stack(params)
    state = jax.tree_util.tree_map(jnp.copy, start)
    m = jax.tree_util.tree_map(jnp.zeros_like, start)
    v = jax.tree_util.tree_map(jnp.zeros_like, start)
    step = _train_step(float(eps), float(lr), quant)
    losses, first = [], None
    for t, (tokens, targets) in enumerate(batches, 1):
        state, m, v, value, norms = step(state, m, v, jnp.float32(t),
                                         jnp.asarray(tokens),
                                         jnp.asarray(targets))
        losses.append(float(value))
        if first is None:
            first = _named(norms)
    change = jax.jit(lambda a, b: _norms(jax.tree_util.tree_map(
        lambda x, y: x - y, a, b)))(state, start)
    return losses, first, _named(change)
