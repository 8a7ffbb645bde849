"""Recurrent layers: Graves LSTM (peepholes), vanilla LSTM, GRU.

Parity: reference GravesLSTM.java:47 — Graves (2013) LSTM with peephole
connections, params packed as RW=[nL, 4nL+3] (GravesLSTMParamInitializer.java:61)
and forget-gate bias initialised to 5.0 (:63-73); and the older LSTM.java:58.

TPU-first re-design: the reference hand-writes BPTT as a Java loop over
timesteps (GravesLSTM.java:74-230). Here forward is one `lax.scan` over time
on batch-major [batch, time, features]; XLA unrolls/pipelines it and
`jax.grad` derives BPTT. The 4 gate matmuls are fused into a single
[n_in, 4n] @ / [n, 4n] @ pair per step so the MXU sees one large matmul, not
four small ones. Sequence masking — stubbed out in the reference
(GravesLSTM.java:100-106) — is implemented: masked steps carry state through
unchanged.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.layers import LayerImpl, register_layer_impl
from deeplearning4j_tpu.nn.layers.common import apply_dropout
from deeplearning4j_tpu.ops.activations import get_activation
from deeplearning4j_tpu.ops.initializers import init_weights


def _lstm_init(conf, key, dtype, peephole: bool):
    n_in, n = conf.n_in, conf.n_out
    k1, k2, k3 = jax.random.split(key, 3)
    b = jnp.zeros((4 * n,), dtype)
    # Gate order: [i, f, o, g]. Forget-gate bias init per the reference.
    b = b.at[n:2 * n].set(conf.forget_gate_bias_init)
    params = {
        "W": init_weights(k1, (n_in, 4 * n), conf.weight_init, dtype,
                          conf.distribution),
        "RW": init_weights(k2, (n, 4 * n), conf.weight_init, dtype,
                           conf.distribution),
        "b": b,
    }
    if peephole:
        # Peephole vectors (the "+3" columns of the reference's packed RW).
        params["pi"] = jnp.zeros((n,), dtype)
        params["pf"] = jnp.zeros((n,), dtype)
        params["po"] = jnp.zeros((n,), dtype)
    return params, {}


def _lstm_apply(conf, params, state, x, *, train=False, rng=None, mask=None,
                peephole: bool = True):
    """x: [batch, time, n_in]; mask: optional [batch, time] (1=valid)."""
    x = apply_dropout(x, conf.dropout, train, rng)
    n = conf.n_out
    batch = x.shape[0]
    act = get_activation(conf.activation)

    # Hoist the input projection out of the scan: one big [B*T, n_in]@[n_in,4n]
    # matmul keeps the MXU busy; the scan only carries the recurrent matmul.
    xz = jnp.einsum("bti,ij->btj", x, params["W"]) + params["b"]
    xz_t = jnp.swapaxes(xz, 0, 1)  # [time, batch, 4n]

    if mask is not None:
        mask_t = jnp.swapaxes(mask.astype(x.dtype), 0, 1)[..., None]  # [T,B,1]
    else:
        mask_t = None

    h0 = jnp.zeros((batch, n), x.dtype)
    c0 = jnp.zeros((batch, n), x.dtype)

    def step(carry, inputs):
        h_prev, c_prev = carry
        if mask_t is None:
            z = inputs
            m = None
        else:
            z, m = inputs
        z = z + h_prev @ params["RW"]
        zi, zf, zo, zg = jnp.split(z, 4, axis=-1)
        if peephole:
            zi = zi + c_prev * params["pi"]
            zf = zf + c_prev * params["pf"]
        i = jax.nn.sigmoid(zi)
        f = jax.nn.sigmoid(zf)
        g = act(zg)
        c = f * c_prev + i * g
        if peephole:
            zo = zo + c * params["po"]
        o = jax.nn.sigmoid(zo)
        h = o * act(c)
        if m is not None:
            h = m * h + (1 - m) * h_prev
            c = m * c + (1 - m) * c_prev
        return (h, c), h

    xs = xz_t if mask_t is None else (xz_t, mask_t)
    (h_last, _), hs = lax.scan(step, (h0, c0), xs)
    if conf.return_sequences:
        return jnp.swapaxes(hs, 0, 1), state  # [batch, time, n]
    return h_last, state


def graves_lstm_init(conf: L.GravesLSTMConf, key, dtype=jnp.float32):
    return _lstm_init(conf, key, dtype, peephole=True)


def graves_lstm_apply(conf, params, state, x, **kw):
    return _lstm_apply(conf, params, state, x, peephole=True, **kw)


register_layer_impl("graveslstm", LayerImpl(graves_lstm_init, graves_lstm_apply))


def lstm_init(conf: L.LSTMConf, key, dtype=jnp.float32):
    return _lstm_init(conf, key, dtype, peephole=False)


def lstm_apply(conf, params, state, x, **kw):
    return _lstm_apply(conf, params, state, x, peephole=False, **kw)


register_layer_impl("lstm", LayerImpl(lstm_init, lstm_apply))


# ---- GRU (TPU-era addition) ----------------------------------------------

def gru_init(conf: L.GRUConf, key, dtype=jnp.float32):
    n_in, n = conf.n_in, conf.n_out
    k1, k2 = jax.random.split(key)
    params = {
        "W": init_weights(k1, (n_in, 3 * n), conf.weight_init, dtype,
                          conf.distribution),
        "RW": init_weights(k2, (n, 3 * n), conf.weight_init, dtype,
                           conf.distribution),
        "b": jnp.zeros((3 * n,), dtype),
    }
    return params, {}


def gru_apply(conf, params, state, x, *, train=False, rng=None, mask=None):
    x = apply_dropout(x, conf.dropout, train, rng)
    n = conf.n_out
    batch = x.shape[0]
    act = get_activation(conf.activation)

    xz = jnp.einsum("bti,ij->btj", x, params["W"]) + params["b"]
    xz_t = jnp.swapaxes(xz, 0, 1)
    mask_t = (None if mask is None
              else jnp.swapaxes(mask.astype(x.dtype), 0, 1)[..., None])

    def step(h_prev, inputs):
        if mask_t is None:
            z = inputs
            m = None
        else:
            z, m = inputs
        zr, zu, zc = jnp.split(z, 3, axis=-1)
        rr, ru, rc = jnp.split(h_prev @ params["RW"], 3, axis=-1)
        r = jax.nn.sigmoid(zr + rr)
        u = jax.nn.sigmoid(zu + ru)
        cand = act(zc + r * rc)
        h = u * h_prev + (1 - u) * cand
        if m is not None:
            h = m * h + (1 - m) * h_prev
        return h, h

    xs = xz_t if mask_t is None else (xz_t, mask_t)
    h_last, hs = lax.scan(step, jnp.zeros((batch, n), x.dtype), xs)
    if conf.return_sequences:
        return jnp.swapaxes(hs, 0, 1), state
    return h_last, state


register_layer_impl("gru", LayerImpl(gru_init, gru_apply))
