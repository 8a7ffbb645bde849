"""Transformer LM designed for hybrid mesh parallelism.

Out-of-reference extension (SURVEY §2.3 item 3: TP/SP/EP are absent from
the 2015 reference; the task brief makes them first-class here).

Parallelization strategy — the scaling-book recipe, with a deliberate
split between the two JAX mechanisms:

- dp/tp/ep ride **GSPMD**: the model is written over FULL arrays; parameters
  are placed with `param_specs` (heads/hidden/experts sharded over the
  `model` axis, everything else replicated) and activations carry
  `with_sharding_constraint` hints. XLA's SPMD partitioner inserts the
  forward AND backward collectives — which is what makes `jax.grad`
  correct without any hand-rolled psum bookkeeping.
- sp (sequence/context parallelism) is the one place XLA cannot infer the
  algorithm: exact long-context attention needs the ring schedule. That
  inner function — and only it — runs under `shard_map`
  (`ring_attention.py`), whose ppermute transpose is exact, so `jax.grad`
  taken OUTSIDE the shard_map stays correct.

`apply(cfg, params, tokens)` with mesh=None is the identical single-chip
model; tests assert step-for-step equivalence between the two.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.parallel.mesh import shard_map
from deeplearning4j_tpu.parallel.ring_attention import attention, ring_attention


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 256
    d_model: int = 64
    n_heads: int = 8
    n_layers: int = 2
    d_ff: int = 256
    n_experts: int = 0          # 0 = dense MLP; >0 = MoE
    # Experts per token: 1 = Switch, 2 = GShard-style top-2 (gate weights
    # renormalized over the chosen experts).
    moe_top_k: int = 1
    # Per-expert buffer size as a multiple of tokens/n_experts (Switch
    # Transformer capacity factor).  >0: capacity-based dispatch — each
    # expert computes ONLY its gathered buffer, so MoE FLOPs scale with
    # this factor, not with n_experts.  0: dense-masked compute (every
    # expert sees every token; exact, no drops — the dispatch oracle).
    moe_capacity_factor: float = 1.25
    # Switch load-balancing auxiliary loss weight: aux = E * sum_e f_e*P_e
    # (f_e = dispatch fraction, P_e = mean router prob).  Without it
    # top-1 routing collapses onto few experts and capacity dispatch
    # drops most tokens; lm_loss adds moe_aux_weight * mean-over-layers.
    moe_aux_weight: float = 0.01
    max_len: int = 512
    dtype: str = "float32"
    attn_bias: bool = False     # GPT-2-style q/k/v/o projection biases
    # GPT-2-style weight tying: the LM head is embed.T (no separate head
    # parameter) — at GPT-2-small scale this is the difference between
    # 124M and 163M params.
    tie_embeddings: bool = False
    # Rematerialize each transformer block in the backward pass
    # (jax.checkpoint): activation memory drops from O(L*B*S*d) to the
    # block boundaries, the standard trade for long-context training.
    remat: bool = False

    def __post_init__(self):
        if self.n_experts and not (1 <= self.moe_top_k <= self.n_experts):
            raise ValueError(
                f"moe_top_k={self.moe_top_k} must be in [1, "
                f"n_experts={self.n_experts}]")

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads


@dataclass(frozen=True)
class MeshAxes:
    """Which mesh axis carries which parallelism dimension."""

    data: str = "data"
    seq: str = "seq"
    model: str = "model"


def init_params(cfg: TransformerConfig, key: jax.Array) -> dict:
    """Full (unsharded) parameter tree; place with `param_specs`."""
    dt = jnp.dtype(cfg.dtype)
    d, h, dh, f = cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.d_ff
    keys = iter(jax.random.split(key, 4 + 8 * cfg.n_layers))

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape, dt) / jnp.sqrt(
            jnp.asarray(fan_in, dt)))

    layers = []
    for _ in range(cfg.n_layers):
        layer = {
            "ln1": {"scale": jnp.ones((d,), dt), "bias": jnp.zeros((d,), dt)},
            "ln2": {"scale": jnp.ones((d,), dt), "bias": jnp.zeros((d,), dt)},
            "attn": {
                "wq": dense(next(keys), (d, h, dh), d),
                "wk": dense(next(keys), (d, h, dh), d),
                "wv": dense(next(keys), (d, h, dh), d),
                "wo": dense(next(keys), (h, dh, d), d),
            },
        }
        if cfg.attn_bias:
            layer["attn"].update(
                bq=jnp.zeros((h, dh), dt), bk=jnp.zeros((h, dh), dt),
                bv=jnp.zeros((h, dh), dt), bo=jnp.zeros((d,), dt))
        if cfg.n_experts:
            e = cfg.n_experts
            layer["moe"] = {
                "gate": dense(next(keys), (d, e), d),
                "w1": dense(next(keys), (e, d, f), d),
                "b1": jnp.zeros((e, f), dt),
                "w2": dense(next(keys), (e, f, d), f),
                "b2": jnp.zeros((e, d), dt),
            }
        else:
            layer["mlp"] = {
                "w1": dense(next(keys), (d, f), d),
                "b1": jnp.zeros((f,), dt),
                "w2": dense(next(keys), (f, d), f),
                "b2": jnp.zeros((d,), dt),
            }
        layers.append(layer)
    # Tied configs: the embedding IS the output projection, so it must
    # carry the head's 1/sqrt(d) scale or initial logits blow up to
    # std ~sqrt(d) (initial loss ~70 instead of ln V).  The first block
    # layer-norms its input, so the smaller input-embedding scale is
    # otherwise inert.
    out = {
        "embed": dense(next(keys), (cfg.vocab_size, d),
                       d if cfg.tie_embeddings else 1),
        "pos": dense(next(keys), (cfg.max_len, d), 1) * 0.02,
        "ln_f": {"scale": jnp.ones((d,), dt), "bias": jnp.zeros((d,), dt)},
        "layers": layers,
    }
    if not cfg.tie_embeddings:
        out["head"] = dense(next(keys), (d, cfg.vocab_size), d)
    return out


def param_specs(cfg: TransformerConfig, model_axis: Optional[str]) -> dict:
    """PartitionSpec tree: tp dims sharded over the model axis, rest
    replicated. wq/wk/wv/wo shard the HEAD dim; mlp the HIDDEN dim; moe
    the EXPERT dim (expert parallelism rides the model axis)."""
    t = model_axis
    layer_spec = {
        "ln1": {"scale": P(), "bias": P()},
        "ln2": {"scale": P(), "bias": P()},
        "attn": {"wq": P(None, t, None), "wk": P(None, t, None),
                 "wv": P(None, t, None), "wo": P(t, None, None)},
    }
    if cfg.attn_bias:
        layer_spec["attn"].update(bq=P(t, None), bk=P(t, None),
                                  bv=P(t, None), bo=P())
    if cfg.n_experts:
        layer_spec["moe"] = {"gate": P(), "w1": P(t, None, None),
                             "b1": P(t, None), "w2": P(t, None, None),
                             "b2": P(t, None)}
    else:
        layer_spec["mlp"] = {"w1": P(None, t), "b1": P(t),
                             "w2": P(t, None), "b2": P()}
    out = {
        "embed": P(),
        "pos": P(),
        "ln_f": {"scale": P(), "bias": P()},
        "layers": [dict(layer_spec) for _ in range(cfg.n_layers)],
    }
    if not cfg.tie_embeddings:
        out["head"] = P()
    return out


def lm_head(params: dict) -> jax.Array:
    """The [d, V] output projection: the explicit head param, or embed.T
    under GPT-2-style weight tying.  Single source of truth for every
    scoring path (apply, decode)."""
    return (params["head"] if "head" in params
            else params["embed"].T)


def gpt2_small(max_len: int = 1024, dtype: str = "bfloat16"
               ) -> TransformerConfig:
    """GPT-2-small-class flagship config: ~124M params with tied
    embeddings (vocab rounded to 50304 for lane-128 tiling), per-block
    remat for long-sequence training.  The scale target of VERDICT r4
    demand #2."""
    return TransformerConfig(
        vocab_size=50304, d_model=768, n_heads=12, n_layers=12,
        d_ff=3072, max_len=max_len, dtype=dtype, attn_bias=True,
        tie_embeddings=True, remat=True)


def gpt2_medium(max_len: int = 1024, dtype: str = "bfloat16"
                ) -> TransformerConfig:
    """GPT-2-medium-class config: ~355M params (1024/16/24), same
    recipe as `gpt2_small` (tied embeddings, lane-128 vocab, remat)."""
    return TransformerConfig(
        vocab_size=50304, d_model=1024, n_heads=16, n_layers=24,
        d_ff=4096, max_len=max_len, dtype=dtype, attn_bias=True,
        tie_embeddings=True, remat=True)


def gpt2_large(max_len: int = 1024, dtype: str = "bfloat16"
               ) -> TransformerConfig:
    """GPT-2-large-class config: ~774M params (1280/20/36).  At this
    scale single-chip training needs accum+remat headroom; the dp/sp/tp
    mesh trainers are the intended path."""
    return TransformerConfig(
        vocab_size=50304, d_model=1280, n_heads=20, n_layers=36,
        d_ff=5120, max_len=max_len, dtype=dtype, attn_bias=True,
        tie_embeddings=True, remat=True)


def _layer_norm(p, x, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def qkv_proj(p, x):
    """[B,S,d] -> q,k,v [B,S,H,K] incl. optional GPT-2-style biases.
    Shared by the training forward and the KV-cached decode path."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    return q, k, v


def out_proj(p, o):
    """[B,S,H,K] attention output -> [B,S,d] incl. optional bias."""
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    if "bo" in p:
        out = out + p["bo"]
    return out


def _attn(p, x, mesh: Optional[Mesh], axes: MeshAxes, causal: bool):
    """x:[B,S,d] full arrays. Ring attention under shard_map when a mesh is
    given (seq axis shards S); plain attention otherwise."""
    q, k, v = qkv_proj(p, x)
    if mesh is None:
        from deeplearning4j_tpu.parallel import kernels

        if kernels.flash_enabled():
            o = kernels.flash_attention(q, k, v, causal)
        else:
            o = attention(q, k, v, causal=causal)
    else:
        from deeplearning4j_tpu.parallel import kernels
        from deeplearning4j_tpu.parallel.ring_attention import (
            ring_flash_attention,
        )

        # Pallas inner block on TPU (fused fwd+bwd, O(S/P) memory);
        # plain-jnp blockwise ring elsewhere.
        inner = (ring_flash_attention if kernels.flash_enabled()
                 else ring_attention)
        spec = P(axes.data, axes.seq, axes.model, None)
        ring = shard_map(
            lambda q, k, v: inner(q, k, v, axes.seq, causal=causal),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
        o = ring(q, k, v)
    return out_proj(p, o)


def _mlp(p, x):
    h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, p["w1"]) + p["b1"])
    return jnp.einsum("bsf,fd->bsd", h, p["w2"]) + p["b2"]


def _router_weights(probs, top_k):
    """(top_idx, weights) [..., k].  k=1: the Switch top-1 router prob
    itself; k>1: GShard-style renormalization over the chosen experts."""
    top_p, top_idx = lax.top_k(probs, top_k)
    if top_k > 1:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return top_idx, top_p


def _moe_dense(p, x, top_k: int = 1):
    """Top-k MoE, dense-masked compute: every expert sees every token and
    the combine weight zeroes non-routed pairs — exact (no capacity
    drops) but O(n_experts) FLOPs.  Kept as the correctness ORACLE for
    `_moe_dispatch` and as the exact inference path; select with
    cfg.moe_capacity_factor = 0."""
    logits = jnp.einsum("bsd,de->bse", x, p["gate"])
    gate_w = jax.nn.softmax(logits, axis=-1)                   # [B,S,E]
    e = p["w1"].shape[0]
    top_idx, w = _router_weights(gate_w, top_k)                # [B,S,k]
    combine = jnp.sum(
        w[..., None] * jax.nn.one_hot(top_idx, e, dtype=x.dtype), axis=-2)
    h = jax.nn.gelu(jnp.einsum("bsd,edf->ebsf", x, p["w1"])
                    + p["b1"][:, None, None, :])
    y = jnp.einsum("ebsf,efd->ebsd", h, p["w2"]) + p["b2"][:, None, None, :]
    return jnp.einsum("ebsd,bse->bsd", y, combine)


def _moe_dispatch(p, x, capacity_factor: float,
                  mesh: Optional[Mesh] = None,
                  axes: MeshAxes = MeshAxes(), top_k: int = 1):
    """Capacity-based top-k dispatch (Switch routing at k=1, GShard-style
    top-2 at k=2; Switch Transformer, Fedus et al. 2021 / GShard, Lepikhin
    et al. 2020 — public formulations): the N*k (token, expert)
    assignments are scattered into a static [E, C, d] buffer with
    C = ceil(capacity_factor * N * k / E), each expert computes ONLY its
    buffer, outputs gather back weighted by the router weight and sum
    over a token's k assignments.  Expert FLOPs therefore scale with the
    capacity factor, NOT with n_experts.  Assignments past an expert's
    capacity (token-major priority: a token's second choice ranks after
    its first) contribute nothing — identity via the surrounding
    residual, the standard drop rule.

    Static shapes throughout (scatter/gather via `.at[]` / advanced
    indexing), so the routing is jit/GSPMD-clean; with a mesh the buffer
    is sharded over the model axis on E, placing each expert's compute
    on its owner (XLA inserts the token all-to-all)."""
    B, S, d = x.shape
    E = p["w1"].shape[0]
    N = B * S
    A = N * top_k                    # total (token, expert) assignments
    C = max(1, min(A, int(math.ceil(capacity_factor * A / E))))  # static
    xf = x.reshape(N, d)
    logits = xf @ p["gate"]                                    # [N,E]
    gate_w = jax.nn.softmax(logits, axis=-1)
    top_idx, top_w = _router_weights(gate_w, top_k)            # [N,k]
    e_flat = top_idx.reshape(-1)                               # [A]
    w_flat = top_w.reshape(-1)
    onehot = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)
    # 0-based slot of each assignment within its expert's buffer
    # (token-major priority), C and above = overflow.
    slot = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, axis=1) - 1
    keep = (slot < C).astype(x.dtype)                          # [A]
    slot = jnp.clip(slot, 0, C - 1)
    x_rep = jnp.repeat(xf, top_k, axis=0)                      # [A, d]
    buf = jnp.zeros((E, C, d), x.dtype).at[e_flat, slot].add(
        x_rep * keep[:, None])

    def constrain(a):
        if mesh is None:
            return a
        return lax.with_sharding_constraint(
            a, NamedSharding(mesh, P(axes.model, None, None)))

    buf = constrain(buf)
    h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", buf, p["w1"])
                    + p["b1"][:, None, :])
    y = jnp.einsum("ecf,efd->ecd", h, p["w2"]) + p["b2"][:, None, :]
    y = constrain(y)
    # Each kept assignment owns its slot exclusively; dropped ones read a
    # foreign slot but are zeroed by `keep`.
    out = y[e_flat, slot] * (w_flat * keep)[:, None]           # [A, d]
    return jnp.sum(out.reshape(N, top_k, d), axis=1).reshape(B, S, d)


def _moe(p, x, capacity_factor: float = 0.0,
         mesh: Optional[Mesh] = None, axes: MeshAxes = MeshAxes(),
         top_k: int = 1):
    """MoE block: capacity-based dispatch when capacity_factor > 0
    (the FLOP-saving default), dense-masked oracle otherwise."""
    if capacity_factor > 0:
        return _moe_dispatch(p, x, capacity_factor, mesh, axes, top_k)
    return _moe_dense(p, x, top_k)


def _moe_aux_loss(p, x):
    """Switch Transformer load-balancing loss (Fedus et al. 2021,
    eq. 4): E * sum_e f_e * P_e over the router's top-1 assignment.
    Minimized (=1) at a uniform assignment; differentiable through P_e."""
    logits = jnp.einsum("bsd,de->bse", x, p["gate"])
    e = p["w1"].shape[0]
    probs = jax.nn.softmax(logits, axis=-1)               # [B,S,E]
    choice = jnp.argmax(logits, axis=-1)                  # [B,S]
    f = jnp.mean(jax.nn.one_hot(choice, e, dtype=x.dtype), axis=(0, 1))
    pbar = jnp.mean(probs, axis=(0, 1))
    return e * jnp.sum(f * pbar)


def apply(cfg: TransformerConfig, params: dict, tokens: jax.Array,
          mesh: Optional[Mesh] = None, axes: MeshAxes = MeshAxes(),
          causal: bool = True, train: bool = False,
          return_aux: bool = False):
    """tokens:[B,S] int32 -> logits [B,S,V]. Pass mesh to parallelize.

    MoE routing: `train=True` (the lm_loss path) uses capacity-based
    dispatch — FLOP-saving but drops overflow tokens, so logits can
    depend on batch composition.  The inference default is the exact
    dense-masked path, keeping scoring deterministic per sequence and
    bit-compatible with the KV-cached `generation.decode_step`.
    `return_aux=True` additionally returns the mean-over-layers Switch
    load-balancing loss (0 for dense configs)."""

    def constrain(a):
        if mesh is None:
            return a
        return lax.with_sharding_constraint(
            a, NamedSharding(mesh, P(axes.data, axes.seq, None)))

    cf = cfg.moe_capacity_factor if train else 0.0

    def block(layer, x):
        x = x + _attn(layer["attn"], _layer_norm(layer["ln1"], x),
                      mesh, axes, causal)
        x = constrain(x)
        h = _layer_norm(layer["ln2"], x)
        if "moe" in layer:
            x = x + _moe(layer["moe"], h, cf, mesh, axes, cfg.moe_top_k)
            aux = _moe_aux_loss(layer["moe"], h)
        else:
            x = x + _mlp(layer["mlp"], h)
            aux = jnp.zeros((), x.dtype)
        return constrain(x), aux

    if cfg.remat:
        block = jax.checkpoint(block)
    x = params["embed"][tokens] + params["pos"][None, :tokens.shape[1], :]
    x = constrain(x)
    auxs = []
    for layer in params["layers"]:
        x, aux = block(layer, x)
        auxs.append(aux)
    x = _layer_norm(params["ln_f"], x)
    logits = jnp.einsum("bsd,dv->bsv", x, lm_head(params))
    if return_aux:
        return logits, jnp.mean(jnp.stack(auxs))
    return logits


def lm_loss(cfg: TransformerConfig, params: dict, tokens: jax.Array,
            targets: jax.Array, mesh: Optional[Mesh] = None,
            axes: MeshAxes = MeshAxes()) -> jax.Array:
    """Mean next-token cross-entropy over the full batch (training mode:
    MoE layers route with capacity-based dispatch + the Switch
    load-balancing auxiliary loss weighted by cfg.moe_aux_weight)."""
    use_aux = bool(cfg.n_experts) and cfg.moe_aux_weight > 0
    out = apply(cfg, params, tokens, mesh, axes, train=True,
                return_aux=use_aux)
    logits, aux = out if use_aux else (out, None)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    loss = jnp.mean(nll)
    if use_aux:
        loss = loss + cfg.moe_aux_weight * aux.astype(loss.dtype)
    return loss
