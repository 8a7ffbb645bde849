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
  taken OUTSIDE the shard_map stays correct.  A causal ring wants a row's
  columns dealt zigzag over the chips (`seq_order`): `apply` and `lm_loss`
  deal them, or take them dealt from a trainer that placed them so.

`apply(cfg, params, tokens)` with mesh=None is the identical single-chip
model; tests assert step-for-step equivalence between the two.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Callable, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.parallel.mesh import shard_map
from deeplearning4j_tpu.parallel.ring_attention import (
    attention,
    ring_attention,
    zigzag_order,
)


class UnsupportedLayerKind(ValueError):
    """A path was handed a configuration whose layer kinds it does not
    compute (raised where the path is built, never a wrong answer)."""


@dataclass(frozen=True)
class YarnRope:
    """Rotary positions with YaRN's blended frequencies (Peng et al.
    2023, as `deepseek_v2` configures it): `theta` and the six numbers
    of `rope_scaling`."""

    theta: float = 10000.0
    factor: float = 1.0
    original_max_len: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclass(frozen=True)
class LatentAttention:
    """Multi-head latent attention's five sizes (DeepSeek-V2,
    arXiv:2405.04434): the query's and the key/value's compressed ranks
    and, a head, the unrotated key width, the rotary width (ONE rotary
    key for all heads) and the value width."""

    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int

    @property
    def row_values(self) -> int:
        """What the cache keeps a token and layer: `[c_kv | k_rope]`."""
        return self.kv_rank + self.rope_dim


@dataclass(frozen=True)
class LinearAttention:
    """A gated delta-rule layer (Kimi Delta Attention, arXiv:2510.26692):
    `heads` heads whose state is a `k_dim x v_dim` matrix each, a causal
    depthwise convolution of `conv_taps` taps before q, k and v, the
    per-channel decay and the output gate as projections through
    `gate_rank`, and write strengths in (0, 2) where `neg_eigval` (else
    (0, 1)).  What the cache keeps a sequence and layer is the state
    (float32) and the convolution's last `conv_taps - 1` inputs."""

    heads: int
    k_dim: int
    v_dim: int
    conv_taps: int = 4
    gate_rank: int = 128
    neg_eigval: bool = True


@dataclass(frozen=True)
class RoutedExperts:
    """A SwiGLU expert layer routed over `published` experts of which
    the slice `held = (lo, hi)` has its weights HERE (one chip's share
    of an expert-parallel deployment; `(0, published)` holds them all).
    The router keeps its published width; what the absent experts would
    have added is left out."""

    published: int
    held: Tuple[int, int]
    per_token: int
    width: int                  # one routed expert's hidden width
    groups: int = 1             # group-limited routing: experts in
    groups_kept: int = 1        # `groups` groups, the best `groups_kept`
    # "softmax" | "sigmoid" (a sigmoid router chooses by score + a
    # per-expert bias and weighs by the score alone)
    score: str = "softmax"
    scale: float = 1.0          # routed_scaling_factor
    renormalize: bool = False   # norm_topk_prob
    shared_width: int = 0       # the always-on expert's width, 0 = none

    def __post_init__(self):
        lo, hi = self.held
        if not 0 <= lo < hi <= self.published:
            raise ValueError(f"experts held {self.held} is no slice of "
                             f"{self.published}")
        if self.published % self.groups or not (
                1 <= self.groups_kept <= self.groups):
            raise ValueError(f"{self.groups_kept} of {self.groups} groups "
                             f"over {self.published} experts")
        if self.score not in ("softmax", "sigmoid"):
            raise ValueError(f"router score {self.score!r}: \"softmax\" "
                             f"or \"sigmoid\"")

    @property
    def n_held(self) -> int:
        return self.held[1] - self.held[0]


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
    # Recompute each block's activations in the backward pass (jax.
    # checkpoint), all but the flash kernels' output and row statistics
    # (kernels.SAVED_NAMES): O(L*B*S*d) falls to the block boundaries.
    remat: bool = False
    # --- the layer vocabulary past GPT-2's (all defaults are GPT-2) ---
    norm: str = "layer"         # "layer" (gain and bias) | "rms" (gain)
    norm_eps: float = 1e-5
    rope: Optional[YarnRope] = None     # None = learned positions
    mlp: str = "gelu"           # "gelu" (biases) | "swiglu" (none)
    latent: Optional[LatentAttention] = None    # None = full heads
    # `experts` layers follow `dense_layers` leading dense ones
    # (first_k_dense_replace); the dense MLP's width is `d_ff`
    experts: Optional[RoutedExperts] = None
    dense_layers: int = 0
    # a head's width where it is not d_model / n_heads, and the K/V
    # heads where they are fewer than the query heads (query head j
    # reads K/V head j // (n_heads / kv_heads))
    head_width: Optional[int] = None
    kv_heads: Optional[int] = None
    # "auto": learned positions unless `rope`; "none": no positions
    positions: str = "auto"
    # each layer's MIXER kind, "full" (softmax attention, or latent
    # where `latent` is set) | "kda" (`linear`); None = every layer full
    mixers: Optional[Tuple[str, ...]] = None
    linear: Optional[LinearAttention] = None
    # full layers' output gate: (a * sigmoid(h W_gate)) W_o, elementwise
    attn_gate: bool = False
    # a head's RMSNorm of q and of k (one gain vector of `head_dim` for
    # all query heads, one for all K/V heads) before the rotation
    qk_norm: bool = False
    # block diffusion: position i sees position j iff j // B <= i // B
    # (causal between blocks of `block_length`, bidirectional inside
    # one; 1 = causal), blocks dealt by ABSOLUTE position; a block is
    # generated by unmasking it over a few forwards, a masked position
    # fed as `mask_token`, and the logits at a position predict THAT
    # position's token (`serving/lm.py`, the block round)
    block_length: int = 1
    mask_token: Optional[int] = None

    def __post_init__(self):
        if self.n_experts and not (1 <= self.moe_top_k <= self.n_experts):
            raise ValueError(
                f"moe_top_k={self.moe_top_k} must be in [1, "
                f"n_experts={self.n_experts}]")
        if self.norm not in ("layer", "rms") or self.mlp not in (
                "gelu", "swiglu"):
            raise ValueError(f"norm {self.norm!r} / mlp {self.mlp!r}")
        if self.experts is not None and self.n_experts:
            raise ValueError("`experts` and `n_experts` are two expert "
                             "layers; a configuration has one")
        if self.positions not in ("auto", "none"):
            raise ValueError(f"positions {self.positions!r}")
        if self.kv_heads is not None and (
                self.kv_heads < 1 or self.n_heads % self.kv_heads):
            raise ValueError(f"{self.kv_heads} K/V heads under "
                             f"{self.n_heads} query heads")
        if self.mixers is not None:
            if (len(self.mixers) != self.n_layers
                    or set(self.mixers) - {"full", "kda"}):
                raise ValueError(f"mixers {self.mixers} for "
                                 f"{self.n_layers} layers")
            if "kda" in self.mixers and self.linear is None:
                raise ValueError("a \"kda\" layer needs `linear`")
        if self.block_length < 1:
            raise ValueError(f"block_length {self.block_length}")
        if self.block_length > 1:
            if self.latent is not None or self.mixers is not None:
                raise UnsupportedLayerKind(
                    "the block mask is the grouped-query path's: no "
                    "latent and no recurrent layers under it")
            if (self.mask_token is None
                    or not 0 <= self.mask_token < self.vocab_size):
                raise ValueError(
                    f"a block model feeds its masked positions as "
                    f"`mask_token` (an id of the vocabulary), got "
                    f"{self.mask_token}")
        if self.qk_norm and self.latent is not None:
            raise UnsupportedLayerKind(
                "q/k norms are the grouped-query path's; latent "
                "attention norms its compressed ranks")

    @property
    def classic(self) -> bool:
        """GPT-2's layer and nothing else: what the trainers, the mesh
        runtimes and the whole-sequence KV cache compute."""
        return (self.norm == "layer" and self.rope is None
                and self.mlp == "gelu" and self.latent is None
                and self.experts is None and self.head_width is None
                and self.kv_heads is None and self.positions == "auto"
                and self.mixers is None and self.linear is None
                and not self.attn_gate and not self.qk_norm
                and self.block_length == 1)

    @property
    def grouped(self) -> bool:
        """Full layers take the grouped-query path (`_grouped_attn`, and
        `generation._grouped_paged_attn` over the K and V pools): fewer
        K/V heads than query heads, the output gate, rotary positions on
        full heads, q/k norms, the block mask."""
        return self.latent is None and (
            self.kv_heads is not None or self.attn_gate
            or self.rope is not None or self.qk_norm
            or self.block_length > 1)

    @property
    def learned_positions(self) -> bool:
        return self.rope is None and self.positions == "auto"

    @property
    def recurrent(self) -> bool:
        """Some layer keeps a state a sequence instead of a K/V history."""
        return "kda" in self.mixer_kinds()

    def mixer_kinds(self) -> Tuple[str, ...]:
        """Each layer's mixer kind, "full" | "kda"."""
        return self.mixers or ("full",) * self.n_layers

    def layer_kinds(self) -> Tuple[str, ...]:
        """Each layer's feed-forward kind: "dense", "moe" (Switch /
        GShard, GELU) or "experts" (`RoutedExperts`)."""
        if self.experts is not None:
            return tuple("dense" if i < self.dense_layers else "experts"
                         for i in range(self.n_layers))
        return ("moe" if self.n_experts else "dense",) * self.n_layers

    @property
    def head_dim(self) -> int:
        if self.head_width is not None:
            return self.head_width
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads

    @property
    def n_kv_heads(self) -> int:
        return self.kv_heads if self.kv_heads is not None else self.n_heads


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
    per_layer = (8 if cfg.classic
                 else 32 if cfg.mixers is not None or cfg.attn_gate else 16)
    keys = iter(jax.random.split(key, 4 + per_layer * cfg.n_layers))

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape, dt) / jnp.sqrt(
            jnp.asarray(fan_in, dt)))

    def gain(n):
        if cfg.norm == "rms":
            return {"scale": jnp.ones((n,), dt)}
        return {"scale": jnp.ones((n,), dt), "bias": jnp.zeros((n,), dt)}

    def swiglu(width, lead=()):
        return {"wg": dense(next(keys), lead + (d, width), d),
                "wu": dense(next(keys), lead + (d, width), d),
                "wd": dense(next(keys), lead + (width, d), width)}

    def kda_layer():
        la = cfg.linear
        hk = (la.heads, la.k_dim)
        taps = (la.conv_taps,) + hk
        return {
            "wq": dense(next(keys), (d,) + hk, d),
            "wk": dense(next(keys), (d,) + hk, d),
            "wv": dense(next(keys), (d, la.heads, la.v_dim), d),
            "conv_q": dense(next(keys), taps, la.conv_taps),
            "conv_k": dense(next(keys), taps, la.conv_taps),
            "conv_v": dense(next(keys),
                            (la.conv_taps, la.heads, la.v_dim),
                            la.conv_taps),
            "wf_down": dense(next(keys), (d, la.gate_rank), d),
            "wf_up": dense(next(keys), (la.gate_rank,) + hk, la.gate_rank),
            # decays of about 0.9 to 0.999 a token
            "a_log": jnp.log(jax.random.uniform(
                next(keys), (la.heads,), dt, 0.02, 0.2)),
            "dt_bias": jnp.zeros(hk, dt),
            "wb": dense(next(keys), (d, la.heads), d),
            "wg_down": dense(next(keys), (d, la.gate_rank), d),
            "wg_up": dense(next(keys), (la.gate_rank, la.heads, la.v_dim),
                           la.gate_rank),
            "o_norm": {"scale": jnp.ones((la.v_dim,), dt)},
            "wo": dense(next(keys), (la.heads, la.v_dim, d),
                        la.heads * la.v_dim),
        }

    layers = []
    for kind, mixer in zip(cfg.layer_kinds(), cfg.mixer_kinds()):
        layer = {"ln1": gain(d), "ln2": gain(d)}
        if mixer == "kda":
            layer["attn"] = kda_layer()
        elif cfg.latent is not None:
            la = cfg.latent
            layer["attn"] = {
                "wdq": dense(next(keys), (d, la.q_rank), d),
                "q_norm": {"scale": jnp.ones((la.q_rank,), dt)},
                "wuq": dense(next(keys),
                             (la.q_rank, h, la.nope_dim + la.rope_dim),
                             la.q_rank),
                "wdkv": dense(next(keys), (d, la.kv_rank + la.rope_dim), d),
                "kv_norm": {"scale": jnp.ones((la.kv_rank,), dt)},
                "wukv": dense(next(keys),
                              (la.kv_rank, h, la.nope_dim + la.v_dim),
                              la.kv_rank),
                "wo": dense(next(keys), (h, la.v_dim, d), h * la.v_dim),
            }
        else:
            hkv = cfg.n_kv_heads
            layer["attn"] = {
                "wq": dense(next(keys), (d, h, dh), d),
                "wk": dense(next(keys), (d, hkv, dh), d),
                "wv": dense(next(keys), (d, hkv, dh), d),
                "wo": dense(next(keys), (h, dh, d), d),
            }
            if cfg.attn_gate:
                layer["attn"]["wgate"] = dense(next(keys), (d, h, dh), d)
            if cfg.qk_norm:
                layer["attn"]["q_norm"] = {"scale": jnp.ones((dh,), dt)}
                layer["attn"]["k_norm"] = {"scale": jnp.ones((dh,), dt)}
        if cfg.attn_bias:
            layer["attn"].update(
                bq=jnp.zeros((h, dh), dt), bk=jnp.zeros((h, dh), dt),
                bv=jnp.zeros((h, dh), dt), bo=jnp.zeros((d,), dt))
        if kind == "experts":
            ex = cfg.experts
            layer["experts"] = {
                "gate": dense(next(keys), (d, ex.published), d),
                **swiglu(ex.width, (ex.n_held,))}
            if ex.score == "sigmoid":
                layer["experts"]["bias"] = jnp.zeros((ex.published,),
                                                     jnp.float32)
            if ex.shared_width:
                layer["experts"]["shared"] = swiglu(ex.shared_width)
        elif kind == "moe":
            e = cfg.n_experts
            layer["moe"] = {
                "gate": dense(next(keys), (d, e), d),
                "w1": dense(next(keys), (e, d, f), d),
                "b1": jnp.zeros((e, f), dt),
                "w2": dense(next(keys), (e, f, d), f),
                "b2": jnp.zeros((e, d), dt),
            }
        elif cfg.mlp == "swiglu":
            layer["mlp"] = swiglu(f)
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
        "ln_f": gain(d),
        "layers": layers,
    }
    if cfg.learned_positions:
        out["pos"] = dense(next(keys), (cfg.max_len, d), 1) * 0.02
    if not cfg.tie_embeddings:
        out["head"] = dense(next(keys), (d, cfg.vocab_size), d)
    return out


def require_classic(cfg: TransformerConfig, who: str) -> None:
    """Raise `UnsupportedLayerKind` unless `cfg` is GPT-2's layer: the
    gate of every path that computes nothing else."""
    if not cfg.classic:
        raise UnsupportedLayerKind(
            f"{who} computes LayerNorm / learned positions / GELU / full "
            f"heads only; this configuration has norm={cfg.norm!r} "
            f"rope={cfg.rope is not None} mlp={cfg.mlp!r} "
            f"latent={cfg.latent is not None} "
            f"experts={cfg.experts is not None} "
            f"mixers={sorted(set(cfg.mixer_kinds()))} "
            f"kv_heads={cfg.kv_heads} positions={cfg.positions!r} (serve "
            f"it through the paged pool)")


def param_specs(cfg: TransformerConfig, model_axis: Optional[str]) -> dict:
    """PartitionSpec tree: tp dims sharded over the model axis, rest
    replicated. wq/wk/wv/wo shard the HEAD dim; mlp the HIDDEN dim; moe
    the EXPERT dim (expert parallelism rides the model axis)."""
    require_classic(cfg, "param_specs (the mesh placement)")
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


def deepseek_v2(layers: int = 60, experts_held: Tuple[int, int] = (0, 160),
                vocab: int = 102400, max_len: int = 163840,
                dtype: str = "bfloat16") -> TransformerConfig:
    """DeepSeek-V2 at its published widths (`deepseek_v2`,
    arXiv:2405.04434): d 5120, 128 heads of latent attention (ranks
    1536 / 512, 128 + 64 rotary a head, values 128), YaRN over 4096
    positions by 40, RMSNorm 1e-6, one dense SwiGLU layer of 12288 then
    expert layers of 160 routed experts of 1536 (6 a token, the best 3
    of 8 groups, softmax scores times 16, not renormalised) beside a
    shared expert of 2 x 1536, untied head.  What a chip of an
    expert-parallel deployment holds is given by the arguments: how
    many `layers`, which `experts_held`, its slice of the vocabulary and
    the context served.  Served through the paged pool only."""
    return TransformerConfig(
        vocab_size=vocab, d_model=5120, n_heads=128, n_layers=layers,
        d_ff=12288, max_len=max_len, dtype=dtype, norm="rms",
        norm_eps=1e-6, mlp="swiglu",
        rope=YarnRope(theta=10000.0, factor=40.0, original_max_len=4096,
                      beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                      mscale_all_dim=0.707),
        latent=LatentAttention(q_rank=1536, kv_rank=512, nope_dim=128,
                               rope_dim=64, v_dim=128),
        experts=RoutedExperts(published=160, held=tuple(experts_held),
                              per_token=6, width=1536, groups=8,
                              groups_kept=3, scale=16.0,
                              shared_width=2 * 1536),
        dense_layers=1)


def solar_open2(layers: int = 48, experts_held: Tuple[int, int] = (0, 320),
                vocab: int = 196608, max_len: int = 1048576,
                dtype: str = "bfloat16") -> TransformerConfig:
    """Solar-Open2-250B at its published widths (`solar_open2`): d 4096,
    no positions, RMSNorm 1e-5; layer i is grouped-query softmax attention
    (64 query heads over 8 K/V heads of 128, an elementwise sigmoid output
    gate) where i % 4 == 0 and a KDA layer (64 heads, state 128 x 128 a
    head, 4-tap convolutions, gates of rank 128, write strengths in
    (0, 2)) otherwise; every layer's feed-forward 320 routed experts of
    1280 (8 a token by sigmoid score + bias, renormalised, scale 1)
    beside one shared expert of 1280; untied head.  The arguments give
    one chip's share, as for `deepseek_v2`.  Served through the paged
    pool only."""
    return TransformerConfig(
        vocab_size=vocab, d_model=4096, n_heads=64, n_layers=layers,
        d_ff=10240, max_len=max_len, dtype=dtype, norm="rms",
        norm_eps=1e-5, mlp="swiglu", head_width=128, kv_heads=8,
        positions="none", attn_gate=True,
        mixers=tuple("full" if i % 4 == 0 else "kda"
                     for i in range(layers)),
        linear=LinearAttention(heads=64, k_dim=128, v_dim=128, conv_taps=4,
                               gate_rank=128, neg_eigval=True),
        experts=RoutedExperts(published=320, held=tuple(experts_held),
                              per_token=8, width=1280, score="sigmoid",
                              scale=1.0, renormalize=True,
                              shared_width=1280))


def sdar_30b_a3b(layers: int = 48, vocab: int = 151936,
                 max_len: int = 32768, block_length: int = 4,
                 dtype: str = "bfloat16") -> TransformerConfig:
    """SDAR-30B-A3B-Chat at its published widths (`sdar_moe`,
    arXiv:2510.06303): d 2048, 32 query heads over 4 K/V heads of 128
    with a head's RMSNorm of q and k, rotary theta 1e6 over the full
    128, RMSNorm 1e-6, every layer 128 routed experts of 768 (8 a token
    by softmax score, renormalised, no shared expert), untied head; a
    block-diffusion model: blocks of `block_length` positions, causal
    between and bidirectional inside, masked positions fed as id 151669.
    Every expert is held.  Served through the paged pool only."""
    return TransformerConfig(
        vocab_size=vocab, d_model=2048, n_heads=32, n_layers=layers,
        d_ff=6144, max_len=max_len, dtype=dtype, norm="rms",
        norm_eps=1e-6, mlp="swiglu", head_width=128, kv_heads=4,
        rope=YarnRope(theta=1e6), qk_norm=True,
        experts=RoutedExperts(published=128, held=(0, 128), per_token=8,
                              width=768, score="softmax",
                              renormalize=True),
        block_length=block_length, mask_token=151669)


def _layer_norm(p, x, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _rms_norm(p, x, eps):
    """x / rms(x) * gain, the statistics in float32."""
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return y.astype(x.dtype) * p["scale"]


def norm(cfg: TransformerConfig, p, x):
    """The configuration's normalisation of x's last axis."""
    if cfg.norm == "rms":
        return _rms_norm(p, x, cfg.norm_eps)
    return _layer_norm(p, x, cfg.norm_eps)


def embed_tokens(cfg: TransformerConfig, params: dict, tokens, positions):
    """tokens [B, S] at `positions` [B, S] (or [S]) -> [B, S, d]: learned
    positions are added here, rotary ones are applied inside attention."""
    x = params["embed"][tokens]
    if cfg.learned_positions:
        x = x + params["pos"][positions]
    return x


def yarn_inv_freq(rope: YarnRope, dim: int) -> np.ndarray:
    """The `dim // 2` inverse frequencies: each blended between the
    interpolated one (`/ factor`) and the unchanged one by a linear ramp
    over the dimensions that rotate `beta_fast` .. `beta_slow` times in
    `original_max_len` positions."""
    i = np.arange(0, dim, 2, dtype=np.float64) / dim
    extra = 1.0 / rope.theta ** i
    inter = extra / rope.factor

    def at(rotations):     # the dimension that turns so often
        return (dim * math.log(rope.original_max_len
                               / (rotations * 2 * math.pi))
                / (2 * math.log(rope.theta)))

    low = max(math.floor(at(rope.beta_fast)), 0)
    high = min(math.ceil(at(rope.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp       # 1 where the frequency stays as it is
    return (inter * (1.0 - keep) + extra * keep).astype(np.float32)


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_cos_sin(rope: YarnRope, dim: int, positions):
    """cos, sin `[..., dim // 2]` (float32) at integer `positions`."""
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(
        yarn_inv_freq(rope, dim))
    m = (_yarn_mscale(rope.factor, rope.mscale)
         / _yarn_mscale(rope.factor, rope.mscale_all_dim))
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def apply_rope(x, cos, sin):
    """Rotate the pairs `(x[i], x[i + dim/2])` of x's last axis."""
    half = x.shape[-1] // 2
    xf = x.astype(jnp.float32)
    a, b = xf[..., :half], xf[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def latent_softmax_scale(cfg: TransformerConfig) -> float:
    """`(nope + rope) ** -0.5`, times YaRN's `mscale_all_dim` factor
    squared where the rotary is scaled."""
    la, rope = cfg.latent, cfg.rope
    scale = (la.nope_dim + la.rope_dim) ** -0.5
    if rope is not None and rope.mscale_all_dim:
        scale *= _yarn_mscale(rope.factor, rope.mscale_all_dim) ** 2
    return scale


def latent_proj(cfg: TransformerConfig, p, x, positions):
    """x [B, S, d] at `positions` [B, S] -> (q_nope [B,S,H,nope],
    q_rope [B,S,H,rope] rotated, c_kv [B,S,kv_rank] after its norm,
    k_rope [B,S,rope] rotated, one for all heads).  `[c_kv | k_rope]`
    is the row the paged pool keeps; keys are rotated BEFORE they are
    kept, so a shipped or reused page stays valid."""
    la = cfg.latent
    c_q = _rms_norm(p["q_norm"], x @ p["wdq"], cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bshk", c_q, p["wuq"])
    down = x @ p["wdkv"]
    c_kv = _rms_norm(p["kv_norm"], down[..., :la.kv_rank], cfg.norm_eps)
    cos, sin = rope_cos_sin(cfg.rope, la.rope_dim, positions)
    q_rope = apply_rope(q[..., la.nope_dim:], cos[..., None, :],
                        sin[..., None, :])
    k_rope = apply_rope(down[..., la.kv_rank:], cos, sin)
    return q[..., :la.nope_dim], q_rope, c_kv, k_rope


def _latent_attn(cfg: TransformerConfig, p, x, causal: bool):
    """Whole-sequence latent attention, NOT absorbed: keys and values
    are up-projected from `c_kv` a head (the published form; the paged
    path's absorbed form is the same function)."""
    la = cfg.latent
    s = x.shape[1]
    with jax.named_scope("attn:latent"):
        q_nope, q_rope, c_kv, k_rope = latent_proj(
            cfg, p, x, jnp.broadcast_to(jnp.arange(s), x.shape[:2]))
        kv = jnp.einsum("bsr,rhk->bshk", c_kv, p["wukv"])
        k_nope, v = kv[..., :la.nope_dim], kv[..., la.nope_dim:]
        sc = (jnp.einsum("bshk,bthk->bhst", q_nope, k_nope)
              + jnp.einsum("bshk,btk->bhst", q_rope, k_rope)
              ).astype(jnp.float32) * latent_softmax_scale(cfg)
        if causal:
            sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc,
                           jnp.finfo(jnp.float32).min / 2)
        w = jax.nn.softmax(sc, axis=-1).astype(x.dtype)
        o = jnp.einsum("bhst,bthk->bshk", w, v)
        return jnp.einsum("bshk,hkd->bsd", o, p["wo"])


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


def seq_order(mesh: Optional[Mesh], axes: MeshAxes, seq_len: int,
              causal: bool = True) -> Optional[np.ndarray]:
    """The order a mesh's attention wants a row's columns in: order[c] is
    the position in the sequence that column c holds.  None is the natural
    order: one device, a `seq` axis of one chip, or a ring without a mask.
    A causal ring over more chips is dealt zigzag, so that each does the
    same work (`ring_attention.zigzag_order`)."""
    n = 1 if mesh is None else mesh.shape.get(axes.seq, 1)
    return zigzag_order(n, seq_len) if causal and n > 1 else None


def _attn(p, x, mesh: Optional[Mesh], axes: MeshAxes, causal: bool):
    """x:[B,S,d] full arrays. Ring attention under shard_map when a mesh is
    given (seq axis shards S, its columns in `seq_order`); plain attention
    otherwise."""
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


def attn_gated(p, h, o):
    """A full layer's output gate, where its parameters have one:
    o [B,S,H,K] * sigmoid(h W_gate), elementwise."""
    if "wgate" not in p:
        return o
    gate = jnp.einsum("bsd,dhk->bshk", h, p["wgate"])
    return o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(o.dtype)


def normed_rotated(cfg: TransformerConfig, p, q, k, positions):
    """q [B,S,H,K] and k [B,S,Hkv,K] of a grouped-query layer as its
    scores take them: each head RMS-normed by the layer's `q_norm` /
    `k_norm` gains where the configuration has them, then rotated over
    the full head at `positions` [B, S] where it has rotary positions
    (plain rotary is `YarnRope(factor=1)`; the half-split convention of
    `apply_rope`).  Keys are rotated BEFORE they are kept, as the latent
    rows are, so a reused or shipped page stays valid.  A configuration
    with neither gets q and k back untouched, in the same program."""
    if not (cfg.qk_norm or cfg.rope is not None):
        return q, k
    with jax.named_scope("attn:rope"):
        if cfg.qk_norm:
            q = _rms_norm(p["q_norm"], q, cfg.norm_eps)
            k = _rms_norm(p["k_norm"], k, cfg.norm_eps)
        if cfg.rope is not None:
            cos, sin = rope_cos_sin(cfg.rope, q.shape[-1], positions)
            cos, sin = cos[..., None, :], sin[..., None, :]
            q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
        return q, k


def _grouped_attn(p, x, causal: bool, cfg: TransformerConfig):
    """Whole-sequence softmax attention with fewer K/V heads than query
    heads and the output gate, one device, no kernel: the path the paged
    one is tested against.  `cfg` gives its q/k norms, rotary positions
    and block mask (position i sees j iff `j // B <= i // B`)."""
    with jax.named_scope("attn:gqa"):
        q, k, v = qkv_proj(p, x)
        b, s, h, kd = q.shape
        q, k = normed_rotated(
            cfg, p, q, k, jnp.broadcast_to(jnp.arange(s), (b, s)))
        g = h // k.shape[2]
        qg = q.reshape(b, s, h // g, g, kd)
        sc = jnp.einsum("bsngk,btnk->bngst", qg, k).astype(
            jnp.float32) * kd ** -0.5
        if causal and cfg.block_length > 1:
            blk = jnp.arange(s) // cfg.block_length
            sc = jnp.where(blk[None, :] <= blk[:, None], sc,
                           jnp.finfo(jnp.float32).min / 2)
        elif causal:
            sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc,
                           jnp.finfo(jnp.float32).min / 2)
        w = jax.nn.softmax(sc, axis=-1).astype(x.dtype)
        o = jnp.einsum("bngst,btnk->bsngk", w, v).reshape(b, s, h, kd)
        return out_proj(p, attn_gated(p, x, o))


def _mlp(p, x):
    h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, p["w1"]) + p["b1"])
    return jnp.einsum("bsf,fd->bsd", h, p["w2"]) + p["b2"]


def _swiglu(p, x):
    """(silu(x W_gate) * x W_up) W_down, no biases."""
    return (jax.nn.silu(x @ p["wg"]) * (x @ p["wu"])) @ p["wd"]


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
                  axes: MeshAxes = MeshAxes(), top_k: int = 1,
                  order: Optional[np.ndarray] = None):
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
    residual, the standard drop rule.  Tokens rank by their position in
    the sequence: where a row's columns are dealt (`order`, as
    `seq_order` gives it), the ranks are counted in the natural order, so
    that a mesh drops what one device drops.

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
    if order is not None:
        onehot = onehot.reshape(B, S, top_k, E)[:, np.argsort(order)]
        onehot = onehot.reshape(A, E)
    slot = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, axis=1) - 1
    if order is not None:
        slot = slot.reshape(B, S, top_k)[:, order].reshape(A)
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


def _grouped(xf, idx, weights, held, n_groups: int, experts):
    """Dropless grouped dispatch, the inference path of every expert
    layer.  xf [N, d] tokens; idx [N, k] the expert (0-based among the
    `n_groups` held here) of each (token, choice) pair; weights [N, k]
    its combine weight; held [N, k] whether the pair is computed here.
    The held pairs are sorted by expert and `experts(rows, sizes,
    expert_of_row)` runs the experts' matmuls over the sorted rows
    (`lax.ragged_dot`: each expert reads its own rows and nothing else,
    so the work is the pairs routed and the weights read are those of
    the experts hit).  Pairs that are not held sort past the last group
    and add nothing.  No capacity, no drops: a token's result is its own
    rows' and does not depend on what shares the batch.
    -> (y [N, d], sizes int32 [n_groups]: the held pairs of each expert)."""
    n, k = idx.shape
    key = jnp.where(held, idx, n_groups).reshape(-1)            # [A]
    order = jnp.argsort(key)                                    # stable
    sizes = jnp.zeros((n_groups + 1,), jnp.int32).at[key].add(1)[:n_groups]
    ys = experts(xf[order // k], sizes, jnp.minimum(key[order],
                                                    n_groups - 1))
    w = jnp.where(held, weights, 0.0).reshape(-1)[order]
    ys = jnp.where((key[order] < n_groups)[:, None],
                   ys.astype(jnp.float32) * w[:, None], 0.0)
    back = jnp.zeros_like(order).at[order].set(jnp.arange(n * k))
    return (jnp.sum(ys[back].reshape(n, k, -1), axis=1).astype(xf.dtype),
            sizes)


def _moe_dropless(p, x, top_k: int = 1):
    """`_moe_dense`'s function (Switch / GShard top-k, GELU experts with
    biases) computed by `_grouped`: equal to the oracle to rounding, at
    the cost of the pairs routed instead of every expert on every
    token."""
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    e = p["w1"].shape[0]
    idx, w = _router_weights(jax.nn.softmax(xf @ p["gate"], axis=-1), top_k)

    def experts(rows, sizes, which):
        hmid = jax.nn.gelu(lax.ragged_dot(rows, p["w1"], sizes)
                           + p["b1"][which])
        return lax.ragged_dot(hmid, p["w2"], sizes) + p["b2"][which]

    y, _ = _grouped(xf, idx, w, jnp.ones_like(idx, bool), e, experts)
    return y.reshape(b, s, d)


def group_limited_top_k(scores, ex: RoutedExperts, bias=None):
    """scores [N, E] -> (idx [N, k], weights [N, k]): a group's score is
    its largest expert score; only experts of the `groups_kept` best
    groups stand; the `per_token` largest of those, weighted by their
    own score times `scale` (renormalised first only where the
    configuration says so).  With `bias` [E] (a sigmoid router's
    correction) the CHOICE is by `scores + bias` and the weights are
    the scores alone."""
    n, e = scores.shape
    if bias is not None:
        choice = scores + bias
        if ex.groups > 1:
            raise UnsupportedLayerKind(
                "a bias-corrected choice over more than one routing group")
        _, idx = lax.top_k(choice, ex.per_token)
        w = jnp.take_along_axis(scores, idx, axis=-1)
        if ex.renormalize and ex.per_token > 1:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return idx, w * ex.scale
    if ex.groups > 1:
        per = e // ex.groups
        best = jnp.max(scores.reshape(n, ex.groups, per), axis=-1)
        _, kept = lax.top_k(best, ex.groups_kept)               # [N, kept]
        stands = jnp.any(kept[:, :, None] == jnp.arange(ex.groups), axis=1)
        scores = jnp.where(jnp.repeat(stands, per, axis=1), scores, 0.0)
    w, idx = lax.top_k(scores, ex.per_token)
    if ex.renormalize and ex.per_token > 1:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * ex.scale


def _routed_experts(ex: RoutedExperts, p, x, valid=None):
    """The `RoutedExperts` layer on x [B, S, d]: route every token over
    all `published` experts (router in float32), compute the pairs that
    fall on the experts held here, add the shared expert.  `valid`
    [B, S] marks the rows that carry a token (a wide round's padding
    routes nowhere).  -> (y [B, S, d], load int32 [3]: pairs held,
    pairs absent, the most pairs any held expert got)."""
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    lo, hi = ex.held
    with jax.named_scope("moe:route"):
        logits = jnp.dot(xf.astype(jnp.float32),
                         p["gate"].astype(jnp.float32),
                         precision=lax.Precision.HIGHEST)
        if ex.score == "sigmoid":
            idx, w = group_limited_top_k(jax.nn.sigmoid(logits), ex,
                                         p["bias"].astype(jnp.float32))
        else:
            idx, w = group_limited_top_k(jax.nn.softmax(logits, axis=-1),
                                         ex)
        here = (idx >= lo) & (idx < hi)
        real = (jnp.ones_like(here) if valid is None
                else jnp.broadcast_to(valid.reshape(-1, 1), here.shape))
        held = here & real

    def experts(rows, sizes, which):
        hmid = (jax.nn.silu(lax.ragged_dot(rows, p["wg"], sizes))
                * lax.ragged_dot(rows, p["wu"], sizes))
        return lax.ragged_dot(hmid, p["wd"], sizes)

    with jax.named_scope("moe:experts"):
        y, sizes = _grouped(xf, idx - lo, w, held, ex.n_held, experts)
    if "shared" in p:
        with jax.named_scope("moe:shared"):
            y = y + _swiglu(p["shared"], xf)
    load = jnp.stack([jnp.sum(sizes), jnp.sum(real & ~here),
                      jnp.max(sizes)]).astype(jnp.int32)
    return y.reshape(b, s, d), load


def _moe(p, x, capacity_factor: float = 0.0,
         mesh: Optional[Mesh] = None, axes: MeshAxes = MeshAxes(),
         top_k: int = 1, order: Optional[np.ndarray] = None):
    """The Switch / GShard expert block.  Three paths compute an expert
    layer in this module, and which one runs is decided here and in
    `feed_forward`:

    - `_moe_dispatch`, capacity dispatch: training (`lm_loss`,
      `apply(train=True)`) and on a mesh.  Static `[E, C, d]` buffers,
      pairs over an expert's capacity are dropped.
    - `_grouped` (through `_moe_dropless` for these GELU experts,
      through `_routed_experts` for a `RoutedExperts` layer), dropless
      grouped matmuls: inference on one device, the whole-sequence
      `apply` and the cached decode paths alike, so that they agree.
    - `_moe_dense`, every expert on every token: the ORACLE the two are
      tested against; nothing serves through it."""
    if capacity_factor > 0:
        return _moe_dispatch(p, x, capacity_factor, mesh, axes, top_k,
                             order)
    if mesh is None:
        return _moe_dropless(p, x, top_k)
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


def feed_forward(cfg: TransformerConfig, layer: dict, h, valid=None,
                 loads: Optional[list] = None):
    """A layer's feed-forward half on the normed h [B, S, d], by the
    kind its parameters have, at inference on one device (`apply` hands
    `block` its own where it trains or runs on a mesh).  A
    `RoutedExperts` layer appends its load counts to `loads`."""
    if "experts" in layer:
        y, load = _routed_experts(cfg.experts, layer["experts"], h, valid)
        if loads is not None:
            loads.append(load)
        return y
    if "moe" in layer:
        return _moe(layer["moe"], h, top_k=cfg.moe_top_k)
    if cfg.mlp == "swiglu":
        return _swiglu(layer["mlp"], h)
    return _mlp(layer["mlp"], h)


def block(cfg: TransformerConfig, layer: dict, x, attend: Callable,
          ffn: Optional[Callable] = None, between: Callable = lambda a: a):
    """ONE pre-norm layer for every family and every path: the caller
    brings `attend(p_attn, normed x) -> [B, S, d]` (whole-sequence,
    ring, cached or paged attention, which may carry a cache in its
    closure) and, where `feed_forward`'s choice is not its own, `ffn`."""
    x = between(x + attend(layer["attn"], norm(cfg, layer["ln1"], x)))
    h = norm(cfg, layer["ln2"], x)
    return between(x + (ffn(layer, h) if ffn is not None
                        else feed_forward(cfg, layer, h)))


def apply(cfg: TransformerConfig, params: dict, tokens: jax.Array,
          mesh: Optional[Mesh] = None, axes: MeshAxes = MeshAxes(),
          causal: bool = True, train: bool = False,
          return_aux: bool = False):
    """tokens:[B,S] int32 -> logits [B,S,V]. Pass mesh to parallelize.

    Tokens and logits are in the natural order whatever the mesh: where
    its attention wants the columns dealt (`seq_order`) they are dealt
    here and the logits put back, which moves [B,S,V] between the chips.
    A training step deals its batch itself and never undoes it
    (`lm_loss`).

    MoE routing: `train=True` (the lm_loss path) uses capacity-based
    dispatch — FLOP-saving but drops overflow tokens, so logits can
    depend on batch composition.  At inference on one device the
    dropless grouped dispatch runs (see `_moe`), per token exact and the
    same function the cached decode paths compute.
    `return_aux=True` additionally returns the mean-over-layers Switch
    load-balancing loss (0 for dense configs).  Latent attention and
    `RoutedExperts` run here whole-sequence on one device, at inference
    (the path the tests hold the paged one against); they do not train
    and do not shard."""
    order = seq_order(mesh, axes, tokens.shape[1], causal)
    if order is None:
        return _apply_dealt(cfg, params, tokens, None, mesh, axes, causal,
                            train, return_aux)
    logits, aux = _apply_dealt(cfg, params, tokens[:, order], order, mesh,
                               axes, causal, train, True)
    logits = logits[:, np.argsort(order)]
    return (logits, aux) if return_aux else logits


def _apply_dealt(cfg: TransformerConfig, params: dict, tokens, order, mesh,
                 axes, causal: bool, train: bool, return_aux: bool):
    """`apply` on a row whose column c holds position `order[c]` (None:
    the natural order); the logits stay in the columns' order."""
    if not cfg.classic and (mesh is not None or train):
        require_classic(cfg, "apply(train=True) / apply(mesh=...)")

    def constrain(a):
        if mesh is None:
            return a
        return lax.with_sharding_constraint(
            a, NamedSharding(mesh, P(axes.data, axes.seq, None)))

    cf = cfg.moe_capacity_factor if train else 0.0

    def attend(p, h):
        if cfg.latent is not None:
            return _latent_attn(cfg, p, h, causal)
        if cfg.grouped:
            return _grouped_attn(p, h, causal, cfg)
        return _attn(p, h, mesh, axes, causal)

    def recur(p, h):
        from deeplearning4j_tpu.parallel import kda

        return kda.whole_sequence(cfg, p, h)

    def layer_of(mixer):
        def one(layer, x):
            aux = [jnp.zeros((), x.dtype)]

            def ffn(layer, h):
                if "moe" not in layer:
                    return feed_forward(cfg, layer, h)
                aux[0] = _moe_aux_loss(layer["moe"], h)
                return _moe(layer["moe"], h, cf, mesh, axes, cfg.moe_top_k,
                            order)

            return block(cfg, layer, x, mixer, ffn, constrain), aux[0]

        if not cfg.remat:
            return one
        from deeplearning4j_tpu.parallel import kernels

        # a mixer that names nothing (no kernel, KDA, latent) saves nothing
        return jax.checkpoint(
            one, policy=jax.checkpoint_policies.save_only_these_names(
                *kernels.SAVED_NAMES))

    # a layer's mixer is its kind's (`mixer_kinds`), one function a kind
    ones = {"full": layer_of(attend), "kda": layer_of(recur)}
    x = params["embed"][tokens]
    if cfg.learned_positions:
        pos = params["pos"]
        x = x + (pos[:tokens.shape[1]] if order is None else pos[order])
    x = constrain(x)
    auxs = []
    kinds = cfg.mixer_kinds()
    for i, layer in enumerate(params["layers"]):
        x, aux = ones[kinds[i]](layer, x)
        auxs.append(aux)
    x = norm(cfg, params["ln_f"], x)
    logits = jnp.einsum("bsd,dv->bsv", x, lm_head(params))
    if return_aux:
        return logits, jnp.mean(jnp.stack(auxs))
    return logits


def lm_loss(cfg: TransformerConfig, params: dict, tokens: jax.Array,
            targets: jax.Array, mesh: Optional[Mesh] = None,
            axes: MeshAxes = MeshAxes(), dealt: bool = False) -> jax.Array:
    """Mean next-token cross-entropy over the full batch (training mode:
    MoE layers route with capacity-based dispatch + the Switch
    load-balancing auxiliary loss weighted by cfg.moe_aux_weight).

    The loss is a mean over positions, so where the mesh wants a row's
    columns dealt (`seq_order`) tokens and targets are dealt alike and
    nothing is put back.  `dealt`: the caller has done so already (the
    trainer, on the host, where it places the batch)."""
    use_aux = bool(cfg.n_experts) and cfg.moe_aux_weight > 0
    order = seq_order(mesh, axes, tokens.shape[1])
    if order is not None and not dealt:
        tokens, targets = tokens[:, order], targets[:, order]
    out = _apply_dealt(cfg, params, tokens, order, mesh, axes, True, True,
                       use_aux)
    logits, aux = out if use_aux else (out, None)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    loss = jnp.mean(nll)
    if use_aux:
        loss = loss + cfg.moe_aux_weight * aux.astype(loss.dtype)
    return loss
