"""Solar-Open2-250B (`config.json` of upstage/Solar-Open2-250B, `model_type`
`solar_open2`): the public keys turned into the program's
`TransformerConfig`, and seeded weights in the layout
`parallel/transformer.py` reads.

Layer i is grouped-query softmax attention where `i` is in `gqa_layers`
(0, 4, ..., 44) and a KDA layer (Kimi Delta Attention, arXiv:2510.26692)
otherwise; every layer's feed-forward is the expert layer.  What the config
does not say, and the file lists under `assumed`: the gates' rank (128), the
form of `use_gqa_gate` (an elementwise sigmoid gate from a projection of its
own), sigmoid router scores with a choice-only bias and one routing group,
the shared expert's width (`n_shared_experts` x 1280).

What is held here is one chip's share of an expert-parallel deployment, as
for `adapters/deepseek_v2.py`: the file's `experts_held` of the
`n_routed_experts_published` routed experts, its slice of the vocabulary,
`num_hidden_layers` layers.  The router keeps its published width.

The weights are the benchmark's, made from the seed on the device (one jitted
call a layer), handed to the program and to the plain reference
(`benchmark/reference/solar_open2.py`) alike.  Scales, chosen so that no
mechanism is idle under random weights:

- every norm gain is random, 1 +- 0.1; projections have unit-variance
  outputs (`fan_in ** -0.5`): a softmax score has deviation about 1 over up
  to 24k keys, a gate's sigmoid spans (0.1, 0.9);
- the convolutions' four taps are `0.5 * N(0, 1)` (unit variance out);
- KDA's decay `alpha = exp(-exp(A_log) * softplus(f + dt_bias))`:
  `exp(A_log)` uniform in (0.5, 2) a head, `softplus(dt_bias)` log-uniform
  in (0.002, 0.05) a channel, the low-rank `f` of deviation 0.5, so that
  decays span about 0.85 to 0.9995 a token, as a trained layer's do: some
  channels forget within tens of tokens, some carry a 16k document;
- write strengths `2 * sigmoid(N(0, 1))` span (0, 2): the negative
  eigenvalues are exercised;
- the router's columns are `d ** -0.5` and its choice-only bias
  `0.1 * N(0, 1)`: sigmoid scores of 0.1 to 0.9, the eight best near 0.9,
  renormalised to about 1/8 each;
- a routed expert's down-projection is `0.2 * width ** -0.5`.  Routing is a
  discrete choice: under bfloat16 the eighth and ninth experts of a token
  change places in a few tokens of a hundred, in the program and in any
  other bfloat16 implementation, and the run's largest served-logit gap is
  then one such flip's size (PR 26 found the same for DeepSeek-V2 and took
  `0.05` under combine weights of 0.3 to 0.8; here a weight is 1/8, so
  0.2 gives a flip the same size, 0.025 of an expert's unit output);
- the shared expert and the mixers are of the residual's own size, and the
  untied head's logits have deviation about 1.
"""

from __future__ import annotations

import functools
import math

from benchmark.adapters.gpt2 import seed_key

EXPERT_OUT_SCALE = 0.2
ROUTER_BIAS_SCALE = 0.1
GATE_RANK_ASSUMED = "head_dim"


def program_config(model: dict, dtype: str, remat: bool):
    """The program's configuration for the public keys in `model`."""
    from deeplearning4j_tpu.parallel import transformer as tfm

    if not hasattr(tfm, "LinearAttention"):
        raise SystemExit(
            "benchmark: this program has no recurrent (KDA) layer kind and "
            "no grouped-query pool (parallel/transformer.py has no "
            "LinearAttention): it cannot run a solar_open2 configuration")
    lin = model["linear_attn_config"]
    if (model["model_type"] != "solar_open2" or model["use_rope"]
            or model["kda_use_full_proj"] or model["first_k_dense_replace"]
            or lin["num_kv_heads"] is not None):
        raise ValueError("not the solar_open2 layer this adapter reads")
    lo, hi = model["experts_held"]
    if hi - lo != model["n_routed_experts"]:
        raise ValueError("n_routed_experts counts the experts held here")
    n = model["num_hidden_layers"]
    cfg = tfm.TransformerConfig(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_heads=model["num_attention_heads"], n_layers=n,
        d_ff=model["intermediate_size"],
        max_len=model["max_position_embeddings"], dtype=dtype, remat=remat,
        tie_embeddings=model["tie_word_embeddings"], norm="rms",
        norm_eps=model["rms_norm_eps"], mlp="swiglu",
        head_width=model["head_dim"], kv_heads=model["num_key_value_heads"],
        positions="none", attn_gate=model["use_gqa_gate"],
        mixers=tuple("full" if i in model["gqa_layers"] else "kda"
                     for i in range(n)),
        linear=tfm.LinearAttention(
            heads=lin["num_heads"], k_dim=lin["head_dim"],
            v_dim=lin["head_dim"], conv_taps=lin["short_conv_kernel_size"],
            gate_rank=model[GATE_RANK_ASSUMED],
            neg_eigval=model["kda_allow_neg_eigval"]),
        experts=tfm.RoutedExperts(
            published=model["n_routed_experts_published"], held=(lo, hi),
            per_token=model["num_experts_per_tok"],
            width=model["moe_intermediate_size"], score="sigmoid",
            scale=float(model["routed_scaling_factor"]),
            renormalize=model["norm_topk_prob"],
            shared_width=(model["n_shared_experts"]
                          * model["moe_intermediate_size"])))
    if model.get("program_preset"):
        # the program's own constructor must give the same sizes
        want = getattr(tfm, model["program_preset"])(
            layers=n, experts_held=(lo, hi), vocab=cfg.vocab_size,
            max_len=cfg.max_len, dtype=dtype)
        if want != cfg:
            raise ValueError(f"{model['program_preset']}() is {want}, the "
                             f"file gives {cfg}")
    return cfg


@functools.lru_cache(maxsize=8)
def _layer_maker(cfg, mixer: str):
    """One jitted maker of a layer whose mixer is `mixer` ("full" | "kda")."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(cfg.dtype)
    d, h, kd, ex, la = (cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.experts,
                        cfg.linear)

    def make(key):
        keys = iter(jax.random.split(key, 32))

        def rnd(shape, scale, shift=0.0, to=dt):
            x = jax.random.normal(next(keys), shape, jnp.float32)
            return (x * scale + shift).astype(to)

        def log_uniform(shape, lo, hi):
            u = jax.random.uniform(next(keys), shape, jnp.float32)
            return jnp.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))

        def gain(n):
            return {"scale": rnd((n,), 0.1, 1.0)}

        def swiglu(width, lead=(), out=1.0):
            return {"wg": rnd(lead + (d, width), d ** -0.5),
                    "wu": rnd(lead + (d, width), d ** -0.5),
                    "wd": rnd(lead + (width, d), out * width ** -0.5)}

        if mixer == "kda":
            hk, hv, r = (la.heads, la.k_dim), (la.heads, la.v_dim), la.gate_rank
            taps = la.conv_taps
            step = log_uniform(hk, 0.002, 0.05)     # softplus(dt_bias)
            attn = {
                "wq": rnd((d,) + hk, d ** -0.5),
                "wk": rnd((d,) + hk, d ** -0.5),
                "wv": rnd((d,) + hv, d ** -0.5),
                "conv_q": rnd((taps,) + hk, taps ** -0.5),
                "conv_k": rnd((taps,) + hk, taps ** -0.5),
                "conv_v": rnd((taps,) + hv, taps ** -0.5),
                "wf_down": rnd((d, r), d ** -0.5),
                "wf_up": rnd((r,) + hk, 0.5 * r ** -0.5),
                "a_log": jnp.log(log_uniform((la.heads,), 0.5, 2.0)
                                 ).astype(dt),
                "dt_bias": jnp.log(jnp.expm1(step)).astype(dt),
                "wb": rnd((d, la.heads), d ** -0.5),
                "wg_down": rnd((d, r), d ** -0.5),
                "wg_up": rnd((r,) + hv, r ** -0.5),
                "o_norm": gain(la.v_dim),
                "wo": rnd(hv + (d,), (la.heads * la.v_dim) ** -0.5)}
        else:
            hkv = cfg.n_kv_heads
            attn = {"wq": rnd((d, h, kd), d ** -0.5),
                    "wk": rnd((d, hkv, kd), d ** -0.5),
                    "wv": rnd((d, hkv, kd), d ** -0.5),
                    "wgate": rnd((d, h, kd), d ** -0.5),
                    "wo": rnd((h, kd, d), (h * kd) ** -0.5)}
        return {
            "ln1": gain(d), "ln2": gain(d), "attn": attn,
            "experts": {
                "gate": rnd((d, ex.published), d ** -0.5),
                "bias": rnd((ex.published,), ROUTER_BIAS_SCALE,
                            to=jnp.float32),
                **swiglu(ex.width, (ex.n_held,), EXPERT_OUT_SCALE),
                "shared": swiglu(ex.shared_width)}}

    return jax.jit(make)


@functools.lru_cache(maxsize=8)
def _ends_maker(cfg):
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(cfg.dtype)
    d, v = cfg.d_model, cfg.vocab_size

    def make(key):
        k1, k2, k3 = jax.random.split(key, 3)
        f32 = jnp.float32
        return {
            "embed": jax.random.normal(k1, (v, d), f32).astype(dt),
            "ln_f": {"scale": (jax.random.normal(k2, (d,), f32) * 0.1
                               + 1.0).astype(dt)},
            "head": (jax.random.normal(k3, (d, v), f32)
                     * d ** -0.5).astype(dt)}

    return jax.jit(make)


def make_params(cfg, seed: int, dtype: str):
    """Weights for the program's `cfg` from `seed`, of `dtype`."""
    import jax

    if (cfg.linear is None or cfg.experts is None or cfg.tie_embeddings
            or not cfg.attn_gate or cfg.experts.score != "sigmoid"):
        raise ValueError("Solar-Open2 has KDA and gated grouped-query "
                         "layers, a sigmoid router and an untied head")
    key = seed_key(seed)
    out = _ends_maker(cfg)(jax.random.fold_in(key, 0))
    out["layers"] = [
        _layer_maker(cfg, mixer)(jax.random.fold_in(key, i + 1))
        for i, mixer in enumerate(cfg.mixer_kinds())]
    return out
