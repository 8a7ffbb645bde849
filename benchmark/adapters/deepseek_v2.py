"""DeepSeek-V2 (`config.json` of deepseek-ai/DeepSeek-V2, `model_type`
`deepseek_v2`; arXiv:2405.04434): the public keys turned into the program's
`TransformerConfig`, and seeded weights in the layout
`parallel/transformer.py` reads.

What is held here is one chip's share of an expert-parallel deployment: the
file's `experts_held` of the `n_routed_experts_published` routed experts, its
slice of the vocabulary, `num_hidden_layers` layers.  The router keeps its
published width.

The weights are the benchmark's, made here from the seed on the device (one
jitted call a layer: a layer's experts are 1.9 GB), handed to the program
and to the plain reference (`benchmark/reference/deepseek_v2.py`) alike.
Scales, chosen so that no mechanism is idle under random weights:

- every norm gain is random, 1 +- 0.1 (a path that drops one is seen);
- projections have unit-variance outputs (`fan_in ** -0.5`), so a score
  before the softmax has a deviation of about `m^2` = 1.6: attention is
  neither uniform nor one-hot over 8k keys;
- the router's columns are `d ** -0.5`: logits of deviation 1 over 160
  experts put the best expert's score near 0.05 and the sixth near 0.02, so
  the group-limited rule and the top 6 both decide something (neither
  uniform nor one-hot), and the combine weights `16 * s_i` are 0.3 to 0.8;
- a routed expert's down-projection is `0.05 * width ** -0.5`.  Routing is
  a discrete choice: under bfloat16 the sixth and seventh experts of a
  token change places in a few tokens of a hundred, in the program and in
  any other bfloat16 implementation, and the largest served-logit gap of a
  run is then one such flip's size.  At full-size expert outputs a flip
  moves a logit by 2 to 4 and the float8 control cannot be told from the
  sound program by the run's largest gap (read on the chip, PR 26: 3.6-4.0
  against 4.1-5.3 over 2,048 positions); at 0.15 the cell read 0.019-0.184
  against the control's 0.230-0.331, too close; at 0.05 a flip is under the
  rest of bfloat16's rounding (0.012-0.049 over 13 runs against 0.268 and
  0.375).  The routed experts still carry weight: leaving them out moves
  logits by several flips' worth;
- the shared expert, the dense layer and attention are of the residual's
  own size, and the untied head's logits have deviation about 1: O(1) under
  the factor 16.
"""

from __future__ import annotations

import functools

from benchmark.adapters.gpt2 import seed_key

ROUTER_SCALE = 1.0
EXPERT_OUT_SCALE = 0.05


def program_config(model: dict, dtype: str, remat: bool):
    """The program's configuration for the public keys in `model`."""
    from deeplearning4j_tpu.parallel import transformer as tfm

    if not hasattr(tfm, "RoutedExperts"):
        raise SystemExit(
            "benchmark: this program has no latent attention and no routed "
            "expert layer (parallel/transformer.py has no RoutedExperts): "
            "it cannot run a deepseek_v2 configuration")
    if (model["model_type"] != "deepseek_v2" or model["hidden_act"] != "silu"
            or model["topk_method"] != "group_limited_greedy"
            or model["rope_scaling"]["type"] != "yarn"
            or model["moe_layer_freq"] != 1 or model["attention_bias"]
            or model["num_key_value_heads"] != model["num_attention_heads"]):
        raise ValueError("not the deepseek_v2 layer this adapter reads")
    lo, hi = model["experts_held"]
    if hi - lo != model["n_routed_experts"]:
        raise ValueError("n_routed_experts counts the experts held here")
    rs = model["rope_scaling"]
    cfg = tfm.TransformerConfig(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_heads=model["num_attention_heads"],
        n_layers=model["num_hidden_layers"], d_ff=model["intermediate_size"],
        max_len=model["max_position_embeddings"], dtype=dtype, remat=remat,
        tie_embeddings=model["tie_word_embeddings"], norm="rms",
        norm_eps=model["rms_norm_eps"], mlp="swiglu",
        rope=tfm.YarnRope(
            theta=float(model["rope_theta"]), factor=float(rs["factor"]),
            original_max_len=rs["original_max_position_embeddings"],
            beta_fast=float(rs["beta_fast"]),
            beta_slow=float(rs["beta_slow"]), mscale=rs["mscale"],
            mscale_all_dim=rs["mscale_all_dim"]),
        latent=tfm.LatentAttention(
            q_rank=model["q_lora_rank"], kv_rank=model["kv_lora_rank"],
            nope_dim=model["qk_nope_head_dim"],
            rope_dim=model["qk_rope_head_dim"], v_dim=model["v_head_dim"]),
        experts=tfm.RoutedExperts(
            published=model["n_routed_experts_published"], held=(lo, hi),
            per_token=model["num_experts_per_tok"],
            width=model["moe_intermediate_size"], groups=model["n_group"],
            groups_kept=model["topk_group"], score=model["scoring_func"],
            scale=float(model["routed_scaling_factor"]),
            renormalize=model["norm_topk_prob"],
            shared_width=(model["n_shared_experts"]
                          * model["moe_intermediate_size"])),
        dense_layers=model["first_k_dense_replace"])
    if model.get("program_preset"):
        # the program's own constructor must give the same sizes
        want = getattr(tfm, model["program_preset"])(
            layers=cfg.n_layers, experts_held=(lo, hi), vocab=cfg.vocab_size,
            max_len=cfg.max_len, dtype=dtype)
        if want != cfg:
            raise ValueError(f"{model['program_preset']}() is {want}, the "
                             f"file gives {cfg}")
    return cfg


@functools.lru_cache(maxsize=8)
def _layer_maker(cfg, kind: str):
    """One jitted maker of a layer of `kind` ("dense" | "experts")."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(cfg.dtype)
    d, h, la, ex = cfg.d_model, cfg.n_heads, cfg.latent, cfg.experts

    def make(key):
        keys = iter(jax.random.split(key, 24))

        def rnd(shape, scale, shift=0.0):
            x = jax.random.normal(next(keys), shape, jnp.float32)
            return (x * scale + shift).astype(dt)

        def gain(n):
            return {"scale": rnd((n,), 0.1, 1.0)}

        def swiglu(width, lead=(), out=1.0):
            return {"wg": rnd(lead + (d, width), d ** -0.5),
                    "wu": rnd(lead + (d, width), d ** -0.5),
                    "wd": rnd(lead + (width, d), out * width ** -0.5)}

        layer = {
            "ln1": gain(d), "ln2": gain(d),
            "attn": {
                "wdq": rnd((d, la.q_rank), d ** -0.5),
                "q_norm": gain(la.q_rank),
                "wuq": rnd((la.q_rank, h, la.nope_dim + la.rope_dim),
                           la.q_rank ** -0.5),
                "wdkv": rnd((d, la.kv_rank + la.rope_dim), d ** -0.5),
                "kv_norm": gain(la.kv_rank),
                "wukv": rnd((la.kv_rank, h, la.nope_dim + la.v_dim),
                            la.kv_rank ** -0.5),
                "wo": rnd((h, la.v_dim, d), (h * la.v_dim) ** -0.5)}}
        if kind == "experts":
            layer["experts"] = {
                "gate": rnd((d, ex.published), ROUTER_SCALE * d ** -0.5),
                **swiglu(ex.width, (ex.n_held,), EXPERT_OUT_SCALE),
                "shared": swiglu(ex.shared_width)}
        else:
            layer["mlp"] = swiglu(cfg.d_ff)
        return layer

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

    if (cfg.latent is None or cfg.experts is None or cfg.tie_embeddings
            or not cfg.experts.shared_width):
        raise ValueError("DeepSeek-V2 has latent attention, routed and "
                         "shared experts and an untied head")
    key = seed_key(seed)
    out = _ends_maker(cfg)(jax.random.fold_in(key, 0))
    out["layers"] = [
        _layer_maker(cfg, kind)(jax.random.fold_in(key, i + 1))
        for i, kind in enumerate(cfg.layer_kinds())]
    return out
