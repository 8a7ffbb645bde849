"""SDAR-30B-A3B-Chat (`config.json` of JetLM/SDAR-30B-A3B-Chat, `model_type`
`sdar_moe`; the family's paper is arXiv:2510.06303): the public keys turned
into the program's `TransformerConfig`, and seeded weights in the layout
`parallel/transformer.py` reads.

An autoregressive mixture-of-experts block (the Qwen3-MoE block: grouped-query
heads with a head's RMSNorm of q and k, rotary positions, every layer 128
routed SwiGLU experts, 8 a token by softmax score, renormalised, no shared
expert) turned into a BLOCK-DIFFUSION model: attention is causal between
blocks of `block_length` positions and bidirectional inside one, and a block
is generated by unmasking it over a few forward passes, a masked position fed
as `mask_token_id`.  What the config does not say, and the file lists under
`assumed`: the q/k norms, the rotary convention, the block length, the mask
id, that the logits are not shifted.

Every expert and the whole vocabulary are held: the file's cut is in depth
alone (`num_hidden_layers` layers, each whole), the other layers lying on
further chips as a pipeline's stages.

The weights are the benchmark's, made from the seed on the device (one jitted
call a layer), handed to the program and to the plain reference
(`benchmark/reference/sdar.py`) alike.  Scales, chosen so that no mechanism is
idle under random weights:

- every norm gain is random, 1 +- 0.1 (the q/k norms' too); projections have
  unit-variance outputs (`fan_in ** -0.5`): after its norm a head's q and k
  have unit mean square, so a score `q . k / sqrt(128)` has deviation about 1;
- the router's columns are `d ** -0.5`: logits of deviation 1 over 128
  experts, the eight best renormalised to about 0.06-0.3 each;
- a routed expert's down-projection is `0.2 * width ** -0.5`, as in the other
  two expert configurations and for their reason: routing is a discrete
  choice, under bfloat16 the eighth and ninth experts of a token change
  places in a few tokens of a hundred, in the program and in any other
  bfloat16 implementation, and the run's largest served-logit gap is then one
  such flip's size; at 0.2 a flip is of the size of the rest of the rounding
  (PR 26 and PR 38 found it so);
- the embedding has unit variance (it is not scaled) and the untied head's
  logits deviation about 1.
"""

from __future__ import annotations

import functools

from benchmark.adapters.gpt2 import seed_key

EXPERT_OUT_SCALE = 0.2


def program_config(model: dict, dtype: str, remat: bool):
    """The program's configuration for the public keys in `model`."""
    from deeplearning4j_tpu.parallel import transformer as tfm

    if not hasattr(tfm, "sdar_30b_a3b"):
        raise SystemExit(
            "benchmark: this program has no block mask, no rotary "
            "grouped-query heads and no q/k norms (parallel/transformer.py "
            "has no sdar_30b_a3b): it cannot run an sdar_moe configuration")
    if (model["model_type"] != "sdar_moe" or model["attention_bias"]
            or model["hidden_act"] != "silu" or model["mlp_only_layers"]
            or model["decoder_sparse_step"] != 1 or model["rope_scaling"]
            or model["use_sliding_window"]):
        raise ValueError("not the sdar_moe layer this adapter reads")
    n = model["num_hidden_layers"]
    cfg = tfm.TransformerConfig(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_heads=model["num_attention_heads"], n_layers=n,
        d_ff=model["intermediate_size"],
        max_len=model["max_position_embeddings"], dtype=dtype, remat=remat,
        tie_embeddings=model["tie_word_embeddings"], norm="rms",
        norm_eps=model["rms_norm_eps"], mlp="swiglu",
        head_width=model["head_dim"], kv_heads=model["num_key_value_heads"],
        rope=tfm.YarnRope(theta=float(model["rope_theta"])),
        qk_norm=bool(model["qk_norm"]),
        experts=tfm.RoutedExperts(
            published=model["num_experts"], held=(0, model["num_experts"]),
            per_token=model["num_experts_per_tok"],
            width=model["moe_intermediate_size"], score="softmax",
            renormalize=model["norm_topk_prob"]),
        block_length=model["block_length"],
        mask_token=model["mask_token_id"])
    if model.get("program_preset"):
        # the program's own constructor must give the same sizes
        want = getattr(tfm, model["program_preset"])(
            layers=n, vocab=cfg.vocab_size, max_len=cfg.max_len,
            block_length=cfg.block_length, dtype=dtype)
        if want != cfg:
            raise ValueError(f"{model['program_preset']}() is {want}, the "
                             f"file gives {cfg}")
    return cfg


@functools.lru_cache(maxsize=8)
def _layer_maker(cfg):
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(cfg.dtype)
    d, h, hkv, kd, ex = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                         cfg.head_dim, cfg.experts)

    def make(key):
        keys = iter(jax.random.split(key, 16))

        def rnd(shape, scale, shift=0.0):
            x = jax.random.normal(next(keys), shape, jnp.float32)
            return (x * scale + shift).astype(dt)

        def gain(n):
            return {"scale": rnd((n,), 0.1, 1.0)}

        lead = (ex.n_held,)
        return {
            "ln1": gain(d), "ln2": gain(d),
            "attn": {"wq": rnd((d, h, kd), d ** -0.5),
                     "wk": rnd((d, hkv, kd), d ** -0.5),
                     "wv": rnd((d, hkv, kd), d ** -0.5),
                     "wo": rnd((h, kd, d), (h * kd) ** -0.5),
                     "q_norm": gain(kd), "k_norm": gain(kd)},
            "experts": {
                "gate": rnd((d, ex.published), d ** -0.5),
                "wg": rnd(lead + (d, ex.width), d ** -0.5),
                "wu": rnd(lead + (d, ex.width), d ** -0.5),
                "wd": rnd(lead + (ex.width, d),
                          EXPERT_OUT_SCALE * ex.width ** -0.5)}}

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

    ex = cfg.experts
    if (ex is None or cfg.tie_embeddings or not cfg.qk_norm
            or cfg.rope is None or ex.score != "softmax"
            or ex.shared_width or ex.n_held != ex.published):
        raise ValueError("SDAR has rotary grouped-query heads with q/k "
                         "norms, a softmax router over experts all held, no "
                         "shared expert and an untied head")
    key = seed_key(seed)
    out = _ends_maker(cfg)(jax.random.fold_in(key, 0))
    out["layers"] = [_layer_maker(cfg)(jax.random.fold_in(key, i + 1))
                     for i in range(cfg.n_layers)]
    return out
