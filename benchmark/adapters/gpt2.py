"""GPT-2 (Radford et al. 2019; `config.json` of openai-community/gpt2*):
the public keys turned into the program's `TransformerConfig`, and seeded
weights in the layout `parallel/transformer.py` reads.

The weights are the benchmark's, made here from the seed in one jitted call
on the device: the program is handed them, and so is the plain reference
(`benchmark/reference/gpt2.py`), which takes nothing the program has made.
Unlike `transformer.init_params` every bias and layer-norm gain is random
too, so that a path that drops one is seen.
"""

from __future__ import annotations

import dataclasses
import functools


def program_config(model: dict, dtype: str, remat: bool):
    """The program's configuration for the public keys in `model`."""
    from deeplearning4j_tpu.parallel import transformer as tfm

    if model["activation_function"] != "gelu_new":
        raise ValueError("the program's MLP is tanh-GELU (gelu_new) only")
    cfg = tfm.TransformerConfig(
        vocab_size=model["vocab_size"], d_model=model["n_embd"],
        n_heads=model["n_head"], n_layers=model["n_layer"],
        d_ff=model["n_inner"] or 4 * model["n_embd"],
        max_len=model["n_positions"], dtype=dtype, attn_bias=True,
        tie_embeddings=model["tie_word_embeddings"], remat=remat)
    preset = model.get("program_preset")
    if preset:
        # the program's own constructor must give the same sizes: the cell
        # runs what `dl4j lm -preset` and `serve -lm` users run
        want = getattr(tfm, preset)(max_len=cfg.max_len, dtype=dtype)
        if dataclasses.replace(want, remat=remat) != cfg:
            raise ValueError(f"{preset}() is {want}, the file gives {cfg}")
    return cfg


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**63: the low 31 bits seed
    it, the rest are folded in (a driver's seed passes 2**31)."""
    import jax

    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


@functools.lru_cache(maxsize=4)
def _maker(n_layers, d, h, f, vocab, max_len, dtype):
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    dh = d // h

    def make(key):
        keys = iter(jax.random.split(key, 24))

        def rnd(shape, scale, shift=0.0):
            x = jax.random.normal(next(keys), shape, jnp.float32)
            return (x * scale + shift).astype(dt)

        L = n_layers
        stacked = {
            "ln1": {"scale": rnd((L, d), 0.1, 1.0), "bias": rnd((L, d), 0.1)},
            "ln2": {"scale": rnd((L, d), 0.1, 1.0), "bias": rnd((L, d), 0.1)},
            "attn": {
                "wq": rnd((L, d, h, dh), d ** -0.5),
                "wk": rnd((L, d, h, dh), d ** -0.5),
                "wv": rnd((L, d, h, dh), d ** -0.5),
                "wo": rnd((L, h, dh, d), d ** -0.5),
                "bq": rnd((L, h, dh), 0.02), "bk": rnd((L, h, dh), 0.02),
                "bv": rnd((L, h, dh), 0.02), "bo": rnd((L, d), 0.02)},
            "mlp": {"w1": rnd((L, d, f), d ** -0.5), "b1": rnd((L, f), 0.02),
                    "w2": rnd((L, f, d), f ** -0.5), "b2": rnd((L, d), 0.02)},
        }
        layers = [jax.tree_util.tree_map(lambda a, i=i: a[i], stacked)
                  for i in range(L)]
        return {
            # tied head: the embedding carries the head's 1/sqrt(d) scale
            "embed": rnd((vocab, d), d ** -0.5),
            "pos": rnd((max_len, d), 0.02),
            "ln_f": {"scale": rnd((d,), 0.1, 1.0), "bias": rnd((d,), 0.1)},
            "layers": layers,
        }

    return jax.jit(make)


def make_params(cfg, seed: int, dtype: str):
    """Weights for the program's `cfg` from `seed`, of `dtype`, made on the
    device in one jitted call."""
    if not cfg.tie_embeddings or not cfg.attn_bias or cfg.n_experts:
        raise ValueError("GPT-2 is dense with biases and a tied head")
    make = _maker(cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.d_ff,
                  cfg.vocab_size, cfg.max_len, dtype)
    return make(seed_key(seed))
