"""Operations and bytes of latent (multi-head latent, absorbed) paged
attention, from shapes: the yardstick's side of `latent_kernel_roofline`.

A cached token is one row `[c_kv | k_rope]` of `row_values` values for ALL
heads; a query head scores a row with a dot over the whole row and mixes the
row's first `v_width` values (the latent `c_kv` is key and value at once).
"""

from __future__ import annotations


def latent_attention_flops(pairs: float, n_heads: int, row_values: int,
                           v_width: int) -> float:
    """One layer: `pairs` (fed column, visible row) pairs, each scored and
    mixed by every head: 2 * (row_values + v_width) a pair and head."""
    return 2.0 * pairs * n_heads * (row_values + v_width)


def latent_attention_bytes(rows: float, row_values: int,
                           itemsize: int = 2) -> float:
    """One layer: the cache rows the attention has to read, once each
    (queries and results are a few rows a lane and are left out)."""
    return float(rows * row_values * itemsize)
