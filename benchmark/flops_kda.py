"""Operations and bytes of the gated delta-rule (KDA) layers and of grouped-
query paged attention, from shapes: the yardstick's side of
`kda_step_roofline`, `kda_chunk_roofline` and `gqa_kernel_roofline`.

A KDA head keeps a state `S [K, V]` in float32 a sequence, and the layer the
`taps - 1` last inputs of its three convolutions (`tail`, `H * (2K + V)`
channels).  A grouped-query layer keeps `kv_heads * head_dim` values a
cached token in each of its two pools.
"""

from __future__ import annotations

STATE_ITEMSIZE = 4      # the state is float32 whatever the model's dtype


def state_row_bytes(layers: int, heads: int, k_dim: int, v_dim: int,
                    taps: int, itemsize: int = 2) -> int:
    """One sequence's recurrent state, all `layers` KDA layers: the state
    matrices and the convolution tails."""
    return layers * (heads * k_dim * v_dim * STATE_ITEMSIZE
                     + (taps - 1) * heads * (2 * k_dim + v_dim) * itemsize)


def kda_step_bytes(lanes: float, layers: int, heads: int, k_dim: int,
                   v_dim: int, taps: int, itemsize: int = 2) -> float:
    """A width-1 round: every active lane's state row and tail read and
    written once (the token's own q, k, v, decay and output are a few KB a
    lane and are left out)."""
    return 2.0 * lanes * state_row_bytes(layers, heads, k_dim, v_dim, taps,
                                         itemsize)


def kda_chunk_flops(tokens: float, layers: int, heads: int, k_dim: int,
                    v_dim: int, chunk: int = 64) -> float:
    """The chunked delta rule on `tokens` fed positions, a head and a chunk
    of `C` positions: the decayed key and query Gram blocks (2 x 2 C^2 K),
    the unit lower-triangular solve against `[K+ | V]` (C^2 (K + V)), the
    pseudo-values' and the outputs' products with the carried state
    (2 x 2 C K V), the intra-chunk output (2 C^2 V) and the state's update
    (2 C K V).  Float32 operations, as the program computes them."""
    c = float(chunk)
    per_chunk = (c * c * (4 * k_dim + (k_dim + v_dim) + 2 * v_dim)
                 + 6 * c * k_dim * v_dim)
    return tokens / c * layers * heads * per_chunk


def kda_chunk_bytes(tokens: float, lanes: float, layers: int, heads: int,
                    k_dim: int, v_dim: int, taps: int,
                    itemsize: int = 2) -> float:
    """A wide round: every active lane's state row and tail read and written
    once, and q, k, the decay (K each), v and the output (V each) of every
    fed position read or written once in float32."""
    per_token = layers * heads * (3 * k_dim + 2 * v_dim) * STATE_ITEMSIZE
    return (kda_step_bytes(lanes, layers, heads, k_dim, v_dim, taps, itemsize)
            + tokens * per_token)


def gqa_paged_bytes(live_pages: float, page_size: int, kv_heads: int,
                    head_dim: int, layers: int = 1,
                    itemsize: int = 2) -> float:
    """The K and V pages a round's grouped-query attention has to read, once
    each: live pages x 2 pools x `page_size` rows of `kv_heads * head_dim`
    values (a query block of a wide round that walks the pages again is the
    kernel's cost, not the round's need)."""
    return float(2 * layers * live_pages * page_size * kv_heads * head_dim
                 * itemsize)
