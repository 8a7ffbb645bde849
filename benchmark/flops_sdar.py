"""Operations and bytes of a block-diffusion model's block round, from shapes:
the yardstick's side of `gqa_block_kernel_roofline`
(`configs/sdar-30b-a3b-serve-6l.json`).

A grouped-query layer keeps `kv_heads * head_dim` values a cached token in
each of its two pools (rotated keys, values).  A denoise round or a commit
pass of a lane reads the lane's committed pages and the page its block lies
in, in every layer, and nothing else of the pool: the block mask changes
which rows a column may see inside the block, not which pages are read.
"""

from __future__ import annotations


def block_paged_bytes(live_pages: float, page_size: int, kv_heads: int,
                      head_dim: int, layers: int, itemsize: int = 2) -> float:
    """The K and V pages a round's attention has to read, once each, all
    `layers`: live pages x 2 pools x `page_size` rows of
    `kv_heads * head_dim` values (a query block of a wide round that walks
    the pages again is the kernel's cost, not the round's need)."""
    return float(2 * layers * live_pages * page_size * kv_heads * head_dim
                 * itemsize)
