"""Operations and bytes an algorithm needs, computed from shapes: the
yardstick's side of every utilization and roofline share.

Copied from `bench.py` (`bench_gpt2`'s FLOP model) and
`parallel/paged_kernel.py:paged_hbm_bytes`, which stay where they are
until a later PR deletes them (PERF.md, Open questions).  One change: the
attention term counts the causal half of the score matrix, which is what
the passes require; `bench.py` counts all of it.
"""

from __future__ import annotations


def train_flops_per_token(cfg, n_params: int, seq: int) -> float:
    """Forward and backward of a dense causal LM, per token: 6 per
    parameter (every parameter is in a matmul; the tied embedding is the
    head) plus attention's two matmuls over the causal half of the scores,
    forward (2 * 2 * S * d / 2) and twice that backward: 6 * L * S * d.
    Recomputation is not counted."""
    return 6.0 * n_params + 6.0 * cfg.n_layers * seq * cfg.d_model


def flash_attention_flops(batch_heads: int, seq: int, head_dim: int,
                          passes: str) -> float:
    """One call of a causal flash attention kernel on [B x H, S, K]:
    `forward` is QK^T and PV (2 matmuls), `dkdv` recomputes the scores and
    forms dP, dV and dK (4), `dq` recomputes the scores and forms dP and dQ
    (3); each matmul is 2 * S * S * K per head over the causal half."""
    matmuls = {"forward": 2, "dkdv": 4, "dq": 3}[passes]
    return matmuls * 2.0 * batch_heads * seq * seq * head_dim / 2.0


def flash_attention_bytes(batch_heads: int, seq: int, head_dim: int,
                          passes: str, itemsize: int = 2) -> float:
    """HBM bytes of that call: q, k, v read and o written forward; q, k, v,
    dO read and dK, dV written (`dkdv`) or dQ written (`dq`).  The rows of
    log-sum-exp and delta, one float a row, are left out."""
    tensors = {"forward": 4, "dkdv": 6, "dq": 5}[passes]
    return float(tensors * batch_heads * seq * head_dim * itemsize)


def paged_hbm_bytes(n_layers: int, lanes: int, live_pages: int,
                    max_pages: int, page_size: int, n_heads: int,
                    head_dim: int, itemsize: int, kernel: bool) -> int:
    """K/V bytes one decode dispatch reads: the gather path touches every
    block-table row, the kernel only the lane's live pages; k and v both.
    Needs live pages per dispatch, which nothing records yet (PERF.md, list
    for the `tracing` issue), so no metric uses it yet."""
    rows = (live_pages if kernel else max_pages) * page_size
    return 2 * n_layers * lanes * rows * n_heads * head_dim * itemsize
