"""Operations and bytes of a dense transformer's pieces, computed from shapes,
and the least time a chip takes for them.

A model family's counts (``bench/families/<family>.py``) are built from
these. Padding counts nothing, and recomputation in the backward pass counts
nothing. A multiply-add is two operations.
"""
from __future__ import annotations

ITEMSIZE = {"bfloat16": 2, "float32": 4}


def block_token_flops(s) -> float:
    """Projections and gated MLP of one block, per token."""
    D, q, kv = s.d_model, s.n_heads * s.head_dim, s.n_kv_heads * s.head_dim
    return 2.0 * D * q + 2.0 * 2 * D * kv + 2.0 * q * D + 3 * 2.0 * D * s.d_ff


def attn_pair_flops(s) -> float:
    """Scores and weighted values of one (query, key) pair, all heads."""
    return 4.0 * s.n_heads * s.head_dim


def unembed_flops(s) -> float:
    return 2.0 * s.d_model * s.vocab


def causal_pairs(n: float) -> float:
    return n * (n + 1) / 2.0


def weight_bytes(s, rows_embedded: int) -> float:
    """Every weight read once; of the embedding table only the rows looked up."""
    from .weights import param_bytes

    total = 0.0
    for path, b in param_bytes(s).items():
        if path == ("embed", "tok"):
            total += rows_embedded * s.d_model * ITEMSIZE[s.dtype]
        else:
            total += b
    return total


def kv_row_bytes(s) -> float:
    """K and V of one token in one block."""
    return 2.0 * s.n_kv_heads * s.head_dim * ITEMSIZE[s.dtype]


def least_time_s(flops: float, nbytes: float, peak_flops: float, peak_bw: float) -> float:
    return max(flops / peak_flops, nbytes / peak_bw)
