"""Operations and bytes that the work needs, computed from shapes.

MoD-aware: a routed block counts only the tokens (prefill, training) or batch
rows (decode) that it routes; padding rows and padded chunk positions count
nothing, and recomputation in the backward pass counts nothing. Attention
counts the (query, key) pairs that the causal mask and the routed ring leave.
A multiply-add is two operations.
"""
from __future__ import annotations

from typing import Iterable, Sequence

import numpy as np

from .spec import ModelSpec

ITEMSIZE = {"bfloat16": 2, "float32": 4}


def block_token_flops(s: ModelSpec) -> float:
    """Projections and gated MLP of one block, per token."""
    D, q, kv = s.d_model, s.n_heads * s.head_dim, s.n_kv_heads * s.head_dim
    return 2.0 * D * q + 2.0 * 2 * D * kv + 2.0 * q * D + 3 * 2.0 * D * s.d_ff


def attn_pair_flops(s: ModelSpec) -> float:
    """Scores and weighted values of one (query, key) pair, all heads."""
    return 4.0 * s.n_heads * s.head_dim


def router_flops(s: ModelSpec) -> float:
    return 2.0 * s.d_model


def predictor_flops(s: ModelSpec) -> float:
    return 2.0 * s.d_model * s.predictor_hidden + 2.0 * s.predictor_hidden


def unembed_flops(s: ModelSpec) -> float:
    return 2.0 * s.d_model * s.vocab


def causal_pairs(n: float) -> float:
    return n * (n + 1) / 2.0


def decode_step_flops(s: ModelSpec, active_pos: Sequence[int],
                      routed_ring: Iterable[Sequence[int]]) -> float:
    """One decode step. ``active_pos``: the position each live row decodes;
    ``routed_ring``: per routed block, the ring entries each routed row
    attends over (itself included)."""
    pos = np.asarray(active_pos, np.float64)
    n = float(pos.size)
    f = s.n_groups * (n * block_token_flops(s) + attn_pair_flops(s) * float(np.sum(pos + 1)))
    for ring in routed_ring:
        ring = np.asarray(ring, np.float64)
        f += ring.size * block_token_flops(s) + attn_pair_flops(s) * float(np.sum(ring))
    f += s.n_groups * n * (router_flops(s) + predictor_flops(s))
    return f + n * unembed_flops(s)


def weight_bytes(s: ModelSpec, rows_embedded: int) -> float:
    """Every weight read once; of the embedding table only the rows looked up."""
    from .weights import param_bytes

    total = 0.0
    for path, b in param_bytes(s).items():
        if path == ("embed", "tok"):
            total += rows_embedded * s.d_model * ITEMSIZE[s.dtype]
        else:
            total += b
    return total


def kv_row_bytes(s: ModelSpec) -> float:
    """K and V of one token in one block."""
    return 2.0 * s.n_kv_heads * s.head_dim * ITEMSIZE[s.dtype]


def decode_step_bytes(s: ModelSpec, active_pos: Sequence[int],
                      routed_ring: Iterable[Sequence[int]]) -> float:
    """Least bytes of one decode step: the weights once, the live K/V of the
    active rows (full blocks: every earlier position; routed blocks: the
    routed rows' rings), the new K/V rows written, the logits written."""
    pos = np.asarray(active_pos, np.float64)
    n = float(pos.size)
    b = weight_bytes(s, int(n))
    b += s.n_groups * kv_row_bytes(s) * (float(np.sum(pos + 1)) + n)
    for ring in routed_ring:
        ring = np.asarray(ring, np.float64)
        b += kv_row_bytes(s) * (float(np.sum(ring)) + ring.size)
    return b + n * s.vocab * 4.0


def chunk_flops(s: ModelSpec, start: int, n_valid: int,
                routed_ring: Iterable[Sequence[int]]) -> float:
    """One prefill chunk of ``n_valid`` real tokens from position ``start``;
    ``routed_ring`` per routed block: for each routed token, the ring entries
    it attends over. Only the last token's logits are computed."""
    n = float(n_valid)
    pairs = n * start + causal_pairs(n)
    f = s.n_groups * (n * block_token_flops(s) + attn_pair_flops(s) * pairs)
    for ring in routed_ring:
        ring = np.asarray(ring, np.float64)
        f += ring.size * block_token_flops(s) + attn_pair_flops(s) * float(np.sum(ring))
    f += s.n_groups * n * router_flops(s)
    return f + unembed_flops(s)


def train_step_flops(s: ModelSpec, batch: int, seq: int) -> float:
    """Forward and backward of one step (backward twice the forward; the
    predictor trains on stop-gradient inputs, so its backward is one forward)."""
    k = s.capacity(seq)
    full = seq * block_token_flops(s) + causal_pairs(seq) * attn_pair_flops(s)
    routed = k * block_token_flops(s) + causal_pairs(k) * attn_pair_flops(s)
    per_seq = (s.n_groups * (full + routed + seq * router_flops(s))
               + seq * unembed_flops(s))
    return batch * (3.0 * per_seq + 2.0 * s.n_groups * seq * predictor_flops(s))


def least_time_s(flops: float, nbytes: float, peak_flops: float, peak_bw: float) -> float:
    return max(flops / peak_flops, nbytes / peak_bw)
