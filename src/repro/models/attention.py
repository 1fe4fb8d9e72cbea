"""Grouped-query attention with position-based masking and ring KV caches.

Design notes (MoD-specific):
- Queries/keys carry explicit *original positions*. MoD gathers a non-
  contiguous sub-sequence of tokens into a routed block; causality is then
  ``kv_pos <= q_pos`` on original positions, and RoPE uses original
  positions. The same code path serves vanilla blocks (positions = arange).
- KV caches are fixed-capacity rings with a per-sequence cursor. Vanilla
  blocks size them at the max context; MoD blocks size them at the block
  capacity ``C = ratio * S`` (the paper's KV-cache saving). Empty slots have
  pos = -1 and are masked out.
- Everything here is batch-pointwise (each row attends only over its own
  cache), which is what lets the SPMD decode path run this code unchanged
  inside a ``shard_map`` region over the batch axes with the model axis
  left to GSPMD (DESIGN.md §SPMD routed execution); the decode TP
  constraint below and the ambient-mesh constraints are no-ops there.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.models import paged_kv as PKV
from repro.models.layers import _dense_init, apply_mrope, apply_rope
from repro.utils import scoped

Params = Dict[str, jax.Array]

NEG_INF = -1e30

# decode-path TP constraint (see decode_attention); toggleable for the
# before/after measurements in benchmarks/perf_iterations.py
DECODE_TP_CONSTRAINT = True


def init_attention(key, cfg: ModelConfig, cross: bool = False) -> Params:
    D = cfg.d_model
    hd = cfg.head_dim
    nq, nkv = cfg.attn.n_heads, cfg.attn.n_kv_heads
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)
    p = {
        "wq": _dense_init(ks[0], D, (D, nq * hd), dtype),
        "wk": _dense_init(ks[1], D, (D, nkv * hd), dtype),
        "wv": _dense_init(ks[2], D, (D, nkv * hd), dtype),
        "wo": _dense_init(ks[3], nq * hd, (nq * hd, D), dtype),
    }
    if cfg.attn.qkv_bias:
        p["bq"] = jnp.zeros((nq * hd,), dtype)
        p["bk"] = jnp.zeros((nkv * hd,), dtype)
        p["bv"] = jnp.zeros((nkv * hd,), dtype)
    return p


def _project_q(params: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    B, S, _ = x.shape
    q = x @ params["wq"]
    if "bq" in params:
        q = q + params["bq"]
    return q.reshape(B, S, cfg.attn.n_heads, cfg.head_dim)


def _project_kv(params: Params, x: jax.Array, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    B, S, _ = x.shape
    k = x @ params["wk"]
    v = x @ params["wv"]
    if "bk" in params:
        k = k + params["bk"]
        v = v + params["bv"]
    nkv, hd = cfg.attn.n_kv_heads, cfg.head_dim
    return k.reshape(B, S, nkv, hd), v.reshape(B, S, nkv, hd)


def _rope_qk(
    q: jax.Array,
    k: jax.Array,
    q_pos: jax.Array,
    kv_pos: jax.Array,
    cfg: ModelConfig,
) -> Tuple[jax.Array, jax.Array]:
    if cfg.attn.pos_emb == "rope":
        q = apply_rope(q, q_pos, cfg.attn.rope_theta)
        k = apply_rope(k, jnp.maximum(kv_pos, 0), cfg.attn.rope_theta)
    elif cfg.attn.pos_emb == "mrope":
        q = apply_mrope(q, q_pos, cfg.attn.rope_theta, cfg.attn.mrope_sections)
        k = apply_mrope(k, jnp.maximum(kv_pos, 0), cfg.attn.rope_theta, cfg.attn.mrope_sections)
    return q, k


def attend(
    q: jax.Array,  # (B, Sq, nq, hd)
    k: jax.Array,  # (B, Skv, nkv, hd)
    v: jax.Array,  # (B, Skv, nkv, hd)
    mask: Optional[jax.Array],  # (B, Sq, Skv) bool, True = attend
    cfg: ModelConfig,
) -> jax.Array:
    """Reference grouped-query attention (materializes S_q x S_kv scores).

    Used for small problems and as the oracle; large sequences go through
    :func:`attend_blocked` (and the Pallas kernel on real TPUs)."""
    B, Sq, nq, hd = q.shape
    nkv = k.shape[2]
    g = nq // nkv
    scale = cfg.attn.softmax_scale or 1.0 / (hd**0.5)
    qg = q.reshape(B, Sq, nkv, g, hd)
    scores = jnp.einsum("bsngh,btnh->bngst", qg, k).astype(jnp.float32) * scale
    if mask is not None:
        scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bngst,btnh->bsngh", probs, v)
    return out.reshape(B, Sq, nq * hd)


# blocked-attention tiling (mirrors the Pallas kernel's BlockSpec tiling)
BLOCK_Q = 1024
BLOCK_KV = 1024
_DENSE_LIMIT = 4 * 1024 * 1024  # Sq*Skv above this -> blocked path


def _pad_to(x, blk, axis):
    pad = (-x.shape[axis]) % blk
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=-1 if x.dtype == jnp.int32 else 0)


def _block_pairs(Nq, Nk, causal, same_blocking):
    if causal and same_blocking and Nq == Nk:
        pairs = [(i, j) for i in range(Nq) for j in range(Nk) if j <= i]
    else:
        pairs = [(i, j) for i in range(Nq) for j in range(Nk)]
    return (
        jnp.asarray([p[0] for p in pairs], jnp.int32),
        jnp.asarray([p[1] for p in pairs], jnp.int32),
    )


def _blk_mask(qp_i, kp_j, causal, window):
    valid = (kp_j[:, None, :] >= 0) & (qp_i[:, :, None] >= 0)
    if causal:
        valid &= kp_j[:, None, :] <= qp_i[:, :, None]
    if window > 0:
        valid &= qp_i[:, :, None] - kp_j[:, None, :] < window
    return valid


@partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _attend_blocked_core(q, k, v, q_pos, kv_pos, causal, window, scale):
    out, _ = _blocked_fwd_impl(q, k, v, q_pos, kv_pos, causal, window, scale)
    return out


def _blocked_fwd_impl(q, k, v, q_pos, kv_pos, causal, window, scale):
    """Online-softmax forward over the (triangular) block grid.

    Returns (out, lse). This scan is hidden behind custom_vjp, so reverse
    mode never saves its per-step carries — the backward pass recomputes
    each block from (q, k, v, lse), the flash-attention strategy. The same
    tiling maps 1:1 onto the Pallas kernel's BlockSpecs (kernels/flash_attention).
    """
    B, Sq, nq, hd = q.shape
    Skv, nkv = k.shape[1], k.shape[2]
    g = nq // nkv
    bq, bkv = min(BLOCK_Q, Sq), min(BLOCK_KV, Skv)
    qb = _pad_to(q, bq, 1)
    qpb = _pad_to(q_pos, bq, 1)
    kb, vb = _pad_to(k, bkv, 1), _pad_to(v, bkv, 1)
    kpb = _pad_to(kv_pos, bkv, 1)
    Nq, Nk = qb.shape[1] // bq, kb.shape[1] // bkv
    qb = qb.reshape(B, Nq, bq, nkv, g, hd)
    kb = kb.reshape(B, Nk, bkv, nkv, hd)
    vb = vb.reshape(B, Nk, bkv, nkv, hd)
    qpb = qpb.reshape(B, Nq, bq)
    kpb = kpb.reshape(B, Nk, bkv)
    ii, jj = _block_pairs(Nq, Nk, causal, bq == bkv and Sq == Skv)

    acc0 = jnp.zeros((Nq, B, bq, nkv, g, hd), jnp.float32)
    m0 = jnp.full((Nq, B, nkv, g, bq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((Nq, B, nkv, g, bq), jnp.float32)

    def body(carry, ij):
        acc, m, l = carry
        i, j = ij
        q_i = jax.lax.dynamic_index_in_dim(qb, i, 1, keepdims=False)
        qp_i = jax.lax.dynamic_index_in_dim(qpb, i, 1, keepdims=False)
        k_j = jax.lax.dynamic_index_in_dim(kb, j, 1, keepdims=False)
        v_j = jax.lax.dynamic_index_in_dim(vb, j, 1, keepdims=False)
        kp_j = jax.lax.dynamic_index_in_dim(kpb, j, 1, keepdims=False)
        s = jnp.einsum("bqngh,btnh->bngqt", q_i, k_j).astype(jnp.float32) * scale
        valid = _blk_mask(qp_i, kp_j, causal, window)
        s = jnp.where(valid[:, None, None, :, :], s, NEG_INF)
        m_i = m[i]
        m_new = jnp.maximum(m_i, jnp.max(s, axis=-1))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(valid[:, None, None, :, :], p, 0.0)
        corr = jnp.where(jnp.isfinite(m_i), jnp.exp(m_i - m_safe), 0.0)
        l_new = l[i] * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bngqt,btnh->bqngh", p.astype(v_j.dtype), v_j).astype(jnp.float32)
        acc_i = acc[i] * jnp.moveaxis(corr, -1, 1)[..., None] + pv
        return (acc.at[i].set(acc_i), m.at[i].set(m_new), l.at[i].set(l_new)), None

    (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0), (ii, jj))
    lse = jnp.where(jnp.isfinite(m), m, 0.0) + jnp.log(jnp.maximum(l, 1e-30))  # (Nq,B,n,g,bq)
    lt = jnp.maximum(jnp.moveaxis(l, -1, 2), 1e-30)  # (Nq,B,bq,nkv,g)
    out = acc / lt[..., None]
    out = out.reshape(Nq, B, bq, nq * hd)
    out = jnp.moveaxis(out, 0, 1).reshape(B, Nq * bq, nq * hd)[:, :Sq]
    return out.astype(q.dtype).reshape(B, Sq, nq, hd), lse


def _blocked_fwd(q, k, v, q_pos, kv_pos, causal, window, scale):
    out, lse = _blocked_fwd_impl(q, k, v, q_pos, kv_pos, causal, window, scale)
    return out, (q, k, v, q_pos, kv_pos, out, lse)


def _blocked_bwd(causal, window, scale, res, dout):
    q, k, v, q_pos, kv_pos, out, lse = res
    B, Sq, nq, hd = q.shape
    Skv, nkv = k.shape[1], k.shape[2]
    g = nq // nkv
    bq, bkv = min(BLOCK_Q, Sq), min(BLOCK_KV, Skv)
    qb = _pad_to(q, bq, 1).reshape(B, -1, bq, nkv, g, hd)
    qpb = _pad_to(q_pos, bq, 1).reshape(B, -1, bq)
    kb = _pad_to(k, bkv, 1).reshape(B, -1, bkv, nkv, hd)
    vb = _pad_to(v, bkv, 1).reshape(B, -1, bkv, nkv, hd)
    kpb = _pad_to(kv_pos, bkv, 1).reshape(B, -1, bkv)
    dob = _pad_to(dout.astype(jnp.float32), bq, 1).reshape(B, -1, bq, nkv, g, hd)
    outb = _pad_to(out.astype(jnp.float32), bq, 1).reshape(B, -1, bq, nkv, g, hd)
    Nq, Nk = qb.shape[1], kb.shape[1]
    ii, jj = _block_pairs(Nq, Nk, causal, bq == bkv and Sq == Skv)

    # delta_i = rowsum(dout * out)   (flash-attention backward identity)
    delta = jnp.einsum("bnqkgh,bnqkgh->bnkgq", dob, outb)  # (B,Nq,nkv,g,bq)

    dqb0 = jnp.zeros((Nq, B, bq, nkv, g, hd), jnp.float32)
    dkb0 = jnp.zeros((Nk, B, bkv, nkv, hd), jnp.float32)
    dvb0 = jnp.zeros((Nk, B, bkv, nkv, hd), jnp.float32)

    def body(carry, ij):
        dqb, dkb, dvb = carry
        i, j = ij
        q_i = jax.lax.dynamic_index_in_dim(qb, i, 1, keepdims=False)
        qp_i = jax.lax.dynamic_index_in_dim(qpb, i, 1, keepdims=False)
        do_i = jax.lax.dynamic_index_in_dim(dob, i, 1, keepdims=False)
        lse_i = jax.lax.dynamic_index_in_dim(lse, i, 0, keepdims=False)  # (B,n,g,bq)
        dl_i = jax.lax.dynamic_index_in_dim(delta, i, 1, keepdims=False)  # (B,n,g,bq)
        k_j = jax.lax.dynamic_index_in_dim(kb, j, 1, keepdims=False)
        v_j = jax.lax.dynamic_index_in_dim(vb, j, 1, keepdims=False)
        kp_j = jax.lax.dynamic_index_in_dim(kpb, j, 1, keepdims=False)
        s = jnp.einsum("bqngh,btnh->bngqt", q_i, k_j).astype(jnp.float32) * scale
        valid = _blk_mask(qp_i, kp_j, causal, window)
        p = jnp.exp(s - lse_i[..., None])
        p = jnp.where(valid[:, None, None, :, :], p, 0.0)  # (B,n,g,bq,bkv)
        dv_j = jnp.einsum("bngqt,bqngh->btnh", p, do_i)
        dp = jnp.einsum("bqngh,btnh->bngqt", do_i, v_j.astype(jnp.float32))
        ds = p * (dp - dl_i[..., None]) * scale
        dq_i = jnp.einsum("bngqt,btnh->bqngh", ds, k_j.astype(jnp.float32))
        dk_j = jnp.einsum("bngqt,bqngh->btnh", ds, q_i.astype(jnp.float32))
        return (
            dqb.at[i].add(dq_i),
            dkb.at[j].add(dk_j),
            dvb.at[j].add(dv_j),
        ), None

    (dqb, dkb, dvb), _ = jax.lax.scan(body, (dqb0, dkb0, dvb0), (ii, jj))
    dq = jnp.moveaxis(dqb, 0, 1).reshape(B, Nq * bq, nq, hd)[:, :Sq].astype(q.dtype)
    dk = jnp.moveaxis(dkb, 0, 1).reshape(B, Nk * bkv, nkv, hd)[:, :Skv].astype(k.dtype)
    dv = jnp.moveaxis(dvb, 0, 1).reshape(B, Nk * bkv, nkv, hd)[:, :Skv].astype(v.dtype)
    zq = np.zeros(q_pos.shape, jax.dtypes.float0)
    zk = np.zeros(kv_pos.shape, jax.dtypes.float0)
    return dq, dk, dv, zq, zk


_attend_blocked_core.defvjp(_blocked_fwd, _blocked_bwd)


def attend_blocked(
    q: jax.Array,  # (B, Sq, nq, hd)
    k: jax.Array,
    v: jax.Array,
    q_pos: jax.Array,  # (B, Sq) int32 (for masking); -1 = padding
    kv_pos: Optional[jax.Array],  # (B, Skv) or None for full (cross) attn
    cfg: ModelConfig,
) -> jax.Array:
    """Flash-style block-triangular attention in pure lax (online softmax).

    Never materializes the S_q x S_kv score matrix, and the custom VJP
    recomputes blocks in the backward pass — O(S) residual memory (out +
    logsumexp), the flash-attention strategy. Positions drive masking, so
    MoD's gathered (non-contiguous but sorted) sub-sequences use the same
    code path as vanilla blocks.
    """
    B, Sq, nq, hd = q.shape
    Skv = k.shape[1]
    scale = cfg.attn.softmax_scale or 1.0 / (hd**0.5)
    causal = cfg.attn.causal and kv_pos is not None
    if kv_pos is None:
        kv_pos = jnp.broadcast_to(jnp.arange(Skv, dtype=jnp.int32)[None], (B, Skv))
    out = _attend_blocked_core(
        q, k, v, q_pos, kv_pos, bool(causal), int(cfg.attn.window), float(scale)
    )
    return out.reshape(B, Sq, nq * hd)


def attend_auto(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_pos: jax.Array,
    kv_pos: Optional[jax.Array],
    cfg: ModelConfig,
) -> jax.Array:
    """Dense for small problems, blocked flash-style for large ones."""
    Sq, Skv = q.shape[1], k.shape[1]
    if Sq * Skv <= _DENSE_LIMIT:
        if kv_pos is None:
            mask = None
        else:
            mask = make_mask(q_pos, kv_pos, cfg.attn.causal, cfg.attn.window)
        return attend(q, k, v, mask, cfg)
    return attend_blocked(q, k, v, q_pos, kv_pos, cfg)


def make_mask(
    q_pos: jax.Array,  # (B, Sq) — for mrope, pass the *t* stream
    kv_pos: jax.Array,  # (B, Skv); entries < 0 are invalid (empty cache slots)
    causal: bool,
    window: int = 0,
) -> jax.Array:
    valid = kv_pos[:, None, :] >= 0
    if causal:
        valid &= kv_pos[:, None, :] <= q_pos[:, :, None]
    if window > 0:
        valid &= q_pos[:, :, None] - kv_pos[:, None, :] < window
    return valid


def _t_pos(pos: jax.Array) -> jax.Array:
    """Scalar ordering stream: for M-RoPE (3,B,S) positions use t."""
    return pos[0] if pos.ndim == 3 else pos


@scoped("attention")
def self_attention(
    params: Params,
    x: jax.Array,
    positions: jax.Array,  # (B,S) or (3,B,S) for mrope
    cfg: ModelConfig,
) -> jax.Array:
    q = _project_q(params, x, cfg)
    k, v = _project_kv(params, x, cfg)
    q, k = _rope_qk(q, k, positions, positions, cfg)
    tp = _t_pos(positions)
    return attend_auto(q, k, v, tp, tp, cfg) @ params["wo"]


@scoped("attention")
def routed_self_attention(
    params: Params,
    ln1: Params,  # the block's pre-attention RMSNorm params
    x: jax.Array,  # (B, S, D) FULL residual stream (not a gathered sub-tensor)
    idx: jax.Array,  # (B, k) routed rows, sorted unique
    pos_sub: jax.Array,  # (B, k) original positions of routed rows
    cfg: ModelConfig,
) -> Tuple[jax.Array, jax.Array]:
    """Fused-dispatch routed attention ("pallas_fused" backend).

    The MoD gather rides the kernel prologue: routed rows are one-hot
    selected out of the full residual stream inside the kernel, then
    normed, projected, rotated and attended (KV = the same routed
    capacity-sized set, position-masked) — bit-for-bit equal to
    ``self_attention(params, rmsnorm(ln1, x_sub), pos_sub, cfg)`` on the
    gathered sub-tensor, which never exists in HBM here. Returns
    ``(a_sub, h_sub = x_sub + a_sub)``, both (B, k, D).
    """
    from repro.kernels.ops import routed_attention_op

    p = {"ln": ln1["scale"], "wq": params["wq"], "wk": params["wk"],
         "wv": params["wv"], "wo": params["wo"]}
    if "bq" in params:
        p.update(bq=params["bq"], bk=params["bk"], bv=params["bv"])
    scale = cfg.attn.softmax_scale or 1.0 / (cfg.head_dim**0.5)
    return routed_attention_op(
        x, idx, pos_sub, p,
        n_heads=cfg.attn.n_heads, n_kv_heads=cfg.attn.n_kv_heads,
        head_dim=cfg.head_dim, scale=float(scale),
        causal=bool(cfg.attn.causal), window=int(cfg.attn.window),
        rope_theta=float(cfg.attn.rope_theta), pos_emb=cfg.attn.pos_emb,
        eps=float(cfg.norm_eps),
    )


@scoped("attention")
def ragged_self_attention(
    params: Params,
    x: jax.Array,  # (1, T, D) flat token stream
    positions: jax.Array,  # (1, T) within-segment positions; -1 = padded tail
    seg_id: jax.Array,  # (T,) int32 segment of each flat row
    cfg: ModelConfig,
) -> jax.Array:
    """Self-attention over a flat ragged token stream (segments packed
    back-to-back, ``input_row_offsets`` layout). Causality is block-diagonal:
    a query attends only within its own segment, at ``kv_pos <= q_pos`` on
    within-segment positions. Adding the cross-segment ``NEG_INF`` entries
    contributes exact-zero softmax terms, so on the dense-``attend`` path
    each segment's rows equal the padded per-sequence attention bit for bit
    (tests/test_ragged.py). The paged pallas twin of this read pattern is
    ``kernels.ragged.ragged_paged_flash_attention``.
    """
    q = _project_q(params, x, cfg)
    k, v = _project_kv(params, x, cfg)
    q, k = _rope_qk(q, k, positions, positions, cfg)
    tp = _t_pos(positions)
    mask = make_mask(tp, tp, cfg.attn.causal, cfg.attn.window)
    mask &= (seg_id[:, None] == seg_id[None, :])[None]
    return attend(q, k, v, mask, cfg) @ params["wo"]


@scoped("attention")
def cross_attention(
    params: Params,
    x: jax.Array,
    enc_k: jax.Array,
    enc_v: jax.Array,
    cfg: ModelConfig,
) -> jax.Array:
    """Encoder-decoder cross attention (no positional rotation, full mask)."""
    q = _project_q(params, x, cfg)
    qpos = jnp.zeros((x.shape[0], x.shape[1]), jnp.int32)
    out = attend_auto(q, enc_k, enc_v, qpos, None, cfg)
    return out @ params["wo"]


def encode_kv(params: Params, enc_out: jax.Array, cfg: ModelConfig):
    """Precompute cross-attention K/V from encoder output (served caches)."""
    return _project_kv(params, enc_out, cfg)


# ---------------------------------------------------------------------------
# KV cache (fixed-capacity ring buffer)
# ---------------------------------------------------------------------------


def init_kv_cache(batch: int, capacity: int, cfg: ModelConfig, dtype=None) -> Params:
    nkv, hd = cfg.attn.n_kv_heads, cfg.head_dim
    dt = dtype or jnp.dtype(cfg.dtype)
    return {
        "k": jnp.zeros((batch, capacity, nkv, hd), dt),
        "v": jnp.zeros((batch, capacity, nkv, hd), dt),
        "pos": jnp.full((batch, capacity), -1, jnp.int32),
        "cursor": jnp.zeros((batch,), jnp.int32),
    }


def kv_cache_specs(batch: int, capacity: int, cfg: ModelConfig) -> Params:
    nkv, hd = cfg.attn.n_kv_heads, cfg.head_dim
    dt = jnp.dtype(cfg.dtype)
    return {
        "k": jax.ShapeDtypeStruct((batch, capacity, nkv, hd), dt),
        "v": jax.ShapeDtypeStruct((batch, capacity, nkv, hd), dt),
        "pos": jax.ShapeDtypeStruct((batch, capacity), jnp.int32),
        "cursor": jax.ShapeDtypeStruct((batch,), jnp.int32),
    }


def cache_write(
    cache: Params,
    k_new: jax.Array,  # (B, S_new, nkv, hd)
    v_new: jax.Array,
    pos_new: jax.Array,  # (B, S_new) int32; -1 entries are skipped
    write_mask: Optional[jax.Array] = None,  # (B, S_new) bool
) -> Params:
    """Ring-buffer write. Entries with write_mask False (or pos<0) write to a
    scratch slot beyond the ring (dropped), keeping shapes static."""
    B, C = cache["pos"].shape
    S_new = pos_new.shape[1]
    if write_mask is None:
        write_mask = pos_new >= 0
    else:
        write_mask = write_mask & (pos_new >= 0)
    # slot index for each new entry: cursor + rank among written entries
    rank = jnp.cumsum(write_mask.astype(jnp.int32), axis=1) - 1  # (B,S_new)
    slot = (cache["cursor"][:, None] + rank) % C
    # route masked-out entries to slot C (scratch row appended below)
    slot = jnp.where(write_mask, slot, C)
    bidx = jnp.broadcast_to(jnp.arange(B)[:, None], (B, S_new))

    def _scat(buf, new):
        padded = jnp.concatenate([buf, jnp.zeros_like(buf[:, :1])], axis=1)
        padded = padded.at[bidx, slot].set(new.astype(buf.dtype))
        return padded[:, :C]

    k = _scat(cache["k"], k_new)
    v = _scat(cache["v"], v_new)
    pos_pad = jnp.concatenate([cache["pos"], jnp.full((B, 1), -1, jnp.int32)], axis=1)
    pos = pos_pad.at[bidx, slot].set(pos_new)[:, :C]
    cursor = cache["cursor"] + jnp.sum(write_mask.astype(jnp.int32), axis=1)
    return {"k": k, "v": v, "pos": pos, "cursor": cursor}


@scoped("attention")
def decode_attention(
    params: Params,
    x: jax.Array,  # (B, 1, D)
    positions: jax.Array,  # (B,1) or (3,B,1)
    cache: Params,
    cfg: ModelConfig,
) -> Tuple[jax.Array, Params]:
    """One decode step: write this token's (rotated) K/V, attend over cache.

    The cache stores *rotated* K — RoPE's relative property only needs each
    key rotated at its own absolute position, so nothing is re-rotated at
    read time (O(1) rotation per step even at 500k context).

    A cache whose rings are the serving pool's pages (``paged_kv.PagedLeaf``)
    is written and read in place (``paged_kv.write_rows``, ``attend_paged``).
    """
    q = _project_q(params, x, cfg)
    k_new, v_new = _project_kv(params, x, cfg)
    q, k_new = _rope_qk(q, k_new, positions, positions, cfg)
    # Decode TP strategy: the KV cache can only shard head_dim over "model"
    # (kv-head counts are below 16); if Q stays head-sharded, GSPMD
    # all-gathers the ENTIRE cache per layer (~1 GiB/step/layer at 32k).
    # Constraining Q to the same head_dim sharding turns QK^T into a
    # partial contraction with a tiny scores psum instead: measured
    # 29.9 -> 3.3 GiB/step/device on granite-8b decode_32k (§Perf cell A).
    if DECODE_TP_CONSTRAINT:
        from repro.distributed.sharding import constrain_spec

        bd = ("pod", "data")
        q = constrain_spec(q, bd, None, None, "model")
        k_new = constrain_spec(k_new, bd, None, None, "model")
        v_new = constrain_spec(v_new, bd, None, None, "model")
    tp = _t_pos(positions)
    if PKV.is_paged(cache["k"]):
        cache = PKV.write_rows(cache, k_new[:, 0], v_new[:, 0], tp[:, 0])
        return PKV.attend_paged(q, cache, tp, cfg) @ params["wo"], cache
    cache = cache_write(cache, k_new, v_new, tp)
    mask = make_mask(tp, cache["pos"], cfg.attn.causal, cfg.attn.window)
    out = attend(q, cache["k"], cache["v"], mask, cfg) @ params["wo"]
    return out, cache


@scoped("attention")
def chunk_self_attention(
    params: Params,
    x: jax.Array,  # (B, C, D) one prefill chunk
    positions: jax.Array,  # (B, C); padded tail entries are -1
    cache: Params,
    cfg: ModelConfig,
    write_mask: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Params]:
    """Continuation-prefill attention: one chunk against a partial cache.

    Unlike :func:`prefill_self_attention` (which attends only within the
    chunk), queries here attend over the *cache* — earlier chunks' KV plus
    this chunk's own entries, written first. Position masking makes that
    exactly causal: a query at position t sees cache entries with
    ``0 <= kv_pos <= t`` and nothing else (empty slots are pos = -1, and
    padded chunk tails are skipped by the write mask). This is the decode
    step's read pattern generalized to C > 1 — the chunked-prefill building
    block that keeps one long prompt from monopolizing an engine step.
    """
    q = _project_q(params, x, cfg)
    k, v = _project_kv(params, x, cfg)
    q, k = _rope_qk(q, k, positions, positions, cfg)
    tp = _t_pos(positions)
    cache = cache_write(cache, k, v, tp, write_mask)
    out = attend_auto(q, cache["k"], cache["v"], tp, cache["pos"], cfg)
    return out @ params["wo"], cache


@scoped("attention")
def prefill_self_attention(
    params: Params,
    x: jax.Array,
    positions: jax.Array,  # (B,S) or (3,B,S)
    cache: Params,
    cfg: ModelConfig,
    write_mask: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Params]:
    """Self-attention that also populates the KV cache (rotated K).

    ``write_mask`` restricts which tokens enter the cache — MoD blocks pass
    the routed-token mask so their capacity-sized cache holds only routed
    tokens.
    """
    q = _project_q(params, x, cfg)
    k, v = _project_kv(params, x, cfg)
    q, k = _rope_qk(q, k, positions, positions, cfg)
    tp = _t_pos(positions)
    out = attend_auto(q, k, v, tp, tp, cfg) @ params["wo"]
    cache = cache_write(cache, k, v, tp, write_mask)
    return out, cache
