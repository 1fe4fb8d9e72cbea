"""Pallas TPU flash attention (GQA + explicit position masking).

Tiling: grid = (B, nq, Nq, Nk); the last axis is "arbitrary" (sequential)
and accumulates the online softmax in VMEM scratch. Query/output blocks are
(block_q, head_dim); K/V blocks are (block_kv, head_dim), both sized so the
working set (q + k + v + scores + acc ≈ 2·bq·hd + 2·bkv·hd + bq·bkv floats)
fits comfortably in the ~16 MiB/core VMEM with MXU-aligned (multiple-of-128)
dims. GQA is expressed in the K/V index_map (query head h reads kv head
h·nkv/nq), so no K/V replication is materialized.

Masking is position-based: q_pos/kv_pos int32 arrays ride along in their own
blocks; causality is ``kv_pos <= q_pos`` on *original* token positions,
which makes the same kernel serve vanilla blocks (positions = arange) and
MoD routed blocks (sorted gathered positions). pos = -1 marks padding.

This module also holds :func:`routed_attention`, the attention half of the
``pallas_fused`` MoD backend: the routed-row gather rides the kernel
prologue as a one-hot selection matmul out of the full ``(B, S, D)``
residual stream (no standalone gather pass, no materialized sub-tensor),
and the kernel carries the whole pre-attention stage — RMSNorm, QKV
projection, RoPE — so the capacity-sized attention runs on rows that never
round-tripped through HBM. See DESIGN.md §Backend selection.

Current blocking: only the capacity axis is tiled (``block_k``); each grid
step stages the full ``(B, S, D)`` stream block and the block's weights and
computes the dense capacity-sized softmax — correct in interpret mode at any
size, but not a Mosaic kernel: the body mirrors the xla block op for op,
which Mosaic does not lower, so compiling it for a TPU raises
``FUSED_NOT_COMPILED``. S/B-axis tiling (streaming the gather accumulation
like kernels/routing.py does) and 2-D per-head bodies are the follow-up;
the bit-for-bit contract vs the xla backend likewise assumes the xla block
takes the dense-``attend`` path (capacity ≤ 2048, which ``ratio·S`` keeps
true at the paper's settings).
"""
from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_KV = 512

# capacity-axis tile of the routed-attention kernel (module-level so tests
# can exercise the padding tail by shrinking it)
ROUTED_BLOCK_K = 128

# Why the two fused-dispatch kernels run only in interpret mode. Their
# bodies mirror the xla block op for op (3-5-D einsums, dynamic slices,
# bf16-accumulated matmuls, 1-D broadcasts) and stage the whole (B, S, D)
# stream and the block's weights in VMEM; Mosaic lowers none of that
# (tests/test_tpu_compile.py keeps this refusal in view).
FUSED_NOT_COMPILED = (
    "MoDConfig.backend='pallas_fused': the fused routed-attention/routed-MLP "
    "kernels do not compile for TPU (their bodies mirror the xla block op for "
    "op and stage the whole stream and block weights in VMEM); they run only "
    "in interpret mode. Use backend='pallas' or 'xla' on a chip. Serving is "
    "unaffected: prefill dispatch falls back to the 'pallas' kernels and "
    "decode routes with XLA ops."
)


def _flash_kernel(
    qpos_ref,
    kpos_ref,
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    acc_ref,
    m_ref,
    l_ref,
    *,
    scale: float,
    causal: bool,
    window: int,
    n_kv_blocks: int,
):
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)  # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)  # (bkv, hd)
    v = v_ref[0, 0]  # (bkv, hd)
    qp = qpos_ref[0]  # (bq,)
    kp = kpos_ref[0]  # (bkv,)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (bq, bkv)
    valid = (kp[None, :] >= 0) & (qp[:, None] >= 0)
    if causal:
        valid &= kp[None, :] <= qp[:, None]
    if window > 0:
        valid &= qp[:, None] - kp[None, :] < window
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[:, 0]  # (bq,)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    m_safe = jnp.where(m_new > NEG_INF / 2, m_new, 0.0)
    p = jnp.exp(s - m_safe[:, None])
    p = jnp.where(valid, p, 0.0)
    corr = jnp.where(m_prev > NEG_INF / 2, jnp.exp(m_prev - m_safe), 0.0)
    l_new = l_ref[:, 0] * corr + jnp.sum(p, axis=-1)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    acc_ref[...] = acc_ref[...] * corr[:, None] + pv
    m_ref[:, 0] = m_new
    l_ref[:, 0] = l_new

    @pl.when(j == n_kv_blocks - 1)
    def _finish():
        l_fin = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[0, 0, :, :] = (acc_ref[...] / l_fin[:, None]).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,  # (B, Sq, nq, hd)
    k: jax.Array,  # (B, Skv, nkv, hd)
    v: jax.Array,
    q_pos: jax.Array,  # (B, Sq) int32
    kv_pos: jax.Array,  # (B, Skv) int32
    *,
    causal: bool = True,
    window: int = 0,
    scale: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_kv: int = DEFAULT_BLOCK_KV,
    interpret: bool = False,
) -> jax.Array:
    """Returns (B, Sq, nq, hd)."""
    B, Sq, nq, hd = q.shape
    Skv, nkv = k.shape[1], k.shape[2]
    assert nq % nkv == 0
    scale = scale if scale is not None else 1.0 / (hd**0.5)
    bq, bkv = min(block_q, Sq), min(block_kv, Skv)
    assert Sq % bq == 0 and Skv % bkv == 0, (Sq, bq, Skv, bkv)
    Nq, Nk = Sq // bq, Skv // bkv

    # heads-first layout so blocks are contiguous (B, n, S, hd)
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)

    grid = (B, nq, Nq, Nk)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window, n_kv_blocks=Nk
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq), lambda b, h, i, j: (b, i)),
            pl.BlockSpec((1, bkv), lambda b, h, i, j: (b, j)),
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bkv, hd), lambda b, h, i, j, _nkv=nkv, _nq=nq: (b, h * _nkv // _nq, j, 0)),
            pl.BlockSpec((1, 1, bkv, hd), lambda b, h, i, j, _nkv=nkv, _nq=nq: (b, h * _nkv // _nq, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, nq, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),  # acc
            pltpu.VMEM((bq, 1), jnp.float32),  # running max
            pltpu.VMEM((bq, 1), jnp.float32),  # running denominator
        ],
        interpret=interpret,
    )(q_pos, kv_pos, qh, kh, vh)
    return jnp.swapaxes(out, 1, 2)



# ---------------------------------------------------------------------------
# Routed attention: MoD gather fused into the attention kernel prologue
# (the attention half of the "pallas_fused" backend, DESIGN.md §Backend
# selection). The kernel mirrors the xla block path op for op —
# models.layers.rmsnorm / apply_rope and models.attention._project_* /
# make_mask / attend — so its output is bit-for-bit equal to
# gather -> self_attention on the sub-tensor. Keep the mirrors in sync.
# ---------------------------------------------------------------------------


class RoutedAttnSpec(NamedTuple):
    """Static config of the routed-attention kernel (hashable: it rides
    custom_vjp's nondiff_argnums and jit static args)."""

    n_heads: int
    n_kv_heads: int
    head_dim: int
    scale: float
    causal: bool
    window: int
    rope_theta: float
    pos_emb: str  # "rope" | "none" (mrope falls back to the pallas backend)
    eps: float
    block_k: int
    interpret: bool


def _mirror_rmsnorm(scale: jax.Array, x: jax.Array, eps: float) -> jax.Array:
    # mirrors models.layers.rmsnorm bitwise
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(dtype)


def _mirror_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    # mirrors models.layers.apply_rope bitwise (lax.iota, not jnp.arange:
    # pallas kernels may not capture array constants; 2i is exact in f32 so
    # the exponents are bit-identical)
    hd = x.shape[-1]
    exponents = jax.lax.iota(jnp.float32, hd // 2) * 2.0 / hd
    freqs = 1.0 / (theta**exponents)
    angles = positions[..., None].astype(jnp.float32) * freqs
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _attn_stage(
    hn_q: jax.Array,  # (B, rows, D) normed routed rows (q side)
    kv_rows_n: jax.Array,  # (B, k, D) normed KV-side rows (superset of q rows)
    qpos: jax.Array,  # (B, rows)
    kvpos: jax.Array,  # (B, k)
    params: Dict[str, jax.Array],
    spec: RoutedAttnSpec,
) -> jax.Array:
    """QKV -> RoPE -> masked attention -> out-proj on (pre-normed) routed
    rows. Shared between the kernel body and the VJP reference so both run
    the exact op sequence of the xla path (attention.self_attention); the
    caller norms ONCE and passes slices, matching the xla path's single
    rmsnorm -> {q,k,v} fan-out (a re-norm would re-associate the cotangent
    accumulation and break grad bit-equality)."""
    B, rows, _ = hn_q.shape
    k = kv_rows_n.shape[1]
    nq, nkv, hd = spec.n_heads, spec.n_kv_heads, spec.head_dim
    q = hn_q @ params["wq"]
    kk = kv_rows_n @ params["wk"]
    vv = kv_rows_n @ params["wv"]
    if "bq" in params:
        q = q + params["bq"]
        kk = kk + params["bk"]
        vv = vv + params["bv"]
    q = q.reshape(B, rows, nq, hd)
    kk = kk.reshape(B, k, nkv, hd)
    vv = vv.reshape(B, k, nkv, hd)
    if spec.pos_emb == "rope":
        q = _mirror_rope(q, qpos, spec.rope_theta)
        kk = _mirror_rope(kk, jnp.maximum(kvpos, 0), spec.rope_theta)
    # mask mirrors models.attention.make_mask
    valid = kvpos[:, None, :] >= 0
    if spec.causal:
        valid = valid & (kvpos[:, None, :] <= qpos[:, :, None])
    if spec.window > 0:
        valid = valid & (qpos[:, :, None] - kvpos[:, None, :] < spec.window)
    # attention mirrors models.attention.attend
    g = nq // nkv
    qg = q.reshape(B, rows, nkv, g, hd)
    s = jnp.einsum("bsngh,btnh->bngst", qg, kk).astype(jnp.float32) * spec.scale
    s = jnp.where(valid[:, None, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(vv.dtype)
    o = jnp.einsum("bngst,btnh->bsngh", p, vv).reshape(B, rows, nq * hd)
    return o @ params["wo"]


def _onehot_gather(x: jax.Array, idx: jax.Array) -> jax.Array:
    """Exact row selection as a one-hot f32 matmul (idx = -1 -> zero row)."""
    S = x.shape[1]
    cols = jax.lax.broadcasted_iota(jnp.int32, idx.shape + (S,), idx.ndim)
    onehot = (idx[..., None] == cols).astype(jnp.float32)
    out = jnp.einsum("bks,bsd->bkd", onehot, x.astype(jnp.float32))
    return out.astype(x.dtype)


def _routed_attn_kernel(
    idx_ref, pos_ref, x_ref, ln_ref, wq_ref, wk_ref, wv_ref, wo_ref,
    *rest, spec: RoutedAttnSpec, k: int
):
    if len(rest) == 5:  # qkv_bias configs carry three extra operands
        bq_ref, bk_ref, bv_ref, a_ref, h_ref = rest
    else:
        (a_ref, h_ref), bq_ref, bk_ref, bv_ref = rest, None, None, None
    t = pl.program_id(0)
    bk = spec.block_k
    idx = idx_ref[...]  # (B, k_pad), pad entries are -1
    pos = pos_ref[...]  # (B, k_pad), pad entries are -1
    x = x_ref[...]  # (B, S, D)
    # gather folded into the prologue: routed rows come straight out of the
    # full residual stream; the sub-tensor never exists in HBM
    xs = _onehot_gather(x, idx)  # (B, k_pad, D)
    hn = _mirror_rmsnorm(ln_ref[...], xs, spec.eps)
    params = {
        "ln": ln_ref[...], "wq": wq_ref[...], "wk": wk_ref[...],
        "wv": wv_ref[...], "wo": wo_ref[...],
    }
    if bq_ref is not None:
        params.update(bq=bq_ref[...], bk=bk_ref[...], bv=bv_ref[...])
    # KV stays the routed capacity-sized set: slice *statically* to the true
    # capacity k so softmax reductions see exactly the xla path's axis
    # length (padding an f32 reduction reorders it — measured non-bitwise)
    xs_t = jax.lax.dynamic_slice_in_dim(xs, t * bk, bk, axis=1)
    hn_t = jax.lax.dynamic_slice_in_dim(hn, t * bk, bk, axis=1)
    qpos_t = jax.lax.dynamic_slice_in_dim(pos, t * bk, bk, axis=1)
    a = _attn_stage(hn_t, hn[:, :k], qpos_t, pos[:, :k], params, spec)
    a_ref[...] = a
    h_ref[...] = xs_t + a


def _routed_attention_call(x, idx, pos_sub, params, spec: RoutedAttnSpec):
    if not spec.interpret:
        raise NotImplementedError(FUSED_NOT_COMPILED)
    B, S, D = x.shape
    k = idx.shape[1]
    bk = min(spec.block_k, k)
    spec = spec._replace(block_k=bk)
    k_pad = -(-k // bk) * bk
    if k_pad != k:
        pad = ((0, 0), (0, k_pad - k))
        idx = jnp.pad(idx, pad, constant_values=-1)
        pos_sub = jnp.pad(pos_sub, pad, constant_values=-1)
    has_bias = "bq" in params
    args = [idx, pos_sub, x, params["ln"], params["wq"], params["wk"],
            params["wv"], params["wo"]]
    in_specs = [
        pl.BlockSpec((B, k_pad), lambda t: (0, 0)),
        pl.BlockSpec((B, k_pad), lambda t: (0, 0)),
        pl.BlockSpec((B, S, D), lambda t: (0, 0, 0)),
        pl.BlockSpec(params["ln"].shape, lambda t: (0,)),
        pl.BlockSpec(params["wq"].shape, lambda t: (0, 0)),
        pl.BlockSpec(params["wk"].shape, lambda t: (0, 0)),
        pl.BlockSpec(params["wv"].shape, lambda t: (0, 0)),
        pl.BlockSpec(params["wo"].shape, lambda t: (0, 0)),
    ]
    if has_bias:
        for key in ("bq", "bk", "bv"):
            args.append(params[key])
            in_specs.append(pl.BlockSpec(params[key].shape, lambda t: (0,)))
    kernel_fn = functools.partial(_routed_attn_kernel, spec=spec, k=k)
    a, h = pl.pallas_call(
        kernel_fn,
        grid=(k_pad // bk,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((B, bk, D), lambda t: (0, t, 0)),
            pl.BlockSpec((B, bk, D), lambda t: (0, t, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, k_pad, D), x.dtype),
            jax.ShapeDtypeStruct((B, k_pad, D), x.dtype),
        ],
        interpret=spec.interpret,
    )(*args)
    return a[:, :k], h[:, :k]


def _routed_attention_host(x, idx, pos_sub, params, spec: RoutedAttnSpec):
    """Pure-jnp mirror of the kernel == the xla backend composition
    (take_along_axis gather -> rmsnorm -> self_attention). The custom VJP
    differentiates *this*, so fused grads are the xla path's grads."""
    x_sub = jnp.take_along_axis(x, idx[..., None], axis=1)
    hn = _mirror_rmsnorm(params["ln"], x_sub, spec.eps)
    a = _attn_stage(hn, hn, pos_sub, pos_sub, params, spec)
    return a, x_sub + a


def _float0(a):
    return np.zeros(a.shape, jax.dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _routed_attention(x, idx, pos_sub, params, spec):
    return _routed_attention_call(x, idx, pos_sub, params, spec)


def _routed_attention_fwd(x, idx, pos_sub, params, spec):
    return _routed_attention_call(x, idx, pos_sub, params, spec), (
        x, idx, pos_sub, params,
    )


def _routed_attention_bwd(spec, res, g):
    x, idx, pos_sub, params = res
    _, vjp = jax.vjp(
        lambda x_, p_: _routed_attention_host(x_, idx, pos_sub, p_, spec), x, params
    )
    dx, dparams = vjp(g)
    return dx, _float0(idx), _float0(pos_sub), dparams


_routed_attention.defvjp(_routed_attention_fwd, _routed_attention_bwd)


def routed_attention(
    x: jax.Array,  # (B, S, D) full residual stream
    idx: jax.Array,  # (B, k) int32 routed rows, sorted unique
    pos_sub: jax.Array,  # (B, k) int32 original positions of routed rows
    params: Dict[str, jax.Array],  # ln, wq, wk, wv, wo (+ bq, bk, bv)
    spec: RoutedAttnSpec,
) -> Tuple[jax.Array, jax.Array]:
    """Fused-dispatch routed attention.

    Returns ``(a_sub, h_sub)``: the attention residual contribution on the
    routed rows and the post-attention hidden ``x[idx] + a`` that feeds the
    routed-MLP kernel — both (B, k, D); no (B, k, D) gather of ``x`` is ever
    written to HBM on the forward path.
    """
    return _routed_attention(x, idx, pos_sub, params, spec)
