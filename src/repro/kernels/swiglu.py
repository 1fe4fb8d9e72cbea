"""Pallas TPU fused SwiGLU MLP: y = (silu(x Wg) * (x Wu)) Wd in one pass.

Grid = (M/bm, F/bf); the F axis is sequential ("arbitrary") and accumulates
the down-projection into a VMEM f32 scratch, so the (M, F) hidden
activation is never materialized in HBM — the fusion that matters for the
memory-roofline term of the MLP. Block sizes default to bm=256, bf=512:
VMEM footprint = x (bm, D) + Wg/Wu (D, bf) + Wd (bf, D) + acc (bm, D)
≈ 2·bm·D·2 + 3·D·bf·2 + bm·D·4 bytes ≈ 13 MiB at D=4096 — inside the
16 MiB/core budget, all dims 128-aligned for the MXU.

This module also holds :func:`routed_mlp_scatter`, the MLP half of the
``pallas_fused`` MoD backend: the block's (Swi/Ge)GLU MLP runs on the
capacity-sized routed rows and the kernel epilogue performs the gated
scatter-add ``x + P @ (gate·(a + m))`` of paper Eq. 1 in the same pass —
the standalone scatter pass of the xla/pallas backends disappears. See
DESIGN.md §Backend selection.
"""
from __future__ import annotations

import functools
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the bitwise models.layers mirrors + float0 helper are shared with the
# routed-attention kernel so the two fused halves can never drift apart
from repro.kernels.flash_attention import FUSED_NOT_COMPILED, _float0, _mirror_rmsnorm


def _swiglu_kernel(x_ref, wg_ref, wu_ref, wd_ref, o_ref, acc_ref, *, n_f_blocks: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]  # (bm, D)
    g = jax.lax.dot_general(
        x, wg_ref[...], (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    u = jax.lax.dot_general(
        x, wu_ref[...], (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    h = (jax.nn.silu(g) * u).astype(x.dtype)  # (bm, bf)
    acc_ref[...] += jax.lax.dot_general(
        h, wd_ref[...], (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(j == n_f_blocks - 1)
    def _finish():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def swiglu(
    x: jax.Array,  # (M, D)
    w_gate: jax.Array,  # (D, F)
    w_up: jax.Array,  # (D, F)
    w_down: jax.Array,  # (F, D)
    *,
    block_m: int = 256,
    block_f: int = 512,
    interpret: bool = False,
) -> jax.Array:
    M, D = x.shape
    F = w_gate.shape[1]
    bm, bf = min(block_m, M), min(block_f, F)
    assert M % bm == 0 and F % bf == 0, (M, bm, F, bf)
    grid = (M // bm, F // bf)
    kernel = functools.partial(_swiglu_kernel, n_f_blocks=F // bf)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, D), lambda i, j: (i, 0)),
            pl.BlockSpec((D, bf), lambda i, j: (0, j)),
            pl.BlockSpec((D, bf), lambda i, j: (0, j)),
            pl.BlockSpec((bf, D), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, D), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, D), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, D), jnp.float32)],
        interpret=interpret,
    )(x, w_gate, w_up, w_down)



# ---------------------------------------------------------------------------
# Routed MLP with gated scatter-add epilogue (the MLP half of the
# "pallas_fused" backend). The MLP math mirrors models.layers.mlp and the
# epilogue mirrors core.routing._scatter_add_tokens bitwise; the custom VJP
# differentiates the mirror, so grads equal the xla path's.
# ---------------------------------------------------------------------------


class RoutedMlpSpec(NamedTuple):
    """Static config (hashable for nondiff_argnums / jit static args)."""

    act: str  # "silu" | "gelu"
    eps: float
    block_s: int
    interpret: bool


def _mirror_mlp(params: Dict[str, jax.Array], x: jax.Array, act: str) -> jax.Array:
    # mirrors models.layers.mlp bitwise
    act_fn = jax.nn.silu if act == "silu" else jax.nn.gelu
    up = x @ params["w_up"]
    if "w_gate" in params:
        up = act_fn(x @ params["w_gate"]) * up
    else:
        up = act_fn(up)
    return up @ params["w_down"]


def _gated_delta(params, h_sub, a_sub, gate, spec: RoutedMlpSpec) -> jax.Array:
    """f32 gated block delta gate·(a + mlp(norm(h))) — shared by kernel/ref."""
    hn = _mirror_rmsnorm(params["ln"], h_sub, spec.eps)
    m = _mirror_mlp(params, hn, spec.act)
    delta = a_sub + m
    return gate[..., None] * delta.astype(jnp.float32)


def _routed_mlp_kernel(
    idx_ref, gate_ref, h_ref, a_ref, ln_ref, wu_ref, wd_ref,
    *rest, spec: RoutedMlpSpec, bs: int
):
    if len(rest) == 4:  # GLU configs carry the gate projection
        wg_ref, x_ref, o_ref, acc_ref = rest
    else:
        (x_ref, o_ref, acc_ref), wg_ref = rest, None
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _mlp():
        # the capacity-sized MLP runs once; its gated delta lives in VMEM
        # scratch for the scatter epilogue below
        params = {"ln": ln_ref[...], "w_up": wu_ref[...], "w_down": wd_ref[...]}
        if wg_ref is not None:
            params["w_gate"] = wg_ref[...]
        acc_ref[...] = _gated_delta(params, h_ref[...], a_ref[...], gate_ref[...], spec)

    # epilogue: gated scatter-add of the delta into this output S-block
    # (one-hot matmul; unique idx -> each row gets at most one contribution,
    # bit-exact vs at[].add — same formulation as kernels/routing.py)
    idx = idx_ref[...]  # (B, k)
    B, k = idx.shape
    rows = jax.lax.broadcasted_iota(jnp.int32, (B, bs, k), 1) + j * bs
    P = (rows == idx[:, None, :]).astype(jnp.float32)
    upd = jnp.einsum("bsk,bkd->bsd", P, acc_ref[...])
    o_ref[...] = x_ref[...] + upd.astype(o_ref.dtype)


def _block_div(seq_len: int, block_s: int) -> int:
    bs = min(block_s, seq_len)
    while seq_len % bs:
        bs -= 1
    return bs


def _routed_mlp_call(x, h_sub, a_sub, idx, gate, params, spec: RoutedMlpSpec):
    if not spec.interpret:
        raise NotImplementedError(FUSED_NOT_COMPILED)
    B, S, D = x.shape
    k = idx.shape[1]
    F = params["w_up"].shape[1]
    bs = _block_div(S, spec.block_s)
    args = [idx, gate.astype(jnp.float32), h_sub, a_sub,
            params["ln"], params["w_up"], params["w_down"]]
    in_specs = [
        pl.BlockSpec((B, k), lambda j: (0, 0)),
        pl.BlockSpec((B, k), lambda j: (0, 0)),
        pl.BlockSpec((B, k, D), lambda j: (0, 0, 0)),
        pl.BlockSpec((B, k, D), lambda j: (0, 0, 0)),
        pl.BlockSpec(params["ln"].shape, lambda j: (0,)),
        pl.BlockSpec((D, F), lambda j: (0, 0)),
        pl.BlockSpec((F, D), lambda j: (0, 0)),
    ]
    if "w_gate" in params:
        args.append(params["w_gate"])
        in_specs.append(pl.BlockSpec((D, F), lambda j: (0, 0)))
    args.append(x)
    in_specs.append(pl.BlockSpec((B, bs, D), lambda j: (0, j, 0)))
    kernel_fn = functools.partial(_routed_mlp_kernel, spec=spec, bs=bs)
    return pl.pallas_call(
        kernel_fn,
        grid=(S // bs,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((B, bs, D), lambda j: (0, j, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S, D), x.dtype),
        scratch_shapes=[pltpu.VMEM((B, k, D), jnp.float32)],
        interpret=spec.interpret,
    )(*args)


def _routed_mlp_host(x, h_sub, a_sub, idx, gate, params, spec: RoutedMlpSpec):
    """Pure-jnp mirror == the xla composition (rmsnorm -> mlp -> gated
    at[].add). The custom VJP differentiates this."""
    gated = _gated_delta(params, h_sub, a_sub, gate, spec)
    update = gated.astype(x.dtype)
    B = x.shape[0]
    return x.at[jnp.arange(B)[:, None], idx].add(update)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _routed_mlp_scatter(x, h_sub, a_sub, idx, gate, params, spec):
    return _routed_mlp_call(x, h_sub, a_sub, idx, gate, params, spec)


def _routed_mlp_fwd(x, h_sub, a_sub, idx, gate, params, spec):
    out = _routed_mlp_call(x, h_sub, a_sub, idx, gate, params, spec)
    return out, (x, h_sub, a_sub, idx, gate, params)


def _routed_mlp_bwd(spec, res, g):
    x, h_sub, a_sub, idx, gate, params = res
    _, vjp = jax.vjp(
        lambda x_, h_, a_, g_, p_: _routed_mlp_host(x_, h_, a_, idx, g_, p_, spec),
        x, h_sub, a_sub, gate, params,
    )
    dx, dh, da, dgate, dparams = vjp(g)
    return dx, dh, da, _float0(idx), dgate, dparams


_routed_mlp_scatter.defvjp(_routed_mlp_fwd, _routed_mlp_bwd)


def routed_mlp_scatter(
    x: jax.Array,  # (B, S, D) full residual stream
    h_sub: jax.Array,  # (B, k, D) post-attention hidden of routed rows
    a_sub: jax.Array,  # (B, k, D) attention contribution of routed rows
    idx: jax.Array,  # (B, k) int32 routed rows, sorted unique
    gate: jax.Array,  # (B, k) f32 router gates
    params: Dict[str, jax.Array],  # ln, w_up, w_down (+ w_gate)
    spec: RoutedMlpSpec,
) -> jax.Array:  # (B, S, D)
    """Routed-MLP kernel whose epilogue is paper Eq. 1's gated combine:
    ``out = x + P @ (gate · (a + mlp(rmsnorm(h))))`` in a single pass over
    the residual stream — no standalone scatter kernel, no HBM round trip
    for the block delta."""
    return _routed_mlp_scatter(x, h_sub, a_sub, idx, gate, params, spec)
