"""Pallas TPU kernels for MoD routed dispatch: fused row-gather + gated
scatter-add (the two data-movement halves of paper Eq. 1).

Both kernels express the data-dependent row permutation as a one-hot
selection matmul so the inner loop is pure MXU work and the (B, S, D)
operand streams through VMEM exactly once:

- ``gather_rows(x, idx)``:  out[b, i] = x[b, idx[b, i]]
  grid (B, S/bs); each step folds P_j^T @ x_block into a (k, D) f32
  accumulator, where P_j[i, r] = [idx[b, i] == j*bs + r].
- ``scatter_add_rows(x, idx, delta, gate)``:
  out[b, s] = x[b, s] + cast(gate[b, i] * delta[b, i]) where idx[b, i] == s
  grid (B, S/bs); each output block is x_block + P_j @ (gate * delta),
  fusing the f32 gating multiply into the scatter pass.

Because top-k indices are unique per sequence, every output row receives at
most one contribution, so the f32 one-hot matmuls are *bit-exact* against
the XLA ``take_along_axis`` / ``at[].add`` formulation (validated in
tests/test_routing_backends.py).

Both ops carry a custom VJP (gather's backward is the scatter kernel with a
unit gate; scatter's backward reuses the gather kernel), so the pallas
backend is usable inside the training graph. On CPU the kernels run with
``interpret=True``; on TPU the same pallas_call lowers to Mosaic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _block_s(seq_len: int, block_s: int) -> int:
    """Largest divisor of seq_len that is <= block_s (blocks must tile S)."""
    bs = min(block_s, seq_len)
    while seq_len % bs:
        bs -= 1
    return bs


# ---------------------------------------------------------------------------
# gather: out[b, i, :] = x[b, idx[b, i], :]
# ---------------------------------------------------------------------------


def _gather_kernel(idx_ref, x_ref, o_ref, acc_ref, *, bs: int, n_blocks: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    idx = idx_ref[0]  # (k, 1)
    k = idx.shape[0]
    # P[i, r] = 1 iff selected row i lives at row r of this S-block
    rows = jax.lax.broadcasted_iota(jnp.int32, (k, bs), 1) + j * bs
    P = (rows == idx).astype(jnp.float32)
    acc_ref[...] += jax.lax.dot_general(
        P,
        x_ref[0].astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(j == n_blocks - 1)
    def _finish():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _gather_call(x, idx, interpret, block_s):
    B, S, D = x.shape
    k = idx.shape[1]
    bs = _block_s(S, block_s)
    n_blocks = S // bs
    kernel = functools.partial(_gather_kernel, bs=bs, n_blocks=n_blocks)
    return pl.pallas_call(
        kernel,
        grid=(B, n_blocks),
        in_specs=[
            pl.BlockSpec((1, k, 1), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, bs, D), lambda b, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, k, D), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, k, D), x.dtype),
        scratch_shapes=[pltpu.VMEM((k, D), jnp.float32)],
        interpret=interpret,
    )(idx.reshape(B, k, 1), x)


# ---------------------------------------------------------------------------
# gated scatter-add: out[b, s, :] = x[b, s, :] (+ cast(gate * delta) if routed)
# ---------------------------------------------------------------------------


def _scatter_kernel(idx_ref, gate_ref, x_ref, d_ref, o_ref, *, bs: int):
    j = pl.program_id(1)
    idx = idx_ref[0]  # (1, k)
    k = idx.shape[1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (bs, k), 0) + j * bs
    P = (rows == idx).astype(jnp.float32)  # (bs, k)
    gated = gate_ref[0] * d_ref[0].astype(jnp.float32)  # (k, 1) * (k, D)
    upd = jax.lax.dot_general(
        P, gated, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    o_ref[0] = x_ref[0] + upd.astype(o_ref.dtype)


def _scatter_call(x, idx, delta, gate, interpret, block_s):
    B, S, D = x.shape
    k = idx.shape[1]
    bs = _block_s(S, block_s)
    kernel = functools.partial(_scatter_kernel, bs=bs)
    return pl.pallas_call(
        kernel,
        grid=(B, S // bs),
        in_specs=[
            pl.BlockSpec((1, 1, k), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, k, 1), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, bs, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, k, D), lambda b, j: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bs, D), lambda b, j: (b, j, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S, D), x.dtype),
        interpret=interpret,
    )(idx.reshape(B, 1, k), gate.astype(jnp.float32).reshape(B, k, 1), x, delta)


# ---------------------------------------------------------------------------
# differentiable wrappers (custom VJP; idx is index-valued -> float0 tangent)
# ---------------------------------------------------------------------------


def _float0(idx):
    return np.zeros(idx.shape, jax.dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _gather_rows(x, idx, interpret, block_s):
    return _gather_call(x, idx, interpret, block_s)


def _gather_fwd(x, idx, interpret, block_s):
    return _gather_call(x, idx, interpret, block_s), (idx, x.shape)


def _gather_bwd(interpret, block_s, res, g):
    idx, x_shape = res
    zeros = jnp.zeros(x_shape, g.dtype)
    ones = jnp.ones(idx.shape, jnp.float32)
    dx = _scatter_call(zeros, idx, g, ones, interpret, block_s)
    return dx, _float0(idx)


_gather_rows.defvjp(_gather_fwd, _gather_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _scatter_add_rows(x, idx, delta, gate, interpret, block_s):
    return _scatter_call(x, idx, delta, gate, interpret, block_s)


def _scatter_fwd(x, idx, delta, gate, interpret, block_s):
    return _scatter_call(x, idx, delta, gate, interpret, block_s), (idx, delta, gate)


def _scatter_bwd(interpret, block_s, res, g):
    idx, delta, gate = res
    g_sub = _gather_call(g, idx, interpret, block_s)  # (B, k, D)
    ddelta = (gate[..., None] * g_sub.astype(jnp.float32)).astype(delta.dtype)
    dgate = jnp.sum(
        g_sub.astype(jnp.float32) * delta.astype(jnp.float32), axis=-1
    ).astype(gate.dtype)
    return g, _float0(idx), ddelta, dgate


_scatter_add_rows.defvjp(_scatter_fwd, _scatter_bwd)


def gather_rows(
    x: jax.Array,  # (B, S, D)
    idx: jax.Array,  # (B, k) int32, unique per row
    *,
    interpret: bool = False,
    block_s: int = 256,
) -> jax.Array:  # (B, k, D)
    return _gather_rows(x, idx, interpret, block_s)


def scatter_add_rows(
    x: jax.Array,  # (B, S, D)
    idx: jax.Array,  # (B, k) int32, unique per row
    delta: jax.Array,  # (B, k, D)
    gate: jax.Array,  # (B, k) f32 router weights
    *,
    interpret: bool = False,
    block_s: int = 256,
) -> jax.Array:  # (B, S, D)
    return _scatter_add_rows(x, idx, delta, gate, interpret, block_s)
