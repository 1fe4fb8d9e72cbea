"""Pallas kernels for the ragged flat-token serving layout.

A mixed prefill+decode engine step carries its work as one flat token
stream ``(total_tokens, ...)`` segmented by ``input_row_offsets`` — segment
``s`` owns rows ``[row_offsets[s], row_offsets[s+1])`` and belongs to one
serving slot (``seg_slot[s]``).  Padding exists only as a bounded tail
behind ``row_offsets[-1]``, never between segments, so compute follows
tokens instead of a padded ``(B, S)`` rectangle (the MoD thesis applied to
the batch dimension).  Three kernel families:

- ``ragged_paged_flash_attention``: flash attention whose queries are the
  flat stream and whose K/V is read *directly out of the block-paged pool*
  — the page table rides the grid as a scalar-prefetch operand (the
  ``kernels/paged.py`` trick) so grid step ``(s, i)`` DMAs exactly one
  physical page (every kv head) of segment ``s``'s slot and updates all
  query heads.  No per-slot ``(ctx,)`` view is ever materialized.
- ``ragged_gather_rows`` / ``ragged_scatter_add_rows``: the MoD dispatch
  pair (paper Eq. 1) over the flat stream.  ``idx`` holds *flat* row
  indices grouped per segment ``(n_seg, k)``; ``-1`` marks masked
  selections (a segment shorter than its top-k capacity), which the
  one-hot matmuls drop exactly — no clamp-and-hope writes into a
  neighbouring segment.
- ``ragged_paged_scatter_rows``: the mixed step's write-back — ``W``
  token rows (decode rows + every prefill token of the step) land in
  their slots' pages in one pass; rows with ``valid=False`` are routed to
  a caller-supplied dump page (the pool's scratch page) so shapes stay
  static.

All kernels run under ``interpret=True`` on CPU (validated against the
``kernels/ref.py`` oracles in tests/test_ragged.py) and lower to Mosaic on
TPU.  Because the attention kernel replays ``_flash_kernel``'s op sequence
per page (block_kv = page_size) and the dispatch kernels are one-hot
matmuls over unique indices, their f32 outputs are bit-for-bit equal to
the padded-path formulations they replace — pinned, not just allclose'd,
in the tests.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import NEG_INF
from repro.kernels.routing import _block_s


# ---------------------------------------------------------------------------
# Ragged paged flash attention
# ---------------------------------------------------------------------------


def _ragged_flash_kernel(
    offs_ref,  # (n_seg+1,) scalar-prefetch
    slot_ref,  # (n_seg,)   scalar-prefetch
    tbl_ref,  # (B, P)      scalar-prefetch
    qpos_ref,  # (1, C, 1) this segment's query positions
    q_ref,  # (1, nq, C, hd) this segment's queries, head-major
    kpos_ref,  # (1, 1, p)
    *refs,  # k (1, p, nkv, hd) [, k scales (1, p, nkv)], v [, v scales],
    #         out (1, nq, C, hd), then the acc / max / denominator scratch
    scale: float,
    causal: bool,
    window: int,
    n_pages: int,
    quant: bool,
):
    """One (segment, page) grid step over every query head. With ``quant``
    the narrow K/V page is widened in VMEM right after the DMA (one f32
    scale per page row per kv head — the same multiply the quantized oracle
    uses), so quantized KV never crosses HBM at full width."""
    if quant:
        k_ref, ks_ref, v_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = refs
    else:
        k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
    s_id = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    seg_len = offs_ref[s_id + 1] - offs_ref[s_id]
    qp = qpos_ref[0]  # (C, 1)
    kp = kpos_ref[0]  # (1, p)
    n_q, seg_cap = q_ref.shape[1], q_ref.shape[2]
    n_kv = k_ref.shape[2]
    # rows past this segment's length hold the *next* segment's tokens —
    # mask them here; the wrapper drops their (garbage-zero) output rows
    in_seg = jax.lax.broadcasted_iota(jnp.int32, (seg_cap, kp.shape[1]), 0) < seg_len
    valid = in_seg & (kp >= 0) & (qp >= 0)
    if causal:
        valid &= kp <= qp
    if window > 0:
        valid &= qp - kp < window

    for h in range(n_q):
        hk = h * n_kv // n_q  # GQA: query head h reads kv head hk
        q = q_ref[0, h].astype(jnp.float32)  # (C, hd)
        k = k_ref[0, :, hk, :].astype(jnp.float32)  # (p, hd)
        v = v_ref[0, :, hk, :]
        if quant:
            k = k * ks_ref[0, :, hk : hk + 1]
            v = v.astype(jnp.float32) * vs_ref[0, :, hk : hk + 1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (C, p)
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[h]  # (C, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        m_safe = jnp.where(m_new > NEG_INF / 2, m_new, 0.0)
        p = jnp.exp(s - m_safe)
        p = jnp.where(valid, p, 0.0)
        corr = jnp.where(m_prev > NEG_INF / 2, jnp.exp(m_prev - m_safe), 0.0)
        l_new = l_ref[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[h] = acc_ref[h] * corr + pv
        m_ref[h] = m_new
        l_ref[h] = l_new

    @pl.when(i == n_pages - 1)
    def _finish():
        l_fin = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l_fin).astype(o_ref.dtype)


def flat_segment_ids(row_offsets: jax.Array, total: int) -> jax.Array:
    """seg_id[t] for every flat row: the segment owning token t (rows past
    ``row_offsets[-1]`` map to the last segment; callers mask them)."""
    t = jnp.arange(total, dtype=jnp.int32)
    n_seg = row_offsets.shape[0] - 1
    return jnp.clip(
        jnp.searchsorted(row_offsets, t, side="right") - 1, 0, n_seg - 1
    ).astype(jnp.int32)


def ragged_paged_flash_attention(
    q: jax.Array,  # (T, nq, hd) flat query stream
    k_pages: jax.Array,  # (N, p, nkv, hd)
    v_pages: jax.Array,  # (N, p, nkv, hd)
    pos_pages: jax.Array,  # (N, p) int32 absolute positions; -1 = empty slot
    table: jax.Array,  # (B, P) int32 per-slot page table
    row_offsets: jax.Array,  # (n_seg+1,) int32, non-decreasing, starts at 0
    seg_slot: jax.Array,  # (n_seg,) int32 — the slot whose pages segment s reads
    q_pos: jax.Array,  # (T,) int32 absolute positions; -1 = invalid row
    *,
    seg_cap: int,  # static bound: every segment has <= seg_cap tokens
    causal: bool = True,
    window: int = 0,
    scale: Optional[float] = None,
    interpret: bool = False,
    k_scales: Optional[jax.Array] = None,  # (N, p, nkv) f32 — quantized KV
    v_scales: Optional[jax.Array] = None,
) -> jax.Array:  # (T, nq, hd); rows past row_offsets[-1] are zero
    T, nq, hd = q.shape
    N, p, nkv, _ = k_pages.shape
    B, P = table.shape
    n_seg = row_offsets.shape[0] - 1
    assert nq % nkv == 0
    assert (k_scales is None) == (v_scales is None)
    quant = k_scales is not None
    scale = scale if scale is not None else 1.0 / (hd**0.5)
    C = int(seg_cap)

    # window each segment's C query rows out of the flat stream (padded by
    # one capacity so the last window stays in bounds), head-major, so every
    # block's last two dims are whole array dims (the Mosaic tiling rule)
    win = row_offsets[:-1, None].astype(jnp.int32) + jnp.arange(C, dtype=jnp.int32)
    qf = jnp.pad(q, ((0, C), (0, 0), (0, 0)))
    q_seg = jnp.take(qf, win, axis=0).transpose(0, 2, 1, 3)  # (n_seg, nq, C, hd)
    qp_seg = jnp.take(
        jnp.pad(q_pos.astype(jnp.int32), (0, C), constant_values=-1), win
    )[..., None]  # (n_seg, C, 1)

    def page(s, i, offs, slot, tbl):
        return tbl[slot[s], i]

    kv_spec = pl.BlockSpec((1, p, nkv, hd), lambda *a: (page(*a), 0, 0, 0))
    sc_spec = pl.BlockSpec((1, p, nkv), lambda *a: (page(*a), 0, 0))
    in_specs = [
        pl.BlockSpec((1, C, 1), lambda s, i, *_: (s, 0, 0)),
        pl.BlockSpec((1, nq, C, hd), lambda s, i, *_: (s, 0, 0, 0)),
        pl.BlockSpec((1, 1, p), lambda *a: (page(*a), 0, 0)),
        kv_spec,
        *([sc_spec] if quant else []),
        kv_spec,
        *([sc_spec] if quant else []),
    ]
    operands = [pos_pages.astype(jnp.int32).reshape(N, 1, p), k_pages]
    if quant:
        operands.append(k_scales.astype(jnp.float32))
    operands.append(v_pages)
    if quant:
        operands.append(v_scales.astype(jnp.float32))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_seg, P),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, nq, C, hd), lambda s, i, *_: (s, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nq, C, hd), jnp.float32),
            pltpu.VMEM((nq, C, 1), jnp.float32),
            pltpu.VMEM((nq, C, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _ragged_flash_kernel, scale=float(scale), causal=bool(causal),
        window=int(window), n_pages=P, quant=quant,
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_seg, nq, C, hd), q.dtype),
        interpret=interpret,
    )(row_offsets.astype(jnp.int32), seg_slot.astype(jnp.int32),
      table.astype(jnp.int32), qp_seg, q_seg, *operands)

    # scatter the (n_seg, C) segment rows back onto the flat stream
    seg_id = flat_segment_ids(row_offsets, T)
    local = jnp.clip(jnp.arange(T, dtype=jnp.int32) - row_offsets[seg_id], 0, C - 1)
    flat = out[seg_id, :, local, :]  # (T, nq, hd)
    live = jnp.arange(T) < row_offsets[-1]
    return jnp.where(live[:, None, None], flat, 0)


# ---------------------------------------------------------------------------
# Ragged MoD dispatch: flat-stream gather / gated scatter-add
# ---------------------------------------------------------------------------


def _ragged_gather_kernel(idx_ref, x_ref, o_ref, acc_ref, *, bs: int, n_blocks: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    idx = idx_ref[0]  # (k, 1) flat row ids; -1 never matches any row
    k = idx.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (k, bs), 1) + j * bs
    P = (rows == idx).astype(jnp.float32)
    acc_ref[...] += jax.lax.dot_general(
        P, x_ref[...].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(j == n_blocks - 1)
    def _finish():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def ragged_gather_rows(
    x: jax.Array,  # (T, D) flat stream
    idx: jax.Array,  # (n_seg, k) int32 flat indices; -1 = masked (zero row)
    *,
    interpret: bool = False,
    block_s: int = 256,
) -> jax.Array:  # (n_seg, k, D)
    T, D = x.shape
    n_seg, k = idx.shape
    bs = _block_s(T, block_s)
    n_blocks = T // bs
    kernel = functools.partial(_ragged_gather_kernel, bs=bs, n_blocks=n_blocks)
    return pl.pallas_call(
        kernel,
        grid=(n_seg, n_blocks),
        in_specs=[
            pl.BlockSpec((1, k, 1), lambda s, j: (s, 0, 0)),
            pl.BlockSpec((bs, D), lambda s, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((1, k, D), lambda s, j: (s, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_seg, k, D), x.dtype),
        scratch_shapes=[pltpu.VMEM((k, D), jnp.float32)],
        interpret=interpret,
    )(idx.astype(jnp.int32).reshape(n_seg, k, 1), x)


def _ragged_scatter_kernel(
    idx_ref, gate_ref, d_ref, x_ref, o_ref, acc_ref, *, bs: int, n_seg: int
):
    j = pl.program_id(0)
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    idx = idx_ref[0]  # (1, k)
    k = idx.shape[1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (bs, k), 0) + j * bs
    P = (rows == idx).astype(jnp.float32)  # -1 matches nothing
    gated = gate_ref[0] * d_ref[0].astype(jnp.float32)  # (k, 1) * (k, D)
    acc_ref[...] += jax.lax.dot_general(
        P, gated, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(s == n_seg - 1)
    def _finish():
        o_ref[...] = x_ref[...] + acc_ref[...].astype(o_ref.dtype)


def ragged_scatter_add_rows(
    x: jax.Array,  # (T, D) flat stream
    idx: jax.Array,  # (n_seg, k) int32 flat indices, unique where >= 0
    delta: jax.Array,  # (n_seg, k, D)
    gate: jax.Array,  # (n_seg, k) f32 (0 at masked selections)
    *,
    interpret: bool = False,
    block_s: int = 256,
) -> jax.Array:  # (T, D)
    T, D = x.shape
    n_seg, k = idx.shape
    bs = _block_s(T, block_s)
    kernel = functools.partial(_ragged_scatter_kernel, bs=bs, n_seg=n_seg)
    return pl.pallas_call(
        kernel,
        grid=(T // bs, n_seg),
        in_specs=[
            pl.BlockSpec((1, 1, k), lambda j, s: (s, 0, 0)),
            pl.BlockSpec((1, k, 1), lambda j, s: (s, 0, 0)),
            pl.BlockSpec((1, k, D), lambda j, s: (s, 0, 0)),
            pl.BlockSpec((bs, D), lambda j, s: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bs, D), lambda j, s: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((T, D), x.dtype),
        scratch_shapes=[pltpu.VMEM((bs, D), jnp.float32)],
        interpret=interpret,
    )(idx.astype(jnp.int32).reshape(n_seg, 1, k),
      gate.astype(jnp.float32).reshape(n_seg, k, 1), delta, x)


# ---------------------------------------------------------------------------
# Ragged paged write-back: W token rows into the pool in one pass
# ---------------------------------------------------------------------------


def ragged_page_targets(
    table: jax.Array,  # (B, P) int32
    slot: jax.Array,  # (W,) int32
    pos: jax.Array,  # (W,) int32 logical positions
    valid: jax.Array,  # (W,) bool
    page_size: int,
    dump_page: int,
) -> tuple:
    """(physical page id, in-page offset) per write row; invalid rows are
    routed to ``dump_page`` (the pool's scratch page) at offset 0."""
    P = table.shape[1]
    lpage = jnp.clip(pos // page_size, 0, P - 1)
    pid = table[jnp.clip(slot, 0, table.shape[0] - 1), lpage]
    pid = jnp.where(valid, pid, dump_page).astype(jnp.int32)
    off = jnp.where(valid, pos % page_size, 0).astype(jnp.int32)
    return pid, off


def ragged_paged_scatter_rows_xla(
    pages: jax.Array,  # lead + (N, p) + tail
    pid: jax.Array,  # (W,) physical page per row
    off: jax.Array,  # (W,) in-page offset per row
    rows: jax.Array,  # lead + (W,) + tail
    page_axis: int = 0,
) -> jax.Array:
    """pages[..., pid[w], off[w], ...] = rows[..., w, ...].

    Valid (pid, off) pairs are unique by contract (one write per token);
    dump-page rows may collide — their contents are garbage by contract.
    """
    N, p = pages.shape[page_axis], pages.shape[page_axis + 1]
    lead = pages.shape[:page_axis]
    tail = pages.shape[page_axis + 2 :]
    flat = pages.reshape(lead + (N * p,) + tail)
    fi = pid * p + off
    idx = (slice(None),) * len(lead) + (fi,)
    flat = flat.at[idx].set(rows.astype(flat.dtype))
    return flat.reshape(pages.shape)


def _ragged_ps_kernel(pid_ref, off_ref, rows_ref, page_ref, o_ref, *, n_rows: int):
    n = pl.program_id(0)
    page = page_ref[0]  # (p, F)
    row_of = jax.lax.broadcasted_iota(jnp.int32, page.shape, 0)
    # every physical page checks each write row; W is the step's token
    # budget (small), so this is a short static loop of masked selects
    # (kernels/paged.py: no one-row stores at unprovable sublane offsets)
    for w in range(n_rows):
        hit = (row_of == off_ref[w]) & (pid_ref[w] == n)
        page = jnp.where(hit, rows_ref[w : w + 1, :], page)
    o_ref[0] = page


def ragged_paged_scatter_rows_pallas(
    pages: jax.Array,  # (N, p, F) canonical layout
    pid: jax.Array,  # (W,)
    off: jax.Array,  # (W,)
    rows: jax.Array,  # (W, F)
    *,
    interpret: bool = False,
) -> jax.Array:
    N, p, F = pages.shape
    W = pid.shape[0]
    kernel = functools.partial(_ragged_ps_kernel, n_rows=W)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(N,),
        in_specs=[
            pl.BlockSpec((W, F), lambda n, pid, off: (0, 0)),
            pl.BlockSpec((1, p, F), lambda n, pid, off: (n, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, p, F), lambda n, pid, off: (n, 0, 0)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, p, F), pages.dtype),
        interpret=interpret,
    )(pid.astype(jnp.int32), off.astype(jnp.int32),
      rows.astype(pages.dtype), pages)
