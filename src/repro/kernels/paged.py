"""Pallas kernels for the block-paged KV pool (serve/cache.PagedCachePool).

A paged cache leaf stores its per-position axis as ``(n_pages, page_size)``
physical blocks instead of a contiguous ``(B, ctx)`` slab; a per-slot page
table ``(B, P = ctx // page_size)`` maps logical pages to physical ones.
Two decode-only data-movement ops (no VJP — the serving step never
differentiates):

- ``paged_gather(pages, table)``: materialize every slot's logical
  ``(ctx,)`` view for the attention read —
  ``out[b, i*p + r] = pages[table[b, i], r]``. The page table rides the
  grid as a scalar-prefetch operand so each (b, i) grid step DMAs exactly
  one physical page (the vLLM paged-attention read pattern).
- ``paged_scatter_rows(pages, table, rows, pos)``: write the decode step's
  single new row per slot into its tail page —
  ``pages[table[b, pos[b] // p], pos[b] % p] = rows[b]``. The grid walks
  physical pages, so untouched pages stream through unchanged and the op
  needs no input/output aliasing to be total.

Both run in ``interpret=True`` on CPU (validated against ``kernels/ref.py``
oracles in tests/test_paged.py) and lower to Mosaic on TPU. The canonical
layout is ``pages (N, p, F)`` / ``rows (B, F)``; the leaf-shaped wrappers
in ``kernels/ops.py`` fold arbitrary lead/tail dims into F.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# ---------------------------------------------------------------------------
# XLA reference implementations (the serving engine's default backend)
# ---------------------------------------------------------------------------


def paged_gather_xla(pages: jax.Array, table: jax.Array, page_axis: int = 0) -> jax.Array:
    """out[..., b, i*p + r, ...] = pages[..., table[b, i], r, ...].

    ``pages``: lead + (N, p) + tail with the page axis at ``page_axis``;
    ``table``: (B, P) int32. Returns lead + (B, P*p) + tail.
    """
    p = pages.shape[page_axis + 1]
    B, P = table.shape
    out = jnp.take(pages, table, axis=page_axis)  # lead + (B, P, p) + tail
    shape = pages.shape[:page_axis] + (B, P * p) + pages.shape[page_axis + 2 :]
    return out.reshape(shape)


def paged_scatter_rows_xla(
    pages: jax.Array,  # lead + (N, p) + tail
    table: jax.Array,  # (B, P) int32
    rows: jax.Array,  # lead + (B,) + tail — one new row per slot
    pos: jax.Array,  # (B,) int32 logical positions
    page_axis: int = 0,
) -> jax.Array:
    """pages[..., table[b, pos[b]//p], pos[b]%p, ...] = rows[..., b, ...].

    Slots whose page-table entry routes to a reserved scratch page may
    collide; writes there are garbage by contract (free slots).
    """
    N, p = pages.shape[page_axis], pages.shape[page_axis + 1]
    lead = pages.shape[:page_axis]
    tail = pages.shape[page_axis + 2 :]
    flat = pages.reshape(lead + (N * p,) + tail)
    pid = jnp.take_along_axis(table, (pos // p)[:, None], axis=1)[:, 0]  # (B,)
    fi = pid * p + pos % p
    idx = (slice(None),) * len(lead) + (fi,)
    flat = flat.at[idx].set(rows.astype(flat.dtype))
    return flat.reshape(pages.shape)


# ---------------------------------------------------------------------------
# Pallas variants (canonical (N, p, F) layout)
# ---------------------------------------------------------------------------


def _gather_kernel(tbl_ref, page_ref, o_ref):
    # the BlockSpec index_map already selected page table[b, i]; pure copy
    o_ref[0, 0] = page_ref[0]


def paged_gather_pallas(
    pages: jax.Array,  # (N, p, F)
    table: jax.Array,  # (B, P) int32
    *,
    interpret: bool = False,
) -> jax.Array:  # (B, P*p, F)
    N, p, F = pages.shape
    B, P = table.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, P),
        in_specs=[pl.BlockSpec((1, p, F), lambda b, i, tbl: (tbl[b, i], 0, 0))],
        out_specs=pl.BlockSpec((1, 1, p, F), lambda b, i, tbl: (b, i, 0, 0)),
    )
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, P, p, F), pages.dtype),
        interpret=interpret,
    )(table, pages)
    return out.reshape(B, P * p, F)


def _gather_dequant_kernel(tbl_ref, page_ref, scale_ref, o_ref):
    # fused dequant: the narrow page is widened in VMEM right after the DMA
    # — quantized KV never crosses HBM at full width. The block multiply is
    # the same expression the xla reference uses (serve/quant.dequant_rows),
    # so both backends produce identical bits.
    from repro.serve.quant import dequant_rows

    o_ref[0, 0] = dequant_rows(page_ref[0], scale_ref[0])


def paged_gather_dequant_pallas(
    pages: jax.Array,  # (N, p, F) narrow (int8 | fp8)
    scales: jax.Array,  # (N, p, G) f32 per-row(-block) scales
    table: jax.Array,  # (B, P) int32
    *,
    interpret: bool = False,
) -> jax.Array:  # (B, P*p, F) f32
    N, p, F = pages.shape
    G = scales.shape[-1]
    B, P = table.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, P),
        in_specs=[
            pl.BlockSpec((1, p, F), lambda b, i, tbl: (tbl[b, i], 0, 0)),
            pl.BlockSpec((1, p, G), lambda b, i, tbl: (tbl[b, i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, p, F), lambda b, i, tbl: (b, i, 0, 0)),
    )
    out = pl.pallas_call(
        _gather_dequant_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, P, p, F), jnp.float32),
        interpret=interpret,
    )(table, pages, scales)
    return out.reshape(B, P * p, F)


def paged_gather_dequant_xla(
    pages: jax.Array,  # (N, p, F) narrow
    scales: jax.Array,  # (N, p, G) f32
    table: jax.Array,  # (B, P) int32
) -> jax.Array:  # (B, P*p, F) f32
    """XLA reference of the fused-dequant gather: gather narrow pages and
    their scales, widen with the shared block multiply."""
    from repro.serve.quant import dequant_rows

    return dequant_rows(
        paged_gather_xla(pages, table), paged_gather_xla(scales, table)
    )


def _scatter_kernel(pid_ref, off_ref, rows_ref, page_ref, o_ref, *, n_slots: int):
    n = pl.program_id(0)
    page = page_ref[0]  # (p, F)
    row_of = jax.lax.broadcasted_iota(jnp.int32, page.shape, 0)
    # each physical page checks every slot for a write landing on it; B is
    # the decode batch (small), so this is a short static loop. The row is
    # written by a masked select, not a one-row store: Mosaic can only
    # store at sublane offsets it can prove tile-aligned.
    for b in range(n_slots):
        hit = (row_of == off_ref[b]) & (pid_ref[b] == n)
        page = jnp.where(hit, rows_ref[b : b + 1, :], page)
    o_ref[0] = page


def paged_scatter_rows_pallas(
    pages: jax.Array,  # (N, p, F)
    table: jax.Array,  # (B, P) int32
    rows: jax.Array,  # (B, F)
    pos: jax.Array,  # (B,) int32
    *,
    interpret: bool = False,
) -> jax.Array:  # (N, p, F)
    N, p, F = pages.shape
    B = pos.shape[0]
    pid = jnp.take_along_axis(table, (pos // p)[:, None], axis=1)[:, 0]
    off = (pos % p).astype(jnp.int32)
    kernel = functools.partial(_scatter_kernel, n_slots=B)
    # pid/off ride in SMEM (scalar prefetch)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(N,),
        in_specs=[
            pl.BlockSpec((B, F), lambda n, pid, off: (0, 0)),
            pl.BlockSpec((1, p, F), lambda n, pid, off: (n, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, p, F), lambda n, pid, off: (n, 0, 0)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, p, F), pages.dtype),
        interpret=interpret,
    )(pid.astype(jnp.int32), off, rows.astype(pages.dtype), pages)
