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

``paged_decode_attention`` is the padded decode step's attention read: one
query per slot against that slot's K/V, read through the page table straight
out of the layer-stacked pool (its ``(L, N, nkv, p, hd)`` view) and only up
to the slot's last live page, which it copies by hand in blocks of 128 keys.
Its oracle is the XLA formulation in ``models/paged_kv.py`` (gather by the
table, then ``attend``).
"""
from __future__ import annotations

import functools
from typing import Optional

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


# ---------------------------------------------------------------------------
# Live-page decode attention over the layer-stacked pool
# ---------------------------------------------------------------------------

NEG_INF = -1e30  # kernels/flash_attention.NEG_INF, the masked-score value
# positions are read as aligned 128-lane windows of the (p, N) plane, the
# window holding page n's column being n // 128
_POS_LANES = 128
# the layer's whole position plane stays in VMEM for the call while one
# buffer of it fits this; a larger pool takes the per-page read
_POS_PLANE_BYTES = 4 * 1024 * 1024


def _split3(e: jax.Array) -> jax.Array:
    """f32 ``e`` as three bf16 parts stacked on axis 1, whose sum is ``e`` to
    2**-27 relative: a product with bf16 values is then exact in f32."""
    hi = e.astype(jnp.bfloat16)
    r = e - hi.astype(jnp.float32)
    mid = r.astype(jnp.bfloat16)
    lo = (r - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return jnp.concatenate([hi, mid, lo], axis=1)


def _live_decode_kernel(
    tbl_ref,  # (B, P) scalar-prefetch page table
    lay_ref,  # (1,) scalar-prefetch layer of the stack
    npg_ref,  # (B,) scalar-prefetch live pages per slot
    qpos_ref,  # (B,) scalar-prefetch query positions
    q_ref,  # (nkv, g, hd) this slot's query heads, grouped by kv head
    k_hbm,  # (L, N, nkv, p, hd) in HBM
    v_hbm,
    pos_ref,  # (p, W) the layer's position plane, in VMEM for the call
    o_ref,  # (nkv, g, hd)
    kbuf,  # (2, nkv, ppb * p, hd) two-deep K/V buffers
    vbuf,
    sem,  # (2,) DMA semaphores, one per buffer
    nxt,  # (1,) SMEM: the buffer the next slot's first block lands in
    *,
    scale: float,
    causal: bool,
    window: int,
    ppb: int,
):
    """One slot: a loop over its live blocks of ``ppb`` pages. Each live
    page's K and V are copied into a two-deep VMEM buffer, the next block's
    copies (the next slot's first, after a slot's last) start before this
    block's compute, and the online softmax is updated once per block over
    its ``ppb * p`` keys, on the lanes."""
    b = pl.program_id(0)
    n_slots = pl.num_programs(0)
    lay = lay_ref[0]
    nkv, g, hd = q_ref.shape
    p = k_hbm.shape[3]
    K = ppb * p

    def page(s, lp):
        # logical page lp of slot s, clamped to its last live page so that
        # the table is read in bounds
        return tbl_ref[s, jnp.minimum(lp, npg_ref[s] - 1)]

    def fetch(s, j, buf, start):
        """Start (or wait for) the copies of slot ``s``'s block ``j``."""
        for i in range(ppb):
            lp = j * ppb + i
            pid = page(s, lp)
            dst = (buf, slice(None), pl.ds(i * p, p))
            copies = [
                pltpu.make_async_copy(k_hbm.at[lay, pid], kbuf.at[dst], sem.at[buf]),
                pltpu.make_async_copy(v_hbm.at[lay, pid], vbuf.at[dst], sem.at[buf]),
            ]

            @pl.when(lp < npg_ref[s])
            def _kv(copies=copies):
                for c in copies:
                    c.start() if start else c.wait()

    @pl.when(b == 0)
    def _first():
        # dead pages of a block keep what the buffer held: make it finite
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)
        nxt[0] = 0
        fetch(0, 0, 0, True)

    s0 = nxt[0]
    npg = npg_ref[b]
    qp = qpos_ref[b]
    n = (npg + ppb - 1) // ppb
    q = q_ref[...]
    f32 = q.dtype == jnp.float32
    precision = jax.lax.Precision.HIGHEST if f32 else None
    lanes = jax.lax.broadcasted_iota(jnp.int32, (p, _POS_LANES), 1)
    rows = jax.lax.broadcasted_iota(jnp.int32, (p, K), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (p, K), 1)
    keys = jax.lax.broadcasted_iota(jnp.int32, (1, K), 1)

    def positions(j):
        """(1, K) stored positions of the block's keys: page i's column of
        the plane lands on lanes ``i * p ..``."""
        w = jnp.zeros((p, K), jnp.int32)
        for i in range(ppb):
            pid = page(b, j * ppb + i)
            win = pl.multiple_of((pid // _POS_LANES) * _POS_LANES, _POS_LANES)
            chunk = pos_ref[:, pl.ds(win, _POS_LANES)]
            col = jnp.sum(jnp.where(lanes == pid % _POS_LANES, chunk, 0), axis=1, keepdims=True)
            w = jnp.where(cols == rows + i * p, col, w)
        return jnp.sum(w, axis=0, keepdims=True)

    def body(j, carry):
        m, l, acc = carry
        buf = (s0 + j) % 2

        @pl.when(j + 1 < n)
        def _next_block():
            fetch(b, j + 1, 1 - buf, True)

        @pl.when((j + 1 == n) & (b + 1 < n_slots))
        def _next_slot():
            fetch(b + 1, 0, 1 - buf, True)

        fetch(b, j, buf, False)
        kp = positions(j)
        valid = (kp >= 0) & (keys < (npg - j * ppb) * p)
        if causal:
            valid &= kp <= qp
        if window > 0:
            valid &= qp - kp < window
        valid = valid[None]  # (1, 1, K)
        k = kbuf[buf].astype(q.dtype)  # (nkv, K, hd)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))), precision=precision,
            preferred_element_type=jnp.float32) * scale  # (nkv, g, K)
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        m_safe = jnp.where(m_new > NEG_INF / 2, m_new, 0.0)
        e = jnp.where(valid, jnp.exp(s - m_safe), 0.0)
        corr = jnp.where(m > NEG_INF / 2, jnp.exp(m - m_safe), 0.0)
        v = vbuf[buf]
        if v.dtype == jnp.bfloat16:
            pv = jax.lax.dot_general(
                _split3(e), v, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)  # (nkv, 3g, hd)
            pv = pv[:, :g] + pv[:, g : 2 * g] + pv[:, 2 * g :]
        else:
            pv = jax.lax.dot_general(
                e, v.astype(jnp.float32), (((2,), (1,)), ((0,), (0,))),
                precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32)
        l = l * corr + jnp.sum(e, axis=-1, keepdims=True)
        return m_new, l, acc * corr + pv

    init = (
        jnp.full((nkv, g, 1), NEG_INF, jnp.float32),
        jnp.zeros((nkv, g, 1), jnp.float32),
        jnp.zeros((nkv, g, hd), jnp.float32),
    )
    _, l, acc = jax.lax.fori_loop(0, n, body, init)
    nxt[0] = (s0 + n) % 2
    o_ref[...] = acc / jnp.maximum(l, 1e-30)


def _live_page(i, b, j, tbl, npg, ppb):
    """Physical page behind input ``i`` of grid step ``(b, j)``: logical page
    ``j * ppb + i``, clamped to the slot's last live page, and blocks past
    that page repeat the last live block, so their inputs keep the previous
    step's block index and no DMA is issued for them."""
    last = npg[b] - 1
    lp = jnp.minimum(jnp.minimum(j, last // ppb) * ppb + i, last)
    return tbl[b, lp]


def _per_page_kernel(
    tbl_ref,  # (B, P) scalar-prefetch page table
    lay_ref,  # (1,) scalar-prefetch layer of the stack (read by the index maps)
    npg_ref,  # (B,) scalar-prefetch live pages per slot
    qpos_ref,  # (B,) scalar-prefetch query positions
    q_ref,  # (g, nkv, 1, hd) this slot's query heads, grouped by kv head
    *refs,  # ppb k pages (nkv, p, hd), ppb v pages, ppb pos blocks (p, 128),
    #         out (g, nkv, 1, hd), then the acc / max / denominator scratch
    scale: float,
    causal: bool,
    window: int,
    ppb: int,
    n_blocks: int,
):
    """One (slot, block of ``ppb`` pages) grid step over every head, each
    page a pipelined input of its own. Pages past the slot's last live page
    are skipped; the online softmax is updated per page, in f32, on the VPU
    (one query row per head)."""
    k_refs, v_refs, pos_refs = refs[:ppb], refs[ppb : 2 * ppb], refs[2 * ppb : 3 * ppb]
    o_ref, acc_ref, m_ref, l_ref = refs[3 * ppb :]
    b = pl.program_id(0)
    j = pl.program_id(1)
    npg = npg_ref[b]
    qp = qpos_ref[b]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    for i in range(ppb):
        lp = j * ppb + i

        @pl.when(lp < npg)
        def _page(i=i, lp=lp):
            rows = pos_refs[i][...]  # (p, 128): the aligned block of columns
            lane = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 1)
            hit = lane == tbl_ref[b, lp] % _POS_LANES
            kp = jnp.sum(jnp.where(hit, rows, 0), axis=1, keepdims=True)  # (p, 1)
            valid = kp >= 0
            if causal:
                valid &= kp <= qp
            if window > 0:
                valid &= qp - kp < window
            valid = valid[None]  # (1, p, 1)
            k = k_refs[i][...].astype(jnp.float32)  # (nkv, p, hd)
            v = v_refs[i][...].astype(jnp.float32)
            for r in range(q_ref.shape[0]):
                q = q_ref[r].astype(jnp.float32)  # (nkv, 1, hd)
                s = jnp.sum(k * q, axis=-1, keepdims=True) * scale  # (nkv, p, 1)
                s = jnp.where(valid, s, NEG_INF)
                m_prev = m_ref[r]  # (nkv, 1, 1)
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
                m_safe = jnp.where(m_new > NEG_INF / 2, m_new, 0.0)
                e = jnp.where(valid, jnp.exp(s - m_safe), 0.0)
                corr = jnp.where(m_prev > NEG_INF / 2, jnp.exp(m_prev - m_safe), 0.0)
                l_ref[r] = l_ref[r] * corr + jnp.sum(e, axis=1, keepdims=True)
                acc_ref[r] = acc_ref[r] * corr + jnp.sum(e * v, axis=1, keepdims=True)
                m_ref[r] = m_new

    @pl.when(j == n_blocks - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _per_page_attention(q, k_pages, v_pages, pos_pages, table, layer, q_pos, *,
                        causal, window, scale, ppb, interpret):
    """:func:`paged_decode_attention` with each page a grid-pipelined input:
    the read for head sizes that are not whole 128-lane tiles or pages of
    other than a multiple of 8 rows, which Mosaic cannot copy by hand (it
    slices an array only at tiles), and for pools whose position plane
    would not fit ``_POS_PLANE_BYTES`` of VMEM (it reads each page's
    positions beside its K/V)."""
    B, nq, hd = q.shape
    L, N, nkv, p, _ = k_pages.shape
    P = table.shape[1]
    assert nq % nkv == 0
    g = nq // nkv
    nb = -(-P // ppb)
    npg = jnp.clip(q_pos.astype(jnp.int32) // p + 1, 1, P)

    def kv_spec(i):
        return pl.BlockSpec(
            (None, None, nkv, p, hd),
            lambda b, j, tbl, lay, n, qp: (lay[0], _live_page(i, b, j, tbl, n, ppb), 0, 0, 0),
        )

    def pos_spec(i):
        return pl.BlockSpec(
            (None, p, _POS_LANES),
            lambda b, j, tbl, lay, n, qp: (
                lay[0], 0, _live_page(i, b, j, tbl, n, ppb) // _POS_LANES),
        )

    head_spec = pl.BlockSpec((None, g, nkv, 1, hd), lambda b, j, *_: (b, 0, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, nb),
        in_specs=[
            head_spec,
            *[kv_spec(i) for i in range(ppb)],
            *[kv_spec(i) for i in range(ppb)],
            *[pos_spec(i) for i in range(ppb)],
        ],
        out_specs=head_spec,
        scratch_shapes=[
            pltpu.VMEM((g, nkv, 1, hd), jnp.float32),
            pltpu.VMEM((g, nkv, 1, 1), jnp.float32),
            pltpu.VMEM((g, nkv, 1, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _per_page_kernel, scale=float(scale), causal=bool(causal),
        window=int(window), ppb=ppb, n_blocks=nb,
    )
    # query head h = hk * g + r reads kv head hk (GQA)
    qg = q.reshape(B, nkv, g, 1, hd).transpose(0, 2, 1, 3, 4)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, g, nkv, 1, hd), jnp.float32),
        interpret=interpret,
    )(
        table.astype(jnp.int32),
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        npg,
        q_pos.astype(jnp.int32),
        qg,
        *([k_pages] * ppb),
        *([v_pages] * ppb),
        *([pos_pages.astype(jnp.int32)] * ppb),
    )
    return out.transpose(0, 2, 1, 3, 4).reshape(B, nq, hd)


def paged_decode_attention(
    q: jax.Array,  # (B, nq, hd) one query per slot
    k_pages: jax.Array,  # (L, N, nkv, p, hd) layer-stacked pool
    v_pages: jax.Array,  # (L, N, nkv, p, hd)
    pos_pages: jax.Array,  # (L, p, N) int32 absolute positions; -1 = empty
    table: jax.Array,  # (B, P) int32 per-slot page table
    layer: jax.Array,  # () int32: which of the L stacked layers to read
    q_pos: jax.Array,  # (B,) int32 query positions
    *,
    causal: bool = True,
    window: int = 0,
    scale: Optional[float] = None,
    pages_per_block: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:  # (B, nq, hd) f32
    """Decode attention read through the page table, live pages only.

    Slot ``b`` reads logical pages ``0 .. q_pos[b] // p`` (its live length)
    of layer ``layer``, ``pages_per_block`` of them at a time (by default as
    many as make 128 keys); the grid runs over slots and a loop over each
    slot's live blocks, so nothing is issued for a page past that length.
    K/V stay in HBM and each live page is copied into VMEM by hand; the
    layer's position plane is fetched once per call. Rows are masked by
    their stored positions as ``attend`` masks them (``pos >= 0``, causal,
    window); a slot with no valid row returns zeros. Positions past a
    slot's own never hold valid rows in the decode step (pages map lazily
    and are scrubbed when mapped), which is what lets the read stop at the
    live length. Scores are ``q @ k^T`` on the operands' own dtype with f32
    accumulation; the weights stay f32 for ``@ v`` (bf16 ``v``: three bf16
    parts of them, which is exact).

    K/V come as ``(L, N, nkv, p, hd)`` and positions as ``(L, p, N)``: the
    pool's ``(L, N, p, nkv, hd)`` and ``(L, N, p)`` pages with the in-page
    axis moved next to the last, which is how the TPU lays them out for
    ``p = 16`` (tiles over ``(p, hd)``, and over ``(p, N)`` for positions),
    so taking them so costs no copy of the pool."""
    B, nq, hd = q.shape
    L, N, nkv, p, _ = k_pages.shape
    P = table.shape[1]
    assert nq % nkv == 0
    g = nq // nkv
    scale = scale if scale is not None else 1.0 / (hd**0.5)
    n_win = -(-N // _POS_LANES)
    if hd % 128 or p % 8 or p * n_win * _POS_LANES * 4 > _POS_PLANE_BYTES:
        return _per_page_attention(
            q, k_pages, v_pages, pos_pages, table, layer, q_pos, causal=causal,
            window=window, scale=scale, ppb=max(1, min(int(pages_per_block or 8), P)),
            interpret=interpret)
    ppb = max(1, min(int(pages_per_block or max(1, _POS_LANES // p)), P))
    npg = jnp.clip(q_pos.astype(jnp.int32) // p + 1, 1, P)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    head_spec = pl.BlockSpec((None, nkv, g, hd), lambda b, *_: (b, 0, 0, 0))
    # one block over-hanging the plane: fetched once, its 128-lane windows
    # all in bounds
    pos_spec = pl.BlockSpec(
        (None, p, n_win * _POS_LANES), lambda b, tbl, lay, n, qp: (lay[0], 0, 0))
    dt = jnp.promote_types(q.dtype, k_pages.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=[head_spec, hbm, hbm, pos_spec],
        out_specs=head_spec,
        scratch_shapes=[
            pltpu.VMEM((2, nkv, ppb * p, hd), k_pages.dtype),
            pltpu.VMEM((2, nkv, ppb * p, hd), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    kernel = functools.partial(
        _live_decode_kernel, scale=float(scale), causal=bool(causal),
        window=int(window), ppb=ppb,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nkv, g, hd), jnp.float32),
        # slots run in order: a slot's last block starts the next one's copies
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(
        table.astype(jnp.int32),
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        npg,
        q_pos.astype(jnp.int32),
        # query head h = hk * g + r reads kv head hk (GQA)
        q.astype(dt).reshape(B, nkv, g, hd),
        k_pages,
        v_pages,
        pos_pages.astype(jnp.int32),
    )
    return out.reshape(B, nq, hd)
