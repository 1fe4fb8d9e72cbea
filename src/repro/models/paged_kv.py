"""Full-attention rings read and written in the paged pool's own pages.

The serving engine's padded decode step (serve/engine.py) hands the model,
in place of each paged ring leaf (``k``, ``v``, ``pos`` of a full-attention
cache, lead + ``(B, ctx)`` + tail), a :class:`PagedLeaf`: the pool's page
stack, the page table and, inside the layer scan, which layer of the stack
it stands for. The stack holds the pool's lead + ``(N, p)`` + tail pages
with the in-page axis ``p`` moved next to the last (:func:`to_leaf`):
``(L, N, nkv, p, hd)`` for K/V, ``(L, p, N)`` for positions, which is how
the TPU lays the pool out, so the move is free there. On the TPU nothing
of shape ``(B, ctx)`` is built for those rings:

- :func:`scan_layers` runs a decode layer scan with the page stacks in the
  carry (indexed by the layer counter), never sliced out and stacked back;
- :func:`write_rows` writes each slot's new row in place at
  ``table[b, pos // p], pos % p``, the rows ``cache_write`` followed by the
  pool's write-back would leave;
- :func:`attend_paged` reads each slot's K/V through the table: on TPU the
  live-page kernel (``kernels/paged.paged_decode_attention``), elsewhere the
  plain XLA formulation (gather by the table, then ``attend`` and its mask),
  which is the kernel's oracle and keeps CPU decode bit-identical to the
  contiguous pool.

At most one lead axis (the layer-group stack) is supported; a leaf without
one (the transformer's tail layer) has ``layer`` None.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.utils import scan_or_loop, scoped


def to_leaf(pages: jax.Array, page_axis: int) -> jax.Array:
    """Pool pages lead + (N, p) + tail -> the stack a PagedLeaf holds."""
    return jnp.moveaxis(pages, page_axis + 1, -2)


def from_leaf(stack: jax.Array, page_axis: int) -> jax.Array:
    """Inverse of :func:`to_leaf`."""
    return jnp.moveaxis(stack, -2, page_axis + 1)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class PagedLeaf:
    """One paged ring leaf: ``pages`` as :func:`to_leaf` leaves them, ``table``
    (B, P) int32, ``layer`` () int32 into the lead axis (None: no lead)."""

    pages: jax.Array
    table: jax.Array
    layer: Optional[jax.Array] = None

    def tree_flatten(self):
        return (self.pages, self.table, self.layer), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def stack(self) -> jax.Array:
        """The pages with exactly one lead (layer) axis."""
        return self.pages if self.layer is not None else self.pages[None]

    def index(self) -> jax.Array:
        return self.layer if self.layer is not None else jnp.int32(0)

    def replace(self, pages: jax.Array) -> "PagedLeaf":
        if self.layer is None:
            pages = pages[0]
        return PagedLeaf(pages, self.table, self.layer)


def is_paged(x: Any) -> bool:
    return isinstance(x, PagedLeaf)


def scan_layers(body: Callable, carry: Any, xs: Any, unroll: bool = False):
    """``scan_or_loop(body, carry, xs)`` with every :class:`PagedLeaf` of
    ``xs`` moved into the carry: the body sees each with the whole page
    stack and ``layer`` set to the scan step, and must return it, updated,
    at the same place in its per-step output (``new_caches`` mirrors the
    caches it was given). The stacked output holds the final stacks."""
    leaves, treedef = jax.tree_util.tree_flatten(xs, is_leaf=is_paged)
    paged = [leaf for leaf in leaves if is_paged(leaf)]
    if not paged:
        return scan_or_loop(body, carry, xs, unroll=unroll)
    for leaf in paged:
        assert leaf.layer is None, "scan_layers takes whole stacks"
    tables = [leaf.table for leaf in paged]
    rest = [leaf for leaf in leaves if not is_paged(leaf)]
    out_def = []

    def inner(c, rest_i):
        c0, stacks, layer = c
        it_rest, it_pg = iter(rest_i), iter(zip(stacks, tables))
        xs_i = treedef.unflatten([
            PagedLeaf(*next(it_pg), layer) if is_paged(leaf) else next(it_rest)
            for leaf in leaves
        ])
        c0, ys = body(c0, xs_i)
        ys_leaves, ys_def = jax.tree_util.tree_flatten(ys, is_leaf=is_paged)
        new = [leaf.pages for leaf in ys_leaves if is_paged(leaf)]
        assert len(new) == len(stacks), "the body must return every paged leaf"
        out_def[:] = [ys_def, [is_paged(leaf) for leaf in ys_leaves]]
        return (c0, new, layer + 1), [leaf for leaf in ys_leaves if not is_paged(leaf)]

    (carry, stacks, _), ys_rest = scan_or_loop(
        inner, (carry, [leaf.pages for leaf in paged], jnp.int32(0)), rest,
        unroll=unroll,
    )
    ys_def, kinds = out_def
    it_rest, it_pg = iter(ys_rest), iter(zip(stacks, tables))
    return carry, ys_def.unflatten([
        PagedLeaf(*next(it_pg)) if k else next(it_rest) for k in kinds
    ])


@scoped("paged.writeback")
def write_rows(
    cache: dict,  # {"k", "v", "pos": PagedLeaf, "cursor": (B,) int32}
    k_new: jax.Array,  # (B, nkv, hd)
    v_new: jax.Array,  # (B, nkv, hd)
    pos_new: jax.Array,  # (B,) int32
) -> dict:
    """The ring write of a decode step, in place in the pages: each slot's
    row lands at page ``table[b, pos // p]``, offset ``pos % p``, as the
    pool's write-back put it after ``cache_write`` (a live slot's cursor is
    its position); a free slot's table maps the scratch page, which no live
    request reads. The cursor advances as ``cache_write`` advances it.

    K/V: each slot's tail page is read, given its row and written back
    whole (a live slot owns its tail page), so the write's window is the
    page's ``(p, last)`` tiles, the layout the attention kernel reads; a
    row-sized window would make XLA keep the stack in another layout and
    copy it at every layer. Positions are written element by element."""
    kl, vl, pl_ = cache["k"], cache["v"], cache["pos"]
    layer = kl.index()
    table = kl.table
    p = pl_.stack().shape[1]  # (L, p, N)
    pid = jnp.take_along_axis(table, (pos_new // p)[:, None], axis=1)[:, 0]
    off = pos_new % p
    hit = jnp.arange(p, dtype=jnp.int32)[None] == off[:, None]  # (B, p)

    def put(leaf, new):
        stack = leaf.stack()  # (L, N) + tail[:-1] + (p, last), or (L, p, N)
        new = new.astype(stack.dtype)
        if stack.ndim == 3:  # positions
            return leaf.replace(stack.at[layer, off, pid].set(new))
        idx = (layer, pid)
        sel = hit.reshape((hit.shape[0],) + (1,) * (stack.ndim - 4) + (p, 1))
        page = jnp.where(sel, jnp.expand_dims(new, -2), stack[idx])
        return leaf.replace(stack.at[idx].set(page))

    cursor = cache["cursor"]
    return {
        "k": put(kl, k_new),
        "v": put(vl, v_new),
        "pos": put(pl_, pos_new),
        "cursor": cursor + (pos_new >= 0).astype(cursor.dtype),
    }


def gather_ring(leaf: PagedLeaf) -> jax.Array:
    """The layer's logical (B, ctx) + tail ring, gathered by the table (the
    XLA read off TPU)."""
    pages = from_leaf(leaf.stack()[leaf.index()], 0)  # (N, p) + tail
    B, P = leaf.table.shape
    out = jnp.take(pages, leaf.table, axis=0)  # (B, P, p) + tail
    return out.reshape((B, P * pages.shape[1]) + pages.shape[2:])


def attend_paged(
    q: jax.Array,  # (B, 1, nq, hd)
    cache: dict,  # after write_rows
    q_pos: jax.Array,  # (B, 1) int32
    cfg,
) -> jax.Array:  # (B, 1, nq * hd)
    """Decode attention over a paged ring: the live-page kernel on TPU, the
    gather + ``attend`` formulation elsewhere (chosen by the platform the
    step lowers for)."""
    from repro.kernels.paged import paged_decode_attention
    from repro.models.attention import attend, make_mask

    B, _, nq, hd = q.shape
    scale = cfg.attn.softmax_scale or 1.0 / (hd**0.5)
    kl, vl, pl_ = cache["k"], cache["v"], cache["pos"]

    def xla(q):
        mask = make_mask(q_pos, gather_ring(pl_), cfg.attn.causal, cfg.attn.window)
        return attend(q, gather_ring(kl), gather_ring(vl), mask, cfg)

    def tpu(q):
        out = paged_decode_attention(
            q[:, 0], kl.stack(), vl.stack(), pl_.stack(), kl.table, kl.index(),
            q_pos[:, 0], causal=bool(cfg.attn.causal), window=int(cfg.attn.window),
            scale=float(scale),
        )
        return out.astype(q.dtype).reshape(B, 1, nq * hd)

    return jax.lax.platform_dependent(q, tpu=tpu, default=xla)
