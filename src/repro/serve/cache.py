"""KV-cache allocators for the serving engine: contiguous and block-paged.

:class:`CachePool`: one cache pytree of fixed shape backs the whole
engine: ``B`` slots by ``ctx`` positions, built once with
:func:`repro.models.api.make_caches`. MoD-block caches inside it are
capacity-sized (``ratio * ctx`` — the paper's KV-memory saving), so the
pool's footprint already reflects the MoD serving win;
:meth:`CachePool.cache_bytes` reports it.

:class:`PagedCachePool`: the same logical pool with full-attention KV
stored as refcounted ``(n_pages, page_size, ...)`` blocks behind per-slot
page tables — lazy page growth, scrub-on-recycle, a hash-chained
prompt-prefix cache with LRU eviction, and per-leaf-kind accounting (MoD
routed rings stay capacity-sized + ring-addressed in the residual pool).
DESIGN.md §Serving engine documents the page-table layout and the
NULL/SCRATCH reserved-page contract.

Slot lifecycle is two jitted scatter ops, both O(slot) and shape-stable:

- :meth:`reset` writes the slot's rows back to their initial values (ring
  cursors to 0, cache positions to -1) so a freed slot can be re-admitted
  without leaking the previous request's KV;
- :meth:`write_slot` scatters a batch-1 cache pytree (e.g. the output of a
  jitted prefill) into the slot's rows — this is how prefilled requests
  enter the decode batch.

The batch axis of every cache leaf is discovered structurally (by diffing
the spec shapes of a B- and a B+1-sized pool), so the pool works for all
four model families — including leaves stacked as (n_groups, B, ...) or
(n_seg, n_pairs, B, ...) — without per-family wiring.

With a ``mesh``, the pool is *batch-sharded*: every leaf is placed with
``distributed.sharding.cache_shardings`` (slots over the data axes, head
dims over "model" where divisible) and the slot-lifecycle scatters keep
that placement via explicit out-shardings. Combined with the engine's
shard-local ``batch_capacity`` routing, a slot's cache rows live on — and
are only ever touched by — the data shard that owns the slot.
"""
from __future__ import annotations

import dataclasses
import hashlib
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.models import api
from repro.serve.quant import QuantConfig, leaf_groups, quantize_rows
from repro.utils import scoped


def _batch_axes(cfg: ModelConfig, batch: int, ctx: int):
    """Pytree of ints: which axis of each cache leaf is the batch axis."""
    a = api.make_caches(cfg, batch, ctx, specs=True)
    b = api.make_caches(cfg, batch + 1, ctx, specs=True)

    def axis(sa, sb):
        diff = [i for i, (x, y) in enumerate(zip(sa.shape, sb.shape)) if x != y]
        assert len(diff) == 1, f"ambiguous batch axis: {sa.shape} vs {sb.shape}"
        return diff[0]

    return jax.tree.map(axis, a, b)


class CachePool:
    """Fixed-shape (B, ctx) cache pool with per-slot reset/write."""

    def __init__(self, cfg: ModelConfig, batch_size: int, ctx: int, mesh=None):
        self.cfg = cfg
        self.batch_size = batch_size
        self.ctx = ctx
        self.mesh = mesh
        self.caches = api.make_caches(cfg, batch_size, ctx)
        self._axes = _batch_axes(cfg, batch_size, ctx)
        # batch-1 template holding every leaf's initial slot value
        self._template = api.make_caches(cfg, 1, ctx)

        out_shardings = None
        if mesh is not None:
            from repro.distributed.sharding import cache_shardings

            sh = cache_shardings(self.caches, mesh, cfg, batch_size)
            self.caches = jax.device_put(self.caches, sh)
            out_shardings = sh

        def scatter(caches, sub, slot):
            return jax.tree.map(
                lambda c, s, ax: jax.lax.dynamic_update_slice_in_dim(c, s.astype(c.dtype), slot, axis=ax),
                caches,
                sub,
                self._axes,
            )

        self._scatter = (
            jax.jit(scatter)
            if out_shardings is None
            else jax.jit(scatter, out_shardings=out_shardings)
        )

    def reset(self, slot: int) -> None:
        """Return the slot's cache rows to their initial (empty) state."""
        self.caches = self._scatter(self.caches, self._template, slot)

    def write_slot(self, slot: int, sub_caches: Any) -> None:
        """Scatter a batch-1 cache pytree (same structure) into a slot."""
        self.caches = self._scatter(self.caches, sub_caches, slot)

    def cache_bytes(self) -> Dict[str, float]:
        """Pool footprint, split by routed ("mod") vs full-capacity leaves.

        ``mod_vs_full_ratio`` makes the paper's KV saving legible: MoD-block
        caches hold capacity(ctx) entries against the full blocks' ctx.
        """
        sizes = {"total": 0.0, "mod": 0.0, "full": 0.0, "kv_bytes": 0.0,
                 "resid_bytes": 0.0}
        pageable = set(_paged_leaf_axes(self.cfg, self.batch_size, self.ctx))
        for i, (path, leaf) in enumerate(
            jax.tree_util.tree_flatten_with_path(self.caches)[0]
        ):
            b = float(leaf.size * leaf.dtype.itemsize)
            sizes["total"] += b
            keys = [getattr(p, "key", None) for p in path]
            sizes["mod" if "mod" in keys else "full"] += b
            # same kv/resid split the paged pool reports (kv = the leaves a
            # paged pool would page), so the two pools' stats are comparable
            sizes["kv_bytes" if i in pageable else "resid_bytes"] += b
        sizes["mod_vs_full_ratio"] = sizes["mod"] / sizes["full"] if sizes["full"] else 0.0
        return sizes


# ---------------------------------------------------------------------------
# Block-paged pool
# ---------------------------------------------------------------------------

# Reserved physical pages. NULL backs every *unmapped* logical page of an
# active slot: its content is the pristine template (cache positions -1, so
# attention masks it out) and it is never written — active slots only write
# at their own `pos`, which always lands in a mapped page. SCRATCH backs the
# page tables of FREE slots: the shared decode step still "writes" their
# (inactive, pos=0) rows somewhere, and scratch absorbs that garbage without
# ever being read by a live request.
NULL_PAGE = 0
SCRATCH_PAGE = 1
_RESERVED = 2


def _paged_leaf_axes(cfg: ModelConfig, batch: int, ctx: int) -> Dict[int, int]:
    """{flat-leaf index -> batch axis} for every *pageable* cache leaf.

    Pageable = a position-addressed ring leaf ("k"/"v"/"pos" with a "cursor"
    sibling) whose capacity is the full ``ctx`` — i.e. the full-attention KV
    rings, where the engine's write cursor equals the absolute position.
    MoD routed-block leaves (capacity-sized, ring-addressed by routed-step
    count, under a "mod" key), SSM states, cursors and enc-dec cross-KV all
    stay slot-contiguous in the residual pool.
    """
    specs = jax.tree_util.tree_flatten_with_path(
        api.make_caches(cfg, batch, ctx, specs=True)
    )[0]
    axes = jax.tree_util.tree_leaves(_batch_axes(cfg, batch, ctx))
    key_tuples = {
        tuple(getattr(p, "key", None) for p in path) for path, _ in specs
    }
    paged: Dict[int, int] = {}
    for i, ((path, spec), ax) in enumerate(zip(specs, axes)):
        keys = tuple(getattr(p, "key", None) for p in path)
        if "mod" in keys or keys[-1] not in ("k", "v", "pos"):
            continue
        if keys[:-1] + ("cursor",) not in key_tuples:
            continue
        if len(spec.shape) <= ax + 1 or spec.shape[ax + 1] != ctx:
            continue
        paged[i] = ax
    return paged


def _quant_leaf_plan(
    cfg: ModelConfig, batch: int, ctx: int, quant: Optional[QuantConfig]
) -> Tuple[Tuple[int, int, str], ...]:
    """(j, G, wide-dtype-name) per paged leaf stored narrow under ``quant``.

    ``j`` indexes the pool's paged-leaf order (sorted flat-leaf ids); only
    the float "k"/"v" rings quantize — the "pos" ring is int32 and stays
    exact (it is what the attention mask reads). MoD routed rings live in
    the residual pool and are already capacity-sized, so v1 leaves them at
    full precision (DESIGN.md §Quantized KV)."""
    if quant is None or not quant.enabled:
        return ()
    specs = jax.tree_util.tree_flatten_with_path(
        api.make_caches(cfg, batch, ctx, specs=True)
    )[0]
    paged_axes = _paged_leaf_axes(cfg, batch, ctx)
    plan = []
    for j, i in enumerate(sorted(paged_axes)):
        path, spec = specs[i]
        keys = tuple(getattr(p, "key", None) for p in path)
        if keys[-1] not in ("k", "v"):
            continue
        if not jnp.issubdtype(jnp.dtype(spec.dtype), jnp.floating):
            continue
        plan.append(
            (j, leaf_groups(spec.shape, quant, paged_axes[i]), str(spec.dtype))
        )
    return tuple(plan)


@dataclasses.dataclass(frozen=True)
class PoolSpec:
    """Static description of a paged pool's leaf layout.

    Hashable and array-free, so the engine's jitted decode step can close
    over it without retaining any particular pool instance's storage (the
    shared jit cache would otherwise pin the first engine's pages alive).
    """

    paged_ids: Tuple[int, ...]
    paged_axes: Tuple[int, ...]
    resid_ids: Tuple[int, ...]
    treedef: Any
    page_size: int
    backend: str
    # batch axis of EVERY flat leaf (paged and residual alike), so a jitted
    # step can slice / update one slot's batch-1 view of the materialized
    # cache pytree — the ragged mixed step's per-segment working state
    axes: Tuple[int, ...] = ()
    # KV quantization (serve/quant.py): which paged leaves (positions in
    # ``paged_ids`` order) are stored narrow, their scale-group counts G,
    # and the wide dtype each dequantizes back to
    quant: Optional[QuantConfig] = None
    quant_ids: Tuple[int, ...] = ()
    quant_groups: Tuple[int, ...] = ()
    quant_dtypes: Tuple[str, ...] = ()


def _qmap(spec: PoolSpec, scales) -> Dict[int, int]:
    """{paged-leaf position j -> scales-list index m}, empty when the call
    carries no scales (unquantized pool or legacy caller)."""
    if not scales:
        return {}
    return {j: m for m, j in enumerate(spec.quant_ids)}


@scoped("paged.materialize")
def paged_materialize_q(
    spec: PoolSpec,
    pages: List[jax.Array],
    scales: List[jax.Array],
    resid: List[jax.Array],
    table: jax.Array,
) -> Any:
    """Logical (B, ctx) cache pytree from paged + residual storage — pure,
    called inside the engine's jitted decode step. Quantized leaves widen
    through the fused-dequant gather (kernels/ops.paged_gather_op with
    scales) back to their wide dtype."""
    from repro.kernels.ops import paged_gather_op

    qmap = _qmap(spec, scales)
    leaves: List[Any] = [None] * (len(spec.paged_ids) + len(spec.resid_ids))
    for j, (i, ax) in enumerate(zip(spec.paged_ids, spec.paged_axes)):
        if j in qmap:
            m = qmap[j]
            leaves[i] = paged_gather_op(
                pages[j], table, page_axis=ax, backend=spec.backend,
                scales=scales[m], out_dtype=spec.quant_dtypes[m],
            )
        else:
            leaves[i] = paged_gather_op(
                pages[j], table, page_axis=ax, backend=spec.backend
            )
    for j, i in enumerate(spec.resid_ids):
        leaves[i] = resid[j]
    return jax.tree_util.tree_unflatten(spec.treedef, leaves)


def inplace_decode(spec: PoolSpec) -> bool:
    """Whether the padded decode step reads and writes this pool's pages in
    place (``paged_rings`` / ``paged_split``) rather than through
    :func:`paged_materialize_q` and :func:`paged_writeback_q`: unquantized
    pools whose paged leaves have at most a layer-stack lead axis. Quantized
    pages keep the gather, which widens them (and its ``paged_backend``)."""
    return not spec.quant_ids and all(ax <= 1 for ax in spec.paged_axes)


def paged_rings(
    spec: PoolSpec, pages: List[jax.Array], resid: List[jax.Array], table: jax.Array
) -> Any:
    """The decode step's cache pytree over the pool's own storage: each
    paged leaf a ``models.paged_kv.PagedLeaf`` (its page stack and the page
    table, nothing gathered), each residual leaf as it is."""
    from repro.models.paged_kv import PagedLeaf, to_leaf

    leaves: List[Any] = [None] * (len(spec.paged_ids) + len(spec.resid_ids))
    for j, (i, ax) in enumerate(zip(spec.paged_ids, spec.paged_axes)):
        leaves[i] = PagedLeaf(to_leaf(pages[j], ax), table)
    for j, i in enumerate(spec.resid_ids):
        leaves[i] = resid[j]
    return jax.tree_util.tree_unflatten(spec.treedef, leaves)


def paged_split(spec: PoolSpec, caches: Any) -> Tuple[List[jax.Array], List[jax.Array]]:
    """(pages, resid) of a cache pytree built by :func:`paged_rings`."""
    from repro.models.paged_kv import from_leaf, is_paged

    leaves = jax.tree_util.tree_leaves(caches, is_leaf=is_paged)
    return ([from_leaf(leaves[i].pages, ax)
             for i, ax in zip(spec.paged_ids, spec.paged_axes)],
            [leaves[i] for i in spec.resid_ids])


@scoped("paged.writeback")
def paged_writeback_q(
    spec: PoolSpec,
    new_caches: Any,
    pages: List[jax.Array],
    scales: List[jax.Array],
    table: jax.Array,
    pos: jax.Array,
) -> Tuple[List[jax.Array], List[jax.Array], List[jax.Array]]:
    """Split an updated logical cache back into (pages, resid, scales).

    The decode step mutates each paged leaf at exactly one logical position
    per slot — its absolute ``pos`` (full-capacity rings write at their
    cursor, and cursor == pos for ctx-capacity leaves; asserted by the
    paged-vs-contiguous equality tests) — so only that row is scattered
    into the slot's tail page. Quantized leaves scatter narrow rows plus
    fresh per-row pow2 scales.
    """
    from repro.kernels.ops import paged_scatter_rows_op

    qmap = _qmap(spec, scales)
    leaves = jax.tree_util.tree_leaves(new_caches)
    new_pages: List[jax.Array] = []
    new_scales = list(scales)
    for j, (i, ax) in enumerate(zip(spec.paged_ids, spec.paged_axes)):
        view = leaves[i]  # lead + (B, ctx) + tail
        idx = pos.reshape((1,) * ax + (-1, 1) + (1,) * (view.ndim - ax - 2))
        rows = jnp.squeeze(
            jnp.take_along_axis(view, idx.astype(jnp.int32), axis=ax + 1), ax + 1
        )
        if j in qmap:
            m = qmap[j]
            new_p, new_s = paged_scatter_rows_op(
                pages[j], table, rows, pos, page_axis=ax, backend=spec.backend,
                scales=scales[m], quant=spec.quant,
            )
            new_pages.append(new_p)
            new_scales[m] = new_s
        else:
            new_pages.append(
                paged_scatter_rows_op(
                    pages[j], table, rows, pos, page_axis=ax, backend=spec.backend
                )
            )
    new_resid = [leaves[i] for i in spec.resid_ids]
    return new_pages, new_resid, new_scales


def slot_slice(spec: PoolSpec, caches: Any, slot: jax.Array) -> Any:
    """Batch-1 view of one slot of a materialized cache pytree (traced
    ``slot`` — used inside the ragged mixed step's segment scan)."""
    leaves = jax.tree_util.tree_leaves(caches)
    out = [
        jax.lax.dynamic_slice_in_dim(leaf, slot, 1, axis=ax)
        for leaf, ax in zip(leaves, spec.axes)
    ]
    return jax.tree_util.tree_unflatten(spec.treedef, out)


def slot_update(spec: PoolSpec, caches: Any, sub: Any, slot: jax.Array) -> Any:
    """Write a batch-1 cache pytree back into ``slot`` of the full pytree."""
    leaves = jax.tree_util.tree_leaves(caches)
    subs = jax.tree_util.tree_leaves(sub)
    out = [
        jax.lax.dynamic_update_slice_in_dim(leaf, s.astype(leaf.dtype), slot, axis=ax)
        for leaf, s, ax in zip(leaves, subs, spec.axes)
    ]
    return jax.tree_util.tree_unflatten(spec.treedef, out)


@scoped("paged.writeback")
def paged_writeback_tokens_q(
    spec: PoolSpec,
    new_caches: Any,
    pages: List[jax.Array],
    scales: List[jax.Array],
    table: jax.Array,
    slot: jax.Array,  # (W,) int32 — slot of each written token row
    pos: jax.Array,  # (W,) int32 — absolute position of each row
    valid: jax.Array,  # (W,) bool — invalid rows land on the scratch page
) -> Tuple[List[jax.Array], List[jax.Array], List[jax.Array]]:
    """Ragged-step write-back: an arbitrary flat list of (slot, pos) token
    rows — this step's decode rows plus every prefill-segment token — is
    scattered from the updated logical cache into the pool's pages in one
    pass per leaf (kernels ``ragged_paged_scatter_rows_op``). The
    fixed-one-row-per-slot :func:`paged_writeback_q` is the decode-only
    special case. Invalid entries (inactive slots, padded segment tails)
    write to SCRATCH_PAGE, which is never read. Quantized leaves scatter
    narrow rows and per-row scales to the same (pid, off) targets."""
    from repro.kernels.ops import ragged_paged_scatter_rows_op

    qmap = _qmap(spec, scales)
    leaves = jax.tree_util.tree_leaves(new_caches)
    ctx = table.shape[1] * spec.page_size
    pos_c = jnp.clip(pos, 0, ctx - 1).astype(jnp.int32)
    slot_c = jnp.clip(slot, 0, table.shape[0] - 1).astype(jnp.int32)
    new_pages: List[jax.Array] = []
    new_scales = list(scales)
    for j, (i, ax) in enumerate(zip(spec.paged_ids, spec.paged_axes)):
        view = leaves[i]  # lead + (B, ctx) + tail
        rows = jnp.take(view, slot_c, axis=ax)  # lead + (W, ctx) + tail
        idx = pos_c.reshape((1,) * ax + (-1, 1) + (1,) * (view.ndim - ax - 2))
        rows = jnp.squeeze(
            jnp.take_along_axis(rows, idx.astype(jnp.int32), axis=ax + 1), ax + 1
        )
        if j in qmap:
            m = qmap[j]
            new_p, new_s = ragged_paged_scatter_rows_op(
                pages[j], table, rows, slot, pos, valid,
                page_axis=ax, backend=spec.backend, dump_page=SCRATCH_PAGE,
                scales=scales[m], quant=spec.quant,
            )
            new_pages.append(new_p)
            new_scales[m] = new_s
        else:
            new_pages.append(
                ragged_paged_scatter_rows_op(
                    pages[j], table, rows, slot, pos, valid,
                    page_axis=ax, backend=spec.backend, dump_page=SCRATCH_PAGE,
                )
            )
    new_resid = [leaves[i] for i in spec.resid_ids]
    return new_pages, new_resid, new_scales


def quant_roundtrip(spec: PoolSpec, caches: Any, mask: jax.Array) -> Any:
    """Round-trip the quantized KV leaves of a logical cache pytree through
    the pool's narrow dtype (serve/quant.roundtrip_leaf), limited to the
    ``mask`` (B, ctx) positions. Identity on unquantized pools.

    The engine calls this at every quantization boundary that is *not* a
    pool write — chunked-prefill chunk ends and speculative in-window
    steps — so the full-precision working state agrees bit-for-bit with
    what a pool write/read cycle of the same rows would produce (pow2
    idempotency then makes the eventual write reproduce these exact
    values). That agreement is what keeps prefix warm-restores,
    ragged-vs-padded and speculative-vs-plain streams identical on the
    quantized path."""
    if spec.quant is None or not spec.quant_ids:
        return caches
    from repro.serve.quant import roundtrip_leaf

    qset = set(spec.quant_ids)
    leaves = list(jax.tree_util.tree_leaves(caches))
    for j, (i, ax) in enumerate(zip(spec.paged_ids, spec.paged_axes)):
        if j in qset:
            leaves[i] = roundtrip_leaf(leaves[i], ax, spec.quant, mask=mask)
    return jax.tree_util.tree_unflatten(spec.treedef, leaves)


@scoped("paged.writeback")
def paged_collect_rows(spec: "PoolSpec", caches: Any, pos: jax.Array) -> List[jax.Array]:
    """Extract each slot's KV row at ``pos[b]`` from a logical cache pytree
    (one row per paged leaf, per slot). The speculative verify scan calls
    this right after every in-window decode step: rows must be collected
    *per step* because a later step at ``pos + j >= ctx`` wraps the ring
    (``cache_write`` writes at ``cursor % ctx``) and would clobber the
    carried logical row before a post-scan extraction could see it.
    Out-of-range positions clip to the last row — the caller masks them
    out of the scatter with ``valid=False``."""
    leaves = jax.tree_util.tree_leaves(caches)
    rows: List[jax.Array] = []
    for i, ax in zip(spec.paged_ids, spec.paged_axes):
        view = leaves[i]  # lead + (B, ctx) + tail
        ctx = view.shape[ax + 1]
        idx = jnp.clip(pos, 0, ctx - 1).astype(jnp.int32)
        idx = idx.reshape((1,) * ax + (-1, 1) + (1,) * (view.ndim - ax - 2))
        rows.append(jnp.squeeze(jnp.take_along_axis(view, idx, axis=ax + 1), ax + 1))
    return rows


@scoped("paged.writeback")
def paged_scatter_rows_q(
    spec: "PoolSpec",
    rows: List[jax.Array],  # per paged leaf: lead + (W,) + tail row stacks
    pages: List[jax.Array],
    scales: List[jax.Array],
    table: jax.Array,
    slot: jax.Array,  # (W,) int32
    pos: jax.Array,  # (W,) int32
    valid: jax.Array,  # (W,) bool — invalid rows land on the scratch page
) -> Tuple[List[jax.Array], List[jax.Array]]:
    """Scatter pre-collected KV rows into the pool's pages — the
    row-stack half of :func:`paged_writeback_tokens_q`, for callers (the
    speculative step) whose rows come out of a scan instead of a final
    logical cache. Returns ``(new_pages, new_scales)``."""
    from repro.kernels.ops import ragged_paged_scatter_rows_op

    qmap = _qmap(spec, scales)
    new_pages: List[jax.Array] = []
    new_scales = list(scales)
    for j, ax in enumerate(spec.paged_axes):
        if j in qmap:
            m = qmap[j]
            new_p, new_s = ragged_paged_scatter_rows_op(
                pages[j], table, rows[j], slot, pos, valid,
                page_axis=ax, backend=spec.backend, dump_page=SCRATCH_PAGE,
                scales=scales[m], quant=spec.quant,
            )
            new_pages.append(new_p)
            new_scales[m] = new_s
        else:
            new_pages.append(
                ragged_paged_scatter_rows_op(
                    pages[j], table, rows[j], slot, pos, valid,
                    page_axis=ax, backend=spec.backend, dump_page=SCRATCH_PAGE,
                )
            )
    return new_pages, new_scales


def lru_cached(cache: "OrderedDict", key: Any, make, maxsize: int):
    """Bounded-LRU memo: the one implementation behind this module's pool-op
    cache and serve/engine.py's jit cache. Eviction only drops the cache's
    reference — live holders keep theirs."""
    v = cache.get(key)
    if v is None:
        v = cache[key] = make()
        while len(cache) > maxsize:
            cache.popitem(last=False)
    else:
        cache.move_to_end(key)
    return v


# Jitted slot-lifecycle ops shared across PagedCachePool instances (the
# benchmarks build several engines per sweep; per-instance jax.jit of bound
# methods would re-trace and re-compile each time). Keyed by everything the
# traces depend on; closures capture only batch-1 template arrays — never a
# pool instance — so a cached op can't pin any pool's page storage alive.
_POOL_OPS_CACHE: "OrderedDict[Any, Tuple]" = OrderedDict()
_POOL_OPS_MAX = 16


def _build_pool_ops(cfg: ModelConfig, batch: int, ctx: int, page_size: int,
                    backend: str, quant: Optional[QuantConfig] = None) -> Tuple:
    full = api.make_caches(cfg, batch, ctx, specs=True)
    _, treedef = jax.tree_util.tree_flatten(full)
    axes = jax.tree_util.tree_leaves(_batch_axes(cfg, batch, ctx))
    paged_axes = _paged_leaf_axes(cfg, batch, ctx)
    paged_ids = sorted(paged_axes)
    n_leaves = len(axes)
    resid_ids = [i for i in range(n_leaves) if i not in paged_axes]
    resid_axes = [axes[i] for i in resid_ids]
    tmpl_flat = jax.tree_util.tree_leaves(api.make_caches(cfg, 1, ctx))
    tmpl_resid = [tmpl_flat[i] for i in resid_ids]
    tmpl_pages = [
        jax.lax.slice_in_dim(
            jax.lax.index_in_dim(tmpl_flat[i], 0, paged_axes[i], keepdims=False),
            0, page_size, axis=paged_axes[i],
        )
        for i in paged_ids
    ]
    P = ctx // page_size
    plan = _quant_leaf_plan(cfg, batch, ctx, quant)
    qinfo = {j: (m, g, dt) for m, (j, g, dt) in enumerate(plan)}

    def reset_resid(resid, slot):
        return [
            jax.lax.dynamic_update_slice_in_dim(r, t.astype(r.dtype), slot, axis=ax)
            for r, t, ax in zip(resid, tmpl_resid, resid_axes)
        ]

    def write(pages, scales, resid, sub, dest, slot):
        # ``dest`` (P,) routes each logical page to its physical page —
        # entries set to SCRATCH_PAGE (shared prefix pages, unmapped tail)
        # are dropped into the scratch page. Quantized leaves fold each
        # written page into canonical (P, p, F) rows, quantize with fresh
        # pow2 scales (exact on rows already round-tripped at a chunk
        # boundary — quantization is idempotent) and scatter narrow pages
        # plus their (P, p, G) scales to the same ``dest``.
        from repro.kernels.ops import _canon_pages, _uncanon

        sub_flat = jax.tree_util.tree_leaves(sub)
        new_pages = []
        new_scales = list(scales)
        for j, i in enumerate(paged_ids):
            ax = paged_axes[i]
            s = jax.lax.index_in_dim(sub_flat[i], 0, ax, keepdims=False)
            s = s.reshape(s.shape[:ax] + (P, page_size) + s.shape[ax + 1 :])
            idx = (slice(None),) * ax + (dest,)
            if j in qinfo:
                m, g, _ = qinfo[j]
                canon, rest = _canon_pages(s, ax)  # (P, p, F)
                q, sc = quantize_rows(canon, g, quant)
                q = _uncanon(q, rest, ax)  # back to leaf page layout
                new_pages.append(pages[j].at[idx].set(q.astype(pages[j].dtype)))
                new_scales[m] = scales[m].at[dest].set(sc)
            else:
                new_pages.append(pages[j].at[idx].set(s.astype(pages[j].dtype)))
        new_resid = [
            jax.lax.dynamic_update_slice_in_dim(
                r, sub_flat[i].astype(r.dtype), slot, axis=ax
            )
            for r, i, ax in zip(resid, resid_ids, resid_axes)
        ]
        return new_pages, new_scales, new_resid

    def scrub(pages, scales, ids):
        # rewrite physical pages ``ids`` (P,; SCRATCH entries harmless) to
        # template content, so a recycled page can't leak a previous
        # request's KV (or stale valid-looking positions) into a new slot;
        # scale rows reset to 1.0 (the template-page scale)
        out = []
        for j, i in enumerate(paged_ids):
            ax = paged_axes[i]
            t = jnp.broadcast_to(
                jnp.expand_dims(tmpl_pages[j], ax),
                tmpl_pages[j].shape[:ax] + (ids.shape[0],) + tmpl_pages[j].shape[ax:],
            )
            idx = (slice(None),) * ax + (ids,)
            out.append(pages[j].at[idx].set(t.astype(pages[j].dtype)))
        new_scales = [s.at[ids].set(1.0) for s in scales]
        return out, new_scales

    def read(pages, scales, resid, table_row, slot):
        # batch-1 logical cache for one slot (chunked prefill works on
        # this view, then write_slot puts it back); quantized leaves come
        # back widened, so the view holds exactly the round-tripped values
        # a re-quantizing write_slot will preserve
        from repro.kernels.ops import paged_gather_op

        qmap = {j: qinfo[j][0] for j in qinfo} if scales else {}
        leaves: List[Any] = [None] * n_leaves
        for j, i in enumerate(paged_ids):
            if j in qmap:
                m, _, dt = qinfo[j]
                leaves[i] = paged_gather_op(
                    pages[j], table_row[None], page_axis=paged_axes[i],
                    backend=backend, scales=scales[m], out_dtype=dt,
                )
            else:
                leaves[i] = paged_gather_op(
                    pages[j], table_row[None], page_axis=paged_axes[i], backend=backend
                )
        for j, i in enumerate(resid_ids):
            leaves[i] = jax.lax.dynamic_slice_in_dim(
                resid[j], slot, 1, axis=resid_axes[j]
            )
        return jax.tree_util.tree_unflatten(treedef, leaves)

    # modlint: disable=jit-in-loop -- _build_pool_ops itself is memoized in
    # the module-level _POOL_OPS_CACHE LRU (via _pool_ops), so these four
    # jits are constructed once per (cfg, batch, ctx, page_size, backend,
    # quant) key, not per engine build. ``write`` and ``scrub`` take the
    # pages and scales donated, so their page writes land in the pool's own
    # buffers instead of a copy of the whole pool; the residual leaves are
    # not donated (callers may hold them across a call).
    return (jax.jit(reset_resid), jax.jit(write, donate_argnums=(0, 1)),
            jax.jit(scrub, donate_argnums=(0, 1)), jax.jit(read))


def _pool_ops(cfg: ModelConfig, batch: int, ctx: int, page_size: int,
              backend: str, quant: Optional[QuantConfig] = None) -> Tuple:
    return lru_cached(
        _POOL_OPS_CACHE,
        (cfg, batch, ctx, page_size, backend, quant),
        lambda: _build_pool_ops(cfg, batch, ctx, page_size, backend, quant),
        _POOL_OPS_MAX,
    )


@dataclasses.dataclass
class PrefixEntry:
    """One memoized chunk-aligned prompt prefix.

    ``pages`` are the shared physical pages holding the prefix's
    full-attention KV; ``resid`` is the batch-1 snapshot of the non-paged
    prefix-dependent state at the boundary (MoD ring caches + cursors), so
    restoring an entry reproduces the *exact* chunked-prefill state — reuse
    is bit-identical to recomputing the prefix.
    """

    n_tokens: int
    pages: Tuple[int, ...]
    resid: Dict[int, jax.Array]  # flat-leaf index -> batch-1 leaf value


class PagedCachePool:
    """Block-paged KV pool: page tables + free-list + prefix cache.

    Full-attention KV leaves are stored as ``(n_pages, page_size, ...)``
    physical blocks shared by all slots; each slot owns a logical page
    table row of ``P = ctx // page_size`` entries. Everything else (MoD
    capacity-sized rings, SSM state, cursors, cross-KV) stays in a
    slot-contiguous *residual* pool, exactly as in :class:`CachePool` —
    page accounting is per-leaf-kind. Engine memory therefore scales with
    *actual* sequence lengths (pages allocate lazily as slots grow) and
    shared prompt prefixes are stored once (hash-chained prefix cache with
    refcounted pages + LRU eviction of unreferenced entries).

    The padded decode step stays once-compiled and fixed-shape and works on
    the pages in place (:func:`paged_rings`): each full-attention layer
    writes its new row into the slot's tail page and reads its live pages
    through the table. Quantized pools, chunked prefill (``read_slot``) and
    the ragged and speculative steps still ``materialize`` the logical
    ``(B, ctx)`` view (kernels/paged gather) and ``writeback`` rows.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        batch_size: int,
        ctx: int,
        page_size: int,
        n_pages: Optional[int] = None,
        prefix_chunk: Optional[int] = None,
        backend: str = "xla",
        prefix_max_entries: int = 64,
        quant: Optional[QuantConfig] = None,
    ):
        if page_size < 1 or ctx % page_size:
            raise ValueError(
                f"page_size {page_size} must divide ctx {ctx}"
            )
        self.cfg = cfg
        self.batch_size = batch_size
        self.ctx = ctx
        self.page_size = page_size
        self.pages_per_slot = P = ctx // page_size
        self.n_pages = int(n_pages) if n_pages else batch_size * P + _RESERVED
        if self.n_pages < _RESERVED + 1:
            raise ValueError(f"n_pages {self.n_pages} leaves no allocatable page")
        self.backend = backend
        # prefix-cache hashing granularity (engine's prefill_chunk); page-
        # aligned so cached boundaries cover only *full* pages
        self.prefix_chunk = prefix_chunk
        if prefix_chunk is not None and prefix_chunk % page_size:
            raise ValueError(
                f"prefix_chunk {prefix_chunk} must be a multiple of "
                f"page_size {page_size}"
            )
        # each entry pins a batch-1 residual snapshot (MoD rings, cursors)
        # in device memory — real bytes the page accounting alone wouldn't
        # see — so the registry is capacity-bounded, not just pressure-
        # evicted, and cache_bytes() reports the snapshot footprint
        self.prefix_max_entries = prefix_max_entries

        # KV quantization: which paged leaves are stored narrow (float k/v
        # rings), their scale-group counts and wide dtypes
        self.quant = quant if (quant is not None and quant.enabled) else None
        plan = _quant_leaf_plan(cfg, batch_size, ctx, self.quant)
        self._quant_ids = tuple(j for j, _, _ in plan)
        self._quant_groups = tuple(g for _, g, _ in plan)
        self._quant_dtypes = tuple(d for _, _, d in plan)

        full = api.make_caches(cfg, batch_size, ctx)
        flat, self._treedef = jax.tree_util.tree_flatten(full)
        self._axes = jax.tree_util.tree_leaves(_batch_axes(cfg, batch_size, ctx))
        self._paged_axes = _paged_leaf_axes(cfg, batch_size, ctx)
        self._paged_ids = sorted(self._paged_axes)
        self._resid_ids = [i for i in range(len(flat)) if i not in self._paged_axes]
        self._template = api.make_caches(cfg, 1, ctx)  # batch-1 initial values
        tmpl_flat = jax.tree_util.tree_leaves(self._template)

        # physical page storage: one template page broadcast n_pages times
        # (template content is position-uniform: zeros, pos = -1). Quantized
        # leaves store the narrow dtype; template zeros quantize exactly
        # (q = 0, scale = 1.0), so NULL/scrubbed pages dequantize back to
        # pristine template content.
        def phys(j, i):
            ax = self._paged_axes[i]
            t = jax.lax.index_in_dim(tmpl_flat[i], 0, ax, keepdims=False)
            page = jax.lax.slice_in_dim(t, 0, page_size, axis=ax)  # lead+(p,)+tail
            arr = jnp.broadcast_to(
                jnp.expand_dims(page, ax),
                page.shape[:ax] + (self.n_pages,) + page.shape[ax:],
            ).copy()
            if j in self._quant_ids:
                arr = arr.astype(self.quant.kv_dtype())
            return arr

        self.pages: List[jax.Array] = [
            phys(j, i) for j, i in enumerate(self._paged_ids)
        ]
        # canonical (n_pages, page_size, G) f32 scales per quantized leaf,
        # indexed by physical page id — refcounted prefix sharing, rollback
        # truncation and scrub-on-recycle carry them with the pages for free
        self.scales: List[jax.Array] = [
            jnp.ones((self.n_pages, page_size, g), jnp.float32)
            for g in self._quant_groups
        ]
        self.resid: List[jax.Array] = [flat[i] for i in self._resid_ids]

        # host-side page accounting
        self.table_np = np.full((batch_size, P), SCRATCH_PAGE, np.int32)
        self.n_mapped = np.zeros((batch_size,), np.int64)
        self.ref = np.zeros((self.n_pages,), np.int64)
        self.cache_cnt = np.zeros((self.n_pages,), np.int64)  # prefix entries per page
        self.free: deque = deque(range(_RESERVED, self.n_pages))
        # pages taken out of circulation by hold_pages() — fault injection
        # and maintenance; neither free nor owned by any slot/prefix entry
        self.held: List[int] = []
        self.prefix: "OrderedDict[bytes, PrefixEntry]" = OrderedDict()
        # telemetry
        self.prefix_hit_tokens = 0
        self.prefix_lookup_tokens = 0
        self.prefix_evictions = 0
        self.peak_pages_in_use = 0
        self.scrubbed_pages = 0  # pages zeroed on (re)mapping

        (self._reset_resid_fn, self._write_fn, self._scrub_fn,
         self._read_fn) = _pool_ops(cfg, batch_size, ctx, page_size, backend,
                                    self.quant)

    # -- pure (jitted) cache-movement ops ------------------------------

    def step_spec(self) -> PoolSpec:
        """Array-free static layout spec for the jitted decode step."""
        return PoolSpec(
            paged_ids=tuple(self._paged_ids),
            paged_axes=tuple(self._paged_axes[i] for i in self._paged_ids),
            resid_ids=tuple(self._resid_ids),
            treedef=self._treedef,
            page_size=self.page_size,
            backend=self.backend,
            axes=tuple(self._axes),
            quant=self.quant,
            quant_ids=self._quant_ids,
            quant_groups=self._quant_groups,
            quant_dtypes=self._quant_dtypes,
        )

    def snapshot_resid(self, work: Any) -> Dict[int, jax.Array]:
        """Residual-leaf snapshot of a batch-1 working cache (the non-paged
        prefix-dependent state stored in a PrefixEntry)."""
        leaves = jax.tree_util.tree_leaves(work)
        return {i: leaves[i] for i in self._resid_ids}

    def overlay_resid(self, work: Any, resid: Dict[int, jax.Array]) -> Any:
        """Replace a batch-1 working cache's residual leaves with a
        snapshot (prefix-cache restore)."""
        leaves = list(jax.tree_util.tree_leaves(work))
        for i, v in resid.items():
            leaves[i] = v
        return jax.tree_util.tree_unflatten(self._treedef, leaves)

    def snapshot_resid_slot(self, slot: int) -> Dict[int, jax.Array]:
        """Batch-1 residual snapshot of one *pool* slot — the ragged mixed
        step keeps its prefill working state in the pool itself, so prefix
        boundaries are snapshotted straight from the slot's residual rows
        (the padded path snapshots its batch-1 ``work`` pytree instead)."""
        return {
            i: jax.lax.dynamic_slice_in_dim(self.resid[j], slot, 1, axis=self._axes[i])
            for j, i in enumerate(self._resid_ids)
        }

    def overlay_resid_slot(self, slot: int, resid: Dict[int, jax.Array]) -> None:
        """Write a residual snapshot into one pool slot's rows (ragged-mode
        prefix restore: the chunk resumes against the pool, not a batch-1
        working copy)."""
        new = list(self.resid)
        for j, i in enumerate(self._resid_ids):
            if i in resid:
                new[j] = jax.lax.dynamic_update_slice_in_dim(
                    new[j], resid[i].astype(new[j].dtype), slot, axis=self._axes[i]
                )
        self.resid = new

    # -- slot lifecycle (host-side accounting + jitted data ops) -------

    def device_table(self) -> jax.Array:
        return jnp.asarray(self.table_np)

    def acquire(self, slot: int) -> None:
        """Claim a slot for a new request: residual rows back to template,
        page table to all-NULL (pristine reads until pages are mapped)."""
        self.release(slot)
        self.table_np[slot, :] = NULL_PAGE
        self.resid = self._reset_resid_fn(self.resid, slot)

    def release(self, slot: int) -> None:
        """Drop the slot's page references; pages go back to the free list
        unless a prefix-cache entry still pins them."""
        for j in range(int(self.n_mapped[slot])):
            pid = int(self.table_np[slot, j])
            if pid < _RESERVED:
                continue
            self.ref[pid] -= 1
            if self.ref[pid] == 0 and self.cache_cnt[pid] == 0:
                self.free.append(pid)
        self.table_np[slot, :] = SCRATCH_PAGE
        self.n_mapped[slot] = 0

    def truncate(self, slot: int, upto_tokens: int) -> int:
        """Speculative rollback: shrink the slot's mapping to the pages
        covering ``upto_tokens`` logical positions, releasing the tail
        pages (decref — a page survives if a prefix-cache entry or another
        slot still pins it). Tail table entries go back to NULL so reads
        past the truncation point hit the pristine NULL page, exactly as
        if those pages were never mapped. Stale rows *inside* the last
        kept page (positions >= upto_tokens) are left in place: the
        causal mask (`kv_pos <= q_pos`) hides them and the next accepted
        tokens overwrite them in position order. Returns the number of
        pages released."""
        keep = min(int(self.n_mapped[slot]), self.pages_needed(upto_tokens))
        dropped = 0
        for j in range(keep, int(self.n_mapped[slot])):
            pid = int(self.table_np[slot, j])
            self.table_np[slot, j] = NULL_PAGE
            if pid < _RESERVED:
                continue
            self.ref[pid] -= 1
            if self.ref[pid] == 0 and self.cache_cnt[pid] == 0:
                self.free.append(pid)
            dropped += 1
        self.n_mapped[slot] = keep
        return dropped

    def _evict_entry(self, key: bytes) -> None:
        entry = self.prefix.pop(key)
        self.prefix_evictions += 1
        for pid in entry.pages:
            self.cache_cnt[pid] -= 1
            if self.cache_cnt[pid] == 0 and self.ref[pid] == 0:
                self.free.append(pid)

    def _pop_free(self) -> Optional[int]:
        """Pop a free page, evicting prefix entries under pressure.

        Only entries whose eviction actually frees a page are evicted (a
        page frees iff no slot references it and this entry is its last
        registry pin) — evicting a still-slot-referenced entry would wipe
        reusable prefixes while freeing nothing. Oldest qualifying entry
        first (LRU order)."""
        while not self.free:
            victim = None
            for h, e in self.prefix.items():
                if any(
                    self.ref[pid] == 0 and self.cache_cnt[pid] == 1
                    for pid in e.pages
                ):
                    victim = h
                    break
            if victim is None:
                return None
            self._evict_entry(victim)
        return self.free.popleft()

    @property
    def allocatable_pages(self) -> int:
        """Hard capacity: every page that can ever hold request KV."""
        return self.n_pages - _RESERVED

    def available_pages(self) -> int:
        """Pages obtainable right now: free-list + evictable prefix pages."""
        evictable = int(
            np.sum((self.ref[_RESERVED:] == 0) & (self.cache_cnt[_RESERVED:] > 0))
        )
        return len(self.free) + evictable

    def pages_needed(self, n_tokens: int) -> int:
        return -(-n_tokens // self.page_size)

    def hold_pages(self, n: int) -> int:
        """Take up to ``n`` allocatable pages out of circulation (fault
        injection / maintenance): popped off the free list — evicting
        prefix entries under pressure like any allocation — into ``held``,
        where neither slots nor the prefix cache can reach them until
        :meth:`release_held`. Returns how many were actually taken (the
        pool may have fewer obtainable). Held pages are a transient
        condition, so ``allocatable_pages`` (the submit-time capacity
        check) is unaffected while ``available_pages`` shrinks — the
        admission gate closes and lazy growth hits the preemption path,
        which is exactly the overload behaviour the fault exercises."""
        taken = 0
        while taken < n:
            pid = self._pop_free()
            if pid is None:
                break
            self.held.append(pid)
            taken += 1
        return taken

    def release_held(self) -> int:
        """Return every held page to the free list; returns the count."""
        n = len(self.held)
        self.free.extend(self.held)
        self.held.clear()
        return n

    def alloc_pages(self, slot: int, upto_tokens: int) -> bool:
        """Map (and scrub) owned pages so the slot covers ``upto_tokens``
        logical positions. False = pool exhausted (caller preempts)."""
        need = self.pages_needed(upto_tokens)
        new_ids = []
        while int(self.n_mapped[slot]) < need:
            pid = self._pop_free()
            if pid is None:
                if new_ids:
                    self.pages, self.scales = self._scrub_fn(
                    self.pages, self.scales, self._pad_ids(new_ids))
                    self.scrubbed_pages += len(new_ids)
                    # partial maps still raise in_use: peak must see them
                    self.peak_pages_in_use = max(
                        self.peak_pages_in_use,
                        int(np.sum(self.ref[_RESERVED:] > 0)),
                    )
                return False
            j = int(self.n_mapped[slot])
            self.table_np[slot, j] = pid
            self.ref[pid] += 1
            self.n_mapped[slot] += 1
            new_ids.append(pid)
        if new_ids:
            self.pages, self.scales = self._scrub_fn(
                    self.pages, self.scales, self._pad_ids(new_ids))
            self.scrubbed_pages += len(new_ids)
        self.peak_pages_in_use = max(
            self.peak_pages_in_use, int(np.sum(self.ref[_RESERVED:] > 0))
        )
        return True

    def _pad_ids(self, ids: List[int]) -> jax.Array:
        pad = [SCRATCH_PAGE] * (self.pages_per_slot - len(ids))
        return jnp.asarray((ids + pad)[: self.pages_per_slot], jnp.int32)

    def write_slot(self, slot: int, sub: Any, start_page: int = 0) -> None:
        """Scatter a batch-1 cache pytree into the slot: residual rows
        wholesale, paged leaves page-by-page into the slot's *owned* pages
        (logical pages below ``start_page`` — restored shared prefix — are
        skipped so shared pages are never rewritten)."""
        dest = np.full((self.pages_per_slot,), SCRATCH_PAGE, np.int32)
        n = int(self.n_mapped[slot])
        dest[start_page:n] = self.table_np[slot, start_page:n]
        self.pages, self.scales, self.resid = self._write_fn(
            self.pages, self.scales, self.resid, sub, jnp.asarray(dest), slot
        )

    def read_slot(self, slot: int) -> Any:
        return self._read_fn(
            self.pages, self.scales, self.resid,
            jnp.asarray(self.table_np[slot]), slot
        )

    # -- prefix cache ---------------------------------------------------

    def _chain_hashes(self, tokens: np.ndarray) -> List[Tuple[int, bytes]]:
        """(boundary n_tokens, chain hash) per full prefill chunk."""
        if self.prefix_chunk is None:
            return []
        c = self.prefix_chunk
        out, h = [], b"paged-prefix"
        for end in range(c, len(tokens) + 1, c):
            h = hashlib.sha1(h + np.ascontiguousarray(tokens[end - c : end]).tobytes()).digest()
            out.append((end, h))
        return out

    def prefix_probe_pages(self, tokens: np.ndarray) -> int:
        """Pages a prefix hit would cover for this prompt — admission-gate
        probe only: touches neither the LRU order nor the hit telemetry."""
        best = 0
        for end, h in self._chain_hashes(tokens):
            if end >= len(tokens) or h not in self.prefix:
                break
            best = len(self.prefix[h].pages)
        return best

    def prefix_match(self, tokens: np.ndarray) -> Optional[Tuple[bytes, PrefixEntry]]:
        """Longest cached chunk-aligned *proper* prefix of ``tokens``
        (strictly shorter than the prompt: at least one token must still
        run through prefill to produce first-token logits)."""
        best = None
        for end, h in self._chain_hashes(tokens):
            if end >= len(tokens):
                break
            e = self.prefix.get(h)
            if e is None:
                break
            best = (h, e)
        self.prefix_lookup_tokens += len(tokens)
        return best

    def prefix_attach(self, slot: int, key: bytes) -> Dict[int, jax.Array]:
        """Map a cached prefix's shared pages into the slot (incref) and
        return the residual-state snapshot to resume prefill from."""
        entry = self.prefix[key]
        self.prefix.move_to_end(key)
        n = len(entry.pages)
        for j, pid in enumerate(entry.pages):
            self.table_np[slot, j] = pid
            self.ref[pid] += 1
        self.n_mapped[slot] = n
        self.prefix_hit_tokens += entry.n_tokens
        self.peak_pages_in_use = max(
            self.peak_pages_in_use, int(np.sum(self.ref[_RESERVED:] > 0))
        )
        return entry.resid

    def prefix_register(
        self, slot: int, tokens: np.ndarray, boundary_resids: Dict[int, Dict[int, jax.Array]]
    ) -> None:
        """Insert entries for every chunk boundary prefilled this admission
        (``boundary_resids``: n_tokens -> residual snapshot at boundary)."""
        for end, h in self._chain_hashes(tokens):
            if h in self.prefix:
                self.prefix.move_to_end(h)
                continue
            if end not in boundary_resids:
                continue
            npg = end // self.page_size
            pages = tuple(int(x) for x in self.table_np[slot, :npg])
            for pid in pages:
                self.cache_cnt[pid] += 1
            self.prefix[h] = PrefixEntry(
                n_tokens=end, pages=pages, resid=boundary_resids[end]
            )
        # capacity bound on entries (their residual snapshots are device
        # memory): evict oldest regardless of page freeability — the point
        # is reclaiming the snapshot, pages follow their refcounts
        while len(self.prefix) > self.prefix_max_entries:
            self._evict_entry(next(iter(self.prefix)))

    # -- telemetry ------------------------------------------------------

    def page_stats(self) -> Dict[str, float]:
        alloc = self.n_pages - _RESERVED
        in_use = int(np.sum(self.ref[_RESERVED:] > 0))
        cached_only = int(
            np.sum((self.ref[_RESERVED:] == 0) & (self.cache_cnt[_RESERVED:] > 0))
        )
        return {
            "n_pages": float(alloc),
            "pages_in_use": float(in_use),
            "pages_cached_only": float(cached_only),
            "pages_free": float(len(self.free)),
            "pages_held": float(len(self.held)),
            "page_utilization": in_use / alloc if alloc else 0.0,
            "page_utilization_peak": (
                self.peak_pages_in_use / alloc if alloc else 0.0
            ),
            "prefix_entries": float(len(self.prefix)),
            "prefix_resid_bytes": self._prefix_resid_bytes(),
            "prefix_hit_rate": (
                self.prefix_hit_tokens / self.prefix_lookup_tokens
                if self.prefix_lookup_tokens
                else 0.0
            ),
            "prefix_evictions": float(self.prefix_evictions),
        }

    def _prefix_resid_bytes(self) -> float:
        """Device bytes pinned by prefix entries' residual snapshots."""
        return float(sum(
            leaf.size * leaf.dtype.itemsize
            for e in self.prefix.values()
            for leaf in e.resid.values()
        ))

    def cache_bytes(self) -> Dict[str, float]:
        """Physical footprint (pages + residual + prefix snapshots), same
        mod/full split as CachePool.

        All paged leaves are full-attention rings, so they count as "full";
        the residual pool carries the capacity-sized MoD rings ("mod"),
        and ``prefix_resid`` is the registry's snapshot memory (bounded by
        ``prefix_max_entries``).
        """
        sizes = {"total": 0.0, "mod": 0.0, "full": 0.0, "paged": 0.0,
                 "resid": 0.0, "prefix_resid": self._prefix_resid_bytes()}
        sizes["total"] += sizes["prefix_resid"]
        paths = jax.tree_util.tree_flatten_with_path(
            api.make_caches(self.cfg, self.batch_size, self.ctx, specs=True)
        )[0]
        for j, i in enumerate(self._paged_ids):
            b = float(self.pages[j].size * self.pages[j].dtype.itemsize)
            sizes["total"] += b
            sizes["full"] += b
            sizes["paged"] += b
        for s in self.scales:
            b = float(s.size * s.dtype.itemsize)
            sizes["total"] += b
            sizes["full"] += b
            sizes["paged"] += b
        for j, i in enumerate(self._resid_ids):
            leaf = self.resid[j]
            b = float(leaf.size * leaf.dtype.itemsize)
            keys = [getattr(p, "key", None) for p in paths[i][0]]
            sizes["total"] += b
            sizes["mod" if "mod" in keys else "full"] += b
            sizes["resid"] += b
        # per-leaf-kind totals for the serving benchmark / stats() surface:
        # kv_bytes is everything page-addressed (narrow pages + scales +
        # the exact int32 pos ring), resid_bytes the slot-contiguous rest
        sizes["kv_bytes"] = sizes["paged"]
        sizes["resid_bytes"] = sizes["resid"]
        sizes["mod_vs_full_ratio"] = sizes["mod"] / sizes["full"] if sizes["full"] else 0.0
        return sizes
