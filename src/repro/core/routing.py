"""Unified routed-execution engine: route-select + dispatch + combine.

Every MoD site in the codebase — train/teacher-forced forwards, prefill, and
batched decode, across all four model families — goes through this module.
The paper's Eq. 1,

    x_{l+1}[i] = x_l[i] + r_i * f(X̃)[i]   if i routed
    x_{l+1}[i] = x_l[i]                    otherwise

factors into three pieces:

1. a :class:`RouteDecision` — *which rows* participate and with *what gate*.
   Two strategies share the interface:

   - ``token_topk`` (train / prefill): per-sequence expert-choice top-k over
     the time axis (paper §3.2); ``idx`` is (B, k).
   - ``batch_capacity`` (decode): the causal score (trained predictor or
     router sigmoid) ranks *sequences*, and the top ``round(ratio·B)`` run
     the block this step; ``idx`` is (kb,). Shapes stay static, so the FLOP
     saving is realizable in batched serving (DESIGN.md §Routing engine).

2. :func:`execute_routed` — run the block's residual on the routed rows and
   gated scatter-add the result back (Eq. 1), via a pluggable backend
   (``MoDConfig.backend``):

   - ``"xla"``: gather (take_along_axis) -> block -> combine (at[].add) —
     the reference path.
   - ``"pallas"``: same three passes, but gather/combine are fused one-hot
     matmul kernels (kernels/routing.py) — one VMEM pass each.
   - ``"pallas_fused"``: no dispatch passes at all. The block supplies a
     ``fused_block_fn`` and the dispatch rides *inside* its compute
     kernels: the gather is the routed-attention kernel's prologue and the
     gated scatter-add is the routed-MLP kernel's epilogue
     (kernels/flash_attention.py / kernels/swiglu.py), so the
     capacity-sized sub-tensor never round-trips through HBM. Blocks that
     cannot fuse (SSM/enc-dec deltas, generic delta_fns, prefill cache
     writes) fall back to the ``pallas`` kernels under the same config.

   ``batch_capacity`` moves (kb, 1, D) rows — far below kernel-worthy size —
   so it always uses XLA ops regardless of backend.

3. aux/loss plumbing — :func:`routing_aux` emits the router BCE, predictor
   BCE/acc and routing stats that train loops weight into the loss.

New block types plug in as a single ``block_delta_fn`` (plus, for decode, a
``block_fn`` that threads caches) instead of re-implementing the
gather/scatter wiring per family.

SPMD: every entry point takes an optional
:class:`repro.distributed.sharding.ShardCtx`. With one, the routing
decision and the dispatch run *per data shard* inside ``shard_map`` (the
(B, S, D) stream is never resharded; ``batch_capacity`` switches to
partitioned per-shard selection preserving the global budget) while the
block's tensor-parallel layouts stay under GSPMD — DESIGN.md §SPMD routed
execution, equivalence pinned in tests/test_routing_spmd.py.
"""
from __future__ import annotations

from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig
from repro.core import router as R
from repro.distributed.sharding import ShardCtx
from repro.utils import scoped

Params = Dict[str, jax.Array]
Aux = Dict[str, jax.Array]

# block_delta_fn(x_sub, pos_sub) -> (delta_sub, aux) — the block's residual
# update on the gathered sub-tensor plus any auxiliary outputs (e.g. MoE
# balance losses when composing MoDE).
BlockDeltaFn = Callable[[jax.Array, Optional[jax.Array]], Tuple[jax.Array, Aux]]

# fused_block_fn(x_full, decision, positions_full) -> (x_new_full, aux) —
# the fused-dispatch execution mode ("pallas_fused"): the block receives the
# FULL residual stream plus the RouteDecision and returns the FULL updated
# stream; gather and gated combine happen inside its compute kernels.
FusedBlockFn = Callable[
    [jax.Array, "RouteDecision", Optional[jax.Array]], Tuple[jax.Array, Aux]
]


class RouteDecision(NamedTuple):
    """Which rows a routed block runs on, and how much their output counts.

    strategy: "token_topk" (idx (B, k) over the time axis) or
              "batch_capacity" (idx (kb,) over the batch axis).
    idx:      routed row indices, sorted ascending, unique.
    gate:     f32 router weight per routed row — multiplies the block output
              so the router stays on the gradient path (paper Eq. 1).
    mask:     routed-membership mask — (B, S) bool for token_topk (the
              aux-loss target), (B,) bool for batch_capacity.
    logits:   full router logits (B, S) f32 when the decision came from the
              learned router on the full tensor (token_topk); None otherwise.
    scores:   (B,) f32 causal ranking scores (predictor or router sigmoid
              logits) for batch_capacity decisions; None for token_topk.
              Surfaced through ``decode_aux`` so the serving scheduler can
              co-rank slots with the router (DESIGN.md §Serving engine).
    """

    strategy: str
    idx: jax.Array
    gate: jax.Array
    mask: jax.Array
    logits: Optional[jax.Array] = None
    scores: Optional[jax.Array] = None


# ---------------------------------------------------------------------------
# Route selection strategies
# ---------------------------------------------------------------------------


@scoped("mod.router")
def decide_tokens(
    params: Params,
    x: jax.Array,  # (B, S, D)
    cfg: ModelConfig,
    rng: Optional[jax.Array] = None,
    spmd: Optional[ShardCtx] = None,
) -> RouteDecision:
    """Train/prefill strategy: expert-choice top-k over the sequence axis.

    ``token_topk`` selection is per-sequence (top-k over the *time* axis),
    so its semantics never depend on how the batch is sharded. Under an
    SPMD :class:`~repro.distributed.sharding.ShardCtx` the router logits +
    top-k run per-shard inside ``shard_map`` over the data axes — bitwise
    identical to the single-device decision, with no cross-device movement
    of the (B, S, D) stream. The stochastic-router control samples one
    (B, S) Gaussian and stays on the plain path (per-shard RNG streams
    would change the control's selections).
    """
    k = cfg.mod.capacity(x.shape[1])
    if (
        spmd is not None
        and spmd.spmd
        and cfg.mod.router_type != "stochastic"
        and x.shape[0] % spmd.data_shards == 0
    ):
        def _local(rp, xl):
            logits_l = R.router_logits(rp, xl)
            idx_l, gate_logits_l, mask_l = R.mod_select(logits_l, k, cfg.mod, None)
            return idx_l, R.apply_gate(gate_logits_l, cfg.mod), mask_l, logits_l

        # fully-manual region (model axes replicated): top_k lowers to sort,
        # which this XLA version cannot partition inside a partial-auto
        # (manual-subgroup) region — and the decision is a per-row scalar op,
        # so replicating it across the model axis costs nothing.
        dspec = spmd.data_spec(2)
        idx, gate, mask, logits = jax.shard_map(
            _local,
            mesh=spmd.mesh,
            in_specs=(jax.tree.map(lambda _: P(), params["router"]), spmd.data_spec(3)),
            out_specs=(dspec, dspec, dspec, dspec),
            check_vma=False,
        )(params["router"], x)
        return RouteDecision("token_topk", idx, gate, mask, logits)
    logits = R.router_logits(params["router"], x)  # (B, S) f32
    idx, gate_logits, topk_mask = R.mod_select(logits, k, cfg.mod, rng)
    gate = R.apply_gate(gate_logits, cfg.mod)
    return RouteDecision("token_topk", idx, gate, topk_mask, logits)


@scoped("mod.router")
def decide_tokens_ragged(
    params: Params,
    x: jax.Array,  # (1, T, D) flat token stream
    row_offsets: jax.Array,  # (n_seg+1,) int32, non-decreasing, starts at 0
    cfg: ModelConfig,
    seg_cap: int,  # static bound: every segment has <= seg_cap tokens
    rng: Optional[jax.Array] = None,
) -> RouteDecision:
    """Segment-aware ``token_topk`` over a flat token stream.

    The expert-choice top-k is per *segment* (one request's tokens between
    consecutive row offsets), exactly the padded path's per-sequence
    selection: each segment's router logits are windowed into a
    ``(n_seg, seg_cap)`` view with tails at ``-inf`` (matching the padded
    chunk's ``positions < 0`` demotion) and ``mod_select`` runs on that —
    for equal-length segments the windowed view IS the padded ``(B, S)``
    tensor, so the decision is bit-for-bit identical. ``idx`` comes back as
    *flat* row indices ``(n_seg, k)`` with masked tail selections at ``-1``
    (never a clamped pointer into a neighbouring segment); ``gate`` is
    zeroed there, and ``mask``/``logits`` keep the flat ``(1, T)`` layout
    so :func:`routing_aux` works unchanged.
    """
    T = x.shape[1]
    n_seg = row_offsets.shape[0] - 1
    C = int(seg_cap)
    k_cap = cfg.mod.capacity(C)
    offs = row_offsets.astype(jnp.int32)
    lens = offs[1:] - offs[:-1]  # (n_seg,)
    logits_flat = R.router_logits(params["router"], x)  # (1, T) f32
    win = offs[:-1, None] + jnp.arange(C, dtype=jnp.int32)[None]  # (n_seg, C)
    valid = jnp.arange(C, dtype=jnp.int32)[None] < lens[:, None]
    win_c = jnp.clip(win, 0, T - 1)
    wlogits = jnp.where(valid, logits_flat[0][win_c], -jnp.inf)
    idx_l, gate_logits, _ = R.mod_select(wlogits, k_cap, cfg.mod, rng)
    gate = R.apply_gate(gate_logits, cfg.mod)
    sel_valid = jnp.take_along_axis(valid, idx_l, axis=1)
    gate = jnp.where(sel_valid, gate, 0.0)
    idx_flat = jnp.where(sel_valid, offs[:-1, None] + idx_l, -1).astype(jnp.int32)
    safe = jnp.where(idx_flat >= 0, idx_flat, T)
    mask_flat = (
        jnp.zeros((T + 1,), bool).at[safe.reshape(-1)].set(True)[:T][None]
    )  # (1, T)
    return RouteDecision("token_topk_ragged", idx_flat, gate, mask_flat, logits_flat)


def execute_routed_ragged(
    decision: RouteDecision,
    x: jax.Array,  # (1, T, D) flat token stream
    block_delta_fn: BlockDeltaFn,
    cfg: ModelConfig,
    positions: Optional[jax.Array] = None,  # (1, T) int32
) -> Tuple[jax.Array, Aux]:
    """Eq. 1 over the flat stream: gather the routed rows of every segment
    into one ``(n_seg, k, D)`` sub-tensor, run the block delta (the block
    sees segments as batch rows — same shapes as the padded path), and
    gated-scatter-add back onto the flat stream.

    Backends mirror :func:`execute_routed`: ``"xla"`` uses a dump-row
    take / at-add, ``"pallas"`` the flat one-hot kernels
    (kernels/ragged.py). ``"pallas_fused"`` has no ragged fused block yet
    and falls back to the pallas dispatch kernels under the same config.
    """
    assert decision.strategy == "token_topk_ragged", decision.strategy
    T = x.shape[1]
    idx = decision.idx  # (n_seg, k) flat, -1 masked
    backend = cfg.mod.backend
    if backend not in BACKENDS:
        raise ValueError(f"unknown MoD backend {backend!r} (want one of {BACKENDS})")
    if positions is None:
        pos_sub = None
    else:
        pos_flat = positions[0]
        pos_sub = jnp.where(idx >= 0, pos_flat[jnp.clip(idx, 0, T - 1)], -1)
    if backend in ("pallas", "pallas_fused"):
        from repro.kernels.ops import ragged_gather_rows_op, ragged_scatter_add_rows_op

        with jax.named_scope("mod.dispatch"):
            x_sub = ragged_gather_rows_op(x[0], idx)
        delta, aux = block_delta_fn(x_sub, pos_sub)
        with jax.named_scope("mod.dispatch"):
            out = ragged_scatter_add_rows_op(x[0], idx, delta, decision.gate)
        return out[None], aux
    with jax.named_scope("mod.dispatch"):
        xp = jnp.concatenate([x[0], jnp.zeros((1, x.shape[2]), x.dtype)])
        x_sub = jnp.take(xp, jnp.where(idx >= 0, idx, T), axis=0)
    delta, aux = block_delta_fn(x_sub, pos_sub)
    with jax.named_scope("mod.dispatch"):
        update = (decision.gate[..., None] * delta.astype(jnp.float32)).astype(x.dtype)
        k = idx.shape[1]
        out = (
            jnp.concatenate([x[0], jnp.zeros((1, x.shape[2]), x.dtype)])
            .at[jnp.where(idx >= 0, idx, T).reshape(-1)]
            .add(update.reshape(idx.shape[0] * k, -1))[:T]
        )
    return out[None], aux


def batch_capacity_k(cfg: ModelConfig, batch: int, data_shards: int = 1) -> int:
    """kb of the batch_capacity strategy: rows routed per decode step.

    ``data_shards == 1``: ``max(1, round(ratio·B))``. With a partitioned
    batch (SPMD decode), every shard routes
    ``kb_local = batch_capacity_k(cfg, B // d)`` of its own rows, so the
    *global* budget is ``d · kb_local``. The single source of truth — the
    serving scheduler budgets admissions against this same (global) number.

    ``ratio <= 0`` returns 0 (not the usual floor of 1): the speculative
    drafter runs the model at ``capacity_ratio=0.0`` to get the pure
    residual-skip path, and a kb=0 ``top_k``/gather/scatter round trip
    over zero rows is well-defined all the way through ``route_decode``.
    """
    if cfg.mod.capacity_ratio <= 0.0:
        return 0
    if data_shards > 1:
        assert batch % data_shards == 0, (batch, data_shards)
        return data_shards * batch_capacity_k(cfg, batch // data_shards)
    return max(1, int(round(cfg.mod.capacity_ratio * batch)))


def capacity_ladder(cfg: ModelConfig, scales) -> Tuple[ModelConfig, ...]:
    """Discrete degraded-capacity configs for the serving engine's
    :class:`~repro.serve.overload.CapacityController`.

    ``scales`` is a descending ladder of multipliers on
    ``cfg.mod.capacity_ratio`` starting at full capacity (level 0 = 1.0).
    Each returned config differs from ``cfg`` only in the ratio, which is
    shape-free at decode time — ``batch_capacity`` caches are sized by the
    *pool's* config, the per-level config only shrinks ``kb``
    (:func:`batch_capacity_k`) — so each level is exactly one extra
    compiled decode step and the jit cache stays bounded by the ladder
    length. MoD-less configs get an all-identical ladder: the ladder then
    degrades only host-side budgets (prefill segments / admissions), never
    the model.
    """
    import dataclasses

    scales = tuple(float(s) for s in scales)
    if not scales or scales[0] != 1.0:
        raise ValueError(f"capacity ladder must start at 1.0, got {scales!r}")
    if any(not (0.0 < s <= 1.0) for s in scales):
        raise ValueError(f"capacity scales must lie in (0, 1], got {scales!r}")
    if any(b >= a for a, b in zip(scales, scales[1:])):
        raise ValueError(f"capacity scales must strictly descend, got {scales!r}")
    if not cfg.mod.enabled:
        return (cfg,) * len(scales)
    return tuple(
        dataclasses.replace(
            cfg,
            mod=dataclasses.replace(
                cfg.mod, capacity_ratio=cfg.mod.capacity_ratio * s
            ),
        )
        for s in scales
    )


@scoped("mod.router")
def decide_batch(
    params: Params,
    x: jax.Array,  # (B, 1, D) — one decode token per sequence
    cfg: ModelConfig,
    active: Optional[jax.Array] = None,  # (B,) bool — live serving slots
    data_shards: int = 1,
) -> RouteDecision:
    """Decode strategy: batch-capacity routing.

    The per-token *decision* must be causal: it comes from the predictor
    (``sampling="predictor"``) or the router's own sigmoid
    (``sampling="aux_loss"`` — r_i is itself causal; only training-time
    *selection* was non-causal). To keep shapes static and realize FLOP
    savings in batched serving, the top ``kb = round(ratio·B)`` scoring
    sequences in the batch go through the block this step.

    ``active`` marks which batch rows hold live sequences (the serving
    engine decodes a fixed-shape batch whose free slots carry padding);
    inactive rows are pushed below every active row in the ranking so
    padding can never steal routed capacity from a real sequence. Shapes —
    and therefore the compiled step — are unchanged; kb stays
    ``round(ratio·B)``.

    ``data_shards > 1`` switches to the *partitioned* selection semantics
    of SPMD decode: the batch splits into ``data_shards`` contiguous
    groups (one per data shard) and each group routes its own top
    ``kb_local = round(ratio·B/d)`` rows. The global budget becomes
    ``batch_capacity_k(cfg, B, d) = d·kb_local`` — close to, but not
    always equal to, the unsharded ``round(ratio·B)``: per-shard rounding
    (and the ≥1-row-per-shard floor) can land above *or* below it. What
    partitioning buys is that selection needs no cross-group information,
    which is what keeps a batch-sharded cache pool's gather/scatter
    shard-local. The same value of ``data_shards``
    must be used on every device count — it is a *semantic* parameter, not
    an execution detail (tests/test_routing_spmd.py pins single-device vs
    8-device equality under the same ``data_shards``).
    """
    B = x.shape[0]
    kb_local = batch_capacity_k(cfg, B // data_shards if data_shards > 1 else B)
    if cfg.mod.sampling == "predictor" and "predictor" in params:
        scores = R.predictor_logits(params["predictor"], x)[:, 0]  # (B,)
    else:
        scores = R.router_logits(params["router"], x)[:, 0]
    ranking = scores if active is None else jnp.where(active, scores, -jnp.inf)
    idx = R.batch_select(ranking, kb_local, data_shards)
    gate_logits = R.router_logits(params["router"], x)[:, 0]  # causal gate
    gate = R.apply_gate(jnp.take(gate_logits, idx), cfg.mod)
    routed = jnp.zeros((B,), bool).at[idx].set(True)
    return RouteDecision("batch_capacity", idx, gate, routed, scores=scores)


# ---------------------------------------------------------------------------
# Dispatch / combine backends
# ---------------------------------------------------------------------------


BACKENDS = ("xla", "pallas", "pallas_fused")


@scoped("mod.dispatch")
def _gather_tokens(x: jax.Array, idx: jax.Array, backend: str) -> jax.Array:
    # pallas_fused lands here only on its fallback path (no fused_block_fn):
    # the standalone pallas kernels are then the best available dispatch
    if backend in ("pallas", "pallas_fused"):
        from repro.kernels.ops import gather_rows_op

        return gather_rows_op(x, idx)
    if backend != "xla":
        raise ValueError(f"unknown MoD backend {backend!r} (want one of {BACKENDS})")
    return jnp.take_along_axis(x, idx[..., None], axis=1)


@scoped("mod.dispatch")
def _scatter_add_tokens(
    x: jax.Array, idx: jax.Array, delta: jax.Array, gate: jax.Array, backend: str
) -> jax.Array:
    if backend in ("pallas", "pallas_fused"):
        from repro.kernels.ops import scatter_add_rows_op

        return scatter_add_rows_op(x, idx, delta, gate)
    if backend != "xla":
        raise ValueError(f"unknown MoD backend {backend!r} (want one of {BACKENDS})")
    update = (gate[..., None] * delta.astype(jnp.float32)).astype(x.dtype)
    B = x.shape[0]
    return x.at[jnp.arange(B)[:, None], idx].add(update)


@scoped("mod.dispatch")
def gather_positions(positions: jax.Array, idx: jax.Array) -> jax.Array:
    """Token-axis position gather. positions: (B,S) or (3,B,S); idx: (B,k)."""
    if positions.ndim == 3:
        return jnp.take_along_axis(positions, idx[None].repeat(3, 0), axis=2)
    return jnp.take_along_axis(positions, idx, axis=1)


@scoped("mod.dispatch")
def _take_batch_positions(positions: jax.Array, idx: jax.Array) -> jax.Array:
    """Batch-axis position gather. positions: (B,1) or (3,B,1); idx: (kb,)."""
    if positions.ndim == 3:
        return jnp.take(positions, idx, axis=1)
    return jnp.take(positions, idx, axis=0)


def _pos_spec(positions: Optional[jax.Array], spmd: ShardCtx) -> Optional[P]:
    """Batch-sharded spec for (B, ...) or M-RoPE (3, B, ...) positions."""
    if positions is None:
        return None
    return spmd.data_spec(positions.ndim, batch_axis=1 if positions.ndim == 3 else 0)


def spmd_gather_tokens(
    x: jax.Array, idx: jax.Array, spmd: ShardCtx, backend: str
) -> jax.Array:
    """Per-shard token gather: each data shard selects its own rows' routed
    tokens inside ``shard_map`` — the (B, S, D) stream is never resharded.
    The region is fully manual (dispatch touches no model-sharded operand:
    the stream's D dim is replicated over the model axis)."""
    return jax.shard_map(
        lambda xl, il: _gather_tokens(xl, il, backend),
        mesh=spmd.mesh,
        in_specs=(spmd.data_spec(3), spmd.data_spec(2)),
        out_specs=spmd.data_spec(3),
        check_vma=False,
    )(x, idx)


def spmd_scatter_add_tokens(
    x: jax.Array,
    idx: jax.Array,
    delta: jax.Array,
    gate: jax.Array,
    spmd: ShardCtx,
    backend: str,
) -> jax.Array:
    """Per-shard gated scatter-add (Eq. 1 combine) inside ``shard_map``."""
    return jax.shard_map(
        lambda xl, il, dl, gl: _scatter_add_tokens(xl, il, dl, gl, backend),
        mesh=spmd.mesh,
        in_specs=(
            spmd.data_spec(3),
            spmd.data_spec(2),
            spmd.data_spec(3),
            spmd.data_spec(2),
        ),
        out_specs=spmd.data_spec(3),
        check_vma=False,
    )(x, idx, delta, gate)


@scoped("mod.dispatch")
def gather_batch(decision: RouteDecision, tree):
    """Gather the routed sequences' slices of a cache pytree (decode)."""
    return jax.tree.map(lambda c: jnp.take(c, decision.idx, axis=0), tree)


@scoped("mod.dispatch")
def scatter_batch(decision: RouteDecision, tree, sub):
    """Write updated routed-sequence slices back into a cache pytree."""
    return jax.tree.map(lambda c, cs: c.at[decision.idx].set(cs), tree, sub)


def execute_routed(
    decision: RouteDecision,
    x: jax.Array,  # (B, S, D) token_topk / (B, 1, D) batch_capacity
    block_delta_fn: BlockDeltaFn,
    cfg: ModelConfig,
    positions: Optional[jax.Array] = None,
    fused_block_fn: Optional[FusedBlockFn] = None,
    spmd: Optional[ShardCtx] = None,
) -> Tuple[jax.Array, Aux]:
    """Gather routed rows -> block residual -> gated scatter-add (Eq. 1).

    Under ``backend="pallas_fused"`` with a ``fused_block_fn``, the three
    passes collapse into the block's own kernels: the fn gets the full
    stream + decision and returns the full updated stream (gather in the
    attention prologue, gated combine in the MLP epilogue). Without a
    ``fused_block_fn`` the pallas dispatch kernels are used instead.

    With an SPMD :class:`ShardCtx`, the token_topk gather and gated
    scatter run per-shard inside ``shard_map`` over the data axes while
    the block delta itself stays under GSPMD — its tensor-parallel param
    layouts (QKV on heads, MLP on ffn) keep working unchanged, with psum
    only where the dense path already implies it. A supplied
    ``fused_block_fn`` already passed the mesh-compat gate
    (``models.blocks.fused_dispatch_supported``) and runs per-shard
    fully-manual; when the mesh splits a fused dim the caller passes None
    and this falls back to the sharded gather/scatter around the xla (or
    pallas) block path.
    """
    use_spmd = spmd is not None and spmd.spmd and x.shape[0] % spmd.data_shards == 0
    if decision.strategy == "token_topk":
        if cfg.mod.backend == "pallas_fused" and fused_block_fn is not None:
            if not use_spmd:
                return fused_block_fn(x, decision, positions)
            return _spmd_fused(decision, x, fused_block_fn, positions, spmd)
        if use_spmd:
            x_sub = spmd_gather_tokens(x, decision.idx, spmd, cfg.mod.backend)
            pos_sub = (
                None if positions is None else gather_positions(positions, decision.idx)
            )
            delta, aux = block_delta_fn(x_sub, pos_sub)
            out = spmd_scatter_add_tokens(
                x, decision.idx, delta, decision.gate, spmd, cfg.mod.backend
            )
            return out, aux
        x_sub = _gather_tokens(x, decision.idx, cfg.mod.backend)
        pos_sub = None if positions is None else gather_positions(positions, decision.idx)
        delta, aux = block_delta_fn(x_sub, pos_sub)
        out = _scatter_add_tokens(x, decision.idx, delta, decision.gate, cfg.mod.backend)
        return out, aux

    assert decision.strategy == "batch_capacity", decision.strategy
    x_sub = _take_batch_rows(x, decision)
    pos_sub = None if positions is None else _take_batch_positions(positions, decision.idx)
    delta, aux = block_delta_fn(x_sub, pos_sub)
    return _add_batch_rows(x, decision, delta), aux


@scoped("mod.dispatch")
def _take_batch_rows(x: jax.Array, decision: RouteDecision) -> jax.Array:
    """The routed rows of a (B, 1, D) decode stream (batch_capacity)."""
    return jnp.take(x, decision.idx, axis=0)


@scoped("mod.dispatch")
def _add_batch_rows(x: jax.Array, decision: RouteDecision, delta: jax.Array) -> jax.Array:
    """Gated scatter-add of the routed rows' block output (batch_capacity)."""
    update = (decision.gate[:, None, None] * delta.astype(jnp.float32)).astype(x.dtype)
    return x.at[decision.idx].add(update)


def _spmd_fused(
    decision: RouteDecision,
    x: jax.Array,
    fused_block_fn: FusedBlockFn,
    positions: Optional[jax.Array],
    spmd: ShardCtx,
) -> Tuple[jax.Array, Aux]:
    """Run a fused-dispatch block per data shard (pure DP: every fused dim
    is whole on every device, so the kernels execute unchanged on the
    shard-local (B/d, S, D) stream). Aux leaves come back stacked with a
    leading shard axis and are averaged — shards hold equal row counts, so
    the mean-of-means equals the global mean for per-token statistics."""
    has_logits = decision.logits is not None
    logits = decision.logits if has_logits else decision.mask

    def _local(xl, il, gl, ml, ll, posl):
        dl = RouteDecision("token_topk", il, gl, ml, ll if has_logits else None)
        out_l, aux_l = fused_block_fn(xl, dl, posl)
        return out_l, jax.tree.map(lambda a: a[None], aux_l)

    dspec = spmd.data_spec(2)
    aux_struct = jax.eval_shape(lambda: fused_block_fn(x, decision, positions)[1])
    aux_specs = jax.tree.map(lambda _: P(spmd.data_axes), aux_struct)
    # fully manual: fused dispatch only runs under pure DP (every fused dim
    # whole per device — models.blocks.fused_dispatch_supported), so any
    # model axis present has size 1 and replication over it is free
    out, aux_stack = jax.shard_map(
        _local,
        mesh=spmd.mesh,
        in_specs=(
            spmd.data_spec(3), dspec, dspec, dspec, dspec, _pos_spec(positions, spmd),
        ),
        out_specs=(spmd.data_spec(3), aux_specs),
        check_vma=False,
    )(x, decision.idx, decision.gate, decision.mask, logits, positions)
    return out, jax.tree.map(lambda a: jnp.mean(a, axis=0), aux_stack)


# ---------------------------------------------------------------------------
# Aux losses / stats
# ---------------------------------------------------------------------------


@scoped("mod.router")
def routing_aux(
    decision: RouteDecision, params: Params, x: jax.Array, cfg: ModelConfig
) -> Aux:
    """Router BCE + stats (+ predictor BCE/acc) for a token_topk decision."""
    aux: Aux = {
        "mod/router_bce": R.router_aux_loss(decision.logits, decision.mask),
        "mod/frac_above_half": jnp.mean(
            (jax.nn.sigmoid(decision.logits) > 0.5).astype(jnp.float32)
        ),
        "mod/gate_mean": jnp.mean(decision.gate),
    }
    if "predictor" in params:
        plogits = R.predictor_logits(params["predictor"], x)
        ploss, pacc = R.predictor_loss_and_acc(plogits, decision.mask)
        aux["mod/predictor_bce"] = ploss
        aux["mod/predictor_acc"] = pacc
    return aux


def decode_aux(decision: RouteDecision) -> Aux:
    """Per-step decode telemetry.

    Scalars stay scalar; the per-sequence entries keep a trailing (B,) axis
    that the family decode steps preserve (they mean aux only over the
    layer-group axis) so the serving scheduler can co-rank live slots with
    the ``batch_capacity`` router.
    """
    aux: Aux = {
        "mod/decode_routed_frac": jnp.mean(decision.mask.astype(jnp.float32)),
        "mod/decode_routed": decision.mask.astype(jnp.float32),  # (B,)
    }
    if decision.scores is not None:
        aux["mod/decode_scores"] = decision.scores.astype(jnp.float32)  # (B,)
    return aux


# ---------------------------------------------------------------------------
# High-level entry points (what the model families call)
# ---------------------------------------------------------------------------


def apply_mod(
    params: Params,  # {"router": ..., "predictor"?: ..., ...}
    x: jax.Array,  # (B, S, D)
    positions: jax.Array,  # (B, S) or (3, B, S)
    block_delta_fn: BlockDeltaFn,
    cfg: ModelConfig,
    rng: Optional[jax.Array] = None,
    fused_block_fn: Optional[FusedBlockFn] = None,
    spmd: Optional[ShardCtx] = None,
) -> Tuple[jax.Array, Aux]:
    """Train-time routed block: token top-k decision + routed execution.

    ``spmd`` (a :class:`ShardCtx`) shards the decision + dispatch per data
    shard; the aux losses (``routing_aux``) are computed on the global
    decision outside the shard_map regions, so their values — and therefore
    the training loss and its gradients — match the single-device path up
    to the usual cross-device reduction-order tolerance.
    """
    decision = decide_tokens(params, x, cfg, rng, spmd)
    out, inner_aux = execute_routed(
        decision, x, block_delta_fn, cfg, positions, fused_block_fn, spmd
    )
    aux: Aux = dict(inner_aux)
    aux.update(routing_aux(decision, params, x, cfg))
    return out, aux


# block_fn(x_sub, pos_sub, caches_sub, decision) -> (delta, new_caches_sub, aux)
DecodeBlockFn = Callable[
    [jax.Array, Optional[jax.Array], Params, RouteDecision],
    Tuple[jax.Array, Params, Aux],
]


def _exec_batch_capacity(
    decision: RouteDecision,
    x: jax.Array,  # (B, 1, D) — global, or one shard's local slice
    caches: Params,
    block_fn: DecodeBlockFn,
    positions: Optional[jax.Array],
) -> Tuple[jax.Array, Params, Aux]:
    """The one copy of batch_capacity execution: row gather -> block ->
    Eq. 1 gated combine + cache gather/scatter. Both the plain
    :func:`route_decode` tail and the per-shard region of
    :func:`_route_decode_spmd` run THIS — which is what makes the
    mesh-vs-reference token-stream identity a structural property rather
    than two implementations happening to agree."""
    caches_sub = gather_batch(decision, caches)
    delta, new_caches_sub, inner = block_fn(
        _take_batch_rows(x, decision),
        None if positions is None else _take_batch_positions(positions, decision.idx),
        caches_sub,
        decision,
    )
    out = _add_batch_rows(x, decision, delta)
    return out, scatter_batch(decision, caches, new_caches_sub), inner


def route_decode(
    params: Params,
    x: jax.Array,  # (B, 1, D)
    caches: Params,
    block_fn: DecodeBlockFn,
    cfg: ModelConfig,
    positions: Optional[jax.Array] = None,
    active: Optional[jax.Array] = None,  # (B,) bool — live serving slots
    spmd: Optional[ShardCtx] = None,
) -> Tuple[jax.Array, Params, Aux]:
    """Decode-time routed block: batch-capacity decision + routed execution.

    Gathers the routed sequences' cache slices, runs ``block_fn`` on the
    (kb, 1, D) sub-batch, scatters both the gated delta and the updated
    caches back. ``block_fn`` receives the decision so call sites can gather
    any extra per-sequence state (e.g. encdec cross-KV) themselves.
    ``active`` (from the serving engine) demotes padding slots in the
    batch-capacity ranking — see :func:`decide_batch`.

    With an SPMD :class:`ShardCtx` the *entire* routed step — causal
    scoring, partitioned top-``kb_local`` selection, cache-slice gather,
    ``block_fn``, and both scatters — runs per data shard inside
    ``shard_map``: a routed sequence's cache rows live on its own shard,
    so a batch-sharded cache pool is never gathered across devices. Model
    (tensor-parallel) axes stay under GSPMD inside the region. Without a
    mesh but with ``spmd.data_shards > 1``, the same partitioned
    *semantics* run on one device — the SPMD reference.
    """
    if spmd is not None:
        # partitioned batch_capacity semantics require equal shard groups —
        # fail with the clear ValueError, not batch_select's bare assert
        spmd.check_batch(x.shape[0])
    if spmd is not None and spmd.spmd:
        return _route_decode_spmd(params, x, caches, block_fn, cfg, positions, active, spmd)
    shards = spmd.data_shards if spmd is not None else 1
    decision = decide_batch(params, x, cfg, active, data_shards=shards)
    out, new_caches, inner_aux = _exec_batch_capacity(
        decision, x, caches, block_fn, positions
    )
    aux: Aux = dict(inner_aux)
    aux.update(decode_aux(decision))
    return out, new_caches, aux


def _route_decode_spmd(
    params: Params,
    x: jax.Array,  # (B, 1, D)
    caches: Params,
    block_fn: DecodeBlockFn,
    cfg: ModelConfig,
    positions: Optional[jax.Array],
    active: Optional[jax.Array],
    spmd: ShardCtx,
) -> Tuple[jax.Array, Params, Aux]:
    """Shard-local batch-capacity decode (see :func:`route_decode`).

    Two shard_map regions, split around an XLA limitation: ``top_k`` lowers
    to a sort, which this XLA version cannot partition inside a
    *partial*-auto (manual-subgroup) region. So the decision runs in a
    fully-manual region (model axes replicated — it's a per-row scalar op),
    and the cache gather + block + scatters run in a partial-auto region
    where the model axis stays under GSPMD so the block's tensor-parallel
    layouts keep working. Row indices crossing the region boundary are
    *shard-local*; concatenated over shards they form the
    ``(d · kb_local,)`` global array whose blocks each shard reads back.
    """
    B = x.shape[0]
    # decide_batch(active=None) ranks raw scores; an all-True mask is the
    # same ranking, and a concrete array keeps the shard_map specs uniform.
    act = jnp.ones((B,), bool) if active is None else active
    route_params = {"router": params["router"]}
    if "predictor" in params:
        route_params["predictor"] = params["predictor"]

    def _decide_local(rp, xl, actl):
        decision_l = decide_batch(rp, xl, cfg, actl)  # local top-kb(B/d)
        return (
            decision_l.idx,
            decision_l.gate,
            decision_l.mask,
            decision_l.scores.astype(jnp.float32),
        )

    dspec1 = spmd.data_spec(1)
    idx, gate, mask, scores = jax.shard_map(
        _decide_local,
        mesh=spmd.mesh,
        in_specs=(jax.tree.map(lambda _: P(), route_params), spmd.data_spec(3), dspec1),
        out_specs=(dspec1, dspec1, dspec1, dspec1),
        check_vma=False,
    )(route_params, x, act)

    def _exec_local(xl, il, gl, ml, sl, cl, posl):
        decision_l = RouteDecision("batch_capacity", il, gl, ml, scores=sl)
        out_l, new_cl, inner = _exec_batch_capacity(
            decision_l, xl, cl, block_fn, posl
        )
        return out_l, new_cl, jax.tree.map(lambda a: a[None], inner)

    cache_specs = jax.tree.map(lambda c: spmd.data_spec(c.ndim), caches)
    # abstract probe: the inner-aux pytree structure (for out_specs) without
    # running the block — a kb_local-row decision over the first rows
    kb_local = batch_capacity_k(cfg, B // spmd.data_shards)
    probe_idx = jnp.arange(kb_local, dtype=jnp.int32)
    probe = RouteDecision(
        "batch_capacity",
        probe_idx,
        jnp.zeros((kb_local,), jnp.float32),
        jnp.zeros((B,), bool),
        scores=jnp.zeros((B,), jnp.float32),
    )
    inner_struct = jax.eval_shape(
        lambda: block_fn(
            jnp.take(x, probe_idx, axis=0),
            None if positions is None else _take_batch_positions(positions, probe_idx),
            gather_batch(probe, caches),
            probe,
        )[2]
    )
    inner_specs = jax.tree.map(lambda _: P(spmd.data_axes), inner_struct)
    out, new_caches, inner_stack = jax.shard_map(
        _exec_local,
        mesh=spmd.mesh,
        in_specs=(
            spmd.data_spec(3),
            dspec1,
            dspec1,
            dspec1,
            dspec1,
            cache_specs,
            _pos_spec(positions, spmd),
        ),
        out_specs=(spmd.data_spec(3), cache_specs, inner_specs),
        check_vma=False,
        axis_names=frozenset(spmd.data_axes),
    )(x, idx, gate, mask, scores, caches, positions)
    aux: Aux = dict(jax.tree.map(lambda a: jnp.mean(a, axis=0), inner_stack))
    # one decode_aux source of truth; it reads only mask/scores (idx here is
    # the concatenation of shard-local row ids, which decode_aux ignores)
    aux.update(
        decode_aux(RouteDecision("batch_capacity", idx, gate, mask, scores=scores))
    )
    return out, new_caches, aux
