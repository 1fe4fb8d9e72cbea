"""Decoder-only LM assembly (dense / MoE / VLM backbones) with MoD routing.

Layers are grouped for `jax.lax.scan` so HLO size and compile time are O(1)
in depth (essential for the 512-chip dry-runs):

- MoD off:            one group per layer: {"full": block}
- MoD every=2 (paper): L//2 groups of {"full": block, "mod": routed block}
- MoD every=1:        one group per layer: {"mod": routed block}

Caches mirror the group structure and are scan-stacked along the group axis.
MoD block KV caches are capacity-sized (``ratio * ctx``) — the paper's KV
memory saving.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.core import router as R
from repro.core import routing as ROUT
from repro.models import attention as A
from repro.models import blocks as BLK
from repro.models.paged_kv import scan_layers
from repro.distributed.sharding import constrain_batch
from repro.utils import scan_or_loop
from repro.models.layers import (
    cross_entropy,
    embed,
    init_embedding,
    init_rmsnorm,
    lm_head,
)

Params = Dict[str, Any]
Aux = Dict[str, jax.Array]


def _prefix(tag: str, aux: Aux) -> Aux:
    return {f"{tag}/{k}": v for k, v in aux.items()}


def group_structure(cfg: ModelConfig) -> Tuple[int, bool, bool, int]:
    """(n_groups, has_full, has_mod, n_tail_full)."""
    L = cfg.n_layers
    if not cfg.mod.enabled:
        return L, True, False, 0
    if cfg.mod.every <= 1:
        return L, False, True, 0
    assert cfg.mod.every == 2, "mod.every must be 1 or 2 (paper settings)"
    return L // 2, True, True, L % 2


def _use_moe(cfg: ModelConfig) -> bool:
    return cfg.family == "moe" or cfg.moe.enabled


def init_mod_wrap(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 3)
    p = {
        "block": BLK.init_block(ks[0], cfg, _use_moe(cfg)),
        "router": R.init_router(ks[1], cfg),
    }
    if cfg.mod.sampling == "predictor":
        p["predictor"] = R.init_predictor(ks[2], cfg)
    return p


def init_lm(key, cfg: ModelConfig) -> Params:
    n_groups, has_full, has_mod, n_tail = group_structure(cfg)
    ks = iter(jax.random.split(key, 8))
    params: Params = {
        "embed": init_embedding(next(ks), cfg),
        "final_norm": init_rmsnorm(cfg.d_model, jnp.dtype(cfg.dtype)),
    }
    groups: Params = {}
    if has_full:
        keys = jax.random.split(next(ks), n_groups)
        groups["full"] = jax.vmap(lambda k: BLK.init_block(k, cfg, _use_moe(cfg)))(keys)
    if has_mod:
        keys = jax.random.split(next(ks), n_groups)
        groups["mod"] = jax.vmap(lambda k: init_mod_wrap(k, cfg))(keys)
    params["groups"] = groups
    if n_tail:
        params["tail"] = BLK.init_block(next(ks), cfg, _use_moe(cfg))
    return params


# ---------------------------------------------------------------------------
# Training / teacher-forced forward
# ---------------------------------------------------------------------------


def _default_positions(x: jax.Array) -> jax.Array:
    B, S = x.shape[0], x.shape[1]
    return jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))


def forward(
    params: Params,
    cfg: ModelConfig,
    tokens: Optional[jax.Array] = None,
    embeds: Optional[jax.Array] = None,
    positions: Optional[jax.Array] = None,
    rng: Optional[jax.Array] = None,
    last_only: bool = False,
    spmd=None,  # Optional[distributed.sharding.ShardCtx] — SPMD MoD dispatch
) -> Tuple[jax.Array, Aux]:
    """Full-sequence forward. Returns (logits (B,S,V), aux).

    ``last_only`` slices to the final position *before* the unembedding so
    serving prefill never materializes (B, S, V) logits. ``spmd`` routes
    every MoD site's decision + dispatch per data shard (DESIGN.md §SPMD
    routed execution); dense blocks and aux losses stay under GSPMD."""
    x = embed(params["embed"], tokens) if embeds is None else embeds
    x = constrain_batch(x)
    if positions is None:
        positions = _default_positions(x)
    key0 = rng if rng is not None else jax.random.PRNGKey(0)

    def body(carry, gp):
        h, key = carry
        key, sub = jax.random.split(key)
        aux: Aux = {}
        if "full" in gp:
            h, a = BLK.block_apply(gp["full"], h, positions, cfg)
            aux.update(_prefix("full", a))
        if "mod" in gp:
            def delta_fn(xs, ps):
                return BLK.block_delta(gp["mod"]["block"], xs, ps, cfg)

            fused_fn = None
            if BLK.fused_dispatch_supported(cfg, spmd):
                def fused_fn(xf, decision, pf):
                    return BLK.block_delta_fused(gp["mod"]["block"], xf, pf, decision, cfg)

            h, a = ROUT.apply_mod(
                gp["mod"], h, positions, delta_fn, cfg, sub,
                fused_block_fn=fused_fn, spmd=spmd,
            )
            aux.update(a)
        return (constrain_batch(h), key), aux

    if cfg.remat == "full":
        body = jax.checkpoint(body)
    elif cfg.remat == "selective":
        # save matmul outputs, recompute elementwise: cuts the backward's
        # full forward recompute (~fwd FLOPs) at the cost of storing the
        # per-layer dot outputs
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    (x, _), aux_stack = scan_or_loop(body, (x, key0), params["groups"], unroll=cfg.unroll_layers)
    aux = jax.tree.map(jnp.mean, aux_stack)
    if "tail" in params:
        x, a = BLK.block_apply(params["tail"], x, positions, cfg)
        aux.update(_prefix("tail", a))
    if last_only:
        x = x[:, -1:]
    logits = lm_head(params, x, cfg)
    return logits, aux


def forward_ragged(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,  # (T,) flat token stream
    row_offsets: jax.Array,  # (n_seg+1,) int32; row_offsets[-1] <= T
    seg_cap: int,  # static bound: every segment has <= seg_cap tokens
    rng: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Aux]:
    """Flat-token forward (the ``input_row_offsets`` layout): segments are
    packed back-to-back on one ``(T,)`` stream instead of padded ``(B, S)``
    rows. Attention is segment-block-diagonal
    (:func:`~repro.models.blocks.block_apply_ragged`), MoD selection is
    per-segment (:func:`~repro.core.routing.decide_tokens_ragged`), and the
    routed block sees segments as batch rows — so for equal-length segments
    every dense-family layer runs the padded path's ops on the padded
    path's values and the logits match (tests/test_ragged.py). MoE blocks
    are the exception: expert capacity buckets are per *stream* row, so on
    the flat layout they span the whole batch — the serving engine's mixed
    step instead replays the padded chunk schedule per segment, which is
    bit-identical for every family. Rows behind ``row_offsets[-1]`` are a
    masked padding tail (positions -1).

    Returns (logits (T, V), aux).
    """
    from repro.kernels.ragged import flat_segment_ids

    T = tokens.shape[0]
    x = embed(params["embed"], tokens[None])  # (1, T, D)
    offs = row_offsets.astype(jnp.int32)
    seg_id = flat_segment_ids(offs, T)
    t = jnp.arange(T, dtype=jnp.int32)
    positions = jnp.where(t < offs[-1], t - offs[seg_id], -1)[None]  # (1, T)
    key0 = rng if rng is not None else jax.random.PRNGKey(0)

    def body(carry, gp):
        h, key = carry
        key, sub = jax.random.split(key)
        aux: Aux = {}
        if "full" in gp:
            h, a = BLK.block_apply_ragged(gp["full"], h, positions, seg_id, cfg)
            aux.update(_prefix("full", a))
        if "mod" in gp:
            decision = ROUT.decide_tokens_ragged(
                gp["mod"], h, offs, cfg, seg_cap, sub
            )

            def delta_fn(xs, ps):
                return BLK.block_delta(gp["mod"]["block"], xs, ps, cfg)

            h_in = h
            h, a = ROUT.execute_routed_ragged(decision, h, delta_fn, cfg, positions)
            a = dict(a)
            a.update(ROUT.routing_aux(decision, gp["mod"], h_in, cfg))
            aux.update(a)
        return (h, key), aux

    (x, _), aux_stack = scan_or_loop(
        body, (x, key0), params["groups"], unroll=cfg.unroll_layers
    )
    aux = jax.tree.map(jnp.mean, aux_stack)
    if "tail" in params:
        x, a = BLK.block_apply_ragged(params["tail"], x, positions, seg_id, cfg)
        aux.update(_prefix("tail", a))
    logits = lm_head(params, x, cfg)
    return logits[0], aux


def lm_loss(
    params: Params,
    cfg: ModelConfig,
    batch: Dict[str, jax.Array],
    rng: Optional[jax.Array] = None,
    spmd=None,
) -> Tuple[jax.Array, Aux]:
    """CE + weighted MoD/MoE auxiliary losses. batch: tokens/embeds, labels,
    optional loss_mask / positions."""
    logits, aux = forward(
        params,
        cfg,
        tokens=batch.get("tokens"),
        embeds=batch.get("embeds"),
        positions=batch.get("positions"),
        rng=rng,
        spmd=spmd,
    )
    ce = cross_entropy(logits, batch["labels"], batch.get("loss_mask"))
    loss = ce
    if cfg.mod.enabled:
        if "mod/router_bce" in aux:
            loss = loss + cfg.mod.aux_loss_weight * aux["mod/router_bce"]
        if "mod/predictor_bce" in aux:
            # stop-grad inputs: trains only the predictor head
            loss = loss + aux["mod/predictor_bce"]
    for k, v in aux.items():
        if k.endswith("moe/lb_loss"):
            loss = loss + cfg.moe.load_balance_weight * v
        elif k.endswith("moe/z_loss"):
            loss = loss + cfg.moe.router_z_weight * v
    aux["ce"] = ce
    aux["loss"] = loss
    return loss, aux


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------


def make_cache(cfg: ModelConfig, batch: int, ctx: int, specs: bool = False) -> Params:
    """Scan-stacked KV caches matching the group structure."""
    n_groups, has_full, has_mod, n_tail = group_structure(cfg)
    mk = A.kv_cache_specs if specs else A.init_kv_cache

    def stack(tree, n):
        if specs:
            return jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((n,) + s.shape, s.dtype), tree
            )
        return jax.tree.map(lambda a: jnp.broadcast_to(a[None], (n,) + a.shape).copy(), tree)

    caches: Params = {"groups": {}}
    if has_full:
        caches["groups"]["full"] = stack(mk(batch, ctx, cfg), n_groups)
    if has_mod:
        c_mod = cfg.mod.capacity(ctx)
        caches["groups"]["mod"] = stack(mk(batch, c_mod, cfg), n_groups)
    if n_tail:
        caches["tail"] = mk(batch, ctx, cfg)
    return caches


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------


def _mod_prefill_group(gp, h, positions, cache, cfg):
    decision = ROUT.decide_tokens(gp, h, cfg)
    filled = {}

    def delta_fn(h_sub, pos_sub):
        delta, c, inner = BLK.block_prefill(
            gp["block"], h_sub, pos_sub, cache, cfg, delta_only=True
        )
        filled["cache"] = c
        return delta, inner

    h, aux = ROUT.execute_routed(decision, h, delta_fn, cfg, positions)
    aux = dict(aux)
    with jax.named_scope("mod.router"):
        aux["mod/router_bce"] = R.router_aux_loss(decision.logits, decision.mask)
    return h, filled["cache"], aux, (decision.logits, decision.mask)


def prefill(
    params: Params,
    cfg: ModelConfig,
    tokens: Optional[jax.Array] = None,
    embeds: Optional[jax.Array] = None,
    positions: Optional[jax.Array] = None,
    ctx: Optional[int] = None,
) -> Tuple[jax.Array, Params]:
    """Teacher-forced pass that also populates caches. Returns (logits, caches)."""
    x = embed(params["embed"], tokens) if embeds is None else embeds
    x = constrain_batch(x)
    B, S = x.shape[0], x.shape[1]
    ctx = ctx or cfg.max_seq_len
    if positions is None:
        positions = _default_positions(x)
    caches = make_cache(cfg, B, ctx)

    def body(carry, xs):
        h = carry
        gp, gc = xs
        new_c = {}
        if "full" in gp:
            h, c, _ = BLK.block_prefill(gp["full"], h, positions, gc["full"], cfg)
            new_c["full"] = c
        if "mod" in gp:
            h, c, _, _ = _mod_prefill_group(gp["mod"], h, positions, gc["mod"], cfg)
            new_c["mod"] = c
        return constrain_batch(h), new_c

    x, new_caches = scan_or_loop(body, x, (params["groups"], caches["groups"]), unroll=cfg.unroll_layers)
    out_caches: Params = {"groups": new_caches}
    if "tail" in params:
        x, c, _ = BLK.block_prefill(params["tail"], x, positions, caches["tail"], cfg)
        out_caches["tail"] = c
    logits = lm_head(params, x, cfg)
    return logits, out_caches


# ---------------------------------------------------------------------------
# Chunked / continuation prefill
# ---------------------------------------------------------------------------


def _mod_chunk_group(gp, h, positions, cache, cfg):
    """Per-chunk token_topk routing for continuation prefill.

    The router selects the top ``capacity(C)`` tokens *within this chunk*
    (masked so padded tail positions can never win a slot or contribute a
    gated delta); routed tokens attend over the MoD ring — earlier chunks'
    routed KV plus their own. This is the compute/quality scheduling
    trade-off of chunked adaptive-compute serving (Elbayad et al. 2020;
    Bapna et al. 2020): routing is chunk-local rather than whole-prompt,
    in exchange for a fixed per-step prefill footprint.
    """
    k_cap = cfg.mod.capacity(h.shape[1])
    with jax.named_scope("mod.router"):
        logits = R.router_logits(gp["router"], h)
        valid = positions >= 0
        idx, gate_logits, mask = R.mod_select(
            jnp.where(valid, logits, -jnp.inf), k_cap, cfg.mod, None
        )
        gate = R.apply_gate(gate_logits, cfg.mod)
        gate = jnp.where(jnp.take_along_axis(valid, idx, axis=1), gate, 0.0)
    decision = ROUT.RouteDecision("token_topk", idx, gate, mask, logits)
    filled = {}

    def delta_fn(h_sub, pos_sub):
        delta, c, _ = BLK.block_chunk(
            gp["block"], h_sub, pos_sub, cache, cfg, delta_only=True
        )
        filled["cache"] = c
        return delta, {}

    h, _ = ROUT.execute_routed(decision, h, delta_fn, cfg, positions)
    return h, filled["cache"]


def prefill_chunk(
    params: Params,
    cfg: ModelConfig,
    caches: Params,
    tokens: jax.Array,  # (B, C) — one fixed-size chunk (padded tail ok)
    start: jax.Array,  # scalar int32: absolute position of tokens[:, 0]
    n_valid: jax.Array,  # scalar int32: real tokens in this chunk (<= C)
) -> Tuple[jax.Array, Params]:
    """One continuation-prefill step: ingest ``tokens[:, :n_valid]`` at
    positions ``start..start+n_valid`` against partially-filled caches.

    Returns (last-valid-position logits (B, V), updated caches). ``start``
    and ``n_valid`` are traced scalars, so one compiled signature serves
    every chunk of every prompt length — the serving engine's retrace cache
    cannot grow with prompt-length diversity. Bit-identical to running the
    same chunk schedule anywhere else (the prefix cache relies on this:
    chunk-boundary state is a pure function of the token prefix).
    """
    x = embed(params["embed"], tokens)
    x = constrain_batch(x)
    B, C = tokens.shape
    ar = jnp.arange(C, dtype=jnp.int32)
    positions = jnp.where(ar[None, :] < n_valid, start + ar[None, :], -1)
    positions = jnp.broadcast_to(positions, (B, C)).astype(jnp.int32)

    def body(h, xs):
        gp, gc = xs
        new_c = {}
        if "full" in gp:
            h, c, _ = BLK.block_chunk(gp["full"], h, positions, gc["full"], cfg)
            new_c["full"] = c
        if "mod" in gp:
            h, c = _mod_chunk_group(gp["mod"], h, positions, gc["mod"], cfg)
            new_c["mod"] = c
        return constrain_batch(h), new_c

    x, new_groups = scan_or_loop(
        body, x, (params["groups"], caches["groups"]), unroll=cfg.unroll_layers
    )
    out_caches: Params = {"groups": new_groups}
    if "tail" in params:
        x, c, _ = BLK.block_chunk(params["tail"], x, positions, caches["tail"], cfg)
        out_caches["tail"] = c
    last = jnp.clip(n_valid - 1, 0, C - 1)
    x = jax.lax.dynamic_slice_in_dim(x, last, 1, axis=1)  # (B, 1, D)
    logits = lm_head(params, x, cfg)[:, 0]
    return logits, out_caches


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def _mod_decode_group(gp, h, positions, cache, cfg, active=None, spmd=None):
    """Batch-capacity MoD decode: top round(ratio*B) sequences route through."""

    def block_fn(h_sub, pos_sub, cache_sub, decision):
        delta, c, _ = BLK.block_decode(
            gp["block"], h_sub, pos_sub, cache_sub, cfg, delta_only=True
        )
        return delta, c, {}

    return ROUT.route_decode(gp, h, cache, block_fn, cfg, positions, active, spmd)


def decode_step(
    params: Params,
    caches: Params,
    cfg: ModelConfig,
    token: jax.Array,  # (B, 1) int32
    pos: jax.Array,  # (B,) int32 — current absolute position
    active: Optional[jax.Array] = None,  # (B,) bool — live serving slots
    spmd=None,  # Optional[ShardCtx] — shard-local batch_capacity routing
) -> Tuple[jax.Array, Params, Aux]:
    """One autoregressive step. Returns (logits (B,V), caches, aux)."""
    x = constrain_batch(embed(params["embed"], token))  # (B,1,D)
    if cfg.attn.pos_emb == "mrope":
        positions = jnp.broadcast_to(pos[None, :, None], (3,) + pos.shape + (1,))
    else:
        positions = pos[:, None]
    if spmd is not None and spmd.spmd and _use_moe(cfg):
        # expert top-k inside the routed block can't lower in a manual
        # region (sort-in-manual-subgroup, same XLA limitation the decision
        # regions dodge) — keep the partitioned routing semantics, execute
        # the dispatch under GSPMD
        spmd = spmd.semantic_only()

    def body(h, xs):
        gp, gc = xs
        new_c = {}
        aux: Aux = {}
        if "full" in gp:
            h, c, _ = BLK.block_decode(gp["full"], h, positions, gc["full"], cfg)
            new_c["full"] = c
        if "mod" in gp:
            h, c, a = _mod_decode_group(
                gp["mod"], h, positions, gc["mod"], cfg, active, spmd
            )
            new_c["mod"] = c
            aux.update(a)
        return constrain_batch(h), (new_c, aux)

    x, (new_caches, aux_stack) = scan_layers(body, x, (params["groups"], caches["groups"]), unroll=cfg.unroll_layers)
    out_caches: Params = {"groups": new_caches}
    # mean only over the layer-group axis: scalar telemetry stays scalar,
    # per-sequence entries (decode scores / routed masks) keep their (B,)
    aux = jax.tree.map(lambda a: jnp.mean(a, axis=0), aux_stack)
    if "tail" in params:
        x, c, _ = BLK.block_decode(params["tail"], x, positions, caches["tail"], cfg)
        out_caches["tail"] = c
    logits = lm_head(params, x, cfg)[:, 0]
    return logits, out_caches, aux
