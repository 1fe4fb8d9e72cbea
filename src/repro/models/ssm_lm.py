"""SSM and hybrid LM assemblies: Mamba2 (pure SSD) and Zamba2-style hybrid.

Mamba2 LM: stack of SSD blocks with pre-norm residuals, scanned in groups —
MoD routes around SSD blocks exactly as it routes around attention+MLP
blocks (the gathered sub-sequence runs the conv + SSD recurrence over routed
tokens only; skipped tokens do not enter that layer's state, the recurrent
analogue of "not attendable", see DESIGN §Arch-applicability).

Zamba2 hybrid: 54 Mamba2 layers with ONE shared attention+MLP block applied
every ``hybrid_attn_every`` layers (weight-shared, per-site KV caches). The
layer stack is scanned as (n_segments, seg_len) so the shared block appears
once in the HLO.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.core import router as R
from repro.core import routing as ROUT
from repro.models import attention as A
from repro.models import blocks as BLK
from repro.models.paged_kv import scan_layers
from repro.models import ssm as SSM
from repro.distributed.sharding import constrain_batch
from repro.utils import scan_or_loop
from repro.models.layers import (
    cross_entropy,
    embed,
    init_embedding,
    init_rmsnorm,
    lm_head,
    rmsnorm,
)

Params = Dict[str, Any]
Aux = Dict[str, jax.Array]


# ---------------------------------------------------------------------------
# Group structure (same pairing logic as transformer.py)
# ---------------------------------------------------------------------------


def group_structure(cfg: ModelConfig) -> Tuple[int, bool, bool]:
    L = cfg.n_layers
    if not cfg.mod.enabled:
        return L, True, False
    if cfg.mod.every <= 1:
        return L, False, True
    assert cfg.mod.every == 2 and L % 2 == 0
    return L // 2, True, True


def init_ssm_mod_wrap(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 3)
    p = {
        "block": {"ln": init_rmsnorm(cfg.d_model, jnp.dtype(cfg.dtype)),
                  "ssm": SSM.init_ssm_block(ks[0], cfg)},
        "router": R.init_router(ks[1], cfg),
    }
    if cfg.mod.sampling == "predictor":
        p["predictor"] = R.init_predictor(ks[2], cfg)
    return p


def _init_ssm_layer(key, cfg: ModelConfig) -> Params:
    return {"ln": init_rmsnorm(cfg.d_model, jnp.dtype(cfg.dtype)),
            "ssm": SSM.init_ssm_block(key, cfg)}


def _ssm_delta(p: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    return SSM.ssm_block(p["ssm"], rmsnorm(p["ln"], x, cfg.norm_eps), cfg)


def init_lm(key, cfg: ModelConfig) -> Params:
    """Pure-SSM LM (mamba2)."""
    n_groups, has_full, has_mod = group_structure(cfg)
    ks = iter(jax.random.split(key, 8))
    params: Params = {
        "embed": init_embedding(next(ks), cfg),
        "final_norm": init_rmsnorm(cfg.d_model, jnp.dtype(cfg.dtype)),
        "groups": {},
    }
    if has_full:
        keys = jax.random.split(next(ks), n_groups)
        params["groups"]["full"] = jax.vmap(lambda k: _init_ssm_layer(k, cfg))(keys)
    if has_mod:
        keys = jax.random.split(next(ks), n_groups)
        params["groups"]["mod"] = jax.vmap(lambda k: init_ssm_mod_wrap(k, cfg))(keys)
    return params


def forward(
    params: Params,
    cfg: ModelConfig,
    tokens: Optional[jax.Array] = None,
    embeds: Optional[jax.Array] = None,
    positions: Optional[jax.Array] = None,
    rng: Optional[jax.Array] = None,
    last_only: bool = False,
    spmd=None,  # Optional[ShardCtx] — SPMD MoD dispatch (DESIGN.md)
) -> Tuple[jax.Array, Aux]:
    x = constrain_batch(embed(params["embed"], tokens) if embeds is None else embeds)
    if positions is None:
        positions = jnp.broadcast_to(
            jnp.arange(x.shape[1], dtype=jnp.int32)[None], x.shape[:2]
        )
    key0 = rng if rng is not None else jax.random.PRNGKey(0)

    def body(carry, gp):
        h, key = carry
        key, sub = jax.random.split(key)
        aux: Aux = {}
        if "full" in gp:
            h = h + _ssm_delta(gp["full"], h, cfg)
        if "mod" in gp:
            def delta_fn(xs, ps):
                return _ssm_delta(gp["mod"]["block"], xs, cfg), {}

            h, a = ROUT.apply_mod(
                gp["mod"], h, positions, delta_fn, cfg, sub, spmd=spmd
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
    if last_only:
        x = x[:, -1:]
    return lm_head(params, x, cfg), aux


def make_cache(cfg: ModelConfig, batch: int, ctx: int, specs: bool = False) -> Params:
    n_groups, has_full, has_mod = group_structure(cfg)
    mk = SSM.ssm_cache_specs if specs else SSM.init_ssm_cache

    def stack(tree, n):
        if specs:
            return jax.tree.map(lambda s: jax.ShapeDtypeStruct((n,) + s.shape, s.dtype), tree)
        return jax.tree.map(lambda a: jnp.broadcast_to(a[None], (n,) + a.shape).copy(), tree)

    caches: Params = {"groups": {}}
    if has_full:
        caches["groups"]["full"] = stack(mk(batch, cfg), n_groups)
    if has_mod:
        caches["groups"]["mod"] = stack(mk(batch, cfg), n_groups)
    return caches


def decode_step(
    params: Params,
    caches: Params,
    cfg: ModelConfig,
    token: jax.Array,  # (B,1)
    pos: jax.Array,  # (B,)
    active: Optional[jax.Array] = None,  # (B,) bool — live serving slots
    spmd=None,  # Optional[ShardCtx] — shard-local batch_capacity routing
) -> Tuple[jax.Array, Params, Aux]:
    x = constrain_batch(embed(params["embed"], token))

    def ssm_decode_delta(p, h, cache):
        out, cache = SSM.ssm_block_decode(p["ssm"], rmsnorm(p["ln"], h, cfg.norm_eps), cache, cfg)
        return out, cache

    def body(h, xs):
        gp, gc = xs
        new_c = {}
        aux: Aux = {}
        if "full" in gp:
            d, c = ssm_decode_delta(gp["full"], h, gc["full"])
            h = h + d
            new_c["full"] = c
        if "mod" in gp:
            def block_fn(h_sub, pos_sub, c_sub, decision):
                d, c = ssm_decode_delta(gp["mod"]["block"], h_sub, c_sub)
                return d, c, {}

            h, new_c["mod"], a = ROUT.route_decode(
                gp["mod"], h, gc["mod"], block_fn, cfg, active=active, spmd=spmd
            )
            aux.update(a)
        return constrain_batch(h), (new_c, aux)

    x, (new_caches, aux_stack) = scan_or_loop(body, x, (params["groups"], caches["groups"]), unroll=cfg.unroll_layers)
    # mean over the layer-group axis only (per-sequence telemetry keeps (B,))
    aux = jax.tree.map(lambda a: jnp.mean(a, axis=0), aux_stack)
    logits = lm_head(params, x, cfg)[:, 0]
    return logits, {"groups": new_caches}, aux


# ---------------------------------------------------------------------------
# Zamba2-style hybrid
# ---------------------------------------------------------------------------


def hybrid_segments(cfg: ModelConfig) -> Tuple[int, int]:
    seg = cfg.hybrid_attn_every
    assert cfg.n_layers % seg == 0, (cfg.n_layers, seg)
    return cfg.n_layers // seg, seg


def init_hybrid(key, cfg: ModelConfig) -> Params:
    """Shared attention block + (n_segments × seg_len) Mamba2 layers.

    MoD (every=2) routes around every other Mamba2 layer within a segment;
    the shared attention block stays full-capacity.
    """
    n_seg, seg = hybrid_segments(cfg)
    ks = iter(jax.random.split(key, 8))
    params: Params = {
        "embed": init_embedding(next(ks), cfg),
        "final_norm": init_rmsnorm(cfg.d_model, jnp.dtype(cfg.dtype)),
        "shared_attn": BLK.init_block(next(ks), cfg, use_moe=False),
    }
    if cfg.mod.enabled:
        assert cfg.mod.every == 2 and seg % 2 == 0
        n_pairs = seg // 2
        kf = jax.random.split(next(ks), n_seg * n_pairs)
        km = jax.random.split(next(ks), n_seg * n_pairs)
        params["groups"] = {
            "full": jax.tree.map(
                lambda a: a.reshape((n_seg, n_pairs) + a.shape[1:]),
                jax.vmap(lambda k: _init_ssm_layer(k, cfg))(kf),
            ),
            "mod": jax.tree.map(
                lambda a: a.reshape((n_seg, n_pairs) + a.shape[1:]),
                jax.vmap(lambda k: init_ssm_mod_wrap(k, cfg))(km),
            ),
        }
    else:
        kf = jax.random.split(next(ks), cfg.n_layers)
        params["groups"] = {
            "full": jax.tree.map(
                lambda a: a.reshape((n_seg, seg) + a.shape[1:]),
                jax.vmap(lambda k: _init_ssm_layer(k, cfg))(kf),
            )
        }
    return params


def forward_hybrid(
    params: Params,
    cfg: ModelConfig,
    tokens: Optional[jax.Array] = None,
    embeds: Optional[jax.Array] = None,
    positions: Optional[jax.Array] = None,
    rng: Optional[jax.Array] = None,
    last_only: bool = False,
    spmd=None,  # Optional[ShardCtx] — SPMD MoD dispatch (DESIGN.md)
) -> Tuple[jax.Array, Aux]:
    x = constrain_batch(embed(params["embed"], tokens) if embeds is None else embeds)
    if positions is None:
        positions = jnp.broadcast_to(
            jnp.arange(x.shape[1], dtype=jnp.int32)[None], x.shape[:2]
        )
    key0 = rng if rng is not None else jax.random.PRNGKey(0)

    def inner_body(carry, gp):
        h, key = carry
        key, sub = jax.random.split(key)
        aux: Aux = {}
        h = h + _ssm_delta(gp["full"], h, cfg)
        if "mod" in gp:
            def delta_fn(xs, ps):
                return _ssm_delta(gp["mod"]["block"], xs, cfg), {}

            h, a = ROUT.apply_mod(
                gp["mod"], h, positions, delta_fn, cfg, sub, spmd=spmd
            )
            aux.update(a)
        return (constrain_batch(h), key), aux

    def outer_body(carry, seg_params):
        h, key = carry
        # shared attention block at segment start (weight-shared across sites)
        h, _ = BLK.block_apply(params["shared_attn"], h, positions, cfg)
        (h, key), aux = scan_or_loop(inner_body, (h, key), seg_params, unroll=cfg.unroll_layers)
        return (constrain_batch(h), key), jax.tree.map(jnp.mean, aux)

    if cfg.remat == "full":
        outer_body = jax.checkpoint(outer_body)
    elif cfg.remat == "selective":
        outer_body = jax.checkpoint(
            outer_body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    (x, _), aux_stack = scan_or_loop(outer_body, (x, key0), params["groups"], unroll=cfg.unroll_layers)
    aux = jax.tree.map(jnp.mean, aux_stack)
    if last_only:
        x = x[:, -1:]
    return lm_head(params, x, cfg), aux


def make_hybrid_cache(cfg: ModelConfig, batch: int, ctx: int, specs: bool = False) -> Params:
    n_seg, seg = hybrid_segments(cfg)
    mk_ssm = SSM.ssm_cache_specs if specs else SSM.init_ssm_cache
    mk_kv = A.kv_cache_specs if specs else A.init_kv_cache

    def stack(tree, shape):
        if specs:
            return jax.tree.map(lambda s: jax.ShapeDtypeStruct(shape + s.shape, s.dtype), tree)
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a[(None,) * len(shape)], shape + a.shape).copy(), tree
        )

    caches: Params = {"attn": stack(mk_kv(batch, ctx, cfg), (n_seg,)), "groups": {}}
    if cfg.mod.enabled:
        n_pairs = seg // 2
        caches["groups"]["full"] = stack(mk_ssm(batch, cfg), (n_seg, n_pairs))
        caches["groups"]["mod"] = stack(mk_ssm(batch, cfg), (n_seg, n_pairs))
    else:
        caches["groups"]["full"] = stack(mk_ssm(batch, cfg), (n_seg, seg))
    return caches


def decode_step_hybrid(
    params: Params,
    caches: Params,
    cfg: ModelConfig,
    token: jax.Array,
    pos: jax.Array,
    active: Optional[jax.Array] = None,  # (B,) bool — live serving slots
    spmd=None,  # Optional[ShardCtx] — shard-local batch_capacity routing
) -> Tuple[jax.Array, Params, Aux]:
    x = embed(params["embed"], token)
    positions = pos[:, None]

    def ssm_decode_delta(p, h, cache):
        out, cache = SSM.ssm_block_decode(p["ssm"], rmsnorm(p["ln"], h, cfg.norm_eps), cache, cfg)
        return out, cache

    def inner_body(h, xs):
        gp, gc = xs
        new_c = {}
        aux: Aux = {}
        d, c = ssm_decode_delta(gp["full"], h, gc["full"])
        h = h + d
        new_c["full"] = c
        if "mod" in gp:
            def block_fn(h_sub, pos_sub, c_sub, decision):
                d, c = ssm_decode_delta(gp["mod"]["block"], h_sub, c_sub)
                return d, c, {}

            h, new_c["mod"], a = ROUT.route_decode(
                gp["mod"], h, gc["mod"], block_fn, cfg, active=active, spmd=spmd
            )
            aux.update(a)
        return h, (new_c, aux)

    def outer_body(h, xs):
        seg_params, seg_caches, attn_cache = xs
        h, attn_cache, _ = BLK.block_decode(params["shared_attn"], h, positions, attn_cache, cfg)
        h, (new_seg, aux) = scan_or_loop(inner_body, h, (seg_params, seg_caches), unroll=cfg.unroll_layers)
        # mean over the within-segment pair axis only
        return constrain_batch(h), (new_seg, attn_cache, jax.tree.map(lambda a: jnp.mean(a, axis=0), aux))

    x, (new_groups, new_attn, aux_stack) = scan_layers(
        outer_body, x, (params["groups"], caches["groups"], caches["attn"])
    )
    aux = jax.tree.map(lambda a: jnp.mean(a, axis=0), aux_stack)
    logits = lm_head(params, x, cfg)[:, 0]
    return logits, {"attn": new_attn, "groups": new_groups}, aux
