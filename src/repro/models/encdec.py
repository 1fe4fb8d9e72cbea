"""Whisper-style encoder-decoder with MoD on the decoder stack.

The audio frontend (log-mel + conv) is a STUB per the assignment:
``input_specs()`` supplies precomputed frame embeddings (B, S_enc, D) that
already include positional information. The encoder is a bidirectional
transformer; the decoder is causal with cross-attention. MoD routes around
*entire decoder blocks* (self-attn + cross-attn + MLP) — the decoder-only
setting is the paper's; the encoder stays dense.
"""
from __future__ import annotations

import dataclasses
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
    embed,
    init_embedding,
    init_mlp,
    init_rmsnorm,
    lm_head,
    mlp,
    rmsnorm,
)

Params = Dict[str, Any]
Aux = Dict[str, jax.Array]


def enc_cfg(cfg: ModelConfig) -> ModelConfig:
    return dataclasses.replace(
        cfg, attn=dataclasses.replace(cfg.attn, causal=False, pos_emb="none")
    )


def init_dec_block(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 3)
    dtype = jnp.dtype(cfg.dtype)
    return {
        "ln1": init_rmsnorm(cfg.d_model, dtype),
        "attn": A.init_attention(ks[0], cfg),
        "lnx": init_rmsnorm(cfg.d_model, dtype),
        "xattn": A.init_attention(ks[1], cfg),
        "ln2": init_rmsnorm(cfg.d_model, dtype),
        "mlp": init_mlp(ks[2], cfg),
    }


def init_dec_mod_wrap(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 3)
    p = {"block": init_dec_block(ks[0], cfg), "router": R.init_router(ks[1], cfg)}
    if cfg.mod.sampling == "predictor":
        p["predictor"] = R.init_predictor(ks[2], cfg)
    return p


def init_encdec(key, cfg: ModelConfig) -> Params:
    ks = iter(jax.random.split(key, 8))
    ecfg = enc_cfg(cfg)
    enc_keys = jax.random.split(next(ks), cfg.n_enc_layers)
    params: Params = {
        "embed": init_embedding(next(ks), cfg),
        "enc_blocks": jax.vmap(lambda k: BLK.init_block(k, ecfg))(enc_keys),
        "enc_norm": init_rmsnorm(cfg.d_model, jnp.dtype(cfg.dtype)),
        "final_norm": init_rmsnorm(cfg.d_model, jnp.dtype(cfg.dtype)),
        "groups": {},
    }
    if cfg.mod.enabled:
        assert cfg.mod.every == 2 and cfg.n_layers % 2 == 0
        n_groups = cfg.n_layers // 2
        params["groups"]["full"] = jax.vmap(lambda k: init_dec_block(k, cfg))(
            jax.random.split(next(ks), n_groups)
        )
        params["groups"]["mod"] = jax.vmap(lambda k: init_dec_mod_wrap(k, cfg))(
            jax.random.split(next(ks), n_groups)
        )
    else:
        params["groups"]["full"] = jax.vmap(lambda k: init_dec_block(k, cfg))(
            jax.random.split(next(ks), cfg.n_layers)
        )
    return params


def encode(params: Params, enc_emb: jax.Array, cfg: ModelConfig) -> jax.Array:
    ecfg = enc_cfg(cfg)
    pos = jnp.broadcast_to(
        jnp.arange(enc_emb.shape[1], dtype=jnp.int32)[None], enc_emb.shape[:2]
    )

    def body(h, bp):
        h, _ = BLK.block_apply(bp, h, pos, ecfg)
        return constrain_batch(h), None

    x, _ = scan_or_loop(body, constrain_batch(enc_emb), params["enc_blocks"], unroll=cfg.unroll_layers)
    return rmsnorm(params["enc_norm"], x, cfg.norm_eps)


def _dec_block(p, x, positions, enc_out, cfg, delta_only=False):
    a = A.self_attention(p["attn"], rmsnorm(p["ln1"], x, cfg.norm_eps), positions, cfg)
    h = x + a
    ek, ev = A.encode_kv(p["xattn"], enc_out, cfg)
    xa = A.cross_attention(p["xattn"], rmsnorm(p["lnx"], h, cfg.norm_eps), ek, ev, cfg)
    h = h + xa
    m = mlp(p["mlp"], rmsnorm(p["ln2"], h, cfg.norm_eps), cfg)
    return (a + xa + m) if delta_only else (h + m)


def forward(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,  # (B, S_dec)
    enc_emb: jax.Array,  # (B, S_enc, D) — stub frontend output
    positions: Optional[jax.Array] = None,
    rng: Optional[jax.Array] = None,
    last_only: bool = False,
    spmd=None,  # Optional[ShardCtx] — SPMD MoD dispatch (DESIGN.md)
) -> Tuple[jax.Array, Aux]:
    enc_out = encode(params, enc_emb, cfg)
    x = constrain_batch(embed(params["embed"], tokens))
    if positions is None:
        positions = jnp.broadcast_to(
            jnp.arange(x.shape[1], dtype=jnp.int32)[None], x.shape[:2]
        )
    key0 = rng if rng is not None else jax.random.PRNGKey(0)

    def body(carry, gp):
        h, key = carry
        key, sub = jax.random.split(key)
        aux: Aux = {}
        h = _dec_block(gp["full"], h, positions, enc_out, cfg)
        if "mod" in gp:
            def delta_fn(xs, ps):
                return _dec_block(gp["mod"]["block"], xs, ps, enc_out, cfg, delta_only=True), {}

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


# ---------------------------------------------------------------------------
# Serving: encoder runs once; decoder decodes with self-KV + cross-KV caches
# ---------------------------------------------------------------------------


def make_cache(
    cfg: ModelConfig, batch: int, ctx: int, specs: bool = False, enc_len: Optional[int] = None
) -> Params:
    enc_len = enc_len or cfg.enc_seq_len
    n_groups = cfg.n_layers // 2 if cfg.mod.enabled else cfg.n_layers
    nkv, hd = cfg.attn.n_kv_heads, cfg.head_dim
    dt = jnp.dtype(cfg.dtype)

    def kv(n, c):
        mk = A.kv_cache_specs if specs else A.init_kv_cache
        tree = mk(batch, c, cfg)
        if specs:
            return jax.tree.map(lambda s: jax.ShapeDtypeStruct((n,) + s.shape, s.dtype), tree)
        return jax.tree.map(lambda a: jnp.broadcast_to(a[None], (n,) + a.shape).copy(), tree)

    def cross(n):
        shape = (n, batch, enc_len, nkv, hd)
        if specs:
            return {"k": jax.ShapeDtypeStruct(shape, dt), "v": jax.ShapeDtypeStruct(shape, dt)}
        return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}

    caches: Params = {"groups": {"full": {"self": kv(n_groups, ctx), "cross": cross(n_groups)}}}
    if cfg.mod.enabled:
        caches["groups"]["mod"] = {
            "self": kv(n_groups, cfg.mod.capacity(ctx)),
            "cross": cross(n_groups),
        }
    return caches


def prefill_cross(params: Params, caches: Params, enc_emb: jax.Array, cfg: ModelConfig) -> Params:
    """Run the encoder once and fill every decoder layer's cross-KV cache."""
    enc_out = encode(params, enc_emb, cfg)

    def fill(gp, gc):
        def one(bp):
            blk = bp["block"] if "block" in bp else bp
            k, v = A.encode_kv(blk["xattn"], enc_out, cfg)
            return {"k": k, "v": v}

        return {**gc, "cross": jax.vmap(one)(gp)}

    new = {}
    for slot in caches["groups"]:
        new[slot] = fill(params["groups"][slot], caches["groups"][slot])
    return {"groups": new}


def _dec_block_decode(p, x, positions, self_cache, cross_kv, cfg, delta_only=False):
    a, self_cache = A.decode_attention(
        p["attn"], rmsnorm(p["ln1"], x, cfg.norm_eps), positions, self_cache, cfg
    )
    h = x + a
    xa = A.cross_attention(
        p["xattn"], rmsnorm(p["lnx"], h, cfg.norm_eps), cross_kv["k"], cross_kv["v"], cfg
    )
    h = h + xa
    m = mlp(p["mlp"], rmsnorm(p["ln2"], h, cfg.norm_eps), cfg)
    out = (a + xa + m) if delta_only else (h + m)
    return out, self_cache


def decode_step(
    params: Params,
    caches: Params,
    cfg: ModelConfig,
    token: jax.Array,  # (B,1)
    pos: jax.Array,  # (B,)
    active: Optional[jax.Array] = None,  # (B,) bool — live serving slots
    spmd=None,  # ShardCtx; downgraded to partitioned semantics (see below)
) -> Tuple[jax.Array, Params, Aux]:
    # The routed block_fn gathers the *global* read-only cross-KV cache via
    # the decision's row ids; inside a shard-local region those ids are
    # shard-relative, so enc-dec decode keeps the partitioned batch_capacity
    # semantics (same routed sets, same budget) but executes the dispatch
    # under GSPMD rather than shard_map.
    if spmd is not None and spmd.spmd:
        spmd = spmd.semantic_only()
    x = constrain_batch(embed(params["embed"], token))
    positions = pos[:, None]

    def body(h, xs):
        gp, gc = xs
        new_c = {}
        aux: Aux = {}
        d, sc = _dec_block_decode(gp["full"], h, positions, gc["full"]["self"], gc["full"]["cross"], cfg)
        h = d
        new_c["full"] = {"self": sc, "cross": gc["full"]["cross"]}
        if "mod" in gp:
            mp, mc = gp["mod"], gc["mod"]

            def block_fn(h_sub, pos_sub, sc_sub, decision):
                # cross-KV is read-only: gather it here (via the decision)
                # so the engine only scatters the mutated self-cache back
                ckv_sub = ROUT.gather_batch(decision, mc["cross"])
                d, sc = _dec_block_decode(
                    mp["block"], h_sub, pos_sub, sc_sub, ckv_sub, cfg, True
                )
                return d, sc, {}

            h, new_self, a = ROUT.route_decode(
                mp, h, mc["self"], block_fn, cfg, positions, active, spmd
            )
            new_c["mod"] = {"self": new_self, "cross": mc["cross"]}
            aux.update(a)
        return constrain_batch(h), (new_c, aux)

    x, (new_groups, aux_stack) = scan_layers(body, x, (params["groups"], caches["groups"]), unroll=cfg.unroll_layers)
    # mean over the layer-group axis only (per-sequence telemetry keeps (B,))
    aux = jax.tree.map(lambda a: jnp.mean(a, axis=0), aux_stack)
    logits = lm_head(params, x, cfg)[:, 0]
    return logits, {"groups": new_groups}, aux
