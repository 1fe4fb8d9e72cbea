"""Core layers: norms, rotary embeddings (RoPE + M-RoPE), (G)LU MLPs, embeds.

All modules follow the same convention: ``init_*(key, cfg, ...) -> params``
(nested dict of arrays) and a pure ``apply`` function. No framework magic —
params are plain pytrees so pjit sharding rules can match on path names.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.utils import scoped

Params = Dict[str, jax.Array]


def _dense_init(key, fan_in: int, shape, dtype) -> jax.Array:
    scale = 1.0 / jnp.sqrt(jnp.maximum(fan_in, 1)).astype(jnp.float32)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------


def init_rmsnorm(d: int, dtype) -> Params:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(params: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta**exponents)  # (head_dim/2,)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding.

    x: (B, S, H, hd); positions: (B, S) int32 — *original* token positions,
    which for MoD-gathered sub-sequences are non-contiguous.
    """
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B,S,hd/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(
    x: jax.Array,
    positions: jax.Array,
    theta: float,
    sections: Tuple[int, int, int],
) -> jax.Array:
    """Qwen2-VL multimodal RoPE: 3 position streams (t, h, w).

    x: (B, S, H, hd); positions: (3, B, S). `sections` gives the number of
    frequency pairs driven by each stream (sum == hd/2).
    """
    hd = x.shape[-1]
    assert sum(sections) == hd // 2, (sections, hd)
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    # choose, per frequency index, which position stream drives it
    sel = jnp.concatenate(
        [jnp.full((s,), i, jnp.int32) for i, s in enumerate(sections)]
    )  # (hd/2,)
    pos = positions.astype(jnp.float32)  # (3,B,S)
    pos_per_freq = jnp.take(pos, sel, axis=0)  # (hd/2, B, S)
    angles = jnp.moveaxis(pos_per_freq, 0, -1) * freqs  # (B,S,hd/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP ((Swi/Ge)GLU or plain)
# ---------------------------------------------------------------------------


def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> Params:
    D, F = cfg.d_model, d_ff or cfg.d_ff
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 3)
    p = {
        "w_up": _dense_init(ks[0], D, (D, F), dtype),
        "w_down": _dense_init(ks[1], F, (F, D), dtype),
    }
    if cfg.glu:
        p["w_gate"] = _dense_init(ks[2], D, (D, F), dtype)
    return p


@scoped("mlp")
def mlp(params: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
    up = x @ params["w_up"]
    if "w_gate" in params:
        up = act(x @ params["w_gate"]) * up
    else:
        up = act(up)
    return up @ params["w_down"]


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------


def init_embedding(key, cfg: ModelConfig) -> Params:
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 2)
    p = {"tok": _dense_init(ks[0], 1, (cfg.vocab, cfg.d_model), dtype)}
    if not cfg.tie_embeddings:
        p["unemb"] = _dense_init(ks[1], cfg.d_model, (cfg.d_model, cfg.vocab), dtype)
    return p


def embed(params: Params, tokens: jax.Array) -> jax.Array:
    from repro.distributed.sharding import constrain_replicated

    # all-gather the table, then gather locally (see constrain_replicated)
    return jnp.take(constrain_replicated(params["tok"]), tokens, axis=0)


def unembed(params: Params, x: jax.Array) -> jax.Array:
    if "unemb" in params:
        return x @ params["unemb"]
    return x @ params["tok"].T


@scoped("lm_head")
def lm_head(params: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Final norm and unembedding: the logits of the stream ``x``."""
    return unembed(params["embed"], rmsnorm(params["final_norm"], x, cfg.norm_eps))


def cross_entropy(
    logits: jax.Array, labels: jax.Array, mask: Optional[jax.Array] = None
) -> jax.Array:
    """Mean CE over valid positions; logits (..., V) in any float dtype."""
    logits32 = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits32, axis=-1)
    gold = jnp.take_along_axis(logits32, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is not None:
        m = mask.astype(jnp.float32)
        return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)
    return jnp.mean(nll)
