"""Shared test fixtures/helpers."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.config import AttentionConfig, MoDConfig, ModelConfig

try:  # requirements-dev.txt installs hypothesis; the pinned local
    # container may lack it, and the suites must degrade, not skip
    import hypothesis  # noqa: F401

    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:
    HAVE_HYPOTHESIS = False


def property_cases(argnames, fallback, build, max_examples=25):
    """Property-based cases when hypothesis is installed, a fixed
    parametrized grid otherwise — the one shim every property suite
    shares (it used to be copy-pasted per file).

    ``build(st)`` returns the ``@given`` strategy kwargs (built lazily so
    this module imports without hypothesis); ``fallback`` is the
    ``pytest.mark.parametrize`` case list for ``argnames``. The GitHub
    Actions lanes install requirements-dev.txt and run the full
    generative suites; a container without hypothesis still executes the
    same properties over the fixed grid.
    """
    if not HAVE_HYPOTHESIS:
        return pytest.mark.parametrize(argnames, fallback)
    import hypothesis.strategies as st
    from hypothesis import given, settings

    def deco(fn):
        return settings(max_examples=max_examples, deadline=None)(
            given(**build(st))(fn)
        )

    return deco


def auto_abstract_mesh(shape, axes):
    """AbstractMesh with every axis under GSPMD (Auto)."""
    from jax.sharding import AbstractMesh, AxisType

    return AbstractMesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def tiny_cfg(**kw) -> ModelConfig:
    base = dict(
        name="t",
        n_layers=4,
        d_model=64,
        d_ff=128,
        vocab=97,
        max_seq_len=64,
        dtype="float32",
        attn=AttentionConfig(n_heads=4, n_kv_heads=2, head_dim=16),
        mod=MoDConfig(enabled=True, capacity_ratio=0.25, every=2, round_to=1),
    )
    base.update(kw)
    return ModelConfig(**base)


def batch_for(cfg: ModelConfig, B: int = 2, S: int = 32, key=None):
    key = key if key is not None else jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    out = {
        "tokens": jax.random.randint(ks[0], (B, S), 0, cfg.vocab),
        "labels": jax.random.randint(ks[1], (B, S), 0, cfg.vocab),
    }
    if cfg.family == "vlm":
        out.pop("tokens")
        out["embeds"] = jax.random.normal(ks[0], (B, S, cfg.d_model), jnp.float32) * 0.02
        out["positions"] = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32)[None, None], (3, B, S)
        ).copy()
    if cfg.family == "encdec":
        out["enc_emb"] = jax.random.normal(ks[2], (B, cfg.enc_seq_len, cfg.d_model)) * 0.02
    return out
