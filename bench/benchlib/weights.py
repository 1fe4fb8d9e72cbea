"""Random weights from the seed, in the layout the program serves and trains.

The benchmark makes the weights itself, so the reference can make the very
same values without taking anything from the program. Each leaf is a normal
draw (a leaf's own key folded from the seed's key and the leaf's index) times
``1/sqrt(fan_in)``; norm scales are ones and biases zeros. Matrices and norms
are stored in the served dtype, the MoD router and predictor in float32, as
the program keeps them.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from .spec import ModelSpec

# (path, shape, storage dtype, kind, fan_in); kind is "normal", "ones" or "zeros"
Leaf = Tuple[Tuple[str, ...], Tuple[int, ...], str, str, int]


def _block(prefix: Tuple[str, ...], s: ModelSpec, dt: str) -> List[Leaf]:
    G, D, F = s.n_groups, s.d_model, s.d_ff
    q, kv = s.n_heads * s.head_dim, s.n_kv_heads * s.head_dim
    return [
        (prefix + ("attn", "wk"), (G, D, kv), dt, "normal", D),
        (prefix + ("attn", "wo"), (G, q, D), dt, "normal", q),
        (prefix + ("attn", "wq"), (G, D, q), dt, "normal", D),
        (prefix + ("attn", "wv"), (G, D, kv), dt, "normal", D),
        (prefix + ("ln1", "scale"), (G, D), dt, "ones", 1),
        (prefix + ("ln2", "scale"), (G, D), dt, "ones", 1),
        (prefix + ("mlp", "w_down"), (G, F, D), dt, "normal", F),
        (prefix + ("mlp", "w_gate"), (G, D, F), dt, "normal", D),
        (prefix + ("mlp", "w_up"), (G, D, F), dt, "normal", D),
    ]


def leaves(s: ModelSpec) -> List[Leaf]:
    """Every parameter, in a fixed order (the order sets each leaf's key)."""
    dt, G, D, Hp = s.dtype, s.n_groups, s.d_model, s.predictor_hidden
    out: List[Leaf] = [
        (("embed", "tok"), (s.vocab, D), dt, "normal", 1),
        (("embed", "unemb"), (D, s.vocab), dt, "normal", D),
        (("final_norm", "scale"), (D,), dt, "ones", 1),
    ]
    out += _block(("groups", "full"), s, dt)
    out += _block(("groups", "mod", "block"), s, dt)
    out += [
        (("groups", "mod", "predictor", "b1"), (G, Hp), "float32", "zeros", 1),
        (("groups", "mod", "predictor", "w1"), (G, D, Hp), "float32", "normal", D),
        (("groups", "mod", "predictor", "w2"), (G, Hp), "float32", "normal", Hp),
        (("groups", "mod", "router", "w"), (G, D), "float32", "normal", D),
    ]
    return out


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole-number seed, also past 32 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _nest(flat: Dict[Tuple[str, ...], Any]) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for path, v in flat.items():
        cur = out
        for p in path[:-1]:
            cur = cur.setdefault(p, {})
        cur[path[-1]] = v
    return out


def round_to(x: jax.Array, dtype: str) -> jax.Array:
    """Float32 ``x`` rounded (to nearest, ties to even) to the values of
    ``dtype``, kept in float32. Done on the bits: a compiler allowed excess
    precision may drop a plain cast down and back up."""
    if jnp.dtype(dtype) == jnp.float32:
        return x
    assert jnp.dtype(dtype) == jnp.bfloat16, dtype
    b = jax.lax.bitcast_convert_type(x, jnp.uint32)
    b = (b + jnp.uint32(0x7FFF) + ((b >> 16) & jnp.uint32(1))) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(b, jnp.float32)


def make_params(s: ModelSpec, key: jax.Array, as_float32: bool = False) -> Dict[str, Any]:
    """The weights for ``key`` (trace under ``jax.jit``: one call on the
    device). ``as_float32`` widens the stored values to float32 exactly, for
    the reference."""
    flat = {}
    for i, (path, shape, dtype, kind, fan_in) in enumerate(leaves(s)):
        if kind == "ones":
            v = jnp.ones(shape, jnp.float32)
        elif kind == "zeros":
            v = jnp.zeros(shape, jnp.float32)
        else:
            k = jax.random.fold_in(key, i)
            v = jax.random.normal(k, shape, jnp.float32) / jnp.sqrt(jnp.float32(fan_in))
        flat[path] = round_to(v, dtype) if as_float32 else v.astype(dtype)
    return _nest(flat)


@functools.lru_cache(maxsize=None)
def params_fn(s: ModelSpec, as_float32: bool):
    """``key -> params``, jitted once per process."""
    return jax.jit(lambda key: make_params(s, key, as_float32))


def param_bytes(s: ModelSpec) -> Dict[Tuple[str, ...], int]:
    """Stored bytes of every leaf."""
    out = {}
    for path, shape, dtype, _, _ in leaves(s):
        n = 1
        for d in shape:
            n *= d
        out[path] = n * jnp.dtype(dtype).itemsize
    return out
