"""Random weights from the seed, in the layout the program serves and trains.

The benchmark makes the weights itself, so the reference can make the very
same values without taking anything from the program. The model family
lists the leaves (``spec.leaves()``: path, shape, storage dtype, kind,
fan-in, in a fixed order). Each leaf is a normal draw (a leaf's own key
folded from the seed's key and the leaf's index) times ``1/sqrt(fan_in)``;
norm scales are ones and biases zeros, each stored in its listed dtype.

On a mesh the one jitted call makes each leaf with the sharding it is given,
so no device holds more than its shard; the draws are partitionable, so the
values are those one device makes, bit for bit.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

# (path, shape, storage dtype, kind, fan_in); kind is "normal", "ones" or "zeros"
Leaf = Tuple[Tuple[str, ...], Tuple[int, ...], str, str, int]


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole-number seed, also past 32 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def nest(flat: Dict[Tuple[str, ...], Any]) -> Dict[str, Any]:
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


def make_params(s: Any, key: jax.Array, as_float32: bool = False) -> Dict[str, Any]:
    """The weights for ``key`` (trace under ``jax.jit``: one call on the
    device). ``as_float32`` widens the stored values to float32 exactly, for
    the reference."""
    flat = {}
    for i, (path, shape, dtype, kind, fan_in) in enumerate(s.leaves()):
        if kind == "ones":
            v = jnp.ones(shape, jnp.float32)
        elif kind == "zeros":
            v = jnp.zeros(shape, jnp.float32)
        else:
            k = jax.random.fold_in(key, i)
            v = jax.random.normal(k, shape, jnp.float32) / jnp.sqrt(jnp.float32(fan_in))
        flat[path] = round_to(v, dtype) if as_float32 else v.astype(dtype)
    return nest(flat)


@functools.lru_cache(maxsize=None)
def params_fn(s: Any, as_float32: bool, shardings: Optional[Tuple[Any, ...]] = None):
    """``key -> params``, jitted once per process. ``shardings``: one per
    leaf of ``s.leaves()``, in order, each leaf's place on the mesh."""
    make = lambda key: make_params(s, key, as_float32)
    if shardings is None:
        return jax.jit(make)
    out = nest({leaf[0]: sh for leaf, sh in zip(s.leaves(), shardings)})
    return jax.jit(make, out_shardings=out)


def spread(s: Any, mesh: Any) -> Tuple[Any, ...]:
    """A sharding per leaf of ``s.leaves()`` that splits the leaf over every
    device of ``mesh``: along its last axis that the device count divides,
    never its first (a family stacks its layers there, and its scan slices
    them one at a time). A leaf with no such axis is whole on every device.
    The reference's own rule, which takes nothing of the program's."""
    from jax.sharding import NamedSharding, PartitionSpec

    n, axes = mesh.size, tuple(mesh.axis_names)
    out = []
    for _, shape, _, _, _ in s.leaves():
        spec = [None] * len(shape)
        split = [a for a in range(1, len(shape)) if shape[a] % n == 0]
        if split:
            spec[split[-1]] = axes
        out.append(NamedSharding(mesh, PartitionSpec(*spec)))
    return tuple(out)


def param_bytes(s: Any) -> Dict[Tuple[str, ...], int]:
    """Stored bytes of every leaf."""
    out = {}
    for path, shape, dtype, _, _ in s.leaves():
        n = 1
        for d in shape:
            n *= d
        out[path] = n * jnp.dtype(dtype).itemsize
    return out
