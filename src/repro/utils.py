"""Small shared utilities: pytree helpers, PRNG splitting, param counting."""
from __future__ import annotations

import functools
import json
import os
from pathlib import Path
from typing import Any, Dict, Iterator

import jax
import jax.numpy as jnp
import numpy as np

# The checkout root (src/repro/utils.py -> ../..): home of the default
# persistent compilation cache and of other run-time caches, all listed in
# .gitignore.
REPO_ROOT = Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set here. Otherwise the cache lives at the fixed
    ``.jax_cache/`` in the checkout: the path is part of what a later run
    must find again, so it never holds a pid, a timestamp or a temporary
    directory. Every entry point calls this before its first compile.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def scoped(name: str):
    """Decorator: trace the function under ``jax.named_scope(name)``, so each
    op it stages carries ``name`` in its HLO ``op_name`` and a profiler
    trace can sum device time by layer. A fresh scope per call keeps nested
    and concurrent traces apart."""

    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)

        return inner

    return wrap


def key_iter(seed_or_key) -> Iterator[jax.Array]:
    """Infinite iterator of fresh PRNG keys."""
    key = jax.random.PRNGKey(seed_or_key) if isinstance(seed_or_key, int) else seed_or_key
    while True:
        key, sub = jax.random.split(key)
        yield sub


def tree_size(tree: Any) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


def tree_bytes(tree: Any) -> int:
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(tree))


def flatten_dict(d: Dict[str, Any], prefix: str = "") -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for k, v in d.items():
        path = f"{prefix}/{k}" if prefix else str(k)
        if isinstance(v, dict):
            out.update(flatten_dict(v, path))
        else:
            out[path] = v
    return out


def unflatten_dict(flat: Dict[str, Any]) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for path, v in flat.items():
        parts = path.split("/")
        cur = out
        for p in parts[:-1]:
            cur = cur.setdefault(p, {})
        cur[parts[-1]] = v
    return out


def global_norm(tree: Any) -> jax.Array:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def has_nan(tree: Any) -> jax.Array:
    leaves = [jnp.any(~jnp.isfinite(x.astype(jnp.float32))) for x in jax.tree.leaves(tree)]
    return jnp.any(jnp.stack(leaves))


def pretty_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024:
            return f"{n:.2f} {unit}"
        n /= 1024
    return f"{n:.2f} PiB"


def dump_json(obj: Any, path: str) -> None:
    with open(path, "w") as f:
        json.dump(obj, f, indent=2, default=float)


def scan_or_loop(body, carry, xs_tree, unroll: bool = False):
    """lax.scan, or an unrolled python loop over the leading axis.

    The unrolled form exists for the roofline probes: XLA's cost_analysis
    counts a while-loop body ONCE regardless of trip count, so per-layer
    FLOPs/bytes are only visible in an unrolled module. Semantics match
    lax.scan (stacked ys).
    """
    import jax
    import jax.numpy as jnp

    if not unroll:
        return jax.lax.scan(body, carry, xs_tree)
    n = jax.tree.leaves(xs_tree)[0].shape[0]
    ys = []
    for i in range(n):
        carry, y = body(carry, jax.tree.map(lambda a: a[i], xs_tree))
        ys.append(y)
    ys_stacked = jax.tree.map(lambda *z: jnp.stack(z), *ys)
    return carry, ys_stacked
