"""Plain float32 pieces of a transformer, and the reference training step.

Nothing here imports the program. A model family's reference
(``bench/families/<family>.py``) is built from these pieces: its weights
come from the seed (``weights.make_params``), every product multiplies at
full float32 precision, and its training loss goes through
``train_readings``: the gradient in blocks of rows, and AdamW with
global-norm clipping and the cosine schedule, storing parameters in the
dtype each leaf lists.

``precision="fp8"`` is the control: every matrix product takes its operands
through float8 (e4m3, one scale per operand) and their gradients through
e5m2, as fp8 training does.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .weights import round_to

NEG = -1e30
HI = jax.lax.Precision.HIGHEST


def _quant(x: jax.Array, dtype, top: float) -> jax.Array:
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / s).astype(dtype).astype(jnp.float32) * s


@jax.custom_vjp
def fp8(x: jax.Array) -> jax.Array:
    """Operand through float8 e4m3 with one scale; its gradient through e5m2."""
    return _quant(x, jnp.float8_e4m3fn, 448.0)


fp8.defvjp(lambda x: (fp8(x), None),
           lambda _, g: (_quant(g, jnp.float8_e5m2, 57344.0),))


def ein(eq: str, a: jax.Array, b: jax.Array, precision: str) -> jax.Array:
    if precision == "fp8":
        a, b = fp8(a), fp8(b)
    return jnp.einsum(eq, a, b, precision=HI)


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def attention(p, x, pos, mask, s, precision: str):
    """x (..., T, D), pos (..., T), mask (..., T, T) -> (..., T, D)."""
    lead = x.shape[:-2]
    T, H, K, hd = x.shape[-2], s.n_heads, s.n_kv_heads, s.head_dim
    q = ein("...td,de->...te", x, p["wq"], precision).reshape(lead + (T, H, hd))
    k = ein("...td,de->...te", x, p["wk"], precision).reshape(lead + (T, K, hd))
    v = ein("...td,de->...te", x, p["wv"], precision).reshape(lead + (T, K, hd))
    q, k = rope(q, pos, s.rope_theta), rope(k, jnp.maximum(pos, 0), s.rope_theta)
    rep = H // K
    k, v = jnp.repeat(k, rep, axis=-2), jnp.repeat(v, rep, axis=-2)
    sc = ein("...qhd,...khd->...hqk", q, k, precision) / np.sqrt(hd)
    sc = jnp.where(mask[..., None, :, :], sc, NEG)
    o = ein("...hqk,...khd->...qhd", jax.nn.softmax(sc, axis=-1), v, precision)
    return ein("...te,ed->...td", o.reshape(lead + (T, H * hd)), p["wo"], precision)


def mlp(p, x, precision):
    g = ein("...td,df->...tf", x, p["w_gate"], precision)
    u = ein("...td,df->...tf", x, p["w_up"], precision)
    return ein("...tf,fd->...td", jax.nn.silu(g) * u, p["w_down"], precision)


def delta(p, x, pos, mask, s, precision):
    """The block's contribution f(x): attention plus MLP, no outer residual."""
    a = attention(p["attn"], rms(x, p["ln1"]["scale"], s.norm_eps), pos, mask, s, precision)
    m = mlp(p["mlp"], rms(x + a, p["ln2"]["scale"], s.norm_eps), precision)
    return a + m


def storage_dtypes(s) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for path, _, dtype, _, _ in s.leaves():
        cur = out
        for p in path[:-1]:
            cur = cur.setdefault(p, {})
        cur[path[-1]] = dtype
    return out


def make_train_step(loss_fn: Callable, s, optim: Dict[str, float], rows_per_block: int,
                    precision: str = "f32"):
    """A jittable ``(params, m, v, step, tokens, labels) -> (params, m, v, loss,
    clipped grads)`` for the family's ``loss_fn(P, s, tokens, labels,
    precision)``. Parameters are float32 holding values of their storage
    dtype; each update is rounded back to it."""
    b1, b2, eps = optim["beta1"], optim["beta2"], optim["eps"]
    wd, clip, lr0 = optim["weight_decay"], optim["clip_norm"], optim["lr"]
    warm, total, floor = optim["warmup_steps"], optim["total_steps"], optim["min_lr_ratio"]
    dtypes = storage_dtypes(s)

    def lr_at(step):
        st = step.astype(jnp.float32)
        w = jnp.minimum(st / max(warm, 1), 1.0)
        t = jnp.clip((st - warm) / max(total - warm, 1), 0.0, 1.0)
        return lr0 * w * (floor + (1.0 - floor) * 0.5 * (1.0 + jnp.cos(jnp.pi * t)))

    def grads(P, tokens, labels):
        B = tokens.shape[0]
        n = B // rows_per_block
        tb = tokens.reshape(n, rows_per_block, -1)
        lb = labels.reshape(n, rows_per_block, -1)
        zero = jax.tree.map(jnp.zeros_like, P)

        def acc(carry, xs):
            loss_sum, g_sum = carry
            l, g = jax.value_and_grad(loss_fn)(P, s, xs[0], xs[1], precision)
            return (loss_sum + l, jax.tree.map(jnp.add, g_sum, g)), None

        (loss, g), _ = jax.lax.scan(acc, (jnp.float32(0), zero), (tb, lb))
        return loss / n, jax.tree.map(lambda a: a / n, g)

    def step_fn(P, m, v, step, tokens, labels):
        loss, g = grads(P, tokens, labels)
        norm = jnp.sqrt(sum(jnp.sum(a * a) for a in jax.tree.leaves(g)))
        g = jax.tree.map(lambda a: a * jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-12)), g)
        count = step + 1
        c1 = 1.0 - b1 ** count.astype(jnp.float32)
        c2 = 1.0 - b2 ** count.astype(jnp.float32)
        lr = lr_at(step)

        def upd(p, gg, mm, vv, dt):
            mm = b1 * mm + (1.0 - b1) * gg
            vv = b2 * vv + (1.0 - b2) * gg * gg
            u = (mm / c1) / (jnp.sqrt(vv / c2) + eps) + wd * (1.0 if p.ndim >= 2 else 0.0) * p
            return round_to(p - lr * u, dt), mm, vv

        out = jax.tree.map(upd, P, g, m, v, dtypes)
        is_leaf = lambda t: isinstance(t, tuple)
        pick = lambda i: jax.tree.map(lambda t: t[i], out, is_leaf=is_leaf)
        return pick(0), pick(1), pick(2), loss, g

    return step_fn


_change_fn = jax.jit(lambda new, old: leaf_change_norms(new, old))


def leaf_norms(tree) -> Dict[str, jax.Array]:
    """Float32 norm of every leaf, keyed by its path."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for p, x in flat}


def leaf_change_norms(new, old) -> Dict[str, jax.Array]:
    return leaf_norms(jax.tree.map(lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                                   new, old))


@functools.lru_cache(maxsize=None)
def _step_fn(loss_fn: Callable, s, optim: tuple, rows_per_block: int, precision: str):
    return jax.jit(make_train_step(loss_fn, s, dict(optim), rows_per_block, precision))


def train_readings(loss_fn: Callable, P0, s, optim, batches, rows_per_block: int,
                   precision: str = "f32") -> Tuple[list, Dict[str, float], Dict[str, float]]:
    """Runs the reference of ``loss_fn`` through ``batches``: each step's
    loss, the clipped first gradient's leaf norms, and the leaf norms of the
    parameters' change after the last step."""
    step = _step_fn(loss_fn, s, tuple(sorted(optim.items())), rows_per_block, precision)
    P = P0
    m = jax.tree.map(jnp.zeros_like, P0)
    v = jax.tree.map(jnp.zeros_like, P0)
    losses, g1 = [], None
    for i, b in enumerate(batches):
        P, m, v, loss, g = step(P, m, v, jnp.int32(i), jnp.asarray(b["tokens"]),
                                jnp.asarray(b["labels"]))
        losses.append(float(loss))
        if i == 0:
            g1 = {k: float(x) for k, x in leaf_norms(g).items()}
        del g
    change = {k: float(x) for k, x in _change_fn(P, P0).items()}
    return losses, g1, change
