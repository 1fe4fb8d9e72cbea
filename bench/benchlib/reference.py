"""Plain reference of the MoD transformer, in float32, from the paper's equations.

It imports nothing of the program. It builds its own weights from the seed
(``weights.make_params``), multiplies at full float32 precision, and follows
Raposo et al. (2024): every other block is routed, a routed token's output is
``x + r * f(x)`` with ``r`` the raw router logit, and the others pass on
``x`` unchanged.

Serving: the configuration routes by chunk-local top-k in prefill and by batch
capacity in decode, which depends on the other requests in the batch. A plain
reference of one request cannot know those, so it takes the program's routing
choices as given (which tokens each routed block ran on) and checks them:
the route margin is how far, in router-score standard deviations, a token
the program left out of a prefill chunk's top-k scores above one it routed.
For decode it gives each routed block's predictor score at every position, so
that the check can rank the rows of a decode step by the reference's scores.
A routed block attends over a ring of the ``capacity(ctx)`` most recent
routed tokens, which a prefill chunk writes before its queries read it; the
reference applies the same rule.

Training: the reference routes by its own top-k, computes the loss with the
router's and the predictor's BCE terms, its gradient in blocks of rows, and
AdamW with global-norm clipping and the cosine schedule, storing parameters in
the dtype the configuration states.

``precision="fp8"`` is the control: every matrix product takes its operands
through float8 (e4m3, one scale per operand) and their gradients through
e5m2, as fp8 training does.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .spec import ModelSpec
from .weights import leaves, round_to

NEG = -1e30
HI = jax.lax.Precision.HIGHEST


def _quant(x: jax.Array, dtype, top: float) -> jax.Array:
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / s).astype(dtype).astype(jnp.float32) * s


@jax.custom_vjp
def _fp8(x: jax.Array) -> jax.Array:
    """Operand through float8 e4m3 with one scale; its gradient through e5m2."""
    return _quant(x, jnp.float8_e4m3fn, 448.0)


_fp8.defvjp(lambda x: (_fp8(x), None),
            lambda _, g: (_quant(g, jnp.float8_e5m2, 57344.0),))


def _ein(eq: str, a: jax.Array, b: jax.Array, precision: str) -> jax.Array:
    if precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a, b, precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _attention(p, x, pos, mask, s: ModelSpec, precision: str):
    """x (..., T, D), pos (..., T), mask (..., T, T) -> (..., T, D)."""
    lead = x.shape[:-2]
    T, H, K, hd = x.shape[-2], s.n_heads, s.n_kv_heads, s.head_dim
    q = _ein("...td,de->...te", x, p["wq"], precision).reshape(lead + (T, H, hd))
    k = _ein("...td,de->...te", x, p["wk"], precision).reshape(lead + (T, K, hd))
    v = _ein("...td,de->...te", x, p["wv"], precision).reshape(lead + (T, K, hd))
    q, k = _rope(q, pos, s.rope_theta), _rope(k, jnp.maximum(pos, 0), s.rope_theta)
    rep = H // K
    k, v = jnp.repeat(k, rep, axis=-2), jnp.repeat(v, rep, axis=-2)
    sc = _ein("...qhd,...khd->...hqk", q, k, precision) / np.sqrt(hd)
    sc = jnp.where(mask[..., None, :, :], sc, NEG)
    o = _ein("...hqk,...khd->...qhd", jax.nn.softmax(sc, axis=-1), v, precision)
    return _ein("...te,ed->...td", o.reshape(lead + (T, H * hd)), p["wo"], precision)


def _mlp(p, x, precision):
    g = _ein("...td,df->...tf", x, p["w_gate"], precision)
    u = _ein("...td,df->...tf", x, p["w_up"], precision)
    return _ein("...tf,fd->...td", jax.nn.silu(g) * u, p["w_down"], precision)


def _delta(p, x, pos, mask, s, precision):
    """The block's contribution f(x): attention plus MLP, no outer residual."""
    a = _attention(p["attn"], _rms(x, p["ln1"]["scale"], s.norm_eps), pos, mask, s, precision)
    m = _mlp(p["mlp"], _rms(x + a, p["ln2"]["scale"], s.norm_eps), precision)
    return a + m


def _group(params, g):
    return jax.tree.map(lambda a: a[g], params["groups"])


# ---------------------------------------------------------------------------
# Serving: one request, the program's routing choices given
# ---------------------------------------------------------------------------


def serve_gaps(P: Dict[str, Any], s: ModelSpec, tokens, routed, event_end, served_next,
               chunk_id, n_chunks: int, ring: int, precision: str = "f32"):
    """tokens (T,) fed to the model; routed (G, T) bool, which positions each
    routed block ran on; event_end (T,): the last position written to the
    routed rings by the call that computed each position (a prefill chunk's
    last token, or the position itself in decode); served_next (T,): the token
    the program served after each position (-1: none); chunk_id (T,): prefill
    chunk of each prompt position (-1 elsewhere).

    Returns the gap of each served token below the reference's best logit
    (T,), the reference's best token (T,), the route margin (G, chunks), and
    each routed block's predictor score at each position (G, T), which
    batch-capacity decode ranks the rows of a step by."""
    T = tokens.shape[0]
    pos = jnp.arange(T, dtype=jnp.int32)
    causal = pos[None, :] <= pos[:, None]
    x = P["embed"]["tok"][tokens]

    def body(x, g):
        gp = _group(P, g)
        full = gp["full"]
        a = _attention(full["attn"], _rms(x, full["ln1"]["scale"], s.norm_eps), pos, causal,
                       s, precision)
        h = x + a
        x = h + _mlp(full["mlp"], _rms(h, full["ln2"]["scale"], s.norm_eps), precision)
        mod = gp["mod"]
        x_in = x
        r = _ein("td,d->t", x, mod["router"]["w"], precision)
        R = routed[g]
        cnt = jnp.cumsum(R.astype(jnp.int32))
        rank = cnt - 1
        keep_from = cnt[event_end] - ring  # ring holds ranks [cnt(end) - ring, cnt(end))
        m = R[:, None] & R[None, :] & causal & (rank[None, :] >= keep_from[:, None])
        d = _delta(mod["block"], x, pos, m, s, precision)
        x = x + jnp.where(R, r, 0.0)[:, None] * d
        # route margin: in each prefill chunk, the best-scoring left-out token
        # against the worst-scoring routed one, in the chunk's score spread
        seg = jnp.where(chunk_id >= 0, chunk_id, n_chunks)
        in_chunk = chunk_id >= 0
        lo = jax.ops.segment_min(jnp.where(R & in_chunk, r, jnp.inf), seg, n_chunks + 1)
        hi = jax.ops.segment_max(jnp.where(~R & in_chunk, r, -jnp.inf), seg, n_chunks + 1)
        cnt_c = jax.ops.segment_sum(in_chunk.astype(jnp.float32), seg, n_chunks + 1)
        mean = jax.ops.segment_sum(jnp.where(in_chunk, r, 0.0), seg, n_chunks + 1) / jnp.maximum(cnt_c, 1)
        var = jax.ops.segment_sum(jnp.where(in_chunk, (r - mean[seg]) ** 2, 0.0), seg,
                                  n_chunks + 1) / jnp.maximum(cnt_c, 1)
        viol = jnp.where(jnp.isfinite(lo) & jnp.isfinite(hi), jnp.maximum(hi - lo, 0.0), 0.0)
        margin = (viol / jnp.sqrt(jnp.maximum(var, 1e-30)))[:n_chunks]
        pr = mod["predictor"]
        hp = jax.nn.relu(_ein("td,dh->th", x_in, pr["w1"], precision) + pr["b1"])
        return x, (margin, _ein("th,h->t", hp, pr["w2"], precision))

    x, (margin, score) = jax.lax.scan(body, x, jnp.arange(s.n_groups))
    x = _rms(x, P["final_norm"]["scale"], s.norm_eps)
    logits = _ein("td,dv->tv", x, P["embed"]["unemb"], precision)
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, jnp.maximum(served_next, 0)[:, None], axis=-1)[:, 0]
    gap = jnp.where(served_next >= 0, best - got, 0.0)
    return gap, jnp.argmax(logits, axis=-1).astype(jnp.int32), margin, score


# ---------------------------------------------------------------------------
# Training: loss, gradient, AdamW
# ---------------------------------------------------------------------------


def _bce(logits, target):
    t = target.astype(jnp.float32)
    return -jnp.mean(t * jax.nn.log_sigmoid(logits) + (1.0 - t) * jax.nn.log_sigmoid(-logits))


def train_loss(P, s: ModelSpec, tokens, labels, precision: str = "f32"):
    """Mean loss over the rows: cross entropy + aux weight * router BCE +
    predictor BCE (the predictor reads stop-gradient inputs)."""
    B, S = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    causal = jnp.broadcast_to(pos[:, None, :] <= pos[:, :, None], (B, S, S))
    k = s.capacity(S)
    x = P["embed"]["tok"][tokens]

    @jax.checkpoint
    def body(x, gp):
        full = gp["full"]
        a = _attention(full["attn"], _rms(x, full["ln1"]["scale"], s.norm_eps), pos, causal,
                       s, precision)
        h = x + a
        x = h + _mlp(full["mlp"], _rms(h, full["ln2"]["scale"], s.norm_eps), precision)
        mod = gp["mod"]
        r = _ein("btd,d->bt", x, mod["router"]["w"], precision)
        _, top = jax.lax.top_k(r, k)
        idx = jnp.sort(top, axis=-1)
        sel = jnp.zeros((B, S), bool).at[jnp.arange(B)[:, None], idx].set(True)
        xs = jnp.take_along_axis(x, idx[..., None], axis=1)
        ps = idx.astype(jnp.int32)
        d = _delta(mod["block"], xs, ps, ps[:, None, :] <= ps[:, :, None], s, precision)
        gate = jnp.take_along_axis(r, idx, axis=1)
        x_new = x.at[jnp.arange(B)[:, None], idx].add(gate[..., None] * d)
        pr = mod["predictor"]
        hp = jax.nn.relu(_ein("btd,dh->bth", jax.lax.stop_gradient(x), pr["w1"], precision)
                         + pr["b1"])
        plog = _ein("bth,h->bt", hp, pr["w2"], precision)
        return x_new, (_bce(r, sel), _bce(plog, sel))

    x, (rbce, pbce) = jax.lax.scan(body, x, P["groups"])
    x = _rms(x, P["final_norm"]["scale"], s.norm_eps)
    logits = _ein("btd,dv->btv", x, P["embed"]["unemb"], precision)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    ce = jnp.mean(lse - gold)
    return ce + s.aux_loss_weight * jnp.mean(rbce) + jnp.mean(pbce)


def storage_dtypes(s: ModelSpec) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for path, _, dtype, _, _ in leaves(s):
        cur = out
        for p in path[:-1]:
            cur = cur.setdefault(p, {})
        cur[path[-1]] = dtype
    return out


def make_train_step(s: ModelSpec, optim: Dict[str, float], rows_per_block: int,
                    precision: str = "f32"):
    """A jittable ``(params, m, v, step, tokens, labels) -> (params, m, v, loss,
    clipped grads)``. Parameters are float32 holding values of their storage
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
            l, g = jax.value_and_grad(train_loss)(P, s, xs[0], xs[1], precision)
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
def _step_fn(s: ModelSpec, optim: tuple, rows_per_block: int, precision: str):
    return jax.jit(make_train_step(s, dict(optim), rows_per_block, precision))


def train_readings(P0, s: ModelSpec, optim, batches, rows_per_block: int,
                   precision: str = "f32") -> Tuple[list, Dict[str, float], Dict[str, float]]:
    """Runs the reference through ``batches``: each step's loss, the clipped
    first gradient's leaf norms, and the leaf norms of the parameters' change
    after the last step."""
    step = _step_fn(s, tuple(sorted(optim.items())), rows_per_block, precision)
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
