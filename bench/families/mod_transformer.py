"""The MoD transformer of Raposo et al. (2024), and its dense twin: a model family.

A configuration file with a ``mod`` section is the paper's model: every other
block is routed, a routed token's output is ``x + r * f(x)`` with ``r`` the
raw router logit, and the others pass on ``x`` unchanged. A file without one
is the same transformer with every block full, the paper's "vanilla
baseline"; every count and the reference below then take the full blocks
alone.

What the harness asks of a family (``benchlib/harness.py``):

- ``spec(conf)``: the model as the file states it (``Spec``), with
  ``leaves()``, the weight layout the benchmark makes from the seed;
- ``program_config(conf, strict)``: the program's config from the file;
- ``recorder``, ``decode_work``, ``chunk_work``, ``serve_check``: what a
  serving run keeps of each call, the FLOPs and least bytes of each decode
  step and prefill chunk, and the check against the reference;
- ``train_loss``, ``train_step_flops``: the reference's training loss and
  the FLOPs of one step;
- ``serve_faults(spec)``: faults of the timed path only this family has.

Serving, routed: the configuration routes by chunk-local top-k in prefill
and by batch capacity in decode, which depends on the other requests in the
batch. A plain reference of one request cannot know those, so it takes the
program's routing choices as given (which tokens each routed block ran on,
read from the routed rings' positions and cursors) and checks them: the
route margin is how far, in router-score standard deviations, a token the
program left out of a prefill chunk's top-k scores above one it routed. For
decode it gives each routed block's predictor score at every position, so
that the check can rank the rows of a decode step by the reference's scores.
A routed block attends over a ring of the ``capacity(ctx)`` most recent
routed tokens, which a prefill chunk writes before its queries read it; the
reference applies the same rule.

Training: the reference routes by its own top-k and adds the router's and
the predictor's BCE terms to the loss.

Counts are MoD-aware: a routed block counts only the tokens (prefill,
training) or batch rows (decode) that it routes, and attention the (query,
key) pairs that the causal mask and the routed ring leave.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import serve_cell as SC
from benchlib import weights as W
from benchlib.flops import (
    attn_pair_flops,
    block_token_flops,
    causal_pairs,
    kv_row_bytes,
    unembed_flops,
    weight_bytes,
)
from benchlib.harness import log
from benchlib.reference import attention, delta, ein, mlp, rms

# ---------------------------------------------------------------------------
# The model as the file states it, and the program's config
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Spec:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    max_seq_len: int
    norm_eps: float
    rope_theta: float
    dtype: str  # the dtype the weights are served and trained in
    # MoD routing; None where the file has no ``mod`` section (dense)
    capacity_ratio: Optional[float] = None  # share of a sequence (or batch) routed
    every: Optional[int] = None  # every other block is routed
    round_to: Optional[int] = None  # capacities round to a multiple of this
    predictor_hidden: Optional[int] = None
    aux_loss_weight: Optional[float] = None

    @property
    def routed(self) -> bool:
        return self.capacity_ratio is not None

    @property
    def n_groups(self) -> int:
        """Routed: layer pairs, one full block and one routed block each.
        Dense: one full block each."""
        if not self.routed:
            return self.n_layers
        assert self.every == 2 and self.n_layers % 2 == 0, "paper layout only"
        return self.n_layers // 2

    def capacity(self, seq_len: int) -> int:
        """Routed tokens of a ``seq_len``-token sequence (or ring capacity):
        ``round(ratio * S)``, rounded down to a multiple of ``round_to`` but
        never below it once ``S >= round_to``."""
        c = int(round(self.capacity_ratio * seq_len))
        if seq_len >= self.round_to:
            c = max(self.round_to, (c // self.round_to) * self.round_to)
        return max(1, min(c, seq_len))

    def batch_capacity(self, batch: int) -> int:
        """Rows routed per decode step: ``max(1, round(ratio * B))``."""
        return max(1, int(round(self.capacity_ratio * batch)))

    def leaves(self) -> List[W.Leaf]:
        """Every parameter, in a fixed order (the order sets each leaf's key).
        Matrices and norms in the served dtype, the router and predictor in
        float32, as the program keeps them."""
        dt, G, D, Hp = self.dtype, self.n_groups, self.d_model, self.predictor_hidden
        out: List[W.Leaf] = [
            (("embed", "tok"), (self.vocab, D), dt, "normal", 1),
            (("embed", "unemb"), (D, self.vocab), dt, "normal", D),
            (("final_norm", "scale"), (D,), dt, "ones", 1),
        ]
        out += _block(("groups", "full"), self, dt)
        if not self.routed:
            return out
        out += _block(("groups", "mod", "block"), self, dt)
        out += [
            (("groups", "mod", "predictor", "b1"), (G, Hp), "float32", "zeros", 1),
            (("groups", "mod", "predictor", "w1"), (G, D, Hp), "float32", "normal", D),
            (("groups", "mod", "predictor", "w2"), (G, Hp), "float32", "normal", Hp),
            (("groups", "mod", "router", "w"), (G, D), "float32", "normal", D),
        ]
        return out


def _block(prefix: Tuple[str, ...], s: Spec, dt: str) -> List[W.Leaf]:
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


def spec(conf: Dict[str, Any]) -> Spec:
    m, mod = conf["model"], conf["model"].get("mod")
    routing = {} if mod is None else dict(
        capacity_ratio=float(mod["capacity_ratio"]), every=int(mod["every"]),
        round_to=int(mod["round_to"]), predictor_hidden=int(mod["predictor_hidden"]),
        aux_loss_weight=float(mod["aux_loss_weight"]))
    return Spec(
        n_layers=int(m["n_layers"]), d_model=int(m["d_model"]),
        n_heads=int(m["n_heads"]), n_kv_heads=int(m["n_kv_heads"]),
        head_dim=int(m["head_dim"]), d_ff=int(m["d_ff"]),
        vocab=int(m["vocab"]), max_seq_len=int(m["max_seq_len"]),
        norm_eps=float(m["norm_eps"]), rope_theta=float(m["rope_theta"]),
        dtype=conf["dtype"], **routing,
    )


def program_config(conf: Dict[str, Any], strict: bool = True) -> Any:
    """The file's numbers laid over the arch it names in the program's
    registry, routed where the file has a ``mod`` section and dense where it
    has none. With ``strict`` every number must already agree with the
    registered arch, so the cell runs the model the program ships."""
    from repro.config import get_config

    m, mod = conf["model"], conf["model"].get("mod")
    base = get_config(conf["arch"])
    routing = dataclasses.replace(base.mod, enabled=False) if mod is None else dataclasses.replace(
        base.mod, enabled=True, capacity_ratio=mod["capacity_ratio"], every=mod["every"],
        gate=mod["gate"], sampling=mod["sampling"], predictor_hidden=mod["predictor_hidden"],
        round_to=mod["round_to"], router_type=mod["router_type"],
        aux_loss_weight=mod["aux_loss_weight"], backend=mod["backend"])
    cfg = dataclasses.replace(
        base,
        n_layers=m["n_layers"], d_model=m["d_model"], d_ff=m["d_ff"], vocab=m["vocab"],
        max_seq_len=m["max_seq_len"], norm_eps=m["norm_eps"], act=m["act"], glu=m["glu"],
        tie_embeddings=m["tie_embeddings"], dtype=conf["dtype"], remat=m["remat"],
        attn=dataclasses.replace(base.attn, n_heads=m["n_heads"], n_kv_heads=m["n_kv_heads"],
                                 head_dim=m["head_dim"], rope_theta=m["rope_theta"]),
        mod=routing,
    )
    if strict and dataclasses.replace(base, dtype=conf["dtype"]) != cfg:
        raise ValueError(f"{conf['name']}: the file's sizes differ from the program's "
                         f"{conf['arch']!r}")
    return cfg


# ---------------------------------------------------------------------------
# Operations and bytes
# ---------------------------------------------------------------------------


def router_flops(s: Spec) -> float:
    return 2.0 * s.d_model


def predictor_flops(s: Spec) -> float:
    return 2.0 * s.d_model * s.predictor_hidden + 2.0 * s.predictor_hidden


def decode_step_flops(s: Spec, active_pos: Sequence[int],
                      routed_ring: Iterable[Sequence[int]]) -> float:
    """One decode step. ``active_pos``: the position each live row decodes;
    ``routed_ring``: per routed block, the ring entries each routed row
    attends over (itself included)."""
    pos = np.asarray(active_pos, np.float64)
    n = float(pos.size)
    f = s.n_groups * (n * block_token_flops(s) + attn_pair_flops(s) * float(np.sum(pos + 1)))
    for ring in routed_ring:
        ring = np.asarray(ring, np.float64)
        f += ring.size * block_token_flops(s) + attn_pair_flops(s) * float(np.sum(ring))
    if s.routed:
        f += s.n_groups * n * (router_flops(s) + predictor_flops(s))
    return f + n * unembed_flops(s)


def decode_step_bytes(s: Spec, active_pos: Sequence[int],
                      routed_ring: Iterable[Sequence[int]]) -> float:
    """Least bytes of one decode step: the weights once, the live K/V of the
    active rows (full blocks: every earlier position; routed blocks: the
    routed rows' rings), the new K/V rows written, the logits written."""
    pos = np.asarray(active_pos, np.float64)
    n = float(pos.size)
    b = weight_bytes(s, int(n))
    b += s.n_groups * kv_row_bytes(s) * (float(np.sum(pos + 1)) + n)
    for ring in routed_ring:
        ring = np.asarray(ring, np.float64)
        b += kv_row_bytes(s) * (float(np.sum(ring)) + ring.size)
    return b + n * s.vocab * 4.0


def chunk_flops(s: Spec, start: int, n_valid: int,
                routed_ring: Iterable[Sequence[int]]) -> float:
    """One prefill chunk of ``n_valid`` real tokens from position ``start``;
    ``routed_ring`` per routed block: for each routed token, the ring entries
    it attends over. Only the last token's logits are computed."""
    n = float(n_valid)
    pairs = n * start + causal_pairs(n)
    f = s.n_groups * (n * block_token_flops(s) + attn_pair_flops(s) * pairs)
    for ring in routed_ring:
        ring = np.asarray(ring, np.float64)
        f += ring.size * block_token_flops(s) + attn_pair_flops(s) * float(np.sum(ring))
    if s.routed:
        f += s.n_groups * n * router_flops(s)
    return f + unembed_flops(s)


def train_step_flops(s: Spec, batch: int, seq: int) -> float:
    """Forward and backward of one step (backward twice the forward; the
    predictor trains on stop-gradient inputs, so its backward is one forward)."""
    full = seq * block_token_flops(s) + causal_pairs(seq) * attn_pair_flops(s)
    if not s.routed:
        return batch * 3.0 * (s.n_groups * full + seq * unembed_flops(s))
    k = s.capacity(seq)
    routed = k * block_token_flops(s) + causal_pairs(k) * attn_pair_flops(s)
    per_seq = (s.n_groups * (full + routed + seq * router_flops(s))
               + seq * unembed_flops(s))
    return batch * (3.0 * per_seq + 2.0 * s.n_groups * seq * predictor_flops(s))


def decode_work(s: Spec, rec: SC.Recorder, i: int) -> Tuple[float, float]:
    """(FLOPs, least bytes) of the recorder's decode step ``i``."""
    live = rec.steps[i].live
    pos = [p for _, _, p in live]
    routed = [] if not s.routed else [
        [min(rec.ring, int(rec.cursor[i][g, b])) for b, _, _ in live if rec.routed[i][g, b]]
        for g in range(s.n_groups)]
    return decode_step_flops(s, pos, routed), decode_step_bytes(s, pos, routed)


def chunk_work(s: Spec, rec: SC.Recorder, uid: int, k: int, start: int, nv: int) -> float:
    """FLOPs of request ``uid``'s prefill chunk ``k``."""
    ring = []
    if s.routed:
        pos_leaf = rec.chunk_pos[uid][k]
        for g in range(s.n_groups):
            p = pos_leaf[g]
            mine = np.sort(p[(p >= start) & (p < start + nv)])
            valid = np.sort(p[p >= 0])
            ring.append(np.searchsorted(valid, mine, side="right"))
    return chunk_flops(s, start, nv, ring)


# ---------------------------------------------------------------------------
# The reference, in float32 (benchlib/reference.py's pieces)
# ---------------------------------------------------------------------------


def _group(params, g):
    return jax.tree.map(lambda a: a[g], params["groups"])


def serve_logits(P: Dict[str, Any], s: Spec, tokens, routed=None, event_end=None,
                 chunk_id=None, n_chunks: int = 0, ring: int = 0, precision: str = "f32"):
    """The full forward of one request: tokens (T,) fed to the model. Routed:
    routed (G, T) bool, which positions each routed block ran on; event_end
    (T,): the last position written to the routed rings by the call that
    computed each position (a prefill chunk's last token, or the position
    itself in decode); chunk_id (T,): prefill chunk of each prompt position
    (-1 elsewhere).

    Returns the logits (T, V) and, routed, the route margin (G, chunks) and
    each routed block's predictor score at each position (G, T), which
    batch-capacity decode ranks the rows of a step by (dense: None, None)."""
    T = tokens.shape[0]
    pos = jnp.arange(T, dtype=jnp.int32)
    causal = pos[None, :] <= pos[:, None]
    x = P["embed"]["tok"][tokens]

    def body(x, g):
        gp = _group(P, g)
        full = gp["full"]
        a = attention(full["attn"], rms(x, full["ln1"]["scale"], s.norm_eps), pos, causal,
                      s, precision)
        h = x + a
        x = h + mlp(full["mlp"], rms(h, full["ln2"]["scale"], s.norm_eps), precision)
        if not s.routed:
            return x, None
        mod = gp["mod"]
        x_in = x
        r = ein("td,d->t", x, mod["router"]["w"], precision)
        R = routed[g]
        cnt = jnp.cumsum(R.astype(jnp.int32))
        rank = cnt - 1
        keep_from = cnt[event_end] - ring  # ring holds ranks [cnt(end) - ring, cnt(end))
        m = R[:, None] & R[None, :] & causal & (rank[None, :] >= keep_from[:, None])
        d = delta(mod["block"], x, pos, m, s, precision)
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
        hp = jax.nn.relu(ein("td,dh->th", x_in, pr["w1"], precision) + pr["b1"])
        return x, (margin, ein("th,h->t", hp, pr["w2"], precision))

    x, ys = jax.lax.scan(body, x, jnp.arange(s.n_groups))
    margin, score = (None, None) if ys is None else ys
    x = rms(x, P["final_norm"]["scale"], s.norm_eps)
    return ein("td,dv->tv", x, P["embed"]["unemb"], precision), margin, score


def serve_gaps(P: Dict[str, Any], s: Spec, tokens, routed, event_end, served_next,
               chunk_id, n_chunks: int, ring: int, precision: str = "f32"):
    """``serve_logits``' arguments, and served_next (T,): the token the
    program served after each position (-1: none).

    Returns the gap of each served token below the reference's best logit
    (T,), the reference's best token (T,), and ``serve_logits``' route margin
    and predictor scores."""
    logits, margin, score = serve_logits(P, s, tokens, routed, event_end, chunk_id, n_chunks,
                                         ring, precision)
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, jnp.maximum(served_next, 0)[:, None], axis=-1)[:, 0]
    gap = jnp.where(served_next >= 0, best - got, 0.0)
    return gap, jnp.argmax(logits, axis=-1).astype(jnp.int32), margin, score


def _bce(logits, target):
    t = target.astype(jnp.float32)
    return -jnp.mean(t * jax.nn.log_sigmoid(logits) + (1.0 - t) * jax.nn.log_sigmoid(-logits))


def train_loss(P, s: Spec, tokens, labels, precision: str = "f32"):
    """Mean loss over the rows: cross entropy, and routed + aux weight *
    router BCE + predictor BCE (the predictor reads stop-gradient inputs)."""
    B, S = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    causal = jnp.broadcast_to(pos[:, None, :] <= pos[:, :, None], (B, S, S))
    k = s.capacity(S) if s.routed else 0
    x = P["embed"]["tok"][tokens]

    @jax.checkpoint
    def body(x, gp):
        full = gp["full"]
        a = attention(full["attn"], rms(x, full["ln1"]["scale"], s.norm_eps), pos, causal,
                      s, precision)
        h = x + a
        x = h + mlp(full["mlp"], rms(h, full["ln2"]["scale"], s.norm_eps), precision)
        if not s.routed:
            return x, None
        mod = gp["mod"]
        r = ein("btd,d->bt", x, mod["router"]["w"], precision)
        _, top = jax.lax.top_k(r, k)
        idx = jnp.sort(top, axis=-1)
        sel = jnp.zeros((B, S), bool).at[jnp.arange(B)[:, None], idx].set(True)
        xs = jnp.take_along_axis(x, idx[..., None], axis=1)
        ps = idx.astype(jnp.int32)
        d = delta(mod["block"], xs, ps, ps[:, None, :] <= ps[:, :, None], s, precision)
        gate = jnp.take_along_axis(r, idx, axis=1)
        x_new = x.at[jnp.arange(B)[:, None], idx].add(gate[..., None] * d)
        pr = mod["predictor"]
        hp = jax.nn.relu(ein("btd,dh->bth", jax.lax.stop_gradient(x), pr["w1"], precision)
                         + pr["b1"])
        plog = ein("bth,h->bt", hp, pr["w2"], precision)
        return x_new, (_bce(r, sel), _bce(plog, sel))

    x, bces = jax.lax.scan(body, x, P["groups"])
    x = rms(x, P["final_norm"]["scale"], s.norm_eps)
    logits = ein("btd,dv->btv", x, P["embed"]["unemb"], precision)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    ce = jnp.mean(lse - gold)
    if bces is None:
        return ce
    rbce, pbce = bces
    return ce + s.aux_loss_weight * jnp.mean(rbce) + jnp.mean(pbce)


# ---------------------------------------------------------------------------
# Serving: what the recorder keeps, and the check
# ---------------------------------------------------------------------------


class RingRecorder(SC.Recorder):
    """Also keeps references to the routed rings' positions after each
    prefill chunk and to their cursors around each decode step, read from
    the pool (the paged one through its own public description), and the
    rows each decode step reports routed (its ``mod/decode_routed`` aux: per
    row, the share of routed blocks that took it). From these the check
    learns which tokens each routed block ran on, and the counts learn the
    routed rows."""

    def __init__(self, engine: Any, cfg: Any, s: Spec, chunk: int, spans: bool):
        from repro.models import api

        super().__init__(engine, chunk, spans)
        if engine.engine_config.page_size is None:  # the contiguous pool
            self._cursor = lambda: engine.pool.caches["groups"]["mod"]["cursor"]
        else:
            paths = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(
                api.make_caches(cfg, engine.batch_size, engine.ctx, specs=True))[0]]
            j = engine.pool.step_spec().resid_ids.index(
                paths.index("['groups']['mod']['cursor']"))
            self._cursor = lambda: engine.pool.resid[j]
        self.ring = s.capacity(engine.ctx)

    def keep_chunk(self, out):
        return out[-1]["groups"]["mod"]["pos"]

    def keep_before(self):
        return self._cursor()

    def keep_out(self, out):
        return next((o["mod/decode_routed"] for o in out
                     if isinstance(o, dict) and "mod/decode_routed" in o), None)

    def keep_after(self):
        return self._cursor()

    def fetch(self) -> None:
        cin, cout, rep = jax.device_get(
            [[s.before for s in self.steps], [s.after for s in self.steps],
             [s.out for s in self.steps]])
        self.routed = [np.asarray(o) - np.asarray(i) for i, o in zip(cin, cout)]  # (G, B)
        self.cursor = [np.asarray(o) for o in cout]
        self.reported = [None if r is None else np.asarray(r) for r in rep]
        self.chunk_pos = {u: [np.asarray(p)[:, 0] for p in jax.device_get([c[1] for c in v])]
                          for u, v in self.chunks.items()}  # (G, ring) per chunk


def recorder(engine: Any, cfg: Any, s: Spec, chunk: int, spans: bool) -> SC.Recorder:
    return RingRecorder(engine, cfg, s, chunk, spans) if s.routed else SC.Recorder(
        engine, chunk, spans)


def routing_of(rec: RingRecorder, uid: int, L: int, n: int, G: int) -> Optional[np.ndarray]:
    """(G, L + n - 1) bool: which positions each routed block ran on, or None
    if a chunk or a decode step of the request was not recorded."""
    C = rec.chunk
    chunks = rec.chunk_pos.get(uid, [])
    if len(chunks) != -(-L // C):
        return None
    R = np.zeros((G, L + n - 1), bool)
    for k, pos in enumerate(chunks):
        lo, hi = k * C, min((k + 1) * C, L)
        for g in range(G):
            p = pos[g]
            R[g, p[(p >= lo) & (p < hi)]] = True
    seen = np.zeros(L + n - 1, bool)
    seen[:L] = True
    for i, st in enumerate(rec.steps):
        for b, u, p in st.live:
            if u == uid and L <= p < L + n - 1:
                R[:, p] = rec.routed[i][:, b] > 0
                seen[p] = True
    return R if seen.all() else None


def rows_off(rec: RingRecorder, kb: int) -> int:
    """Decode steps and routed blocks whose routed rows are not as the
    configuration states: a (step, routed block) pair whose routed live rows
    number other than ``min(kb, live rows)`` or that routed a free row; a step
    whose routed rows differ from what it reports itself; a step the recorder
    did not see."""
    off = rec.unrecorded
    for i, st in enumerate(rec.steps):
        rows = [b for b, _, _ in st.live]
        free = np.ones(rec.routed[i].shape[1], bool)
        free[rows] = False
        off += int(np.sum(rec.routed[i][:, rows].sum(axis=1) != min(kb, len(rows))))
        off += int(np.sum(rec.routed[i][:, free].sum(axis=1) != 0))
        if rec.reported[i] is not None:
            share = (rec.routed[i] > 0).mean(axis=0)
            off += int(not np.allclose(share[rows], rec.reported[i][rows], atol=1e-6))
    return off


def decode_margins(rank, scores: Dict[int, np.ndarray]) -> np.ndarray:
    """For each decode step and routed block of ``rank`` (``(live rows, routed
    (G, B))``), how far, in the live rows' score standard deviations, the best
    reference score of a live row the block left out lies above the worst of
    one it routed (0 where the program's top rows are the reference's)."""
    out = []
    for live, routed in rank:
        for g in range(routed.shape[0]):
            sc = np.array([scores[u][g, p] for _, u, p in live])
            took = np.array([routed[g, b] > 0 for b, _, _ in live])
            if took.all() or not took.any():
                continue
            viol = max(0.0, float(sc[~took].max() - sc[took].min()))
            out.append(viol / max(float(sc.std()), 1e-30))
    return np.asarray(out, np.float64)


@functools.lru_cache(maxsize=None)
def _gaps_fn(s: Spec, n_chunks: int, ring: int, precision: str = "f32"):
    return jax.jit(lambda P, *a: serve_gaps(P, s, *a, n_chunks=n_chunks, ring=ring,
                                            precision=precision))


def _reference_check(s: Spec, seed: int, items, rank, ctx: int, C: int, ring: int,
                     control: Optional[str] = None, mesh: Any = None):
    """Runs the reference once over each of ``items`` (``(uid, prompt, served
    tokens, routing, sampled)``). Over the sampled requests' served tokens: the
    mean and the widest gap of a served token's logit below the reference's
    best, and the share of served tokens that are not the reference's best.
    Routed, also over their prefill chunks and routed blocks: the mean and the
    widest route margin; over the steps of ``rank``: the mean and the widest
    decode margin (``decode_margins``). With ``control`` (``"fp8"``), the
    tokens that the reference computed in that precision puts first at each
    served position stand in for the served ones. On ``mesh`` (the engine's,
    where it has one) the weights are made split over its devices."""
    P = W.params_fn(s, True, None if mesh is None else W.spread(s, mesh))(W.seed_key(seed))
    fn = _gaps_fn(s, ctx // C, ring)
    lower = control and _gaps_fn(s, ctx // C, ring, control)
    gaps, margins, scores = [], [], {}
    flips = 0
    p = np.arange(ctx)
    for uid, prompt, served, R, sampled in items:
        L, n = prompt.size, served.size
        T = L + n - 1
        fed = np.zeros(ctx, np.int32)
        fed[:L], fed[L:T] = prompt, served[:-1]
        routed = None
        if s.routed:
            routed = np.zeros((s.n_groups, ctx), bool)
            routed[:, :T] = R
        event_end = np.where(p < L, np.minimum((p // C + 1) * C, L) - 1, p).astype(np.int32)
        served_next = np.full(ctx, -1, np.int32)
        served_next[L - 1:T] = served
        chunk_id = np.where(p < L, p // C, -1).astype(np.int32)
        if lower:
            served = np.asarray(lower(P, fed, routed, event_end, served_next, chunk_id)[1])[L - 1:T]
            served_next[L - 1:T] = served
        gp, top, mg, sc = fn(P, fed, routed, event_end, served_next, chunk_id)
        if s.routed:
            scores[uid] = np.asarray(sc)
        if sampled:
            gaps.append(np.asarray(gp)[L - 1:T])
            flips += int(np.sum(np.asarray(top)[L - 1:T] != served))
            if s.routed:
                margins.append(np.asarray(mg)[:, : -(-L // C)].ravel())
    g = np.concatenate(gaps)
    out = {"logit_gap_mean": float(g.mean()), "logit_gap_max": float(g.max()),
           "token_flip_share": flips / g.size}
    if not s.routed:
        return out
    m = np.concatenate(margins)
    d = decode_margins(rank, scores)
    out.update({"route_margin_mean": float(m.mean()), "route_margin_max": float(m.max()),
                "decode_margin_mean": float(d.mean()) if d.size else float("nan"),
                "decode_margin_max": float(d.max()) if d.size else float("nan"),
                "decode_rankings": float(d.size)})
    return out


def serve_check(s: Spec, seed: int, rec: SC.Recorder, sampled: List[int], last: List[int],
                prompts: Dict[int, np.ndarray], served: Dict[int, List[int]],
                ecfg: Any, control: Optional[str] = None) -> Callable[[], Dict[str, float]]:
    """Takes from the recorder, now, what the check needs (host arrays only)
    and returns the check, to run once the engine is freed: the reference
    over the ``sampled`` requests and, routed, every request live in the
    decode steps ``last``; and ``decode_rows_off``, exact. Routed, that
    counts the decode steps whose routed rows are not as the configuration
    states (``rows_off``); dense, the engine steps that decoded through a
    call the recorder did not see. ``control``: see ``_reference_check``."""
    names = ["logit_gap_mean", "logit_gap_max", "token_flip_share"]
    if s.routed:
        names += ["route_margin_mean", "route_margin_max", "decode_margin_mean",
                  "decode_margin_max"]
        rank = [(rec.steps[i].live, rec.routed[i]) for i in last]
        uids = sorted(set(sampled) | {u for live, _ in rank for _, u, _ in live})
        off = rows_off(rec, s.batch_capacity(ecfg.batch_size))
        ring = rec.ring
    else:
        rank, uids, off, ring = [], sorted(sampled), rec.unrecorded, 0
    items = []
    for u in uids:
        toks = np.asarray(served[u], np.int32)
        R = routing_of(rec, u, prompts[u].size, toks.size, s.n_groups) if s.routed else None
        items.append((u, prompts[u], toks, R, u in sampled))

    def check() -> Dict[str, float]:
        nums = {k: float("nan") for k in names}
        if not sampled or (s.routed and (not rank or any(it[3] is None for it in items))):
            log("check: no finished request or no decode step, or routing not fully recorded")
        else:
            nums = _reference_check(s, seed, items, rank, ecfg.ctx, ecfg.prefill_chunk, ring,
                                    control, ecfg.mesh)
        if s.routed:
            log(f"check: {len(rank)} decode steps ranked over {len(items)} requests")
        nums["decode_rows_off"] = off
        return nums

    return check


def reversed_ranking(engine: Any) -> None:
    """Batch-capacity decode routes the live rows that score lowest: the
    served weights' predictor output layer negated, so the engine's own
    ranking runs backwards (the prefill's top-k reads the router, not the
    predictor, and is left as it is)."""

    def flip(path, x):
        return -x if jax.tree_util.keystr(path) == "['groups']['mod']['predictor']['w2']" else x

    engine.params = jax.tree_util.tree_map_with_path(flip, engine.params)


def serve_faults(s: Spec) -> Dict[str, Callable[[Any], None]]:
    """Faults of the timed serving path that only this family has, for
    ``bench/readings.py``."""
    return {"reversed_ranking": reversed_ranking} if s.routed else {}
