"""A serving cell: set-up, warm-up, the measured window, the check.

Set-up builds the weights on the device from the seed and the engine with the
configuration's settings, and queues the warm-up requests and every request
of the backlog. Warm-up steps the engine until every warm-up request has
finished, which compiles every program the window uses and staggers the
slots. The window steps the engine for the stated seconds. Afterwards the
check holds what the window served to the float32 reference
(``reference.serve_gaps``): a sample of the finished requests' tokens, and
the rows that each routed block of the window's last decode steps chose.

``Recorder`` wraps the engine's step and, passing their arguments through
untouched, its prefill-chunk and decode-step calls. Around each call it writes
a host span, and it keeps references to the routed rings' positions and
cursors (small arrays: nothing is copied and nothing waits for the device),
read from the pool through its own public description. From these the check
learns which tokens each routed block ran on, and the FLOP and byte counts
learn the routed rows. An engine step whose slots decoded through a call the
recorder did not see is counted, and fails the check.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from . import flops as FL
from . import traffic as TR
from .harness import log
from .spec import ModelSpec


def held(nums: Dict[str, float], limits: Dict[str, float]) -> Dict[str, Dict[str, float]]:
    """The numbers that have a limit, beside it; the others are logged only."""
    for k, v in nums.items():
        if k not in limits:
            log(f"reading {k} = {v!r} (not compared)")
    return {k: {"value": nums[k], "limit": lim} for k, lim in limits.items()}


def span(on: bool, name: str):
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class Step:
    """One decode call: when it was dispatched, the live rows ``(slot, uid,
    position)``, the routed rings' cursors before and after it (G, B), and the
    rows routed as the step itself reports them (its ``mod/decode_routed``
    aux: per row, the share of routed blocks that took it)."""

    t: float
    live: list
    cin: Any
    cout: Any = None
    reported: Any = None


class Recorder:
    """Wraps one engine's calls (see the module docstring)."""

    def __init__(self, engine: Any, cfg: Any, chunk: int, spans: bool):
        import jax

        from repro.models import api

        self.spans, self.chunk = spans, chunk
        paths = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(
            api.make_caches(cfg, engine.batch_size, engine.ctx, specs=True))[0]]
        self.cursor_j = engine.pool.step_spec().resid_ids.index(
            paths.index("['groups']['mod']['cursor']"))
        self.chunks: Dict[int, List[Tuple[float, Any]]] = {}
        self.steps: List[Step] = []
        self.unrecorded = 0  # engine steps whose slots decoded unseen
        self._uid = -1
        self._step: Optional[Step] = None
        prefill, chunk_fn, step_fn = engine._chunked_prefill, engine._chunk_fn, engine._step_fn
        engine_step = engine.step
        cursor = lambda: engine.pool.resid[self.cursor_j]  # noqa: E731

        def chunked_prefill(slot, req, *a, **k):
            self._uid = req.uid
            self.chunks[req.uid] = []
            return prefill(slot, req, *a, **k)

        def chunk_call(*a, **k):
            t = time.perf_counter()
            with span(self.spans, "prefill_chunk.dispatch"):
                out = chunk_fn(*a, **k)
            self.chunks[self._uid].append((t, out[-1]["groups"]["mod"]["pos"]))
            return out

        def step_call(*a, **k):
            live = [(s.idx, s.req.uid, s.pos) for s in engine.slots if s.active]
            self._step = Step(time.perf_counter(), live, cursor())
            with span(self.spans, "decode_step.dispatch"):
                out = step_fn(*a, **k)
            self._step.reported = next((o["mod/decode_routed"] for o in out
                                        if isinstance(o, dict) and "mod/decode_routed" in o), None)
            return out

        def step():
            decoding = any(s.active for s in engine.slots)
            self._step = None
            done = engine_step()
            if self._step is not None:
                self._step.cout = cursor()
                self.steps.append(self._step)
            elif decoding:
                self.unrecorded += 1
            return done

        engine._chunked_prefill = chunked_prefill
        engine._chunk_fn = chunk_call
        engine._step_fn = step_call
        engine.step = step

    def fetch(self) -> None:
        """Bring the kept arrays to the host (after the window)."""
        import jax

        cin, cout, rep = jax.device_get(
            [[s.cin for s in self.steps], [s.cout for s in self.steps],
             [s.reported for s in self.steps]])
        self.routed = [np.asarray(o) - np.asarray(i) for i, o in zip(cin, cout)]  # (G, B)
        self.cursor = [np.asarray(o) for o in cout]
        self.reported = [None if r is None else np.asarray(r) for r in rep]
        self.chunk_pos = {u: [np.asarray(p)[:, 0] for p in jax.device_get([c[1] for c in v])]
                          for u, v in self.chunks.items()}  # (G, ring) per chunk


@dataclasses.dataclass
class ServeRun:
    """What the metric readers read of a serving run."""

    spec: ModelSpec
    window_s: float
    tokens: int  # generated in the window
    steps: int  # engine steps in the window
    decode_work: List[Tuple[float, float]]  # (flops, least bytes), decode steps in the window
    chunk_flops: List[float]  # prefill chunks dispatched in the window
    td: Any = None  # trace data (--trace 1)
    red: Any = None  # its reduction over the window
    peaks: Any = None
    t_open: float = 0.0  # window open, on time.perf_counter


def _decode_work(spec: ModelSpec, rec: Recorder, i: int, ring: int) -> Tuple[float, float]:
    live = rec.steps[i].live
    pos = [p for _, _, p in live]
    routed = [[min(ring, int(rec.cursor[i][g, b])) for b, _, _ in live if rec.routed[i][g, b]]
              for g in range(spec.n_groups)]
    return FL.decode_step_flops(spec, pos, routed), FL.decode_step_bytes(spec, pos, routed)


def _chunk_work(spec: ModelSpec, pos_leaf: np.ndarray, start: int, nv: int) -> float:
    ring = []
    for g in range(spec.n_groups):
        p = pos_leaf[g]
        mine = np.sort(p[(p >= start) & (p < start + nv)])
        valid = np.sort(p[p >= 0])
        ring.append(np.searchsorted(valid, mine, side="right"))
    return FL.chunk_flops(spec, start, nv, ring)


def routing_of(rec: Recorder, uid: int, L: int, n: int, G: int) -> Optional[np.ndarray]:
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


def rows_off(rec: Recorder, kb: int) -> int:
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
def _gaps_fn(spec: ModelSpec, n_chunks: int, ring: int):
    import jax

    from . import reference as REF

    return jax.jit(lambda P, *a: REF.serve_gaps(P, spec, *a, n_chunks=n_chunks, ring=ring))


def _reference_check(spec: ModelSpec, seed: int, items, rank, ctx: int, C: int, ring: int):
    """Runs the reference once over each of ``items`` (``(uid, prompt, served
    tokens, routing, sampled)``). Over the sampled requests' served tokens: the
    mean and the widest gap of a served token's logit below the reference's
    best, and the share of served tokens that are not the reference's best;
    over their prefill chunks and routed blocks: the mean and the widest route
    margin; over the steps of ``rank``: the mean and the widest decode margin
    (``decode_margins``)."""
    from . import weights as W

    P = W.params_fn(spec, True)(W.seed_key(seed))
    fn = _gaps_fn(spec, ctx // C, ring)
    gaps, margins, scores = [], [], {}
    flips = 0
    p = np.arange(ctx)
    for uid, prompt, served, R, sampled in items:
        L, n = prompt.size, served.size
        T = L + n - 1
        fed = np.zeros(ctx, np.int32)
        fed[:L], fed[L:T] = prompt, served[:-1]
        routed = np.zeros((spec.n_groups, ctx), bool)
        routed[:, :T] = R
        event_end = np.where(p < L, np.minimum((p // C + 1) * C, L) - 1, p).astype(np.int32)
        served_next = np.full(ctx, -1, np.int32)
        served_next[L - 1:T] = served
        chunk_id = np.where(p < L, p // C, -1).astype(np.int32)
        gp, top, mg, sc = fn(P, fed, routed, event_end, served_next, chunk_id)
        scores[uid] = np.asarray(sc)
        if sampled:
            gaps.append(np.asarray(gp)[L - 1:T])
            flips += int(np.sum(np.asarray(top)[L - 1:T] != served))
            margins.append(np.asarray(mg)[:, : -(-L // C)].ravel())
    g, m = np.concatenate(gaps), np.concatenate(margins)
    d = decode_margins(rank, scores)
    return {"logit_gap_mean": float(g.mean()), "logit_gap_max": float(g.max()),
            "token_flip_share": flips / g.size, "route_margin_mean": float(m.mean()),
            "route_margin_max": float(m.max()),
            "decode_margin_mean": float(d.mean()) if d.size else float("nan"),
            "decode_margin_max": float(d.max()) if d.size else float("nan"),
            "decode_rankings": float(d.size)}


def run(cell, cfg, spec: ModelSpec, seed: int, seconds: float, trace: bool, devices,
        clock, trace_dir: str, control: Optional[str] = None,
        fault: Optional[Callable[[Any], None]] = None):
    """One serving run: (result without ``checks``, checks, ServeRun, peak
    device memory). ``control`` serves with the program's own int8 path
    (weights and K/V pages); ``fault(engine)`` breaks the timed path."""
    import jax

    from repro.serve import EngineConfig, Request, ServingEngine
    from repro.serve.quant import QuantConfig

    from . import weights as W

    eng_conf, mix = cell.config["engine"], cell.traffic
    params = W.params_fn(spec, False)(W.seed_key(seed))
    ecfg = EngineConfig(
        batch_size=int(eng_conf["slots"]), ctx=int(eng_conf["ctx"]),
        page_size=int(eng_conf["page_size"]), prefill_chunk=int(eng_conf["prefill_chunk"]),
        policy=eng_conf["policy"],
        quant=QuantConfig(kv=control, weights=control) if control else QuantConfig(),
    )
    engine = ServingEngine(params, cfg, engine=ecfg)
    rec = Recorder(engine, cfg, ecfg.prefill_chunk, trace)
    if fault is not None:
        fault(engine)
    ring, C = spec.capacity(ecfg.ctx), ecfg.prefill_chunk

    reqs = TR.serve_requests(mix, seed, spec.vocab)
    emitted = [0]
    served: Dict[int, List[int]] = {}
    by_uid: Dict[int, TR.ServeRequest] = {}

    def stream(uid: int, tok: int) -> None:
        emitted[0] += 1
        served[uid].append(tok)

    with span(trace, "submit"):
        for r in reqs:
            uid = engine.submit(Request(tokens=r.prompt, max_new_tokens=r.max_new, stream=stream))
            served[uid], by_uid[uid] = [], r
    warm_uids = {u for u, r in by_uid.items() if r.warmup}
    while not warm_uids <= {o.uid for o in engine.finished}:
        engine.step()
    jax.block_until_ready(engine.pool.pages)
    log(f"warm-up: {engine.step_count} steps; compile {clock.seconds:.1f}s, "
        f"{clock.compiles} compiles, cache hits {clock.hits} misses {clock.misses}")

    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    compiles0 = clock.compiles
    n_done0, emitted0, steps0 = len(engine.finished), emitted[0], engine.step_count
    with span(trace, "bench.window"):
        t_open = time.perf_counter()
        while time.perf_counter() - t_open < seconds:
            with span(trace, "engine.step"):
                engine.step()
        t_close = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    window_s = t_close - t_open
    tokens = emitted[0] - emitted0
    in_window = engine.finished[n_done0:]
    memory = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    log(f"memory_stats after the window: {devices[0].memory_stats()}")
    log(f"window: {window_s:.3f}s, {engine.step_count - steps0} steps, {tokens} tokens, "
        f"{len(in_window)} requests finished; compiles in window {clock.compiles - compiles0}")

    rec.fetch()
    in_win = [i for i, st in enumerate(rec.steps) if t_open <= st.t <= t_close]
    decode_work = [_decode_work(spec, rec, i, ring) for i in in_win]
    chunk_flops = []
    for uid, chunks in rec.chunks.items():
        L = by_uid[uid].prompt.size
        for k, (t, _) in enumerate(chunks):
            if t_open <= t <= t_close:
                chunk_flops.append(_chunk_work(spec, rec.chunk_pos[uid][k], k * C,
                                               min(C, L - k * C)))
    record = ServeRun(spec, window_s, tokens, engine.step_count - steps0, decode_work,
                      chunk_flops, t_open=t_open)

    # the check: a sample of the window's finished requests drawn from the
    # seed, with the longest among them, and every request live in the
    # window's last decode steps, against the reference
    done = [o for o in in_window if o.ok]
    sample = []
    if done:
        longest = max(done, key=lambda o: len(o.tokens))
        rest = [o for o in done if o is not longest]
        pick = TR.rng(seed, 3).permutation(len(rest))[: int(mix["check"]["requests"]) - 1]
        sample = [longest] + [rest[i] for i in sorted(pick)]
    last = in_win[-int(mix["check"]["rank_steps"]):]
    rank = [(rec.steps[i].live, rec.routed[i]) for i in last]
    sampled = {o.uid for o in sample}
    uids = sorted(sampled | {u for live, _ in rank for _, u, _ in live})
    items = []
    for u in uids:
        prompt, toks = by_uid[u].prompt, np.asarray(served[u], np.int32)
        items.append((u, prompt, toks, routing_of(rec, u, prompt.size, toks.size, spec.n_groups),
                      u in sampled))
    off = rows_off(rec, spec.batch_capacity(ecfg.batch_size))
    del engine, params, rec
    gc.collect()

    nums = {k: float("nan") for k in
            ("logit_gap_mean", "logit_gap_max", "token_flip_share", "route_margin_mean",
             "route_margin_max", "decode_margin_mean", "decode_margin_max")}
    t_check = time.perf_counter()
    if sample and rank and all(it[3] is not None for it in items):
        nums = _reference_check(spec, seed, items, rank, ecfg.ctx, C, ring)
    else:
        log("check: no finished request or no decode step, or routing not fully recorded")
    log(f"check: {len(sample)} requests sampled, {sum(len(served[o.uid]) for o in sample)} "
        f"served tokens; {len(rank)} decode steps ranked over {len(items)} requests; "
        f"reference {time.perf_counter() - t_check:.1f}s")
    nums["decode_rows_off"] = off
    checks = held(nums, cell.checks)
    correct = bool(sample) and all(
        np.isfinite(c["value"]) and c["value"] <= c["limit"] for c in checks.values())
    result = {"correct": correct, "attempted": len(in_window),
              "failed": len(in_window) - len(done), "readings": nums}
    return result, checks, record, memory
