"""A serving cell: set-up, warm-up, the measured window, the check.

Set-up builds the weights on the device from the seed and the engine with the
configuration's settings, and queues the warm-up requests and every request
of the backlog. Warm-up steps the engine until every warm-up request has
finished, which compiles every program the window uses and staggers the
slots. The window steps the engine for the stated seconds. Afterwards the
model family's check (``serve_check``) holds what the window served to its
float32 reference: a sample of the finished requests, drawn from the seed,
with the longest among them, and whatever else the family compares.

``Recorder`` wraps the engine's step and, passing their arguments through
untouched, its prefill-chunk and decode-step calls. Around each call it writes
a host span and notes the live rows of each decode call; a family's recorder
keeps more of each call through the ``keep_*`` hooks (small arrays: nothing is
copied and nothing waits for the device). An engine step whose slots decoded
through a call the recorder did not see is counted (``unrecorded``); every
family's check fails on it.

A configuration whose ``engine`` names a ``mesh`` (``{"data": d, "model":
m}``, as many chips as its workload) is served by one engine over a
``("data", "model")`` mesh of the cell's own devices: the weights are made
on it, each leaf with the sharding the program gives it, so the engine places
nothing anew and no device holds the whole model; the family's reference
runs on the same mesh after the engine is freed.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from . import traffic as TR
from . import weights as W
from .harness import log


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
    position)``, and what the family's recorder kept before the call, of its
    outputs, and after the engine step."""

    t: float
    live: list
    before: Any = None
    out: Any = None
    after: Any = None


class Recorder:
    """Wraps one engine's calls (see the module docstring). A family that
    needs more of each call subclasses it and overrides the ``keep_*`` hooks
    and ``fetch``."""

    def __init__(self, engine: Any, chunk: int, spans: bool):
        self.spans, self.chunk = spans, chunk
        self.chunks: Dict[int, List[Tuple[float, Any]]] = {}
        self.steps: List[Step] = []
        self.unrecorded = 0  # engine steps whose slots decoded unseen
        self._uid = -1
        self._step: Optional[Step] = None
        prefill, chunk_fn, step_fn = engine._chunked_prefill, engine._chunk_fn, engine._step_fn
        engine_step = engine.step

        def chunked_prefill(slot, req, *a, **k):
            self._uid = req.uid
            self.chunks[req.uid] = []
            return prefill(slot, req, *a, **k)

        def chunk_call(*a, **k):
            t = time.perf_counter()
            with span(self.spans, "prefill_chunk.dispatch"):
                out = chunk_fn(*a, **k)
            self.chunks[self._uid].append((t, self.keep_chunk(out)))
            return out

        def step_call(*a, **k):
            live = [(s.idx, s.req.uid, s.pos) for s in engine.slots if s.active]
            self._step = Step(time.perf_counter(), live, self.keep_before())
            with span(self.spans, "decode_step.dispatch"):
                out = step_fn(*a, **k)
            self._step.out = self.keep_out(out)
            return out

        def step():
            decoding = any(s.active for s in engine.slots)
            self._step = None
            done = engine_step()
            if self._step is not None:
                self._step.after = self.keep_after()
                self.steps.append(self._step)
            elif decoding:
                self.unrecorded += 1
            return done

        engine._chunked_prefill = chunked_prefill
        engine._chunk_fn = chunk_call
        engine._step_fn = step_call
        engine.step = step

    def keep_chunk(self, out: Any) -> Any:
        """What to keep of a prefill chunk call's outputs."""
        return None

    def keep_before(self) -> Any:
        """What to keep before a decode call."""
        return None

    def keep_out(self, out: Any) -> Any:
        """What to keep of a decode call's outputs."""
        return None

    def keep_after(self) -> Any:
        """What to keep after the engine step that made a decode call."""
        return None

    def fetch(self) -> None:
        """Bring what was kept to the host (after the window)."""


@dataclasses.dataclass
class ServeRun:
    """What the metric readers read of a serving run."""

    spec: Any
    window_s: float
    tokens: int  # generated in the window
    steps: int  # engine steps in the window
    decode_work: List[Tuple[float, float]]  # (flops, least bytes), decode steps in the window
    chunk_flops: List[float]  # prefill chunks dispatched in the window
    td: Any = None  # trace data (--trace 1)
    red: Any = None  # its reduction over the window
    peaks: Any = None
    t_open: float = 0.0  # window open, on time.perf_counter


def mesh_of(eng_conf: Dict[str, Any], devices):
    """The ``("data", "model")`` mesh the configuration names over the cell's
    devices, or None where it names none."""
    shape = eng_conf.get("mesh")
    if shape is None:
        return None
    from jax.sharding import AxisType, Mesh

    grid = np.asarray(devices).reshape(int(shape["data"]), int(shape["model"]))
    return Mesh(grid, ("data", "model"), axis_types=(AxisType.Auto,) * 2)


def program_shardings(spec: Any, mesh) -> Tuple[Any, ...]:
    """Per leaf of ``spec.leaves()``, the sharding the engine places it with
    on ``mesh`` (``repro.distributed.sharding.param_shardings``)."""
    import jax

    from repro.config import MeshConfig
    from repro.distributed.sharding import param_shardings
    from repro.utils import flatten_dict

    leaves = spec.leaves()
    shapes = W.nest({p: jax.ShapeDtypeStruct(shape, dt) for p, shape, dt, _, _ in leaves})
    mcfg = MeshConfig(pod=1, data=mesh.shape["data"], model=mesh.shape["model"], fsdp=False)
    flat = flatten_dict(param_shardings(shapes, mesh, mcfg))
    return tuple(flat["/".join(p)] for p, _, _, _, _ in leaves)


def run(cell, family, cfg, spec: Any, seed: int, seconds: float, trace: bool, devices,
        clock, trace_dir: str, control: Optional[str] = None,
        fault: Optional[Callable[[Any], None]] = None):
    """One serving run of ``family``'s model: (result without ``checks``,
    checks, ServeRun, peak device memory). ``fault(engine)`` breaks the timed
    path. ``control="int8"`` serves with the program's own int8 path (weights
    and K/V pages); ``control="fp8"`` puts the reference computed through
    float8 in the program's place: at each position of the served requests,
    the check reads the token that it puts first, not the served one."""
    import jax

    from repro.serve import EngineConfig, Request, ServingEngine
    from repro.serve.quant import QuantConfig

    eng_conf, mix = cell.config["engine"], cell.traffic
    mesh = mesh_of(eng_conf, devices)
    shardings = None if mesh is None else program_shardings(spec, mesh)
    params = W.params_fn(spec, False, shardings)(W.seed_key(seed))
    page_size = eng_conf["page_size"]
    ecfg = EngineConfig(
        batch_size=int(eng_conf["slots"]), ctx=int(eng_conf["ctx"]),
        page_size=None if page_size is None else int(page_size),
        prefill_chunk=int(eng_conf["prefill_chunk"]), policy=eng_conf["policy"],
        n_pages=eng_conf.get("n_pages"), mesh=mesh,
        quant=QuantConfig(kv="int8", weights="int8") if control == "int8" else QuantConfig(),
    )
    engine = ServingEngine(params, cfg, engine=ecfg)
    rec = family.recorder(engine, cfg, spec, ecfg.prefill_chunk, trace)
    if fault is not None:
        fault(engine)
    C = ecfg.prefill_chunk

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
    jax.block_until_ready(engine.pool.pages if ecfg.page_size is not None else engine.pool.caches)
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
    if ecfg.page_size is not None:
        log(f"pool: at most {engine.pool.peak_pages_in_use} of {engine.pool.n_pages} pages in "
            f"use; {engine.preemptions} preemptions")

    rec.fetch()
    in_win = [i for i, st in enumerate(rec.steps) if t_open <= st.t <= t_close]
    decode_work = [family.decode_work(spec, rec, i) for i in in_win]
    chunk_flops = []
    for uid, chunks in rec.chunks.items():
        L = by_uid[uid].prompt.size
        for k, (t, _) in enumerate(chunks):
            if t_open <= t <= t_close:
                chunk_flops.append(family.chunk_work(spec, rec, uid, k, k * C,
                                                     min(C, L - k * C)))
    record = ServeRun(spec, window_s, tokens, engine.step_count - steps0, decode_work,
                      chunk_flops, t_open=t_open)

    # the check: a sample of the window's finished requests drawn from the
    # seed, with the longest among them, and what else the family compares
    # of the window's last decode steps, against the family's reference
    done = [o for o in in_window if o.ok]
    sample = []
    if done:
        longest = max(done, key=lambda o: len(o.tokens))
        rest = [o for o in done if o is not longest]
        pick = TR.rng(seed, 3).permutation(len(rest))[: int(mix["check"]["requests"]) - 1]
        sample = [longest] + [rest[i] for i in sorted(pick)]
    last = in_win[-int(mix["check"]["rank_steps"]):]
    prompts = {u: r.prompt for u, r in by_uid.items()}
    reference = family.serve_check(spec, seed, rec, [o.uid for o in sample], last, prompts,
                                   served, ecfg, None if control == "int8" else control)
    del engine, params, rec
    gc.collect()

    t_check = time.perf_counter()
    nums = reference()
    log(f"check: {len(sample)} requests sampled, {sum(len(served[o.uid]) for o in sample)} "
        f"served tokens; reference {time.perf_counter() - t_check:.1f}s")
    checks = held(nums, cell.checks)
    correct = bool(sample) and all(
        np.isfinite(c["value"]) and c["value"] <= c["limit"] for c in checks.values())
    result = {"correct": correct, "attempted": len(in_window),
              "failed": len(in_window) - len(done), "readings": nums}
    return result, checks, record, memory
