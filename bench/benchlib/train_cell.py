"""A training cell: set-up, the first steps, the measured window, the check.

Set-up builds one object, the program's jitted train step (``make_train_step``
with the state donated, as ``launch/train.py`` builds it) with its state, and
drives it from the seed through its first ``check_steps`` steps with the
window's own call and feed; those steps compile it, time it and give the
readings the check compares. The window then goes on stepping the same
object, with a new batch made on the host for every step, keeping about
``AHEAD_S`` seconds of steps dispatched ahead of the one whose loss it reads,
so that a host that stands still for a moment does not leave the chip idle.
When its time is up it sends nothing more, reads every loss it sent, and only
then reads the clock: all the steps sent count, over all that time. After the
window the state is freed and the model family's float32 reference (its
``train_loss`` through ``reference.train_readings``) follows the same first
steps:

- ``loss_gap``: each step's loss against the reference's, relative;
- ``grad_gap``: the clipped first gradient, recovered from the optimizer's
  first moment after one step (``m / (1 - beta1)``), leaf by leaf: the gap
  between the two norms, over the larger of the reference leaf's norm and
  the median leaf's;
- ``change_gap``: the same for the parameters' change over the first steps,
  leaving out the leaves whose reference gradient is under a thousandth of
  the median leaf's (their change is round-off).
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import math
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from . import traffic as TR
from .harness import log

AHEAD_S = 6.0  # seconds of steps in flight ahead of the loss the window reads


@dataclasses.dataclass
class TrainRun:
    """What the metric readers read of a training run."""

    spec: Any
    window_s: float
    steps: int
    tokens: int
    step_flops: float
    td: Any = None
    red: Any = None
    peaks: Any = None
    t_open: float = 0.0  # window open, on time.perf_counter


def leaf_gap(prog: Dict[str, float], ref: Dict[str, float], keep=None) -> float:
    keys = [k for k in ref if keep is None or k in keep]
    med = float(np.median([ref[k] for k in keys]))
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys}
    worst = max(gaps, key=gaps.get)
    log(f"worst leaf {worst}: {prog[worst]!r} against {ref[worst]!r} (median leaf {med!r})")
    return gaps[worst]


def compare(prog, ref) -> Dict[str, float]:
    """The three numbers the check compares, from (losses, first-gradient
    leaf norms, change leaf norms) of the program and of the reference."""
    (pl, pg, pc), (rl, rg, rc) = prog, ref
    med_g = float(np.median(list(rg.values())))
    moved = {k for k, v in rg.items() if v >= 1e-3 * med_g}
    if len(moved) < len(rg):
        log(f"change_gap leaves out {sorted(set(rg) - moved)}: reference gradient under "
            f"a thousandth of the median leaf's")
    return {
        "loss_gap": max(abs(a - b) / abs(b) for a, b in zip(pl, rl)),
        "grad_gap": leaf_gap(pg, rg),
        "change_gap": leaf_gap(pc, rc, moved),
    }


def run(cell, family, cfg, spec: Any, seed: int, seconds: float, trace: bool, devices,
        clock, trace_dir: str, control: Optional[str] = None,
        fault: Optional[Callable[[Callable], Callable]] = None):
    """One training run of ``family``'s model: (result without ``checks``,
    checks, TrainRun, memory).
    ``control="fp8"`` holds the reference computed through float8 to the
    float32 one instead of the program (no window)."""
    import jax
    import jax.numpy as jnp

    from repro.config import OptimConfig, TrainConfig
    from repro.optim import adamw_init
    from repro.train.loop import make_train_step

    from . import reference as REF
    from . import weights as W
    from .serve_cell import held, span

    mix = cell.traffic
    B, S, n_check = int(mix["batch"]), int(mix["seq_len"]), int(mix["check_steps"])
    o = mix["optim"]
    ocfg = OptimConfig(lr=o["lr"], min_lr_ratio=o["min_lr_ratio"],
                       warmup_steps=o["warmup_steps"], total_steps=o["total_steps"],
                       beta1=o["beta1"], beta2=o["beta2"], eps=o["eps"],
                       weight_decay=o["weight_decay"], clip_norm=o["clip_norm"])
    tcfg = TrainConfig(global_batch=B, seq_len=S, optim=ocfg)
    batch = lambda i: {k: jnp.asarray(v) for k, v in
                       TR.train_batch(mix, seed, i, spec.vocab).items()}
    limits = cell.checks
    rows = int(mix["reference_rows"])

    if control is not None:
        P0 = W.params_fn(spec, True)(W.seed_key(seed))
        host = [TR.train_batch(mix, seed, i, spec.vocab) for i in range(n_check)]
        got = REF.train_readings(family.train_loss, P0, spec, o, host, rows, precision=control)
        ref = REF.train_readings(family.train_loss, P0, spec, o, host, rows)
        nums = compare(got, ref)
        return ({"correct": False, "attempted": 0, "failed": 0, "readings": nums},
                held(nums, limits), None, 0)

    raw = make_train_step(cfg, tcfg)
    if fault is not None:
        raw = fault(raw)
    step = jax.jit(raw, donate_argnums=(0,))
    params = W.params_fn(spec, False)(W.seed_key(seed))
    params0 = jax.tree.map(jnp.copy, params)
    state = {"params": params, "opt": jax.jit(adamw_init)(params),
             "step": jnp.zeros((), jnp.int32)}
    del params
    losses: List[Any] = []
    b1 = ocfg.beta1
    g1 = change = None
    for i in range(n_check):
        state, metrics = step(state, batch(i))
        losses.append(metrics["loss"])
        if i == 0:
            g1 = jax.jit(lambda m: REF.leaf_norms(jax.tree.map(lambda x: x / (1.0 - b1), m)))(
                state["opt"]["m"])
            jax.block_until_ready(g1)
            t_warm = time.perf_counter()
    jax.block_until_ready(losses[-1])
    step_s = (time.perf_counter() - t_warm) / max(n_check - 1, 1)
    ahead = max(1, math.ceil(AHEAD_S / step_s))
    change = jax.jit(REF.leaf_change_norms)(state["params"], params0)
    prog = ([float(x) for x in losses], {k: float(v) for k, v in g1.items()},
            {k: float(v) for k, v in change.items()})
    del params0
    log(f"first {n_check} steps: losses {prog[0]}; compile {clock.seconds:.1f}s, "
        f"{clock.compiles} compiles, cache hits {clock.hits} misses {clock.misses}; "
        f"{step_s:.3f}s a step, {ahead} in flight")

    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    compiles0 = clock.compiles
    i, failed, n = n_check, 0, 0
    pending: collections.deque = collections.deque()  # losses sent and not yet read
    with span(trace, "bench.window"):
        t_open = time.perf_counter()
        while time.perf_counter() - t_open < seconds:
            with span(trace, "prepare_batch"):
                b = batch(i)
            with span(trace, "train_step.dispatch"):
                state, metrics = step(state, b)
            pending.append(metrics["loss"])
            i += 1
            n += 1
            while len(pending) > ahead:
                with span(trace, "loss.wait"):
                    failed += int(not np.isfinite(float(pending.popleft())))
        with span(trace, "loss.wait"):
            while pending:
                failed += int(not np.isfinite(float(pending.popleft())))
        t_close = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    window_s = t_close - t_open
    memory = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    log(f"memory_stats after the window: {devices[0].memory_stats()}")
    log(f"window: {window_s:.3f}s, {n} steps; compiles in window {clock.compiles - compiles0}")
    record = TrainRun(spec, window_s, n, n * B * S, family.train_step_flops(spec, B, S),
                      t_open=t_open)
    del state, metrics, step
    gc.collect()

    t_check = time.perf_counter()
    P0 = W.params_fn(spec, True)(W.seed_key(seed))
    host = [TR.train_batch(mix, seed, j, spec.vocab) for j in range(n_check)]
    ref = REF.train_readings(family.train_loss, P0, spec, o, host, rows)
    del P0
    log(f"check: reference {n_check} steps in {time.perf_counter() - t_check:.1f}s")
    nums = compare(prog, ref)
    checks = held(nums, limits)
    correct = all(np.isfinite(c["value"]) and c["value"] <= c["limit"] for c in checks.values())
    return ({"correct": bool(correct and failed == 0), "attempted": n, "failed": failed,
             "readings": nums}, checks, record, memory)
