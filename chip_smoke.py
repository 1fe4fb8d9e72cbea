"""Bring-up smoke test: the MoD serving and training paths on a TPU.

Run from the root of the repository, on a machine with a TPU:

    python chip_smoke.py             # one chip: serve, kernel and train phases
    python chip_smoke.py --chips 4   # four chips: --spmd serving vs its
                                     # one-device partitioned reference only

Phases (one process, in order; any failed check exits non-zero):

- serve: ``repro.launch.serve`` at the full width of ``mod-paper-1b``
  (random weights from a fixed seed, bf16): 8 slots, 16 requests of 512
  prompt tokens, 64 generated tokens each, paged pool with 16-token pages,
  xla backends. Every request must finish ok with 64 tokens, every decode
  step's logits must be finite, and the MoD decode routed fraction must be
  within 0.02 of the 0.125 capacity.
- kernel: the same requests twice more, on compiled Pallas (Mosaic)
  kernels. First with ``EngineConfig(paged_backend="pallas")`` alone: the
  prefill is the serve phase's own program, so the first decode step's
  logits must agree with the serve phase's at a bf16 tolerance. Then with
  ``MoDConfig.backend="pallas_fused"`` as well, which serves its prefill
  dispatch through the ``pallas`` kernels: the serve checks must hold, the
  served prefill program must hold Mosaic kernels, and the K/V it writes
  for the first routed block and the dense block after it (the blocks that
  only the first, backend-independent, MoD top-k choice reaches) must
  agree with the serve phase's at a bf16 tolerance. Its bf16 logits are
  not held to the serve phase's: a one-ulp rounding difference in one
  routed block flips later MoD top-k choices, and over 12 routed blocks of
  random weights the logits part completely. So the dispatch kernels are
  also held end to end in float32 with float32 matmuls, where rounding
  cannot move a top-k choice: xla and ``pallas_fused`` serving, first
  decode step's logits within ``F32_RTOL``. Last, every Mosaic kernel the
  served path can select (MoD dispatch; the ragged engine's flat-stream
  dispatch, attention in bf16 and int8, and page write-back; the paged
  pool's gather and scatter in bf16, int8 and fp8) runs on random data at
  the serving shapes against its ``kernels/ref.py`` oracle. The share of
  greedy tokens that agree is printed for every comparison.
- train: 3 steps of ``mod-paper-220m`` at sequence 2048, batch 8, through
  ``repro.launch.train`` (the config recomputes activations in the
  backward pass). The loss must be finite.

``--chips 4`` serves the same requests with ``--spmd`` on a (4, 1)
("data", "model") mesh: once in bf16, the configuration's dtype, with the
serve checks; then in float32 with float32 matmuls, with the serve checks
and against one device with ``data_shards=4`` (the same partitioned
routing) in float32, whose first decode step's logits it must match
within ``F32_RTOL`` (bf16 would part for the reason above). Each ``--spmd``
run prints ``bytes_in_use`` per device and fails unless the parameters and
the pool are spread over all four chips. With 8 slots over 4 data shards,
partitioned routing sends one row of each shard's two through the routed
blocks: half the rows, not 1/8.

Earlier lines print compile seconds, decode step times and peak device
memory per phase; they are diagnostics. The last line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
The script refuses to run on anything but a TPU.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "mod-paper-1b"
SLOTS, REQUESTS, PROMPT_LEN, GEN = 8, 16, 512, 64
CAPACITY = 0.125
SERVE_ARGV = [
    "--arch", ARCH, "--batch", str(SLOTS), "--requests", str(REQUESTS),
    "--prompt-len", str(PROMPT_LEN), "--gen", str(GEN),
]
BF16, F32 = ["--dtype", "bfloat16"], ["--dtype", "float32"]
PAGED_ARGV = ["--page-size", "16"]
TRAIN_ARGV = [
    "--arch", "mod-paper-220m", "--steps", "3", "--batch", "8", "--seq", "2048",
]
# agreement bounds for logits (and dispatch kernel outputs), relative to
# their largest magnitude: about two bf16 ulps, and far above float32
# reduction-order noise yet far below a flipped MoD top-k choice
LOGIT_RTOL = 2e-2
F32_RTOL = 1e-3


class PhaseFailed(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, and persistent
    cache hits/misses, from JAX's own monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.hits = 0
        self.misses = 0

        def on_duration(event: str, duration: float, **_):
            if event.startswith("/jax/core/compile/"):
                self.seconds += duration

        def on_event(event: str, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self):
        return self.seconds, self.hits, self.misses


class LogitTap:
    """EngineConfig.logit_tap: keeps the first decode step's logits, checks
    every step's for finiteness and stamps each step's end on the host
    clock (the tap runs after the logits reached the host, so the device
    work of the step is done)."""

    def __init__(self):
        self.first: Optional[np.ndarray] = None
        self.all_finite = True
        self.stamps: List[float] = []

    def __call__(self, logits: np.ndarray) -> None:
        self.stamps.append(time.perf_counter())
        if self.first is None:
            self.first = np.array(logits, np.float32)
        self.all_finite &= bool(np.isfinite(logits).all())

    def median_step_s(self) -> float:
        # skip the first steps: they share their window with prefill calls
        gaps = np.diff(self.stamps[SLOTS:])
        return float(np.median(gaps)) if gaps.size else float("nan")


def _serve(argv: List[str], clock: CompileClock, name: str, shards: int = 1,
           **overrides):
    """One ``repro.launch.serve`` run with the serve checks; returns the
    run (engine included: callers drop it to free its device memory) and
    its logit tap."""
    from repro.launch import serve

    tap = LogitTap()
    c0 = clock.snapshot()
    t0 = time.perf_counter()
    result = serve.main(argv, logit_tap=tap, **overrides)
    wall = time.perf_counter() - t0
    c1 = clock.snapshot()
    bad = [o.uid for o in result.outputs if not o.ok or len(o.tokens) != GEN]
    check(len(result.outputs) == REQUESTS,
          f"{name}: {len(result.outputs)} of {REQUESTS} requests finished")
    check(not bad, f"{name}: requests {bad} did not finish ok with {GEN} tokens")
    check(tap.first is not None and tap.all_finite, f"{name}: non-finite decode logits")
    # partitioned routing (data_shards > 1) routes round(ratio * B / d) rows
    # in each of the d shard groups, at least one each
    want = shards * max(1, round(CAPACITY * SLOTS / shards)) / SLOTS
    frac = result.engine.stats()["mean_routed_frac"]
    check(abs(frac - want) <= 0.02,
          f"{name}: MoD decode routed fraction {frac} not within 0.02 of {want}")
    step_s = tap.median_step_s()
    print(f"[chip_smoke] {name}: ok; wall {wall:.1f}s, compile {c1[0] - c0[0]:.1f}s, "
          f"cache hits {c1[1] - c0[1]} misses {c1[2] - c0[2]}; "
          f"routed fraction {frac:.4f}; median decode step {step_s * 1e3:.2f} ms "
          f"({SLOTS / step_s:.0f} tok/s at {SLOTS} slots)", flush=True)
    return result, tap


def _tokens(result) -> Dict[int, np.ndarray]:
    return {o.uid: np.asarray(o.tokens) for o in result.outputs}


def _agreement(a: Dict[int, np.ndarray], b: Dict[int, np.ndarray]) -> float:
    return float(np.mean(np.concatenate([a[u] == b[u] for u in sorted(a)])))


def _max_diff(name: str, ref: np.ndarray, got: np.ndarray, rtol: float = LOGIT_RTOL) -> bool:
    """Prints the largest difference; True where it is within ``rtol`` of
    the largest reference magnitude."""
    ref, got = np.asarray(ref, np.float32), np.asarray(got, np.float32)
    err = float(np.max(np.abs(ref - got)))
    scale = float(np.max(np.abs(ref)))
    print(f"[chip_smoke] {name}: max|diff| {err:.4g} (max|ref| {scale:.4g}, "
          f"bound {rtol * scale:.4g})", flush=True)
    return err <= rtol * scale


def _peak(dev) -> str:
    stats = dev.memory_stats() or {}
    return f"{stats.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB"


def _prefill_kv(run, name: str, mosaic: bool = False) -> Dict[str, np.ndarray]:
    """The engine's own jitted batch-1 prefill, the program that served
    every prompt, run again on the first request's prompt: the K/V it
    writes for the first routed block (group 0 "mod") and for the dense
    block after it (group 1 "full"). The only MoD top-k choice that reaches
    these is the first routed block's, whose input no dispatch backend
    touches, so every backend must agree here. With ``mosaic`` the prefill
    program must hold Mosaic kernels."""
    import jax
    import jax.numpy as jnp

    eng = run.engine
    toks = jnp.asarray(min(run.outputs, key=lambda o: o.uid).prompt)[None]
    prefill = eng._prefill_fn.lower(eng.params, toks).compile()
    check(not mosaic or "tpu_custom_call" in prefill.as_text(),
          f"{name}: the served prefill runs no Mosaic kernel")
    _, cache = prefill(eng.params, toks)
    groups = cache["groups"]
    return {f"group {g} {blk} {leaf}": np.asarray(jax.device_get(groups[blk][leaf][g]))
            for blk, g in (("mod", 0), ("full", 1)) for leaf in ("k", "v", "pos")}


def _kernel_cases(D: int, H: int, HD: int, groups: int, page: int, slots: int,
                  pages_per_slot: int, prompt: int, k: int, seed: int = 0) -> List[tuple]:
    """``(name, kernel, oracle, args, rtol)`` for every Mosaic kernel the
    served path can select, on random data at the serving shapes of
    ``tests/test_tpu_compile.py``. ``rtol`` 0 means bit-equal: those
    kernels only move (or exactly widen) bytes."""
    import functools

    import jax.numpy as jnp

    from repro.kernels import ops, ref
    from repro.kernels import ragged as rg

    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)

    def int8(*shape):
        return jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)

    def pow2(*shape):  # per-row scales of the quantized pool
        return jnp.asarray(2.0 ** rng.integers(-9, -5, shape), jnp.float32)

    cases = []
    # MoD dispatch of one served prompt
    x = normal(1, prompt, D)
    idx = jnp.asarray(np.sort(rng.permutation(prompt)[:k])[None], jnp.int32)
    gate = jnp.asarray(rng.random((1, k)), jnp.float32)
    cases += [
        ("dispatch gather", ops.gather_rows_op, ref.gather_rows_ref, (x, idx), 0.0),
        ("dispatch scatter-add", ops.scatter_add_rows_op, ref.scatter_add_rows_ref,
         (x, idx, normal(1, k, D), gate), LOGIT_RTOL),
    ]

    # the ragged engine's flat stream: segments of at most seg_cap prefill
    # tokens, each continuing its own slot's paged cache
    n_pages = slots * pages_per_slot + 2  # + the pool's NULL and SCRATCH pages
    tbl = 2 + np.arange(slots * pages_per_slot, dtype=np.int32).reshape(slots, -1)
    table = jnp.asarray(tbl)
    ctx = pages_per_slot * page
    T, seg_cap, kr = 1024, 256, 32
    lens = [seg_cap, 131, 0, 200]
    offs = np.concatenate([[0], np.cumsum(lens)]).astype(np.int32)
    ridx = np.full((len(lens), kr), -1, np.int32)
    seg_slot = np.array([0, 3, 5, 6], np.int32)
    pos_pages = np.full((n_pages, page), -1, np.int32)
    q_pos = np.full((T,), -1, np.int32)
    for s, L in enumerate(lens):
        sel = np.sort(rng.permutation(L)[:kr])
        ridx[s, : sel.size] = offs[s] + sel
        n = int(rng.integers(max(L, 1), ctx + 1))  # the slot holds n positions
        t = np.arange(n)
        pos_pages[tbl[seg_slot[s], t // page], t % page] = t
        q_pos[offs[s]: offs[s + 1]] = np.arange(n - L, n)
    ridx = jnp.asarray(ridx)
    rgate = jnp.where(ridx >= 0, jnp.asarray(rng.random(ridx.shape), jnp.float32), 0.0)
    xf = normal(T, D)
    cases += [
        ("ragged gather", ops.ragged_gather_rows_op, ref.ragged_gather_rows_ref,
         (xf, ridx), 0.0),
        ("ragged scatter-add", ops.ragged_scatter_add_rows_op,
         ref.ragged_scatter_add_rows_ref, (xf, ridx, normal(len(lens), kr, D), rgate),
         LOGIT_RTOL),
    ]
    rest = (jnp.asarray(pos_pages), table, jnp.asarray(offs), jnp.asarray(seg_slot),
            jnp.asarray(q_pos))
    q = normal(T, H, HD)
    cases.append((
        "ragged attention bf16", functools.partial(ops.ragged_attention_op, seg_cap=seg_cap),
        ref.ragged_attention_ref,
        (q, normal(n_pages, page, H, HD), normal(n_pages, page, H, HD)) + rest, LOGIT_RTOL,
    ))

    def quant_attention(q, kp, ks, vp, vs, *rest):
        return ops.ragged_attention_op(q, kp, vp, *rest, seg_cap=seg_cap,
                                       k_scales=ks, v_scales=vs)

    kv_shape, sc_shape = (n_pages, page, H, HD), (n_pages, page, H)
    cases.append((
        "ragged attention int8", quant_attention, ref.ragged_attention_quant_ref,
        (q, int8(*kv_shape), pow2(*sc_shape), int8(*kv_shape), pow2(*sc_shape)) + rest,
        LOGIT_RTOL,
    ))

    # the paged pool, leaves folded to (pages, page, features) as the pool
    # folds them: every layer group's K (or V) heads, and the positions
    F = groups * H * HD
    pos = jnp.asarray(rng.integers(0, ctx, slots), jnp.int32)  # one decode row per slot
    gather = functools.partial(ops.paged_gather_op, backend="pallas")
    scatter = functools.partial(ops.paged_scatter_rows_op, backend="pallas")
    leaves = {
        "bf16": (normal(n_pages, page, F), normal(slots, F)),
        "int8": (int8(n_pages, page, F), int8(slots, F)),
        "fp8": (normal(n_pages, page, F).astype(jnp.float8_e4m3fn),
                normal(slots, F).astype(jnp.float8_e4m3fn)),
        "pos": (jnp.asarray(rng.integers(-1, ctx, (n_pages, page, groups)), jnp.int32),
                jnp.asarray(rng.integers(0, ctx, (slots, groups)), jnp.int32)),
    }
    for kind, (pages, rows) in leaves.items():
        cases.append((f"paged scatter {kind}", scatter, ref.paged_scatter_rows_ref,
                      (pages, table, rows, pos), 0.0))
        if kind in ("int8", "fp8"):
            cases.append((
                f"paged gather+dequant {kind}",
                lambda p, s, t: ops.paged_gather_op(p, t, backend="pallas", scales=s),
                ref.paged_gather_dequant_ref, (pages, pow2(n_pages, page, groups * H), table),
                0.0,
            ))
        else:
            cases.append((f"paged gather {kind}", gather, ref.paged_gather_ref,
                          (pages, table), 0.0))

    # the ragged engine's write-back: decode rows and four one-page prefill
    # segments in one pass; rows past ``valid`` land on the scratch page,
    # which holds garbage by contract, so pages 0 and 1 are not compared
    W = slots + 4 * page
    flat = rng.permutation(slots * ctx)[:W]  # unique (slot, position) targets
    wargs = (normal(n_pages, page, F), table, normal(W, F),
             jnp.asarray(flat // ctx, jnp.int32), jnp.asarray(flat % ctx, jnp.int32),
             jnp.asarray(rng.random(W) > 0.1))

    def write_back(pages, table, rows, slot, pos, valid):
        return ops.ragged_paged_scatter_rows_op(
            pages, table, rows, slot, pos, valid, backend="pallas", dump_page=1)[2:]

    def write_back_ref(pages, table, rows, slot, pos, valid):
        pid, off = rg.ragged_page_targets(table, slot, pos, valid, page, 1)
        return ref.ragged_paged_scatter_rows_ref(pages, pid, off, rows)[2:]

    cases.append(("ragged page write-back", write_back, write_back_ref, wargs, 0.0))
    return cases


def _check_kernels() -> None:
    """Every served-path Mosaic kernel, compiled for the chip (never
    interpret mode), against its oracle at mod-paper-1b's serving shapes."""
    import jax

    from repro.config import get_config
    from repro.kernels import ops
    from repro.models.transformer import group_structure

    check(not ops.on_cpu(), "kernels would run in interpret mode")
    cfg = get_config(ARCH)
    cases = _kernel_cases(
        D=cfg.d_model, H=cfg.attn.n_heads, HD=cfg.head_dim,
        groups=group_structure(cfg)[0], page=int(PAGED_ARGV[1]), slots=SLOTS,
        pages_per_slot=-(-(PROMPT_LEN + GEN) // int(PAGED_ARGV[1])),
        prompt=PROMPT_LEN, k=cfg.mod.capacity(PROMPT_LEN),
    )
    for name, kernel, oracle, args, rtol in cases:
        compiled = jax.jit(kernel).lower(*args).compile()
        check("tpu_custom_call" in compiled.as_text(),
              f"kernel phase: {name} did not compile to a Mosaic kernel")
        check(_max_diff(f"kernel phase: {name} kernel vs oracle", oracle(*args),
                        compiled(*args), rtol),
              f"kernel phase: {name} kernel disagrees with its oracle")


def one_chip(dev, clock: CompileClock) -> None:
    import jax

    run, serve_tap = _serve(SERVE_ARGV + BF16 + PAGED_ARGV, clock, "serve phase")
    serve_tokens = _tokens(run)
    serve_kv = _prefill_kv(run, "serve phase")
    del run
    print(f"[chip_smoke] serve phase: peak device memory {_peak(dev)}", flush=True)

    run, tap = _serve(SERVE_ARGV + BF16 + PAGED_ARGV, clock,
                      "kernel phase (paged pool)", paged_backend="pallas")
    check(_max_diff("kernel phase (paged pool): first decode step logits vs serve "
                    "phase", serve_tap.first, tap.first),
          "kernel phase (paged pool): first decode step logits disagree")
    print(f"[chip_smoke] kernel phase (paged pool): greedy tokens agreeing with the "
          f"serve phase {_agreement(serve_tokens, _tokens(run)):.4f}", flush=True)
    del run

    run, tap = _serve(SERVE_ARGV + BF16 + PAGED_ARGV + ["--backend", "pallas_fused"],
                      clock, "kernel phase (dispatch)", paged_backend="pallas")
    name = "kernel phase (dispatch)"
    for leaf, got in _prefill_kv(run, name, mosaic=True).items():
        # positions are bit-equal (the same routed tokens); K/V a bf16
        # rounding apart at most, past the dispatch kernels' scatter-add
        rtol = 0.0 if leaf.endswith("pos") else LOGIT_RTOL
        check(_max_diff(f"{name}: served prefill {leaf} vs serve phase", serve_kv[leaf],
                        got, rtol),
              f"{name}: served prefill {leaf} disagrees with the serve phase")
    _max_diff(f"{name}: first decode step logits vs serve phase, not checked (bf16 "
              "routing divergence)", serve_tap.first, tap.first)
    print(f"[chip_smoke] {name}: greedy tokens agreeing with the serve phase "
          f"{_agreement(serve_tokens, _tokens(run)):.4f}; peak device memory "
          f"{_peak(dev)}", flush=True)
    del run
    _check_kernels()
    with jax.default_matmul_precision("highest"):
        run, ref_tap = _serve(SERVE_ARGV + F32 + PAGED_ARGV, clock,
                              "kernel phase (float32 xla)")
        ref_tokens = _tokens(run)
        del run
        run, tap = _serve(SERVE_ARGV + F32 + PAGED_ARGV + ["--backend", "pallas_fused"],
                          clock, "kernel phase (float32 dispatch)")
    check(_max_diff("kernel phase (float32 dispatch): first decode step logits vs "
                    "float32 xla", ref_tap.first, tap.first, F32_RTOL),
          "kernel phase (float32 dispatch): first decode step logits disagree")
    print(f"[chip_smoke] kernel phase (float32 dispatch): greedy tokens agreeing with "
          f"float32 xla {_agreement(ref_tokens, _tokens(run)):.4f}; peak device memory "
          f"{_peak(dev)}", flush=True)
    del run

    from repro.launch import train

    ckpt = ROOT / ".cache" / "chip_smoke_ckpt"
    shutil.rmtree(ckpt, ignore_errors=True)
    c0 = clock.snapshot()
    t0 = time.perf_counter()
    metrics = train.main(TRAIN_ARGV + ["--ckpt-dir", str(ckpt)])
    shutil.rmtree(ckpt, ignore_errors=True)
    c1 = clock.snapshot()
    loss = metrics.get("loss", float("nan"))
    check(np.isfinite(loss), f"train phase: loss {loss}")
    print(f"[chip_smoke] train phase: ok; loss {loss:.4f}; wall "
          f"{time.perf_counter() - t0:.1f}s, compile {c1[0] - c0[0]:.1f}s, cache hits "
          f"{c1[1] - c0[1]} misses {c1[2] - c0[2]}; peak device memory {_peak(dev)}",
          flush=True)


def _check_spread(run, name: str) -> None:
    """The --spmd engine's pool is sharded over all four chips, and each
    chip holds within a factor two of the others' bytes."""
    import jax

    mesh_bytes = [(d.memory_stats() or {}).get("bytes_in_use", 0) for d in jax.devices()]
    print(f"[chip_smoke] {name}: bytes_in_use per device "
          + ", ".join(f"{b / 2**30:.2f} GiB" for b in mesh_bytes), flush=True)
    leaves = jax.tree.leaves(run.engine.pool.caches)
    check(all(len(x.sharding.device_set) == 4 for x in leaves),
          f"{name}: the cache pool is not spread over all four chips")
    check(min(mesh_bytes) >= 0.5 * max(mesh_bytes),
          f"{name}: device memory is not spread over all four chips")


def four_chips(clock: CompileClock) -> None:
    """--spmd serving on a (4, 1) ("data", "model") mesh: in bf16 with the
    serve checks, then in float32 against the same partitioned routing
    semantics (data_shards=4) on one device.

    The pair runs in float32 with float32 matmuls: a TPU's default precision
    multiplies float32 operands in one bfloat16 pass, which leaves the two
    programs a bf16 rounding apart, enough to flip MoD top-k choices."""
    import jax

    run, _ = _serve(SERVE_ARGV + BF16 + ["--spmd"], clock, "spmd serving (bf16)", shards=4)
    _check_spread(run, "spmd serving (bf16)")
    del run
    with jax.default_matmul_precision("highest"):
        run, spmd_tap = _serve(SERVE_ARGV + F32 + ["--spmd"], clock,
                               "spmd serving (float32)", shards=4)
        _check_spread(run, "spmd serving (float32)")
        spmd_tokens = _tokens(run)
        del run
        run, ref_tap = _serve(SERVE_ARGV + F32, clock, "one-device reference", shards=4,
                              data_shards=4)
    check(_max_diff("spmd serving (float32): first decode step logits vs one-device "
                    "reference", ref_tap.first, spmd_tap.first, F32_RTOL),
          "spmd serving (float32): first decode step logits disagree")
    print(f"[chip_smoke] spmd serving (float32): greedy tokens agreeing with the "
          f"one-device reference {_agreement(spmd_tokens, _tokens(run)):.4f}", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: run only the four-chip --spmd comparison")
    args = ap.parse_args()
    if not (ROOT / "src" / "repro").is_dir():
        print("[chip_smoke] FAIL: run from a checkout of the repository "
              "(src/repro not found next to chip_smoke.py)", file=sys.stderr)
        return 2

    import jax

    from repro.utils import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"[chip_smoke] FAIL: no TPU (JAX platform {dev.platform!r}); this "
              "test runs only on the chip", file=sys.stderr)
        return 1
    print(f"[chip_smoke] device {dev.device_kind} x {len(devices)}; compile cache "
          f"{enable_compile_cache()}", flush=True)
    if len(devices) < args.chips:
        print(f"[chip_smoke] FAIL: --chips {args.chips} but {len(devices)} device(s)",
              file=sys.stderr)
        return 1
    clock = CompileClock()
    try:
        if args.chips == 4:
            four_chips(clock)
        else:
            one_chip(dev, clock)
    except PhaseFailed as e:
        print(f"[chip_smoke] FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
