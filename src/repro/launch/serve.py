"""Serving driver: continuous-batching MoD decode over a request stream.

Loads a checkpoint if given (otherwise random init), then drives the
continuous-batching engine (``repro.serve``, DESIGN.md §Serving engine):
requests are submitted on an arrival schedule, admitted into a fixed
``(B, ctx)`` decode batch as slots free up, prefilled (batched for dense
families, stepped for SSM/hybrid/enc-dec), and decoded until EOS or their
token budget. Reports decode throughput, per-request latency percentiles,
MoD routed fraction, and the pool's KV footprint. The decode step is the
exact function the ``decode_*`` dry-run cells lower at 512 chips.

Engine flags (``--page-size``/``--ragged``/``--speculate``/``--quant-kv``
...) come from the shared :func:`repro.serve.add_engine_args` group, so
this driver and ``benchmarks/serving.py`` expose the same surface.

  PYTHONPATH=src python -m repro.launch.serve --arch mod-paper-60m \
      --smoke --batch 8 --prompt-len 32 --gen 32 --requests 16

``main(argv, **engine_overrides)`` is also the programmatic entry point
(``chip_smoke.py`` drives it): it returns the :class:`ServeRun`, and the
keyword overrides replace :class:`~repro.serve.EngineConfig` fields that
have no flag (``paged_backend``, ``logit_tap``).
"""
from __future__ import annotations

import argparse
import dataclasses
from typing import Any, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.config import ModelConfig, get_config, smoke_config
from repro.data.synthetic import SyntheticLM
from repro.models import api
from repro.serve import EngineConfig, Request, ServingEngine, add_engine_args
from repro.utils import enable_compile_cache


@dataclasses.dataclass
class ServeRun:
    """What one serving run produced: the resolved model config, the
    engine (its stats and pool) and every finished request."""

    cfg: ModelConfig
    engine: ServingEngine
    outputs: List[Any]


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mod-paper-60m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--batch", type=int, default=8, help="decode-batch slots")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32, help="tokens per request")
    ap.add_argument("--requests", type=int, default=0,
                    help="total requests (default: 2x batch)")
    ap.add_argument("--arrival-every", type=int, default=0,
                    help="submit one request every N engine steps (0 = all upfront)")
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--backend", default=None,
                    choices=["xla", "pallas", "pallas_fused"],
                    help="MoD dispatch backend (default: the arch's own)")
    ap.add_argument("--spmd", action="store_true",
                    help="serve over a ('data','model') mesh spanning every "
                         "available device: batch-sharded cache pool + "
                         "shard-local MoD routing (force a multi-device CPU "
                         "host with XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=8)")
    ap.add_argument("--model-axis", type=int, default=1,
                    help="tensor-parallel degree of the --spmd mesh")
    ap.add_argument("--priority", default="batch",
                    choices=["batch", "latency"],
                    help="priority class for the submitted requests: "
                         "latency-tier is admitted first and keeps full "
                         "MoD capacity under overload (DESIGN.md "
                         "§Overload control)")
    ap.add_argument("--deadline", type=float, default=0.0,
                    help="per-request deadline in seconds from submit; "
                         "expired requests finish as 'expired' instead of "
                         "occupying slots (0 = no deadline)")
    ap.add_argument("--inject-faults", type=int, default=-1,
                    help="thread a seeded FaultInjector through the "
                         "engine (NaN/Inf logits, page exhaustion, "
                         "stragglers, preemption storms) with this seed; "
                         "-1 = off")
    add_engine_args(ap)
    return ap.parse_args(argv)


def run(args: argparse.Namespace, **engine_overrides: Any) -> ServeRun:
    """Build the model and engine from parsed flags and serve the request
    stream; ``engine_overrides`` replace EngineConfig fields."""
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    cfg = dataclasses.replace(cfg, dtype=args.dtype)
    if args.backend:
        from repro.config import with_mod_backend

        cfg = with_mod_backend(cfg, args.backend)

    params = api.init_model(jax.random.PRNGKey(0), cfg)
    if args.ckpt_dir:
        restored = CheckpointManager(args.ckpt_dir).restore_latest()
        if restored:
            step, state = restored
            params = jax.tree.map(jnp.asarray, state["params"])
            print(f"[serve] loaded checkpoint step {step}")

    mesh = None
    if args.spmd:
        from repro.launch.mesh import auto_mesh, describe_mesh

        mesh = auto_mesh(args.model_axis)
        print(f"[serve] SPMD mesh: {describe_mesh(mesh)}")

    n_requests = args.requests or 2 * args.batch
    data = SyntheticLM(cfg.vocab, args.prompt_len, seed=7)
    prompts = np.asarray(data.batch(0, n_requests)["tokens"])[:, : args.prompt_len]

    ctx = args.prompt_len + args.gen
    injector = None
    if args.inject_faults >= 0:
        from repro.serve import FaultInjector

        injector = FaultInjector.seeded(args.inject_faults)
    overrides = dict(mesh=mesh, fault_injector=injector)
    overrides.update(engine_overrides)
    ecfg = EngineConfig.from_args(args, batch_size=args.batch, ctx=ctx, **overrides)
    engine = ServingEngine(params, cfg, engine=ecfg)

    outputs = engine.run_stream(
        [Request(tokens=prompts[i], max_new_tokens=args.gen,
                 priority=args.priority,
                 deadline_s=args.deadline or None)
         for i in range(n_requests)],
        args.arrival_every,
    )
    return ServeRun(cfg, engine, outputs)


def report(args: argparse.Namespace, result: ServeRun) -> None:
    cfg, engine, outputs = result.cfg, result.engine, result.outputs
    ctx = args.prompt_len + args.gen
    injector = engine.engine_config.fault_injector
    s = engine.stats()
    lat = np.asarray([o.residency_steps for o in outputs], np.float64)
    wait = np.asarray([o.queue_steps for o in outputs], np.float64)
    kv = engine.pool.cache_bytes()
    print(f"[serve] arch={cfg.name} slots={args.batch} ctx={ctx} "
          f"requests={len(outputs)} policy={args.policy}")
    if engine.spmd is not None and engine.scheduler.routed_capacity is not None:
        print(f"[serve] shard-local routing: data_shards={engine.spmd.data_shards} "
              f"global kb={engine.scheduler.routed_capacity} "
              f"(= d * round(ratio * B/d))")
    print(f"[serve] {s['steps']:.0f} engine steps in {s['wall_s']:.2f}s: "
          f"{s['tokens_per_s']:.1f} tok/s aggregate, "
          f"mean occupancy {s['mean_occupancy']:.2f}/{args.batch}")
    print(f"[serve] latency (steps): p50={np.percentile(lat, 50):.0f} "
          f"p95={np.percentile(lat, 95):.0f}; queue wait mean={wait.mean():.1f}")
    if np.isfinite(s["mean_routed_frac"]):
        scores = np.asarray([o.mean_score for o in outputs])
        print(f"[serve] MoD decode routed fraction: {s['mean_routed_frac']:.3f} "
              f"(capacity_ratio={cfg.mod.capacity_ratio}); "
              f"per-request router score mean={np.nanmean(scores):.3f} "
              f"spread={np.nanstd(scores):.3f}; "
              f"KV pool {kv['total']/2**20:.1f} MiB "
              f"(mod/full cache ratio {kv['mod_vs_full_ratio']:.2f})")
    if args.page_size:
        print(f"[serve] paged pool: page_size={args.page_size} "
              f"pages={s['n_pages']:.0f} "
              f"peak_utilization={s['page_utilization_peak']:.2f} "
              f"prefix_hit_rate={s['prefix_hit_rate']:.2f} "
              f"preemptions={s['preemptions']:.0f} "
              f"prefill_tokens_computed={s['prefill_tokens_computed']:.0f}")
    if args.quant_kv != "none":
        print(f"[serve] quantized KV: kv={args.quant_kv} "
              f"scales={args.quant_scale} "
              f"kv_bytes={s['kv_bytes']/2**20:.2f} MiB "
              f"(+ resid {s['resid_bytes']/2**20:.2f} MiB full-precision)")
    if args.ragged:
        print(f"[serve] ragged mixed step: segments={args.ragged_segments} "
              f"padded_token_fraction={s['padded_token_fraction']:.3f} "
              f"compilations={engine.decode_compilations or 0}")
    if args.speculate:
        print(f"[serve] speculative: n={args.speculate} "
              f"draft_ratio={args.draft_ratio} "
              f"accept_rate={s['speculative_accept_rate']:.3f} "
              f"tokens_per_round={s['speculative_tokens_per_round']:.2f} "
              f"rounds={s['speculative_rounds']:.0f}")
    if args.deadline or args.adaptive_capacity or injector is not None:
        ok = sum(1 for o in outputs if o.ok)
        print(f"[serve] lifecycle: ok={ok}/{len(outputs)} "
              f"shed={s['shed']:.0f} expired={s['expired']:.0f} "
              f"cancelled={s['cancelled']:.0f} failed={s['failed']:.0f}")
    if args.adaptive_capacity:
        print(f"[serve] capacity controller: "
              f"level_max={s.get('capacity_level_max', 0.0):.0f} "
              f"level_changes={s.get('capacity_level_changes', 0.0):.0f} "
              f"degraded_decode_steps="
              f"{s.get('degraded_decode_steps', 0.0):.0f}")
    if injector is not None:
        fired = ", ".join(f"{f['kind']}@{f['step']}" for f in injector.fired)
        print(f"[serve] faults fired: {fired or 'none'}")
    first = min(outputs, key=lambda o: o.uid)
    print(f"[serve] sample continuation: {first.tokens[-10:].tolist()}")


def main(argv: Optional[Sequence[str]] = None, **engine_overrides: Any) -> ServeRun:
    args = parse_args(argv)
    enable_compile_cache()
    result = run(args, **engine_overrides)
    report(args, result)
    return result


if __name__ == "__main__":
    main()
