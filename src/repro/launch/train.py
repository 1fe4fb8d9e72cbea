"""Production training driver.

Single-process form of the multi-host launcher: builds the mesh, shards the
train state per distributed.sharding rules, and runs the fault-tolerant
Trainer (auto-resume, async checkpoints, NaN circuit breaker). On a real
TPU pod slice the same file runs under ``jax.distributed.initialize()``
(see launch/run_multipod.sh); on this CPU container it runs 1x1.

  PYTHONPATH=src python -m repro.launch.train --arch mod-paper-60m \
      --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

``main(argv)`` returns the last step's metrics (``chip_smoke.py`` drives it).
"""
from __future__ import annotations

import argparse
import dataclasses
from typing import Dict, Optional, Sequence

import jax
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.config import MeshConfig, OptimConfig, TrainConfig, get_config, smoke_config
from repro.data.loader import ShardedLoader
from repro.data.synthetic import SyntheticLM
from repro.distributed.sharding import batch_shardings, state_shardings
from repro.launch.mesh import make_mesh
from repro.train.loop import Trainer, make_train_step
from repro.utils import enable_compile_cache


def main(argv: Optional[Sequence[str]] = None) -> Dict[str, float]:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mod-paper-60m")
    ap.add_argument("--smoke", action="store_true", help="reduced config of the arch family")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--data-axis", type=int, default=0, help="0 = all devices")
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--fsdp", action="store_true")
    ap.add_argument("--dtype", default=None, help="override model dtype (e.g. float32)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    if args.dtype:
        cfg = dataclasses.replace(cfg, dtype=args.dtype)

    n_dev = jax.device_count()
    data_ax = args.data_axis or max(1, n_dev // max(args.model_axis, 1))
    mcfg = MeshConfig(pod=1, data=data_ax, model=args.model_axis, fsdp=args.fsdp)
    mesh = make_mesh(mcfg)

    tcfg = TrainConfig(
        global_batch=args.batch,
        seq_len=args.seq,
        microbatches=args.microbatches,
        optim=OptimConfig(lr=args.lr, warmup_steps=max(10, args.steps // 20),
                          total_steps=args.steps),
        ckpt_dir=args.ckpt_dir,
        log_every=10,
        ckpt_every=max(50, args.steps // 4),
    )

    loader = ShardedLoader(
        SyntheticLM(cfg.vocab, args.seq, seed=tcfg.seed),
        args.batch,
        mesh=mesh,
        batch_axes=tuple(a for a in ("pod", "data") if a in mesh.shape),
    )

    from repro.distributed.sharding import shard_ctx

    spmd = shard_ctx(mesh, fsdp=args.fsdp)
    with jax.set_mesh(mesh):
        step_raw = make_train_step(cfg, tcfg, spmd=spmd)
        # shard the state according to the rules; metrics replicated
        import jax.numpy as jnp

        from repro.train.loop import make_train_state, train_state_specs

        state_spec = train_state_specs(jax.random.PRNGKey(tcfg.seed), cfg)
        st_sh = state_shardings(state_spec, mesh, mcfg)
        jitted = jax.jit(step_raw, in_shardings=(st_sh, None), out_shardings=(st_sh, None),
                         donate_argnums=(0,))

        ckpt = CheckpointManager(tcfg.ckpt_dir, keep=tcfg.keep_ckpts, async_save=tcfg.async_ckpt)
        trainer = Trainer(cfg, tcfg, loader, jitted_step=jitted, ckpt=ckpt)

        from repro.utils import flatten_dict

        flat_sh = flatten_dict(st_sh)

        def sharding_fn(path, arr):  # elastic reshard-on-load
            return flat_sh.get(path)

        state = trainer.init_or_resume(sharding_fn)
        start = int(state["step"])
        state, metrics = trainer.run(state, max(0, args.steps - start))
        trainer.ckpt.save(int(state["step"]), state, wait=True)
        print(f"[train] done at step {int(state['step'])}: "
              f"ce={metrics.get('ce', float('nan')):.4f}")
    loader.close()
    return metrics


if __name__ == "__main__":
    main()
