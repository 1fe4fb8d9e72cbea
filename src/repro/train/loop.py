"""Train step factory + fault-tolerant training loop.

``make_train_step`` builds the pure step function (value_and_grad -> clip ->
cosine LR -> AdamW), with optional gradient accumulation over microbatches
(a lax.scan whose carry is the f32 grad accumulator, so the implicit DP
all-reduce happens once per *global* step, not once per microbatch).

``Trainer`` wires it to the data loader and checkpoint manager:
auto-resume from the newest readable checkpoint, periodic async saves,
NaN-loss circuit breaker, and a per-step host heartbeat (the hook where a
multi-host deployment plugs straggler detection — see DESIGN.md §4).
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.config import ModelConfig, TrainConfig
from repro.models import api
from repro.optim import adamw_init, adamw_update, clip_by_global_norm, cosine_schedule

State = Dict[str, Any]


def make_train_state(key, cfg: ModelConfig) -> State:
    params = api.init_model(key, cfg)
    return {"params": params, "opt": adamw_init(params), "step": jnp.zeros((), jnp.int32)}


def train_state_specs(key, cfg: ModelConfig) -> State:
    """ShapeDtypeStruct pytree of the train state (no allocation)."""
    return jax.eval_shape(lambda k: make_train_state(k, cfg), key)


def make_train_step(
    cfg: ModelConfig, tcfg: TrainConfig, spmd=None
) -> Callable[[State, Dict[str, jax.Array]], Tuple[State, Dict[str, jax.Array]]]:
    """``spmd`` (``distributed.sharding.ShardCtx``) makes every MoD site's
    routing decision + dispatch run per data shard inside shard_map while
    dense blocks / aux losses stay under GSPMD — pass it when the step is
    jitted over a real mesh (launch/train.py).

    Raises ValueError up front where the step would run the fused-dispatch
    kernels compiled: they run only in interpret mode (CPU)."""
    from repro.kernels.flash_attention import FUSED_NOT_COMPILED
    from repro.kernels.ops import on_cpu
    from repro.models.blocks import fused_dispatch_supported

    if cfg.mod.enabled and fused_dispatch_supported(cfg, spmd) and not on_cpu():
        raise ValueError(FUSED_NOT_COMPILED)
    ocfg = tcfg.optim

    def loss_fn(params, batch, step):
        rng = jax.random.fold_in(jax.random.PRNGKey(tcfg.seed), step)
        return api.model_loss(params, cfg, batch, rng=rng, spmd=spmd)

    def _split_micro(x, n):
        # M-RoPE positions are (3, B, S): split axis 1; everything else
        # splits its leading batch axis.
        if x.ndim >= 2 and x.shape[0] == 3 and x.shape[1] % n == 0 and x.shape[0] != n:
            return jnp.swapaxes(x.reshape((3, n, x.shape[1] // n) + x.shape[2:]), 0, 1)
        return x.reshape((n, x.shape[0] // n) + x.shape[1:])

    def grads_of(params, batch, step):
        if tcfg.microbatches <= 1:
            (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch, step)
            return loss, aux, grads

        def micro(carry, mb):
            acc, loss_sum = carry
            (loss, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb, step)
            acc = jax.tree.map(lambda a, gg: a + gg.astype(jnp.float32), acc, g)
            return (acc, loss_sum + loss), aux

        n = tcfg.microbatches
        mbs = jax.tree.map(lambda x: _split_micro(x, n), batch)
        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (acc, loss_sum), aux = jax.lax.scan(micro, (zeros, jnp.zeros(())), mbs)
        grads = jax.tree.map(lambda a: a / n, acc)
        return loss_sum / n, jax.tree.map(lambda x: x[-1], aux), grads

    def step_fn(state: State, batch) -> Tuple[State, Dict[str, jax.Array]]:
        loss, aux, grads = grads_of(state["params"], batch, state["step"])
        with jax.named_scope("optimizer"):
            grads, gnorm = clip_by_global_norm(grads, ocfg.clip_norm)
            lr = cosine_schedule(state["step"], ocfg)
            params, opt = adamw_update(state["params"], grads, state["opt"], ocfg, lr)
        metrics = {k: v for k, v in aux.items()}
        metrics.update({"grad_norm": gnorm, "lr": lr, "loss": loss})
        new_state = {"params": params, "opt": opt, "step": state["step"] + 1}
        return new_state, metrics

    return step_fn


class Trainer:
    """Fault-tolerant loop: resume -> step -> heartbeat -> checkpoint."""

    def __init__(
        self,
        cfg: ModelConfig,
        tcfg: TrainConfig,
        loader,
        jitted_step: Optional[Callable] = None,
        ckpt: Optional[CheckpointManager] = None,
        log_fn: Callable[[str], None] = print,
    ):
        self.cfg, self.tcfg, self.loader = cfg, tcfg, loader
        self.step_fn = jitted_step or jax.jit(make_train_step(cfg, tcfg), donate_argnums=(0,))
        self.ckpt = ckpt or CheckpointManager(
            tcfg.ckpt_dir, keep=tcfg.keep_ckpts, async_save=tcfg.async_ckpt
        )
        self.log = log_fn
        self.heartbeats: list = []  # (step, wall_time) — straggler telemetry

    def init_or_resume(self, sharding_fn=None) -> State:
        restored = self.ckpt.restore_latest(sharding_fn)
        if restored is not None:
            step, state = restored
            self.log(f"[trainer] resumed from checkpoint step {step}")
            state["step"] = jnp.asarray(state["step"])
            if hasattr(self.loader, "step"):
                self.loader.step = int(step)
            return state
        self.log("[trainer] fresh init")
        return make_train_state(jax.random.PRNGKey(self.tcfg.seed), self.cfg)

    def run(self, state: State, n_steps: int) -> Tuple[State, Dict[str, float]]:
        last_metrics: Dict[str, float] = {}
        start_step = int(state["step"])
        for i in range(n_steps):
            batch = next(iter(self.loader)) if not hasattr(self.loader, "__next__") else next(self.loader)
            t0 = time.time()
            state, metrics = self.step_fn(state, batch)
            loss = float(metrics["loss"])
            self.heartbeats.append((start_step + i, time.time() - t0))
            if not np.isfinite(loss):
                # circuit breaker: dump diagnostics, stop before corrupting
                # the checkpoint chain with NaN params.
                self.ckpt.wait()
                raise FloatingPointError(f"non-finite loss at step {start_step + i}")
            step_no = start_step + i + 1
            if step_no % self.tcfg.log_every == 0:
                self.log(
                    f"[trainer] step {step_no} loss={loss:.4f} "
                    f"ce={float(metrics.get('ce', loss)):.4f} "
                    f"gnorm={float(metrics['grad_norm']):.3f}"
                )
            if step_no % self.tcfg.ckpt_every == 0:
                self.ckpt.save(step_no, state)
            last_metrics = {k: float(np.asarray(v).mean()) for k, v in metrics.items()}
        self.ckpt.wait()
        return state, last_metrics
