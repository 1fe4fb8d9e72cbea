"""GPipe-style pipeline parallelism over a mesh axis (shard_map + ppermute).

Maps pipeline stages onto the "pod" axis as an alternative to DP-over-pod
(MeshConfig.pp_stages): each stage holds its own layer shard; microbatches
stream through with ``lax.ppermute`` hops between neighbours. The schedule
is the classic GPipe fill-run-drain loop expressed as a single lax.scan of
length (n_micro + n_stages - 1); bubble fraction = (S-1)/(M+S-1).

This composes with everything else in the framework: inside a stage the
layers still use the TP/FSDP rules over ("data", "model"), since shard_map
here maps ONLY the pipeline axis.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_forward(
    stage_fn: Callable[[Any, jax.Array, jax.Array], jax.Array],
    params_stacked: Any,  # leaves with leading [n_stages] dim
    x_micro: jax.Array,  # (n_micro, B_mb, ...) microbatched inputs
    mesh: Mesh,
    axis: str = "pod",
) -> jax.Array:
    """Run x through n_stages sequential stages living on `axis`.

    stage_fn(stage_params, x, stage_index) -> y, applied by every device to
    whatever microbatch currently resides on it. Returns outputs in
    microbatch order (as produced by the last stage).
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    total = n_micro + n_stages - 1

    def per_stage(params_local, x_local):
        # params_local: this stage's params (leading dim 1 from shard_map)
        params_here = jax.tree.map(lambda a: a[0], params_local)
        stage = jax.lax.axis_index(axis)
        x_local = x_local[0]  # (n_micro, B_mb, ...)
        buf = jnp.zeros_like(x_local[0])
        outs = jnp.zeros_like(x_local)

        def step(carry, t):
            buf, outs = carry
            # stage 0 injects microbatch t (if any remain); others take
            # the neighbour's output from the previous tick
            inject = jnp.where(t < n_micro, t, n_micro - 1)
            incoming = jnp.where(stage == 0, x_local[inject], buf)
            y = stage_fn(params_here, incoming, stage)
            # pass to the next stage; the last stage's output is collected
            nxt = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)]
            )
            out_t = t - (n_stages - 1)
            take = jnp.clip(out_t, 0, n_micro - 1)
            outs = jax.lax.cond(
                (out_t >= 0) & (stage == n_stages - 1),
                lambda o: o.at[take].set(y),
                lambda o: o,
                outs,
            )
            return (nxt, outs), None

        (buf, outs), _ = jax.lax.scan(step, (buf, outs), jnp.arange(total))
        # broadcast results from the last stage to all (so output is
        # replicated over the pipeline axis, matching out_specs)
        outs = jax.lax.ppermute(
            outs, axis, [((n_stages - 1 + i) % n_stages, i) for i in range(n_stages)]
        ) if n_stages > 1 else outs
        return outs[None]

    spec_p = jax.tree.map(lambda _: P(axis), params_stacked)
    fn = jax.shard_map(
        per_stage,
        mesh=mesh,
        in_specs=(spec_p, P(axis)),
        out_specs=P(axis),
        check_vma=False,
    )
    # replicate microbatches to every stage (each consumes what it needs)
    x_rep = jnp.broadcast_to(x_micro[None], (n_stages,) + x_micro.shape)
    out = fn(params_stacked, x_rep)
    return out[0]


def bubble_fraction(n_micro: int, n_stages: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)
