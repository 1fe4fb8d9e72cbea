"""Compile-only sizing: each cell's programs compiled for a described TPU v5e
(no chip attached), with XLA's memory analysis of each.

    JAX_PLATFORMS=cpu python3 bench/sizing.py [--slots 16 8] [--batch 8 16 32]

For the serving configuration it compiles the engine's paged decode step and
its prefill chunk at the configuration's context for each slot count; for
training the donated train step for each batch size. Nothing is allocated at
full size: every argument is a shape. A program fits when its arguments, its
outputs not aliased to them and its temporaries together stay under the
chip's 16 GiB (the process holds nothing else of that size while it runs);
the compiler here does not refuse every program that does not.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from benchlib import harness  # noqa: E402

GB = 1e9
HBM = 16 * 2**30  # one TPU v5e


def _specs(tree, sh):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh), tree)


def _report(name: str, compiled) -> float:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes
             - m.alias_size_in_bytes)
    print(f"{name}: arguments {m.argument_size_in_bytes / GB:.2f} GB, outputs "
          f"{m.output_size_in_bytes / GB:.2f} GB (aliased {m.alias_size_in_bytes / GB:.2f}), "
          f"temporaries {m.temp_size_in_bytes / GB:.2f} GB; together {total / GB:.2f} GB: "
          f"{'fits' if total < HBM else 'does not fit'}", flush=True)
    return total


def _program_config(conf):
    return harness.family(conf.get("family", harness.DEFAULT_FAMILY)).program_config(conf)


def serve_programs(conf, slots: int, sh) -> float:
    """The paged decode step and the prefill chunk, as the engine builds them
    (``repro.serve.engine``: ``_build_step_fn`` and ``_make_chunk``)."""
    from repro.models import api
    from repro.serve import cache as CA

    cfg = _program_config(conf)
    e = conf["engine"]
    ctx, page, chunk = int(e["ctx"]), int(e["page_size"]), int(e["prefill_chunk"])
    full = api.make_caches(cfg, slots, ctx, specs=True)
    flat, treedef = jax.tree_util.tree_flatten(full)
    axes = jax.tree_util.tree_leaves(CA._batch_axes(cfg, slots, ctx))
    paged = CA._paged_leaf_axes(cfg, slots, ctx)
    paged_ids = sorted(paged)
    resid_ids = [i for i in range(len(flat)) if i not in paged]
    spec = CA.PoolSpec(tuple(paged_ids), tuple(paged[i] for i in paged_ids), tuple(resid_ids),
                       treedef, page, "xla", axes=tuple(axes))
    n_pages = slots * (ctx // page) + 2

    def page_leaf(x, ax):
        shape = list(x.shape)
        del shape[ax]
        shape[ax] = page
        shape.insert(ax, n_pages)
        return jax.ShapeDtypeStruct(tuple(shape), x.dtype, sharding=sh)

    pages = [page_leaf(flat[i], paged[i]) for i in paged_ids]
    resid = [jax.ShapeDtypeStruct(flat[i].shape, flat[i].dtype, sharding=sh) for i in resid_ids]
    params = _specs(jax.eval_shape(lambda k: api.init_model(k, cfg), jax.random.PRNGKey(0)), sh)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=sh)

    def step(p, pages, scales, resid, table, t, pos, act):
        caches = CA.paged_materialize_q(spec, pages, scales, resid, table)
        logits, new_caches, aux = api.model_decode(p, caches, cfg, t, pos, act)
        new_pages, new_resid, new_scales = CA.paged_writeback_q(
            spec, new_caches, pages, scales, table, pos)
        return logits, new_pages, new_resid, new_scales, aux

    act = jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=sh)
    dec = jax.jit(step).lower(params, pages, [], resid, i32(slots, ctx // page),
                              i32(slots, 1), i32(slots), act).compile()
    total = _report(f"decode step, {slots} slots, ctx {ctx}", dec)
    one = _specs(api.make_caches(cfg, 1, ctx, specs=True), sh)
    chunk_fn = jax.jit(lambda p, c, toks, start, nv: api.model_prefill_chunk(
        p, cfg, c, toks, start, nv))
    s32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=sh)
    _report(f"prefill chunk of {chunk}", chunk_fn.lower(params, one, i32(1, chunk), s32,
                                                         s32).compile())
    return total


def train_program(conf, mix, batch: int, sh) -> float:
    from repro.config import OptimConfig, TrainConfig
    from repro.train.loop import make_train_step, train_state_specs

    cfg = _program_config(conf)
    S = int(mix["seq_len"])
    tcfg = TrainConfig(global_batch=batch, seq_len=S, optim=OptimConfig())
    state = _specs(train_state_specs(jax.random.PRNGKey(0), cfg), sh)
    b = {k: jax.ShapeDtypeStruct((batch, S), jnp.int32, sharding=sh) for k in ("tokens", "labels")}
    step = jax.jit(make_train_step(cfg, tcfg), donate_argnums=(0,))
    return _report(f"train step, batch {batch} x {S}", step.lower(state, b).compile())


def main() -> int:
    from jax.experimental import topologies

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--slots", type=int, nargs="*", default=[16, 8])
    ap.add_argument("--batch", type=int, nargs="*", default=[8, 16, 32])
    args = ap.parse_args()
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    sh = SingleDeviceSharding(topo.devices[0])
    read = lambda p: json.loads((BENCH / p).read_text())
    serve = read("configs/mod-paper-1b.json")
    for n in args.slots:
        serve_programs(serve, n, sh)
    train, mix = read("configs/mod-paper-220m.json"), read("traffic/train-seq2048.json")
    for b in args.batch:
        train_program(train, mix, b, sh)
    return 0


if __name__ == "__main__":
    sys.exit(main())
