import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST be the first lines, before any jax import: jax locks the device
#   count at first init, and the production dry-run needs 512 host devices.

"""Multi-pod dry-run: lower + compile every (architecture × input shape)
cell against the production meshes and extract roofline inputs.

For each cell the appropriate step function is lowered with
ShapeDtypeStruct stand-ins (zero allocation):

  train_4k     -> full train_step (fwd + bwd + AdamW update, donated state)
  prefill_32k  -> forward with last-position logits
  decode_*     -> serve_step (one token against a seq_len KV/SSM cache)

Success criteria: ``.lower().compile()`` succeeds, ``memory_analysis()``
fits per-device HBM, and the collective schedule parses. Records go to a
JSON file consumed by ``benchmarks/roofline.py`` and EXPERIMENTS.md.

Usage:
  python -m repro.launch.dryrun --arch granite-8b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod | --both-meshes]
  python -m repro.launch.dryrun --all --out results/dryrun.json
"""
import argparse
import dataclasses
import json
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.config import (
    SHAPES,
    MeshConfig,
    ModelConfig,
    TrainConfig,
    get_config,
    list_archs,
    shape_applicable,
)
from repro.distributed.sharding import (
    batch_shardings,
    cache_shardings,
    param_shardings,
    state_shardings,
)
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh
from repro.models import api
from repro.train.loop import make_train_step, train_state_specs

ASSIGNED_ARCHS = [
    "granite-8b",
    "mistral-nemo-12b",
    "qwen2-7b",
    "granite-20b",
    "zamba2-2.7b",
    "phi3.5-moe-42b-a6.6b",
    "olmoe-1b-7b",
    "mamba2-1.3b",
    "whisper-tiny",
    "qwen2-vl-7b",
]


def mesh_config(multi_pod: bool, fsdp: bool = True) -> MeshConfig:
    return MeshConfig(pod=2 if multi_pod else 1, data=16, model=16, fsdp=fsdp)


def lower_cell(
    cfg: ModelConfig,
    shape_name: str,
    mesh,
    mcfg: MeshConfig,
    seq_override: Optional[int] = None,
    microbatches: int = 8,
):
    """Build + lower the right step function for one cell. Returns lowered."""
    shape = SHAPES[shape_name]
    if seq_override is not None:
        shape = dataclasses.replace(shape, seq_len=seq_override)
    key = jax.random.PRNGKey(0)
    specs = api.input_specs(cfg, shape)

    if shape.kind == "train":
        # microbatches=8: grad accumulation bounds live activations to an
        # eighth of the per-device batch (v5e HBM budget); the DP grad
        # reduction still happens once per global step.
        tcfg = TrainConfig(
            global_batch=shape.global_batch,
            seq_len=shape.seq_len,
            microbatches=microbatches,
        )
        state_spec = train_state_specs(key, cfg)
        state_sh = state_shardings(state_spec, mesh, mcfg)
        batch_sh = batch_shardings(specs, mesh)
        step = make_train_step(cfg, tcfg)
        jf = jax.jit(
            step,
            in_shardings=(state_sh, batch_sh),
            out_shardings=(state_sh, None),
            donate_argnums=(0,),
        )
        return jf.lower(state_spec, specs)

    params_spec = jax.eval_shape(lambda k: api.init_model(k, cfg), key)
    params_sh = param_shardings(params_spec, mesh, mcfg)

    if shape.kind == "prefill":
        batch_sh = batch_shardings(specs, mesh)

        def fwd(params, batch):
            logits, _ = api.model_forward(params, cfg, batch, last_only=True)
            return logits

        jf = jax.jit(fwd, in_shardings=(params_sh, batch_sh))
        return jf.lower(params_spec, specs)

    # decode
    cache_spec = specs["caches"]
    cache_sh = cache_shardings(cache_spec, mesh, cfg, shape.global_batch)
    tok_sh = batch_shardings({"token": specs["token"], "pos": specs["pos"]}, mesh)

    def serve_step(params, caches, token, pos):
        return api.model_decode(params, caches, cfg, token, pos)

    jf = jax.jit(
        serve_step,
        in_shardings=(params_sh, cache_sh, tok_sh["token"], tok_sh["pos"]),
        out_shardings=(None, cache_sh, None),
        donate_argnums=(1,),
    )
    return jf.lower(params_spec, cache_spec, specs["token"], specs["pos"])


def run_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    fsdp: bool = True,
    collect_hlo: bool = True,
    cfg_override: Optional[ModelConfig] = None,
    microbatches: int = 8,
) -> Dict[str, Any]:
    cfg = cfg_override or get_config(arch)
    shape = SHAPES[shape_name]
    rec: Dict[str, Any] = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "family": cfg.family,
    }
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        rec["status"] = "skipped"
        rec["reason"] = why
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    mcfg = mesh_config(multi_pod, fsdp)
    t0 = time.time()
    # ambient mesh lets model-internal sharding constraints (scan carries)
    # resolve bare PartitionSpecs — see distributed.sharding.constrain_batch
    with jax.set_mesh(mesh):
        lowered = lower_cell(cfg, shape_name, mesh, mcfg, microbatches=microbatches)
        rec["lower_s"] = round(time.time() - t0, 2)
        t0 = time.time()
        compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t0, 2)

    ma = compiled.memory_analysis()
    rec["memory"] = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
    }
    ca = compiled.cost_analysis() or {}
    rec["cost"] = {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "transcendentals": float(ca.get("transcendentals", 0.0)),
    }
    if collect_hlo:
        txt = compiled.as_text()
        rec["collectives"] = {
            k: v
            for k, v in hlo_analysis.analyze_collectives(txt).items()
            if k != "details"
        }
        rec["trip_counts"] = hlo_analysis.loop_trip_counts(txt)
    rec["status"] = "ok"
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="architecture id (see configs/)")
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--all", action="store_true", help="all assigned arch x shape cells")
    ap.add_argument("--multi-pod", action="store_true", help="2x16x16 mesh (512 chips)")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--out", default=None, help="write JSON records here")
    args = ap.parse_args()

    cells = []
    archs = ASSIGNED_ARCHS if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for mp in meshes:
        for a in archs:
            for s in shapes:
                cells.append((a, s, mp))

    records = []
    failures = 0
    for a, s, mp in cells:
        label = f"{a} × {s} × {'2x16x16' if mp else '16x16'}"
        try:
            rec = run_cell(a, s, mp, fsdp=not args.no_fsdp)
        except Exception as e:
            traceback.print_exc()
            rec = {"arch": a, "shape": s, "mesh": "2x16x16" if mp else "16x16",
                   "status": "FAILED", "error": f"{type(e).__name__}: {e}"}
            failures += 1
        records.append(rec)
        if rec["status"] == "ok":
            m = rec["memory"]
            print(
                f"[dryrun] {label:56s} OK  compile={rec['compile_s']:7.1f}s "
                f"args/dev={m['argument_bytes']/2**30:7.2f}GiB "
                f"temp/dev={m['temp_bytes']/2**30:7.2f}GiB "
                f"coll/dev={rec.get('collectives', {}).get('total_wire_bytes_per_device', 0)/2**30:7.3f}GiB"
            )
        elif rec["status"] == "skipped":
            print(f"[dryrun] {label:56s} SKIP ({rec['reason']})")
        else:
            print(f"[dryrun] {label:56s} FAIL ({rec['error']})")
        sys.stdout.flush()

    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(records, f, indent=1)
        print(f"[dryrun] wrote {len(records)} records to {args.out}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
