"""Serving cells on a (1, 4) mesh of four CPU devices, for
``test_bench_mesh.py``. JAX fixes its device count when it starts, so this
runs in a process of its own:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python bench/tests/mesh_child.py <copy of the checkout> <out.json>

In the copy (``BENCHMARK.json`` and ``bench/``) it adds, as new files and
entries alone, a tiny MoD and a tiny dense configuration whose engine names
the mesh, each with a four-chip cell on the contiguous pool, and runs each
cell as ``bench/run.py`` does: as it stands, and with a served token altered
where it is produced. It also makes each configuration's weights on one
device and on the mesh. What it saw goes to ``out.json``.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH.parent / "src"), str(BENCH), str(BENCH / "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402
import numpy as np  # noqa: E402

import run as R  # noqa: E402
import tiny  # noqa: E402
from benchlib import harness as H  # noqa: E402
from benchlib import serve_cell as SC  # noqa: E402
from benchlib import weights as W  # noqa: E402

MESH = {"data": 1, "model": 4}
SEED = 3_000_000_041
LIMITS = {"mod": {"logit_gap_mean": 0.05, "route_margin_mean": 0.05, "decode_margin_mean": 0.05,
                  "decode_rows_off": 0},
          "dense": {"logit_gap_mean": 0.05, "decode_rows_off": 0}}


def config(kind: str) -> dict:
    """A tiny configuration of the MoD transformer (``mod``) or its dense
    twin, served by one engine over the mesh from the contiguous pool."""
    base = {"mod": "mod-paper-1b", "dense": "mod-paper-1b-vanilla"}[kind]
    conf = json.loads((BENCH / "configs" / f"{base}.json").read_text())
    conf.update(name=f"mod-paper-tiny-{kind}-mesh",
                arch="mod-paper-60m" + ("" if kind == "mod" else "-vanilla"))
    conf["model"].update(n_layers=2, d_model=64, n_heads=2, n_kv_heads=2, head_dim=32, d_ff=128,
                         vocab=256, max_seq_len=128)
    conf["engine"] = {"slots": 4, "ctx": 128, "page_size": None, "prefill_chunk": 32,
                      "policy": "mod_aware", "mesh": MESH}
    return conf


def add_cell(root: Path, conf: dict, limits: dict, chips: int = 4) -> str:
    """The configuration, a short backlog mix, the check's limits and a cell
    on ``chips`` chips, as new files and entries; returns the cell's name."""
    name = f"{conf['name']}.short-backlog"
    (root / f"bench/configs/{conf['name']}.json").write_text(json.dumps(conf))
    mix = {"kind": "serve", "requests": 12, "block": 4,
           "prompt": {"dist": "uniform", "min": 8, "max": 40},
           "output": {"dist": "uniform", "min": 4, "max": 12},
           "warmup": {"requests": 4, "output": {"dist": "linspace", "min": 2, "max": 4}},
           "check": {"requests": 2, "rank_steps": 4}}
    (root / "bench/traffic/short-backlog.json").write_text(json.dumps(mix))
    (root / f"bench/checks/{name}.json").write_text(json.dumps({"limits": limits}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": conf["name"], "source": "https://arxiv.org/abs/2404.02258",
                             "file": f"bench/configs/{conf['name']}.json", "reduced": [],
                             "why": "test"})
    bench["workloads"].append({"name": name, "config": conf["name"],
                               "traffic": "short-backlog", "chips": chips, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return name


def run_cell(root: Path, cell, devices, **kw) -> dict:
    out, checks = R.measure(cell, SEED, 3.0, False, devices, root=root, strict=False,
                            cache=False, **kw)
    return {"correct": out["correct"], "checks": checks, "readings": out["readings"],
            "metrics": sorted(out["metrics"]), "device": out["device"]}


def weights(root: Path, conf: dict, devices) -> dict:
    """The weights on one device and on the mesh, both the program's (with
    the shardings the engine places them with) and the reference's."""
    from repro.config import MeshConfig
    from repro.distributed.sharding import param_shardings

    family = H.family(H.DEFAULT_FAMILY, root)
    s = family.spec(conf)
    mesh = SC.mesh_of(conf["engine"], devices)
    key = W.seed_key(SEED)
    out = {}
    for what, f32, shardings in (("program", False, SC.program_shardings(s, mesh)),
                                 ("reference", True, W.spread(s, mesh))):
        one = jax.tree_util.tree_leaves_with_path(W.params_fn(s, f32)(key))
        split = W.params_fn(s, f32, shardings)(key)
        if what == "program":
            mcfg = MeshConfig(pod=1, data=1, model=4, fsdp=False)
            same = jax.tree.map(lambda x, sh: x.sharding == sh, split,
                                param_shardings(split, mesh, mcfg))
            out["program_as_the_engine_places"] = all(jax.tree.leaves(same))
        leaves = []
        for (path, a), b in zip(one, jax.tree.leaves(split)):
            shards = [sh.data for sh in b.addressable_shards]
            leaves.append({
                "path": jax.tree_util.keystr(path), "size": int(a.size),
                "bits_equal": np.asarray(a).tobytes() == np.asarray(b).tobytes(),
                "devices": sorted({str(sh.device) for sh in b.addressable_shards}),
                "replicated": b.sharding.is_fully_replicated,
                "shard_sizes": [int(x.size) for x in shards],
            })
        out[what] = leaves
    return out


def main(root: str, out_path: str) -> int:
    root = Path(root)
    devices = jax.devices()[:4]
    assert len(devices) == 4, devices
    result = {}
    for kind in ("mod", "dense"):
        conf = config(kind)
        cell = H.resolve(add_cell(root, conf, LIMITS[kind]), root)
        result[kind] = {"program": run_cell(root, cell, devices),
                        "token_altered": run_cell(root, cell, devices, fault=tiny.altered),
                        "weights": weights(root, conf, devices)}
    Path(out_path).write_text(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], sys.argv[2]))
