"""BENCHMARK.json keeps to its contract, every piece it names is a file of its
own, and a cell is added by new files and entries alone."""
import hashlib
import json
import re
import shutil
from pathlib import Path

import jax
import pytest

from benchlib import harness as H

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_top_level_keys_and_paths():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["command"][:2] == ["python3", "bench/run.py"]
    assert all((ROOT / p).is_dir() for p in BENCH["paths"])
    assert 1 <= BENCH["run_seconds"] <= 51 and isinstance(BENCH["run_seconds"], int)


def test_names_units_and_entry_keys():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"} and NAME.match(c["name"])
        assert (ROOT / c["file"]).is_file() and c["file"].startswith("bench/")
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0 < m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_each_cell_finds_its_pieces_and_reports_enough(cell):
    c = H.resolve(cell, ROOT)
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and c.per_layer
    for m in c.per_layer:
        assert m["moves"] in e2e
        assert callable(H.reader(m["name"], ROOT))
    for m in c.end_to_end:
        if m["name"] != "setup_s":
            assert callable(H.reader(m["name"], ROOT))
    assert c.traffic["kind"] in ("serve", "train") and c.checks


def _digest(root: Path):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file() and "__pycache__" not in p.parts}


def _copy_bench(tmp_path: Path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "*.pyc"))
    return _digest(tmp_path / "bench")


def _tiny_config(kind: str, name: str) -> dict:
    """A configuration at tiny widths: ``mod`` the MoD transformer of
    mod-paper-1b, ``dense`` its vanilla twin, each on the 60m arch."""
    base = {"mod": "mod-paper-1b", "dense": "mod-paper-1b-vanilla"}[kind]
    conf = json.loads((ROOT / f"bench/configs/{base}.json").read_text())
    conf.update(name=name, arch="mod-paper-60m" + ("" if kind == "mod" else "-vanilla"))
    conf["model"].update(n_layers=2, d_model=64, n_heads=2, n_kv_heads=2, head_dim=32, d_ff=128,
                         vocab=256, max_seq_len=128)
    conf["engine"].update(slots=2, ctx=128, prefill_chunk=32)
    return conf


def _add_cell(root: Path, conf: dict, limits: dict) -> str:
    """The configuration, a short backlog mix, the check's limits and the
    cell, as new files and entries; returns the cell's name."""
    name = f"{conf['name']}.short-backlog"
    (root / f"bench/configs/{conf['name']}.json").write_text(json.dumps(conf))
    mix = {"kind": "serve", "requests": 8, "block": 4,
           "prompt": {"dist": "uniform", "min": 8, "max": 40},
           "output": {"dist": "uniform", "min": 4, "max": 12},
           "warmup": {"requests": 2, "output": {"dist": "linspace", "min": 2, "max": 4}},
           "check": {"requests": 2, "rank_steps": 4}}
    (root / "bench/traffic/short-backlog.json").write_text(json.dumps(mix))
    (root / f"bench/checks/{name}.json").write_text(json.dumps({"limits": limits}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": conf["name"], "source": "https://arxiv.org/abs/2404.02258",
                             "file": f"bench/configs/{conf['name']}.json", "reduced": [],
                             "why": "test"})
    bench["workloads"].append({"name": name, "config": conf["name"],
                               "traffic": "short-backlog", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return name


@pytest.mark.parametrize("kind", ["mod", "dense"])
def test_a_cell_is_added_by_new_files_and_entries_alone(tmp_path, kind):
    """A new configuration (the MoD transformer, or its dense twin), traffic
    mix, per-layer metric and cell, as new files plus entries in
    BENCHMARK.json, run at a tiny size on the CPU with no other file edited."""
    import run as R

    before = _copy_bench(tmp_path)
    name = _add_cell(tmp_path, _tiny_config(kind, f"mod-paper-tiny-{kind}"),
                     {"logit_gap_mean": 0.05, "decode_rows_off": 0})
    (tmp_path / "bench/metrics/decode_steps_per_s.py").write_text(
        "def read(run):\n    return run.steps / run.window_s\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["per_layer"].append({"name": "decode_steps_per_s", "unit": "1/s", "better": "higher",
                               "source": "host_clock", "layer": "engine step loop",
                               "moves": "serve_tokens_per_s", "workloads": [name]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = H.resolve(name, tmp_path)
    assert [m["name"] for m in cell.per_layer] == ["decode_steps_per_s"]
    assert H.reader("decode_steps_per_s", tmp_path)(type("R", (), {"steps": 6, "window_s": 2.0})) == 3
    out, checks = R.measure(cell, 3_000_000_019, 3.0, False, jax.devices()[:1], root=tmp_path,
                            strict=False, cache=False)
    assert set(out["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert set(checks) == {"logit_gap_mean", "decode_rows_off"} and out["correct"]
    assert ("route_margin_mean" in out["readings"]) == (kind == "mod")
    after = _digest(tmp_path / "bench")
    assert {k: v for k, v in after.items() if k in before} == before


TOY_FAMILY = '''"""A toy family: the MoD transformer's dense reading under another name,
noting each part of it that the harness uses."""
import dataclasses
from pathlib import Path

from benchlib import harness

base = harness.family("mod_transformer", Path(__file__).resolve().parents[2])
USED = set()


class Spec(base.Spec):
    def leaves(self):
        USED.add("layout")
        return super().leaves()


def spec(conf):
    USED.add("spec")
    return Spec(**dataclasses.asdict(base.spec(conf)))


def serve_check(*args):
    check = base.serve_check(*args)

    def reference():
        USED.add("reference")
        return check()

    return reference


program_config, recorder = base.program_config, base.recorder
decode_work, chunk_work = base.decode_work, base.chunk_work
train_loss, train_step_flops = base.train_loss, base.train_step_flops
serve_faults = base.serve_faults
'''


def test_a_family_is_a_file_found_by_name(tmp_path):
    """A family file dropped into ``bench/families/`` and named by a
    configuration's ``family`` gives the cell its spec, weight layout and
    reference, with no other file edited."""
    import run as R

    before = _copy_bench(tmp_path)
    (tmp_path / "bench/families/toy.py").write_text(TOY_FAMILY)
    conf = dict(_tiny_config("dense", "toy-tiny"), family="toy")
    cell = H.resolve(_add_cell(tmp_path, conf, {"logit_gap_mean": 0.05, "decode_rows_off": 0}),
                     tmp_path)
    assert cell.family == "toy"
    out, checks = R.measure(cell, 3_000_000_031, 3.0, False, jax.devices()[:1], root=tmp_path,
                            strict=False, cache=False)
    assert out["correct"] and set(checks) == {"logit_gap_mean", "decode_rows_off"}
    assert H.family("toy", tmp_path).USED == {"spec", "layout", "reference"}
    after = _digest(tmp_path / "bench")
    assert {k: v for k, v in after.items() if k in before} == before
