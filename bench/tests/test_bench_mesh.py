"""A serving cell on the chips its workload names: a configuration whose
engine names a mesh runs, by new files and entries alone, as one engine over
a (1, 4) mesh of four CPU devices (``mesh_child.py``, in a process of its
own); its weights are made on the mesh, equal to one device's bit for bit;
a mesh that is not the cell's chips is refused; and shares of peaks are
shares of every chip the cell holds."""
import json
import os
import subprocess
import sys
import types
from pathlib import Path

import pytest

import mesh_child as MC
from benchlib import harness as H
from benchlib.peaks import PEAKS, peaks_for
from benchlib.trace import TraceData
from test_bench_layout import _copy_bench, _digest

ROOT = Path(__file__).resolve().parents[2]
KINDS = ["mod", "dense"]


@pytest.fixture(scope="module")
def mesh_run(tmp_path_factory):
    """(what ``mesh_child.py`` saw, the digests of ``bench/`` before and
    after) in a copy of the checkout."""
    root = tmp_path_factory.mktemp("mesh")
    before = _copy_bench(root)
    out = root / "out.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, str(Path(MC.__file__)), str(root), str(out)],
                       env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(out.read_text()), before, _digest(root / "bench")


@pytest.mark.parametrize("kind", KINDS)
def test_a_mesh_cell_is_added_by_new_files_and_entries_alone(mesh_run, kind):
    """A four-chip cell whose configuration names a (1, 4) mesh runs on the
    contiguous pool, correct, with no file of ``bench/`` edited."""
    result, before, after = mesh_run
    run = result[kind]["program"]
    assert run["correct"], run["checks"]
    assert set(run["checks"]) == set(MC.LIMITS[kind])
    assert run["metrics"] == ["serve_tokens_per_s", "setup_s"]
    assert run["device"]["count"] == 4
    assert {k: v for k, v in after.items() if k in before} == before


@pytest.mark.parametrize("kind", KINDS)
def test_a_token_altered_on_the_mesh_fails(mesh_run, kind):
    run = mesh_run[0][kind]["token_altered"]
    assert not run["correct"]
    assert not run["checks"]["logit_gap_mean"]["value"] <= MC.LIMITS[kind]["logit_gap_mean"]


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("what", ["program", "reference"])
def test_weights_made_on_the_mesh_are_one_devices_in_shards(mesh_run, kind, what):
    """Bit for bit the one-device weights; every leaf that is split holds a
    quarter on each device, and no device holds the whole model. The
    program's are placed as the engine places them, so it moves nothing."""
    w = mesh_run[0][kind]["weights"]
    leaves = w[what]
    assert all(leaf["bits_equal"] for leaf in leaves)
    assert all(len(leaf["devices"]) == 4 for leaf in leaves)
    for leaf in leaves:
        if not leaf["replicated"]:
            assert leaf["shard_sizes"] == [leaf["size"] // 4] * 4, leaf["path"]
    whole = sum(leaf["size"] for leaf in leaves)
    held = sum(leaf["shard_sizes"][0] for leaf in leaves)
    assert held < whole / 3
    if what == "program":
        assert w["program_as_the_engine_places"]


@pytest.mark.parametrize("mesh,chips", [({"data": 1, "model": 4}, 1),
                                        ({"data": 2, "model": 1}, 4), (None, 4)])
def test_a_mesh_that_is_not_the_cells_chips_is_refused(tmp_path, mesh, chips):
    _copy_bench(tmp_path)
    conf = MC.config("dense")
    if mesh is None:
        del conf["engine"]["mesh"]
    else:
        conf["engine"]["mesh"] = mesh
    name = MC.add_cell(tmp_path, conf, MC.LIMITS["dense"], chips=chips)
    with pytest.raises(ValueError, match=name):
        H.resolve(name, tmp_path)


def _run(chips: int):
    """One traced run, read against the peaks of ``chips`` chips:
    every device ran ``jit_step`` for the same second."""
    steps = [("jit_step(7)", 0.0, 1e9)]
    return types.SimpleNamespace(
        decode_work=[(3e12, 2e9), (4e12, 3e9)], chunk_flops=[5e12], window_s=2.0,
        td=TraceData([[]] * chips, [steps] * chips, []),
        red=types.SimpleNamespace(lo=0.0, hi=2e9),
        peaks=peaks_for("TPU v5 lite", chips), steps=10, step_flops=6e12)


@pytest.mark.parametrize("metric", ["decode_mfu", "decode_step_roofline", "train_mfu"])
def test_shares_of_peaks_are_of_every_chip(metric):
    """The same run read on four chips is a quarter of its reading on one,
    and on one chip the peaks are the table's."""
    read = H.reader(metric, ROOT)
    one, four = read(_run(1)), read(_run(4))
    assert 0 < one <= 100
    assert four == pytest.approx(one / 4, rel=1e-12)
    assert peaks_for("TPU v5 lite") == PEAKS["TPU v5 lite"]
