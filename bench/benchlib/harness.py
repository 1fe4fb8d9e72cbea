"""Finding a cell's pieces by name, checking the device, printing the result.

Everything a cell needs is found from ``BENCHMARK.json`` by name, so a later
change adds a cell, a traffic mix, a configuration or a metric by adding files
and entries, never by editing this one:

- the configuration: the file its ``configs`` entry names;
- the model family: ``bench/families/<family>.py``, named by the
  configuration file's ``family`` (``DEFAULT_FAMILY``, the MoD transformer,
  where it names none). It supplies all that depends on the architecture:
  the spec read from the file (``spec``), the program's config
  (``program_config``), the weight leaves (``spec.leaves()``), the float32
  reference, the FLOP and byte counts, and the check's numbers;
- the traffic mix: ``bench/traffic/<traffic>.json`` (its ``kind`` picks the
  runner: ``serve`` or ``train``);
- each metric: ``bench/metrics/<name>.py``, a reader with ``read(run)``;
- the limits of the cell's check: ``bench/checks/<workload>.json``;
- the chips: a serving configuration's ``engine.mesh`` (``{"data": d,
  "model": m}``) serves the cell from one engine over a mesh of ``d * m``
  chips, which must be the workload's ``chips``; without it, one chip.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from pathlib import Path
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
DEFAULT_FAMILY = "mod_transformer"


class NoAccelerator(RuntimeError):
    pass


@dataclasses.dataclass
class Cell:
    workload: Dict[str, Any]
    config: Dict[str, Any]  # the configuration file's contents
    traffic: Dict[str, Any]  # the traffic file's contents
    end_to_end: List[Dict[str, Any]]  # metrics this cell reports with --trace 0
    per_layer: List[Dict[str, Any]]  # and with --trace 1
    checks: Dict[str, float]  # the limit of each number the check compares

    @property
    def name(self) -> str:
        return self.workload["name"]

    @property
    def family(self) -> str:
        return self.config.get("family", DEFAULT_FAMILY)

    @property
    def chips(self) -> int:
        """The chips the configuration's mesh spans (1 without one)."""
        mesh = self.config.get("engine", {}).get("mesh") or {"data": 1, "model": 1}
        return int(mesh["data"]) * int(mesh["model"])


def _reports(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have {sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if m["moves"] in moved and _reports(m, name)]
    checks = json.loads((root / "bench" / "checks" / f"{name}.json").read_text())["limits"]
    cell = Cell(w, config, traffic, e2e, per_layer, checks)
    if cell.chips != int(w["chips"]):
        raise ValueError(f"workload {name!r} asks for {w['chips']} chips, but its "
                         f"configuration's engine mesh spans {cell.chips}")
    return cell


def reader(metric: str, root: Path = ROOT) -> Callable[[Any], Optional[float]]:
    """``read(run)`` of ``bench/metrics/<metric>.py``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def family(name: str, root: Path = ROOT) -> ModuleType:
    """The model family module ``bench/families/<name>.py``, loaded once per
    file."""
    path = (root / "bench" / "families" / f"{name}.py").resolve()
    if path not in _FAMILIES:
        spec = importlib.util.spec_from_file_location(f"bench_family_{name}", path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = mod  # dataclasses look their module up
        spec.loader.exec_module(mod)
        _FAMILIES[path] = mod
    return _FAMILIES[path]


_FAMILIES: Dict[Path, ModuleType] = {}


def check_device(chips: int):
    """The devices, or NoAccelerator where JAX finds no TPU or too few."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(f"no TPU: JAX platform is {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX finds {len(devices)}")
    return devices[:chips]


def enable_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent cache at ``$JAX_COMPILATION_CACHE_DIR`` or the fixed
    ``.jax_cache/`` of the checkout, keeping every program however small or
    quick to compile."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(root / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, and persistent
    cache hits and misses, from JAX's own monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0

        def on_duration(event: str, duration: float, **_):
            if event.startswith("/jax/core/compile/"):
                self.seconds += duration
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1

        def on_event(event: str, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def emit(result: Dict[str, Any], checks: Dict[str, Dict[str, float]]) -> None:
    """The compared numbers beside their limits as the last lines of standard
    error, and the result as the last line of standard output, ``checks``
    last in it."""
    for name, c in checks.items():
        print(f"[bench] check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    out = dict(result)
    out["checks"] = checks
    print(json.dumps(out), flush=True)
