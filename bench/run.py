"""Run one benchmark cell once, on the machine this is started on.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. The cell's pieces are found by name from
``BENCHMARK.json`` (see ``bench/benchlib/harness.py``). The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its per-layer
metrics with ``--trace 1``), ``device``, with ``--trace 1`` ``breakdown``,
and last ``checks``: each number the correctness check compared, beside its
limit. The same numbers end standard error.

Exits 3, printing no result, where JAX finds no TPU or fewer chips than the
cell asks for; 2 where the program (``src/repro``) is not beside it.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(BENCH))


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    return ap.parse_args(argv)


def measure(cell, seed: int, seconds: float, trace: bool, devices, root: Path = ROOT,
            control=None, fault=None, strict: bool = True, t0: float = T0,
            cache: bool = True):
    """One run of ``cell``; returns (result line without ``checks``, checks).
    ``control`` runs the cell's control in place of the program, ``fault``
    breaks the timed path (both for ``bench/readings.py`` and the tests)."""
    from benchlib import harness as H
    from benchlib import program
    from benchlib import serve_cell, train_cell
    from benchlib.peaks import peaks_for
    from benchlib.trace import load, reduce

    clock = H.CompileClock()
    if cache:
        H.enable_compile_cache(root)
    family = H.family(cell.family, root)
    spec = family.spec(cell.config)
    cfg = family.program_config(cell.config, strict=strict)
    program.check_layout(cfg, spec)
    peaks = (peaks_for(devices[0].device_kind, len(devices))
             if devices[0].platform == "tpu" else None)
    trace_dir = root / ".bench_trace" / cell.name
    shutil.rmtree(trace_dir, ignore_errors=True)
    runner = {"serve": serve_cell, "train": train_cell}[cell.traffic["kind"]]
    result, checks, rec, memory = runner.run(cell, family, cfg, spec, seed, seconds, trace,
                                             devices, clock, str(trace_dir), control=control,
                                             fault=fault)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(memory)}
    metrics = {}
    if rec is not None and not trace:
        for m in cell.end_to_end:
            v = rec.t_open - t0 if m["name"] == "setup_s" else H.reader(m["name"], root)(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    breakdown = None
    if rec is not None and trace:
        t_trace = time.perf_counter()
        rec.td = load(str(trace_dir))
        rec.red = reduce(rec.td)
        rec.peaks = peaks
        device["busy_s"] = rec.red.busy_s
        device["window_s"] = rec.red.window_s
        for m in cell.per_layer:
            v = H.reader(m["name"], root)(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        breakdown = {"device_ops": [list(x) for x in rec.red.top_ops],
                     "idle_gaps": [list(x) for x in rec.red.idle_by_span]}
        shutil.rmtree(trace_dir, ignore_errors=True)
        H.log(f"trace: read and reduced in {time.perf_counter() - t_trace:.1f}s")
    out = dict(result, metrics=metrics, device=device)
    if breakdown is not None:
        out["breakdown"] = breakdown
    return out, checks


def main(argv=None) -> int:
    args = parse(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("[bench] the program (src/repro) is not in this checkout", file=sys.stderr)
        return 2
    from benchlib import harness as H

    cell = H.resolve(args.workload)
    try:
        devices = H.check_device(int(cell.workload["chips"]))
    except H.NoAccelerator as e:
        H.log(str(e))
        return 3
    H.log(f"{cell.name} seed {args.seed}: {devices[0].device_kind} x {len(devices)}")
    out, checks = measure(cell, args.seed, args.seconds, bool(args.trace), devices)
    H.emit(out, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
