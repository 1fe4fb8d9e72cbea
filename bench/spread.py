"""Run-to-run spread of a cell's end-to-end metrics, for setting its bounds.

    python3 bench/spread.py --workload <name> --seeds 11 12 13 14 15 16 \
        --sets 2 --seconds 30 [--traced 21 22 23]

Runs ``bench/run.py`` once per seed and set, each run a process of its own and
one at a time (this process never touches JAX, so each run holds the chip
alone), then ``--trace 1`` once per ``--traced`` seed. Each result line is
appended to ``chiprun_out/spread-<workload>.jsonl``. For every metric it
prints each set's median and spread, the distance between the first and
third quartile (``statistics.quantiles(values, n=4)``) as a share of the
median; a bound is about five times the wider spread, and never under 1%.
"""
from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def run(workload: str, seed: int, seconds: float, trace: int) -> dict:
    cmd = [sys.executable, "bench/run.py", "--workload", workload, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", str(trace)]
    t = time.perf_counter()
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    last = p.stdout.strip().splitlines()[-1] if p.stdout.strip() else ""
    out = json.loads(last) if last.startswith("{") else {}
    out.update(seed=seed, trace=trace, rc=p.returncode, wall_s=time.perf_counter() - t,
               log=[x for x in p.stderr.splitlines()
                    if x.startswith(("[bench] warm-up", "[bench] first", "[bench] window",
                                     "[bench] pool", "[bench] check:", "[bench] memory_stats"))])
    if p.returncode or not out.get("correct"):
        sys.stderr.write(p.stderr[-4000:])
    return out


def spread(values) -> float:
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--traced", type=int, nargs="*", default=[])
    args = ap.parse_args()
    log = ROOT / "chiprun_out" / f"spread-{args.workload}.jsonl"
    log.parent.mkdir(exist_ok=True)
    sets = []
    for k in range(args.sets):
        lines = []
        for seed in args.seeds:
            out = run(args.workload, seed, args.seconds, 0)
            out["set"] = k
            lines.append(out)
            print(json.dumps(out), flush=True)
            with open(log, "a") as f:
                f.write(json.dumps(out) + "\n")
        sets.append(lines)
    for seed in args.traced:
        out = run(args.workload, seed, args.seconds, 1)
        print(json.dumps(out), flush=True)
        with open(log, "a") as f:
            f.write(json.dumps(out) + "\n")
    names = sorted({m for s in sets for line in s for m in line.get("metrics", {})})
    for name in names:
        widest = 0.0
        for k, lines in enumerate(sets):
            vals = [line["metrics"][name]["value"] for line in lines if name in line.get("metrics", {})]
            if len(vals) >= 2:
                widest = max(widest, spread(vals))
                print(f"{name} set {k}: median {statistics.median(vals)!r} spread "
                      f"{spread(vals)!r} over {len(vals)} runs", flush=True)
        print(f"{name}: widest spread {widest!r}, five times {5 * widest!r}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
