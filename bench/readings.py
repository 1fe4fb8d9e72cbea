"""Readings that set a cell's check limits, on the chip, in one process.

    python3 bench/readings.py --workload <name> --seeds 12 --controls 3 \
        --faults 3 --seconds 30 [--first-seed N] [--control fp8]

For ``--seeds`` seeds it runs the cell as ``bench/run.py`` does and prints the
numbers its check compares (the lower readings). For ``--controls`` seeds it
runs the control (the upper readings): by default, a serving cell's
program with its own int8 path switched on (int8 weights and int8 K/V
pages), a training cell's reference computed through float8, held to the
float32 reference; ``--control fp8`` puts a serving cell's reference
computed through float8 in the program's place. For
``--faults`` seeds of a training cell it runs the step with half of each
batch left out, the mean taken over the rest, and for as many the step
returning its state unchanged; of a serving cell, every eighth served token
altered where it is sampled, and for as many each fault of the model
family's own (``serve_faults``: for the routed MoD transformer, decode
routing the live rows that score lowest). One JSON line per run, also
appended to ``chiprun_out/readings-<workload>.jsonl``. The benchmark's own
runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))


def frozen(step):
    """The timed step returning the state it was given."""
    return lambda state, batch: (state, step(state, batch)[1])


def half_batch(step):
    """The timed step with the second half of every batch left out."""
    import jax

    return lambda state, batch: step(state, jax.tree.map(lambda x: x[: x.shape[0] // 2], batch))


def altered_tokens(engine):
    """Every eighth token the engine serves, plus one, where it is sampled."""
    sample, vocab = engine._sample, engine.cfg.vocab
    engine._sample = lambda req, row, i: (sample(req, row, i) + (i % 8 == 5)) % vocab


def main() -> int:
    import run as R
    from benchlib import harness as H

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--faults", type=int, default=0)
    ap.add_argument("--control", choices=["int8", "fp8"])
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    args = ap.parse_args()
    cell = H.resolve(args.workload)
    family = H.family(cell.family)
    devices = H.check_device(int(cell.workload["chips"]))
    out = BENCH.parent / "chiprun_out" / f"readings-{cell.name}.jsonl"
    out.parent.mkdir(exist_ok=True)
    control = args.control or {"serve": "int8", "train": "fp8"}[cell.traffic["kind"]]
    plan = [("program", None, None)] * args.seeds + [("control", control, None)] * args.controls
    if cell.traffic["kind"] == "train":
        plan += [("half_batch", None, half_batch)] * args.faults
        plan += [("frozen", None, frozen)] * args.faults
    else:
        faults = dict(altered_tokens=altered_tokens,
                      **family.serve_faults(family.spec(cell.config)))
        for name, fault in faults.items():
            plan += [(name, None, fault)] * args.faults
    for i, (what, ctl, fault) in enumerate(plan):
        seed = args.first_seed + 7919 * i
        t = time.perf_counter()
        res, checks = R.measure(cell, seed, args.seconds, False, devices, control=ctl,
                                fault=fault, t0=t)
        line = {"what": what, "seed": seed, "correct": res["correct"],
                "numbers": res["readings"],
                "metrics": {k: m["value"] for k, m in res["metrics"].items()},
                "memory_peak_bytes": res["device"]["memory_peak_bytes"],
                "seconds": time.perf_counter() - t}
        print(json.dumps(line), flush=True)
        with open(out, "a") as f:
            f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
