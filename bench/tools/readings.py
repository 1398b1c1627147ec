#!/usr/bin/env python3
"""Readings that set a cell's limits: the program, its planted faults and
the float8 control, on several seeds, in one process on the chip.

    python3 bench/tools/readings.py --workload <cell> --seconds <s> \
        --seeds 11,12,13 [--faults half_batch] [--control 1]

For each seed it makes a whole run of the cell (set-up, window, check),
prints the compared numbers, then, with ``--control 1``, the same
numbers for the plain reference computed in float8 in the program's
place; each fault named runs the cell again with that fault planted in
the timed path. One JSON line per reading goes to stdout. The benchmark's
own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", default="")
    ap.add_argument("--control", type=int, default=1)
    args = ap.parse_args()
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from bench import harness, spec
    from bench import train_common as TC
    from bench.jobs import generate_job

    cell = spec.load_cell(args.workload, ROOT)
    faults = [f for f in args.faults.split(",") if f]

    def emit(**kw):
        print(json.dumps(kw), flush=True)

    for seed in (int(s) for s in args.seeds.split(",")):
        def after(run, seed=seed):
            if not args.control:
                return
            t0 = time.perf_counter()
            if cell.traffic["job"] == "generate_job":
                numbers = [("logit_gap", generate_job.gaps(run, "fp8"),
                            cell.limits["logit_gap"])]
            else:
                numbers = TC.control(run)
            emit(seed=seed, kind="control", seconds=time.perf_counter() - t0,
                 compared={n: v for n, v, _ in numbers})

        res = harness.execute(cell, seed, args.seconds, False,
                              t_start=time.perf_counter(), root=ROOT,
                              after=after)
        emit(seed=seed, kind="program", correct=res["correct"],
             metrics={k: v["value"] for k, v in res["metrics"].items()},
             memory=res["device"]["memory_peak_bytes"],
             compared={k: v["value"] for k, v in res["compared"].items()})
        for fault in faults:
            res = harness.execute(cell, seed, args.seconds, False,
                                  t_start=time.perf_counter(), root=ROOT,
                                  fault=fault)
            emit(seed=seed, kind=fault, correct=res["correct"],
                 compared={k: v["value"] for k, v in
                           res["compared"].items()})
    return 0


if __name__ == "__main__":
    sys.exit(main())
