#!/usr/bin/env python3
"""ACAI chip benchmark: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells, configurations and metrics are listed in ``BENCHMARK.json`` at the
root of the checkout. The run needs a TPU with published peaks in
``bench/peaks.json`` and as many chips as the cell names; otherwise it
exits nonzero and prints no result. With ``--trace 0`` the last line of
stdout is a JSON object holding the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics read from a profiler trace of the
window. Either way the run checks what the window produced against the
plain reference and prints each compared number beside its limit, last
on stderr and under ``compared`` in the JSON line.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# the TPU runtime writes no log files (by default it writes to /tmp)
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("bench/run.py runs from a checkout of the repository "
              "(src/repro not found)", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from bench import harness, spec

    cell = spec.load_cell(args.workload, ROOT)
    try:
        result = harness.execute(cell, args.seed, args.seconds,
                                 bool(args.trace), t_start=T_START, root=ROOT)
    except harness.NoChip as e:
        print(f"no run: {e}", file=sys.stderr)
        return 3
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
