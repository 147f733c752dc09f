#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip at the
cell's own size: for each seed the program's numbers against the reference
(the lower reading) or, with ``--plant``, those of the control or of a fault
of ``harness/faults.py`` (the upper reading), each through the harness's own
comparison and result line. One process; a short window.

    python3 benchmarks/tools/calibrate.py <workload> --seeds 1 2 3 ... \\
        [--plant control] [--seconds 2]

Prints one JSON line per seed and appends it to
``chiprun_out/calibrate_<workload>.jsonl``. Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import io
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import common, faults  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--plant", choices=sorted(faults.PLANTS))
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args()
    cell = common.load_cell(args.workload)
    common.require_program()
    if not args.allow_cpu:
        common.require_chips(cell.chips)
    out_dir = os.path.join(common.REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"calibrate_{args.workload}.jsonl")
    for seed in args.seeds:
        buf = io.StringIO()
        t0 = time.perf_counter()
        bench_run.run_cell(
            cell, seed, args.seconds, False,
            hooks=faults.hooks(args.plant) if args.plant else None, out=buf,
            t_process=time.perf_counter(),
        )
        line = json.loads(buf.getvalue().splitlines()[-1])
        rec = {
            "seed": seed, "plant": args.plant, "correct": line["correct"],
            "checked": line["checked"], "losses": line.get("losses"),
            "steps": line.get("steps"), "metrics": line["metrics"],
            "seconds": time.perf_counter() - t0,
        }
        print(json.dumps(rec), flush=True)
        with open(path, "a") as f:
            f.write(json.dumps(rec) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
