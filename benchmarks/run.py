#!/usr/bin/env python3
"""One cell of the benchmark, once.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, one cell: it finds the cell's configuration, traffic and
per-layer readers by the names in BENCHMARK.json, sets up (everything that
compiles, compiles here), measures for ``--seconds``, compares what the
timed path produced with the plain reference, and prints one JSON line last.
It needs a TPU with exactly the chips the cell asks for; there is no CPU
fallback. See benchmarks/README.md.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.harness import common  # noqa: E402


def layer_metrics(cell, record: dict, units: dict) -> dict:
    """Every per-layer metric the cell reports, each from a reader of its
    own; a reader that finds nothing to read returns None and is left out."""
    out = {}
    for m in cell.per_layer:
        value = common.load_by_path("layer_metrics", m["name"]).read(record)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": units[m["name"]]}
    return out


def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             hooks=None, out=None, t_process=T_PROCESS) -> bool:
    """Everything after the look for a chip (the tests enter here)."""
    common.require_program()
    common.setup_compile_cache()
    driver = common.load_by_path("drivers", cell.traffic["driver"])
    res = driver.run(cell, seed, seconds, trace, t_process, hooks)
    units = common.units_of()
    device = common.device_record()
    device["memory_peak_bytes"] = res["memory_peak_bytes"]
    breakdown = None
    if trace:
        tr = res["record"]["trace"]
        device["busy_s"], device["window_s"] = tr["busy_s"], tr["window_s"]
        breakdown = {
            "device_ops": tr["device_ops"], "idle_gaps": tr["idle_gaps"],
        }
        metrics = layer_metrics(cell, res["record"], units)
    else:
        metrics = {
            k: {"value": float(v), "unit": units[k]}
            for k, v in res["end_to_end"].items()
        }
    return common.emit_result(
        checks=res["checks"], attempted=res["attempted"],
        failed=res["failed"], metrics=metrics, device=device,
        breakdown=breakdown, extra=res.get("extra"), out=out,
    )


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    try:
        cell = common.load_cell(args.workload)
        common.require_program()
        common.require_chips(cell.chips)
        run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except common.BenchError as e:
        sys.stderr.write(f"benchmarks/run.py: {e}\n")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
