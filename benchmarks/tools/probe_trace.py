#!/usr/bin/env python3
"""Run one cell with ``--trace 1``, keep the trace, and write what it holds
(planes, lines, the commonest event names) to ``chiprun_out/`` — the look by
hand that comes before any code is written against a trace.

    python3 benchmarks/tools/probe_trace.py <workload> <seed> <seconds>
"""

from __future__ import annotations

import collections
import json
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import common, trace  # noqa: E402


def summarize(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for ln in plane.lines:
            names = collections.Counter()
            dur = collections.Counter()
            stats = {}
            n = 0
            for ev in ln.events:
                names[ev.name] += 1
                dur[ev.name] += ev.duration_ns
                if ev.name not in stats:
                    try:
                        stats[ev.name] = {
                            str(k): str(v)[:160] for k, v in ev.stats
                        }
                    except Exception as e:  # the look must not fail the run
                        stats[ev.name] = {"error": repr(e)}
                n += 1
            lines.append({
                "line": ln.name, "events": n,
                "top_by_time": [
                    [k, v * 1e-9, names[k], stats[k]]
                    for k, v in dur.most_common(40)
                ],
            })
        planes.append({"plane": plane.name, "lines": lines})
    return {"planes": planes}


def main() -> int:
    workload, seed, seconds = sys.argv[1], int(sys.argv[2]), float(sys.argv[3])
    cell = common.load_cell(workload)
    common.require_program()
    common.require_chips(cell.chips)
    bench_run.run_cell(cell, seed, seconds, True, hooks={"keep_trace": True})
    tdir = os.path.join(common.REPO, ".bench_out", "trace")
    path = trace.find_xplane(tdir)
    out = os.path.join(common.REPO, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"trace_summary_{workload}.json"), "w") as f:
        json.dump(summarize(path), f, indent=1)
    size = os.path.getsize(path)
    sys.stderr.write(f"xplane bytes: {size}\n")
    if size < 30 << 20:
        shutil.copy(path, os.path.join(out, f"{workload}.xplane.pb"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
