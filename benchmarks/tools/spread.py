#!/usr/bin/env python3
"""Spreads of the end-to-end metrics over sets of runs, as the bounds are
set from them: for each metric and set, the distance between the first and
third quartile (``statistics.quantiles(values, n=4)``) as a share of the
median.

    python3 benchmarks/tools/spread.py chiprun_out/sets_<cell>.jsonl

Each line of the file: {"set": 1, "seed": n, "line": <a run's result line>}.
"""

from __future__ import annotations

import collections
import json
import statistics
import sys


def spread(values) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main() -> int:
    sets = collections.defaultdict(lambda: collections.defaultdict(list))
    bad = 0
    for raw in open(sys.argv[1]):
        rec = json.loads(raw)
        line = rec["line"]
        bad += not line["correct"]
        for name, m in line["metrics"].items():
            sets[name][rec["set"]].append(m["value"])
    print(f"runs not correct: {bad}")
    for name, by_set in sets.items():
        for k, values in sorted(by_set.items()):
            # the first run of a call compiles: setup_s is judged without it
            vals = values[1:] if name == "setup_s" and k == 1 else values
            print(f"{name} set {k}: n={len(vals)} median={statistics.median(vals):.6g} "
                  f"spread={100 * spread(vals):.3f}% values={[round(v, 4) for v in values]}")
        widest = max(
            spread(v[1:] if name == "setup_s" and k == 1 else v)
            for k, v in by_set.items()
        )
        meds = [statistics.median(v[1:] if name == "setup_s" and k == 1 else v)
                for k, v in sorted(by_set.items())]
        drift = abs(meds[-1] - meds[0]) / meds[0] if len(meds) > 1 else 0.0
        print(f"{name}: widest spread {100 * widest:.3f}% -> five times "
              f"{100 * 5 * widest:.2f}%; second median off the first by "
              f"{100 * drift:.3f}%")
    return 0


if __name__ == "__main__":
    sys.exit(main())
