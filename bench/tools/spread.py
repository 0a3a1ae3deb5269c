#!/usr/bin/env python3
"""Spread of a cell's metrics over sets of runs, as the bounds are set.

    python3 bench/tools/spread.py A1.out A2.out ... -- B1.out B2.out ...

Each file holds one run's standard output; its last line is the result.
For each set and metric: the median, and the spread, the distance
between the first and third quartile (``statistics.quantiles(n=4)``) as
a share of the median.  Sets are separated by ``--``.
"""

from __future__ import annotations

import json
import statistics
import sys


def result(path: str) -> dict:
    with open(path) as f:
        lines = [ln for ln in f.read().splitlines() if ln.strip()]
    return json.loads(lines[-1])


def spread(values) -> float:
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2


def main(argv) -> int:
    sets, cur = [], []
    for a in argv:
        if a == "--":
            sets.append(cur)
            cur = []
        else:
            cur.append(a)
    sets.append(cur)
    for i, paths in enumerate(sets):
        runs = [result(p) for p in paths]
        names = sorted({m for r in runs for m in r["metrics"]})
        for name in names:
            vals = [r["metrics"][name]["value"] for r in runs
                    if name in r["metrics"]]
            print(json.dumps({"set": i, "metric": name, "n": len(vals),
                              "median": statistics.median(vals),
                              "spread": spread(vals) if len(vals) > 1
                              else None, "values": vals}))
        print(json.dumps({"set": i, "correct": [r["correct"] for r in runs]}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
