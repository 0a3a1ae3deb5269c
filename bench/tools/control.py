#!/usr/bin/env python3
"""Readings of the control, which must come out as not correct.

    python3 bench/tools/control.py --workload volume-wavelet-128 --seeds 1 2 3

The control is the plain reference put in the program's place, computed
on the field rounded to bfloat16, the precision below the float32 the
configurations state.  For each seed it takes as many fields of the
cell's traffic, at the cell's own size, as a run compares, and prints
the number a run compares (``mismatched_points``, limit 0) for the
control's answers against the float32 reference.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    from bench import compare, fields, registry
    cell = registry.cell(ROOT, args.workload)
    t = cell.traffic
    dims = tuple(int(d) for d in t["dims"])
    for seed in args.seeds:
        t0 = time.perf_counter()
        total = 0
        for i in range(int(t["check_samples"])):
            f = fields.make(t["family"], dims, seed, i)
            total += compare.mismatch(compare.control_points(f, dims),
                                      compare.reference_points(f, dims))
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "samples": int(t["check_samples"]),
                          "mismatched_points": total,
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
