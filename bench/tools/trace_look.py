#!/usr/bin/env python3
"""Look at one profiler trace of the pipeline by hand.

    python3 bench/tools/trace_look.py --out <dir> [--dims 64]

Runs one warm-up and one traced diagram of a wavelet-seeded field through
``PersistencePipeline()`` with the harness's spans, and writes to
``--out``: ``summary.json`` (planes, lines, event counts, the names of
the longest device events) and the trace's ``.xplane.pb``.  This is how
the kernel names the metric readers match on were found.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
from collections import Counter
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dims", type=int, default=64)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    import jax
    from bench import fields, run
    from repro.compile_cache import use_checkout_cache
    use_checkout_cache(ROOT)
    from repro.core.grid import Grid
    from repro.pipeline import PersistencePipeline, TopoRequest
    dims = (args.dims,) * 3
    g = Grid.of(*dims)
    pipe = PersistencePipeline()
    pipe.run(TopoRequest(field=fields.make("wavelet-seeded", dims, 1, 0),
                         grid=g))
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    log_dir = str(out / "log")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    f = fields.make("wavelet-seeded", dims, 1, 1)
    with run.stage_spans():
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.diagram"):
                pipe.run(TopoRequest(field=f, grid=g))
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    data = jax.profiler.ProfileData.from_file(path)
    summary = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            top = sorted(evs, key=lambda e: -e.duration_ns)[:15]
            lines.append({
                "line": line.name, "events": len(evs),
                "names": Counter(e.name for e in evs).most_common(25),
                "longest": [[e.name, e.start_ns, e.duration_ns]
                            for e in top]})
        summary.append({"plane": plane.name, "lines": lines})
    (out / "summary.json").write_text(json.dumps(summary, indent=1))
    shutil.copy(path, out / "trace.xplane.pb")
    shutil.rmtree(log_dir, ignore_errors=True)
    from bench import tracing
    tr = tracing.load_file(str(out / "trace.xplane.pb"))
    print(json.dumps({"busy_s": tr.busy_s(), "window_s": tr.window_s,
                      "top_ops": tr.top_ops(10),
                      "idle_gaps": tr.idle_gaps(10),
                      "xplane_bytes": os.path.getsize(out /
                                                      "trace.xplane.pb")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
