#!/usr/bin/env python3
"""Chip benchmark of the persistence-diagram system: one cell, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``workloads`` in ``BENCHMARK.json``) names a configuration and a
traffic mix; ``bench/registry.py`` finds their files and the driver that
runs the configuration.  A run:

1. refuses to run (exit 2, no result) unless JAX finds a TPU with as many
   chips as the cell asks for;
2. keeps JAX's persistent compilation cache where the program's
   ``use_checkout_cache`` puts it: ``JAX_COMPILATION_CACHE_DIR``, or else
   ``<checkout>/.jax_cache``;
3. builds its fields from ``--seed`` and warms up every shape on a field
   that is not measured (set-up, reported as ``setup_s``);
4. measures for ``--seconds``; with ``--trace 1`` under the profiler,
   reporting the per-layer metrics instead of the end-to-end ones;
5. compares a sample of the window's answers, drawn from the seed, with
   the plain reference (``bench/reference.py``), prints each number
   compared beside its limit on standard error, and prints one JSON
   result line last on standard output.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import compare, registry, tracing  # noqa: E402
from bench.peaks import peaks  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclass
class Run:
    """What a per-layer metric reader reads."""

    cell: registry.Cell
    seed: int
    device_kind: str
    n_devices: int
    window: dict                       # the driver's window record
    compiles_in_window: int = 0
    compiled_in_window: List[str] = field(default_factory=list)
    peak_bytes: int = 0
    trace: Optional[tracing.Trace] = None

    @property
    def peaks(self) -> dict:
        return peaks(self.device_kind)

    def stage_mean(self, stage: str) -> Optional[float]:
        vals = self.window.get("stage_seconds", {}).get(stage)
        return sum(vals) / len(vals) if vals else None


class _Compiles:
    """Counts programs compiled (or loaded from the cache) while armed,
    with the names JAX logs for them."""

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0
        self.names: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        jax.config.update("jax_log_compiles", True)
        lg = logging.getLogger("jax")
        lg.propagate = False      # names are collected, not printed
        lg.addHandler(self._Handler(self))

    class _Handler(logging.Handler):
        def __init__(self, owner):
            super().__init__(logging.DEBUG)
            self.owner = owner

        def emit(self, record):
            msg = record.getMessage()
            if self.owner.armed and msg.startswith(
                    "Finished XLA compilation of "):
                self.owner.names.append(msg.split(" of ", 1)[1]
                                        .rsplit(" in ", 1)[0])

    def _on_event(self, event, duration, **kw):
        if self.armed and event == COMPILE_EVENT:
            self.count += 1


def host_steal_s() -> Optional[float]:
    """CPU seconds the hypervisor gave to others, summed over this
    machine's cores (``steal`` of ``/proc/stat``); None off Linux."""
    try:
        with open("/proc/stat") as f:
            fields = f.readline().split()
        return int(fields[8]) / os.sysconf("SC_CLK_TCK")
    except (OSError, IndexError, ValueError):
        return None


@contextlib.contextmanager
def stage_spans():
    """Put the program's stages on the profiler's clock as ``stage.<name>``
    host spans, for the length of a traced window: every
    ``StageReport.stage`` and the pipeline's ``maybe_span`` (the batched
    gradient of ``PersistencePipeline._run_group``)."""
    import jax
    from repro.pipeline import api, stages

    def wrap(orig):
        @contextlib.contextmanager
        def traced(first, name, *args, **kw):
            with jax.profiler.TraceAnnotation("stage." + name):
                with orig(first, name, *args, **kw) as r:
                    yield r
        return traced

    saved = stages.StageReport.stage, api.maybe_span
    stages.StageReport.stage = wrap(saved[0])
    api.maybe_span = wrap(saved[1])
    try:
        yield
    finally:
        stages.StageReport.stage, api.maybe_span = saved


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def measure(drv, state, seconds: float, trace: bool):
    """Run the driver's window, under the profiler when ``trace``."""
    if not trace:
        return drv.window(state, seconds, span), None
    import jax
    log_dir = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    try:
        with stage_spans():
            jax.profiler.start_trace(log_dir, profiler_options=opts)
            try:
                out = drv.window(state, seconds, span)
            finally:
                jax.profiler.stop_trace()
        return out, tracing.load(log_dir)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)


def main(argv=None, *, root: Path = ROOT, require_tpu: bool = True) -> int:
    """One run of one cell.  ``require_tpu=False`` is for the benchmark's
    own tests, which rehearse a run on the CPU."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    t_start = T_START if argv is None else time.perf_counter()

    cell = registry.cell(root, args.workload)
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if require_tpu and platform != "tpu":
        print(f"bench: needs a TPU, JAX found {platform!r}", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    kind = devices[0].device_kind
    if require_tpu:
        peaks(kind)                     # an unknown chip is an error
    src = str(Path(root) / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.compile_cache import use_checkout_cache
    use_checkout_cache(root)
    # keep every program: the D0 rounds compile in well under a second
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compiles = _Compiles()

    drv = registry.driver(root, cell.config["driver"])
    state = drv.setup(cell, args.seed, args.seconds)
    setup_s = time.perf_counter() - t_start

    compiles.armed = True
    cpu0, steal0 = time.process_time(), host_steal_s()
    out, trace = measure(drv, state, args.seconds, bool(args.trace))
    cpu_s, steal1 = time.process_time() - cpu0, host_steal_s()
    compiles.armed = False
    used = devices[:cell.chips]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in used)

    run = Run(cell=cell, seed=args.seed, device_kind=kind,
              n_devices=len(used), window=out,
              compiles_in_window=compiles.count,
              compiled_in_window=list(compiles.names), peak_bytes=peak,
              trace=trace)
    samples = drv.samples(state, out, args.seed)
    drv.close(state)
    del state
    out.pop("answers", None)

    # ---- correctness: the sampled answers against the plain reference --
    mismatched = 0
    for s in samples:
        ref = compare.reference_points(s["field"], s["dims"])
        mismatched += compare.mismatch(s["points"], ref)
    checks = {
        "mismatched_points": {"value": mismatched, "limit": 0},
        "unanswered": {"value": int(out.get("unanswered", 0)), "limit": 0},
        "compared": {"value": len(samples), "limit": 1},
    }
    correct = (mismatched == 0 and checks["unanswered"]["value"] == 0
               and len(samples) >= 1)

    # ---- metrics ----------------------------------------------------------
    metrics = {}
    if args.trace:
        for m in cell.per_layer:
            val = registry.metric_reader(root, m["name"]).read(run)
            if val is None:
                print(f"bench: {m['name']} found nothing to read",
                      file=sys.stderr)
            else:
                metrics[m["name"]] = {"value": val, "unit": m["unit"]}
    else:
        e2e = dict(out["end_to_end"], setup_s=setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    device = {"platform": platform, "kind": kind, "count": len(used),
              "memory_peak_bytes": peak}
    result = {"correct": bool(correct),
              "attempted": int(out["attempted"]),
              "failed": int(out["failed"]),
              "metrics": metrics, "device": device}
    if trace is not None:
        device["busy_s"] = trace.busy_s()
        device["window_s"] = trace.window_s
        result["breakdown"] = {"device_ops": trace.top_ops(10),
                               "idle_gaps": trace.idle_gaps(10)}
    result["window_compiled"] = run.compiled_in_window[:20]
    result["compared"] = checks
    print(f"bench: {cell.name} seed={args.seed} setup_s={setup_s} "
          f"window_compiles={run.compiles_in_window} "
          f"{json.dumps(run.compiled_in_window[:20])}", file=sys.stderr)
    stages = {k: run.stage_mean(k) for k in out.get("stage_seconds", {})}
    steal = None if steal0 is None else steal1 - steal0
    print(f"bench: window_s={out['window_s']} process_cpu_s={cpu_s} "
          f"host_steal_s={steal} stage_mean_s={json.dumps(stages)}",
          file=sys.stderr)
    for name, c in checks.items():
        rel = ">=" if name == "compared" else "<="
        print(f"compared {name} {c['value']} limit {rel} {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
