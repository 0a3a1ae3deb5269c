"""Render EXPERIMENTS.md §Dry-run / §Roofline tables from results/dryrun,
and emit the machine-readable benchmarks (BENCH_pipeline.json,
BENCH_gradient.json).

    PYTHONPATH=src python -m benchmarks.report [--dir results/dryrun]
    PYTHONPATH=src python -m benchmarks.report --section pipeline \
        [--out BENCH_pipeline.json]
    PYTHONPATH=src python -m benchmarks.report --section gradient \
        [--quick] [--out BENCH_gradient.json]
    PYTHONPATH=src python -m benchmarks.report --section stream \
        [--quick] [--out BENCH_stream.json]
    PYTHONPATH=src python -m benchmarks.report --section api \
        [--quick] [--out BENCH_api.json]
    PYTHONPATH=src python -m benchmarks.report --section approx \
        [--quick] [--out BENCH_approx.json]
    PYTHONPATH=src python -m benchmarks.report --section scale \
        [--quick] [--out BENCH_scale.json]
    PYTHONPATH=src python -m benchmarks.report --section serve \
        [--quick] [--out BENCH_serve.json]

The pipeline section runs ``PersistencePipeline`` over a fixed field set
and dumps every ``StageReport`` (nested per-stage wall times + algorithm
counters).  The gradient section A/B-times the front-end paths (im2col
pre-pass vs fused gather) with vertices/s and the modeled HBM
bytes/vertex, so the perf trajectory is tracked PR-over-PR.  The stream
section A/B-times the out-of-core engine (``diagram_stream``) against
the in-memory path, recording peak resident field bytes and the
load/compute overlap from the ``StreamReport``.  The scale section runs
the overlapped sharded-streaming front-end at 1/2/4/8 shards (weak +
strong, one forced-host-device subprocess per point) with
slots-normalized efficiency and the halo overlap fraction, cross-checks
bit-identity against the in-memory diagram, and in full mode records a
256^3 memmap-streamed sharded run and gates weak-scaling efficiency at
4 shards >= 60%.  The serve section is the cached-serving traffic-storm
harness (``repro.cache`` + ``TopoService``): cold-miss vs warm-hit
latency distributions, epsilon-aware reuse (an exact or tighter-bound
entry answering a looser epsilon request), progressive refinement
upgrading its cache entry in place, a burst storm under an admission
policy (degraded count > 0, zero unhandled errors), and a shed probe —
with every served-from-cache result either bit-identical to (exact) or
bound-checked against (approximate) a fresh in-benchmark computation.
"""

import argparse
import json
import os
import platform
import statistics
import time
from pathlib import Path


def bench_env():
    """Environment metadata stamped into every BENCH_*.json: platform,
    python, cpu count, jax/jaxlib versions, visible XLA devices, and
    the XLA flags in effect — so a regression diff always says *where*
    both numbers came from."""
    env = {"platform": platform.platform(),
           "python": platform.python_version(),
           "cpu_count": os.cpu_count(),
           "xla_flags": os.environ.get("XLA_FLAGS", "")}
    try:
        import jax
        import jaxlib
        env["jax"] = jax.__version__
        env["jaxlib"] = jaxlib.__version__
        env["devices"] = [str(d) for d in jax.devices()]
    except Exception:                  # pragma: no cover - no jax
        env["jax"] = env["jaxlib"] = None
        env["devices"] = []
    return env


def bench_doc(schema, quick=None, **extra):
    """The common BENCH_*.json skeleton: schema tag + environment stamp
    (plus the legacy top-level platform/python keys older tooling
    reads), then the section's own payload."""
    doc = {"schema": schema,
           "platform": platform.platform(),
           "python": platform.python_version(),
           "env": bench_env()}
    if quick is not None:
        doc["quick"] = bool(quick)
    doc.update(extra)
    return doc


def write_bench(out_path, doc):
    Path(out_path).write_text(json.dumps(doc, indent=1))


def timed(fn, reps=1, warmup=0):
    """THE timing helper: ``warmup`` untimed calls, then ``reps`` timed
    ones.  Returns ``(stats, last_output)`` where stats carries the raw
    samples plus min/median (min for gates — least noise-sensitive —
    median for reporting)."""
    for _ in range(warmup):
        fn()
    times, out = [], None
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn()
        times.append(time.perf_counter() - t0)
    return {"min_s": min(times), "median_s": statistics.median(times),
            "times_s": times}, out


def fmt_bytes(b):
    if b is None:
        return "-"
    for unit in ("B", "KB", "MB", "GB", "TB"):
        if abs(b) < 1024:
            return f"{b:.1f}{unit}"
        b /= 1024
    return f"{b:.1f}PB"


def fmt_s(x):
    if x is None:
        return "-"
    return f"{x*1e3:.2f}" if x < 10 else f"{x*1e3:.0f}"


def load(dir_):
    recs = []
    for p in sorted(Path(dir_).glob("*.json")):
        try:
            recs.append(json.loads(p.read_text()))
        except Exception:
            pass
    return recs


def roofline_table(recs, mesh="single"):
    rows = []
    head = ("| cell | FLOPs/dev | bytes/dev | coll bytes/dev | compute ms |"
            " memory ms | coll ms | dominant | useful | frac |")
    sep = "|" + "---|" * 10
    rows.append(head)
    rows.append(sep)
    for r in recs:
        if r.get("mesh") != mesh or "error" in r:
            continue
        if "skipped" in r:
            rows.append(f"| {r['arch']} × {r['shape']} | — | — | — | — | — |"
                        f" — | skipped | — | — |")
            continue
        coll = r.get("collectives", {})
        cb = sum(v for k, v in coll.items()
                 if k in ("all-gather", "all-reduce", "reduce-scatter",
                          "all-to-all", "collective-permute"))
        dom = r.get("dominant", "?")
        dom_s = {"compute": r.get("compute_s"), "memory": r.get("memory_s"),
                 "collective": r.get("collective_s")}.get(dom)
        tot = (r.get("compute_s") or 0)
        frac = (r.get("compute_s") / dom_s) if dom_s else None
        ur = r.get("useful_ratio")
        rows.append(
            f"| {r['arch']} × {r['shape']} | {r.get('flops_per_device',0):.3g}"
            f" | {fmt_bytes(r.get('bytes_per_device'))}"
            f" | {fmt_bytes(cb)}"
            f" | {fmt_s(r.get('compute_s'))} | {fmt_s(r.get('memory_s'))}"
            f" | {fmt_s(r.get('collective_s'))} | {dom}"
            f" | {f'{ur:.2f}' if ur else '-'}"
            f" | {f'{frac:.2f}' if frac else '-'} |")
    return "\n".join(rows)


def dryrun_table(recs):
    rows = ["| cell | mesh | devices | compile s | args/dev | temps/dev |"
            " collectives (#) | status |", "|" + "---|" * 8]
    for r in recs:
        if "skipped" in r:
            rows.append(f"| {r['arch']} × {r['shape']} | {r['mesh']} | - |"
                        f" - | - | - | - | SKIP ({r['skipped'][:40]}…) |")
            continue
        if "error" in r:
            rows.append(f"| {r['arch']} × {r['shape']} | {r['mesh']} | - |"
                        f" - | - | - | - | FAIL {r['error'][:60]} |")
            continue
        ma = r.get("memory_analysis", {})
        rows.append(
            f"| {r['arch']} × {r['shape']} | {r['mesh']} | {r['n_devices']}"
            f" | {r.get('compile_s', 0):.0f}"
            f" | {fmt_bytes(ma.get('argument_size_in_bytes'))}"
            f" | {fmt_bytes(ma.get('temp_size_in_bytes'))}"
            f" | {r.get('collectives', {}).get('count', '-')}"
            f" | OK |")
    return "\n".join(rows)


def pipeline_bench(out_path, dims=(8, 8, 8), fields=("wavelet", "random"),
                   backends=("np", "jax"), block_counts=(1, 4), batch=4):
    """Run the PersistencePipeline benchmark matrix, write BENCH json."""
    from repro.core.grid import Grid
    from repro.fields import make_field
    from repro.pipeline import PersistencePipeline

    g = Grid.of(*dims)
    runs = []
    for field in fields:
        f = make_field(field, dims, seed=0)
        for backend in backends:
            for nb in block_counts:
                pipe = PersistencePipeline(backend=backend, n_blocks=nb,
                                           distributed=nb > 1)
                pipe.diagram(f, grid=g)  # warm-up: keep jit compile out
                res = pipe.diagram(f, grid=g)
                runs.append({
                    "field": field, "dims": list(dims), "backend": backend,
                    "n_blocks": nb, "distributed": nb > 1,
                    "report": res.report.to_dict(),
                })
        # batched path: one compiled program over `batch` same-shape fields
        pipe = PersistencePipeline(backend="jax")
        fs = [make_field(field, dims, seed=s) for s in range(batch)]
        pipe.diagrams(fs, grid=g)  # warm-up: compile the batched program
        ress = pipe.diagrams(fs, grid=g)
        runs.append({
            "field": field, "dims": list(dims), "backend": "jax",
            "n_blocks": 1, "batched": batch,
            "report": ress[0].report.to_dict(),
        })
    doc = bench_doc("ddms-pipeline-bench/v1", runs=runs)
    write_bench(out_path, doc)
    print(f"wrote {out_path}: {len(runs)} runs")
    for r in runs:
        stages = {c["name"]: c["seconds"] for c in r["report"]["children"]}
        tag = f"b{r['batched']}" if "batched" in r else f"nb{r['n_blocks']}"
        total = sum(stages.values())
        print(f"  {r['field']}/{r['backend']}/{tag}: total={total*1e3:.1f}ms "
              + " ".join(f"{k}={v*1e3:.1f}" for k, v in stages.items()))


def gradient_bench(out_path, quick=False):
    """A/B the gradient front-end paths; write BENCH_gradient.json.

    Runs, per grid size, the fused jit program ("jax"), a pre-pass-style
    jnp path (eager int64 im2col gather + column keys — the before-PR
    formulation), and the two Pallas kernels (fused vs im2col pre-pass)
    in interpret mode on a small grid.  Pre-pass and fused rows are
    cross-checked bit-exact before timing.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import gradient as GRAD
    from repro.core.grid import Grid, vertex_order
    from repro.fields import make_field
    from repro.kernels import ops, ref as REF
    from repro.kernels.ops import gradient_hbm_model

    # the pre-pass-style jnp reference: eager gather, no rank narrowing
    prepass_jit = jax.jit(
        lambda nbrs, ov: REF.lower_star_gradient_jnp(nbrs, ov))

    def prepass_style(g, o):
        nbrs = GRAD.neighbor_orders(g, jnp.asarray(o), xp=jnp)
        return prepass_jit(nbrs, o)

    def timed_mean(fn, reps):
        st, out = timed(fn, reps=reps, warmup=1)  # warmup: compile
        return sum(st["times_s"]) / reps, out

    sizes = [(8, 8, 8)] if quick else [(16, 16, 16), (32, 32, 32)]
    pallas_dims = (6, 6, 6) if quick else (16, 16, 8)
    runs = []

    for dims in sizes:
        g = Grid.of(*dims)
        f = make_field("random", dims, seed=6)
        o = jnp.asarray(np.asarray(vertex_order(f.astype(np.float64))))
        # the prepass comparator above gathers eagerly in int64 (the
        # pre-PR formulation), so model its traffic at 8 B/rank
        model = gradient_hbm_model(dims)
        model["prepass"] = gradient_hbm_model(dims,
                                              rank_bytes=8)["prepass"]
        reps = 2 if quick else 3
        s_pre, rows_pre = timed_mean(
            lambda: jax.block_until_ready(prepass_style(g, o)), reps)
        s_fus, rows_fus = timed_mean(
            lambda: jax.block_until_ready(
                ops.lower_star_gradient(g, o, backend="jax")), reps)
        for a, b in zip(rows_pre, rows_fus):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        runs.append({"dims": list(dims), "backend": "jax",
                     "paths": {
                         "prepass": {"seconds": s_pre,
                                     "vertices_per_s": g.nv / s_pre,
                                     "model_bytes_per_vertex":
                                         model["prepass"]},
                         "fused": {"seconds": s_fus,
                                   "vertices_per_s": g.nv / s_fus,
                                   "model_bytes_per_vertex":
                                       model["fused"]}},
                     "speedup": s_pre / s_fus})

    g = Grid.of(*pallas_dims)
    f = make_field("random", pallas_dims, seed=6)
    o = jnp.asarray(np.asarray(vertex_order(f.astype(np.float64))))
    model = gradient_hbm_model(pallas_dims)
    s_pre, rows_pre = timed_mean(lambda: jax.block_until_ready(
        ops.lower_star_gradient(g, o, backend="pallas_prepass")), 1)
    s_fus, rows_fus = timed_mean(lambda: jax.block_until_ready(
        ops.lower_star_gradient(g, o, backend="pallas")), 1)
    for a, b in zip(rows_pre, rows_fus):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    runs.append({"dims": list(pallas_dims), "backend": "pallas",
                 "interpret_mode": True,
                 "paths": {
                     "prepass": {"seconds": s_pre,
                                 "vertices_per_s": g.nv / s_pre,
                                 "model_bytes_per_vertex":
                                     model["prepass"]},
                     "fused": {"seconds": s_fus,
                               "vertices_per_s": g.nv / s_fus,
                               "model_bytes_per_vertex": model["fused"]}},
                 "speedup": s_pre / s_fus})

    doc = bench_doc("ddms-gradient-bench/v1", quick=quick, runs=runs)
    write_bench(out_path, doc)
    print(f"wrote {out_path}: {len(runs)} runs")
    for r in runs:
        p = r["paths"]
        print(f"  {r['backend']}/{'x'.join(map(str, r['dims']))}: "
              f"prepass={p['prepass']['vertices_per_s']:.0f}v/s "
              f"fused={p['fused']['vertices_per_s']:.0f}v/s "
              f"speedup={r['speedup']:.2f}x "
              f"bytes/v {p['prepass']['model_bytes_per_vertex']:.0f}->"
              f"{p['fused']['model_bytes_per_vertex']:.1f}")


def stream_bench(out_path, quick=False):
    """Streamed (out-of-core) vs in-memory throughput; BENCH_stream.json.

    Runs ``PersistencePipeline.diagram`` and ``diagram_stream`` on the
    same fields (warmed, so compile time stays out), cross-checks the
    diagrams, and records end-to-end vertices/s plus the StreamReport
    byte accounting (peak resident field bytes, load/compute overlap).
    """
    from repro.core.diagram import same_offdiagonal
    from repro.core.grid import Grid
    from repro.fields import make_field
    from repro.pipeline import PersistencePipeline
    from repro.stream import ArraySource

    dims = (16, 16, 16) if quick else (32, 32, 32)
    chunk_zs = (4, 8) if quick else (8, 16)
    g = Grid.of(*dims)
    pipe = PersistencePipeline(backend="jax")
    runs = []
    for field in ("wavelet", "random"):
        f = make_field(field, dims, seed=0)
        src = ArraySource(f.reshape(dims[::-1]))
        pipe.diagram(f, grid=g)                      # warm-up: compile
        t0 = time.perf_counter()
        ref = pipe.diagram(f, grid=g)
        mem_s = time.perf_counter() - t0
        for cz in chunk_zs:
            pipe.diagram_stream(src, chunk_z=cz)     # warm-up chunk shapes
            t0 = time.perf_counter()
            res = pipe.diagram_stream(src, chunk_z=cz)
            st_s = time.perf_counter() - t0
            assert same_offdiagonal(res.diagram, ref.diagram)
            runs.append({
                "field": field, "dims": list(dims), "backend": "jax",
                "chunk_z": cz,
                "in_memory": {"seconds": mem_s,
                              "vertices_per_s": g.nv / mem_s,
                              "resident_field_bytes": f.nbytes},
                "streamed": {"seconds": st_s,
                             "vertices_per_s": g.nv / st_s,
                             "resident_field_bytes":
                                 res.stream.peak_resident_field_bytes},
                "stream_report": res.stream.to_dict(),
            })
    doc = bench_doc("ddms-stream-bench/v1", quick=quick, runs=runs)
    write_bench(out_path, doc)
    print(f"wrote {out_path}: {len(runs)} runs")
    for r in runs:
        m, s = r["in_memory"], r["streamed"]
        sr = r["stream_report"]
        print(f"  {r['field']}/cz{r['chunk_z']}: "
              f"in-mem={m['vertices_per_s']:.0f}v/s "
              f"streamed={s['vertices_per_s']:.0f}v/s "
              f"({s['seconds']/m['seconds']:.2f}x time) "
              f"resident {fmt_bytes(m['resident_field_bytes'])}->"
              f"{fmt_bytes(s['resident_field_bytes'])} "
              f"overlap={sr['overlap_s']*1e3:.1f}ms")


def api_bench(out_path, quick=False):
    """Declarative request-path overhead + wire format; BENCH_api.json.

    Interleaves the legacy entry point (``pipe.diagram``, now a shim)
    with the declarative path (``pipe.run(TopoRequest(...))``) on a
    warmed pipeline and compares medians — the request/lower/compile
    resolver is pure Python and must stay within 5% of the legacy call
    (asserted).  Also records plan-cache hit counters and the wire
    round-trip (``to_bytes``/``from_bytes``) size and time.
    """
    import numpy as np

    from repro.core.grid import Grid
    from repro.fields import make_field
    from repro.pipeline import (DiagramResult, PersistencePipeline,
                                PlanCache, TopoRequest)

    dims = (8, 8, 8) if quick else (16, 16, 16)
    reps = 5 if quick else 9
    g = Grid.of(*dims)
    f = make_field("wavelet", dims, seed=0)
    cache = PlanCache()
    pipe = PersistencePipeline(backend="jax", plan_cache=cache)
    req = TopoRequest(field=f, grid=g)
    pipe.diagram(f, grid=g)      # warm-up: compile + trace out of the loop
    pipe.run(req)

    def timed1(fn):
        st, out = timed(fn)
        return st["min_s"], out

    legacy, declarative = [], []
    res = None
    for i in range(reps):        # interleaved A/B, order alternated to
        # cancel systematic first-runner bias (this box has ~2x noise)
        if i % 2 == 0:
            legacy.append(timed1(lambda: pipe.diagram(f, grid=g))[0])
            dt, res = timed1(lambda: pipe.run(req))
            declarative.append(dt)
        else:
            dt, res = timed1(lambda: pipe.run(req))
            declarative.append(dt)
            legacy.append(timed1(lambda: pipe.diagram(f, grid=g))[0])
    m_leg = min(legacy)
    m_dec = min(declarative)
    med = {"legacy": statistics.median(legacy),
           "request": statistics.median(declarative)}

    # The 5% gate measures the *added* request-path machinery directly
    # (request resolve -> lower -> compile on a warm cache) against the
    # end-to-end time: on a box with ~2x run-to-run variance, the A/B
    # end-to-end delta above is dominated by noise (both entry points
    # execute the same resolver), so it is recorded but not gated.
    n_res = 200
    t0 = time.perf_counter()
    for _ in range(n_res):
        pipe.lower(req).compile(pipe.plan_cache)
    resolver_s = (time.perf_counter() - t0) / n_res
    overhead = resolver_s / m_leg

    t0 = time.perf_counter()
    blob = res.to_bytes()
    enc_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    back = DiagramResult.from_bytes(blob)
    dec_s = time.perf_counter() - t0
    assert back.betti() == res.betti()

    doc = bench_doc(
        "ddms-api-bench/v1", quick=quick,
        dims=list(dims), reps=reps,
        legacy_min_s=m_leg, request_min_s=m_dec,
        legacy_median_s=med["legacy"],
        request_median_s=med["request"],
        resolver_s=resolver_s,
        request_overhead_frac=overhead,
        plan_cache=cache.stats(),
        wire={"bytes": len(blob), "encode_s": enc_s,
              "decode_s": dec_s,
              "pairs": int(sum(len(res.pairs(p, min_persistence=0))
                               for p in range(g.dim)))})
    write_bench(out_path, doc)
    print(f"wrote {out_path}: legacy={m_leg*1e3:.2f}ms "
          f"request={m_dec*1e3:.2f}ms "
          f"resolver={resolver_s*1e6:.0f}us ({overhead*100:.3f}% of call) "
          f"wire={len(blob)}B enc={enc_s*1e6:.0f}us dec={dec_s*1e6:.0f}us "
          f"cache={cache.stats()}")
    assert overhead < 0.05, \
        f"request-path overhead {overhead*100:.2f}% exceeds the 5% budget"
    # one compile per (dims, backend, n_blocks) across all of the above
    assert cache.build_counts[(g.dims, "jax", 1)] == 1
    return doc


def _approx_bench_field(dims):
    """A smooth two-blob field with a mild ripple: large-scale features
    the coarse levels keep (so approximation genuinely engages) plus
    enough small structure for a non-trivial diagram."""
    import numpy as np
    nz, ny, nx = dims[::-1]
    z, y, x = np.meshgrid(np.linspace(0, 1, nz), np.linspace(0, 1, ny),
                          np.linspace(0, 1, nx), indexing="ij")
    f = np.exp(-2.0 * ((x - .45) ** 2 + (y - .55) ** 2 + (z - .5) ** 2))
    f += 0.5 * np.exp(-2.5 * ((x - .75) ** 2 + (y - .25) ** 2
                              + (z - .6) ** 2))
    f += 0.01 * np.cos(4 * np.pi * x) * np.cos(4 * np.pi * y) \
        * np.cos(4 * np.pi * z)
    return f.astype(np.float32)


def approx_bench(out_path, quick=False):
    """Approximate vs exact diagrams (repro.approx); BENCH_approx.json.

    Runs the exact pipeline and ``epsilon``-bounded approximations on a
    64^3 smooth field (epsilon = 1% and 5% of the field range; the
    acceptance gate is >= 2x wall-clock speedup at 5%), machine-checks
    the guarantee (``bottleneck_feasible`` at the reported bound) for
    every run, and records the preview latency — time to the *first*
    progressive result, hierarchy construction included."""
    import numpy as np

    from repro.approx import bottleneck_feasible, refine
    from repro.core.grid import Grid
    from repro.pipeline import PersistencePipeline, TopoRequest

    dims = (32, 32, 32) if quick else (64, 64, 64)
    pcts = (0.05, 0.10) if quick else (0.01, 0.05)  # quick: coarser grid
    # needs a looser epsilon for the coarse path to engage in CI smoke
    g = Grid.of(*dims)
    f = _approx_bench_field(dims)
    frange = float(np.ptp(f))
    pipe = PersistencePipeline(backend="jax")
    req = TopoRequest(field=f, grid=g)

    pipe.run(req)                                 # warm: exact compile
    t0 = time.perf_counter()
    exact = pipe.run(req)
    exact_s = time.perf_counter() - t0

    runs = []
    for pct in pcts:
        eps = pct * frange
        pipe.run(req.replace(epsilon=eps))        # warm: level compile
        t0 = time.perf_counter()
        res = pipe.run(req.replace(epsilon=eps))
        s = time.perf_counter() - t0
        # the guarantee, machine-checked into the artifact
        guaranteed = all(
            bottleneck_feasible(res.pairs(p, min_persistence=0),
                                exact.pairs(p, min_persistence=0),
                                res.error_bound + 1e-9)
            for p in range(g.dim))
        assert guaranteed, f"bound violated at epsilon={pct:.0%} of range"
        runs.append({
            "epsilon_frac_of_range": pct, "epsilon": eps,
            "level": res.approx_level, "stride": res.approx_stride,
            "error_bound": res.error_bound,
            "seconds": s, "exact_seconds": exact_s,
            "speedup": exact_s / s,
            "bottleneck_guarantee_checked": guaranteed,
            "n_pairs_d0": int(len(res.pairs(0, min_persistence=0))),
        })

    # preview latency: time to the FIRST progressive result (hierarchy
    # build + coarsest level), on a warm cache
    for r in refine(pipe, req):
        break                                     # warm coarsest level
    t0 = time.perf_counter()
    preview = next(iter(refine(pipe, req)))
    preview_s = time.perf_counter() - t0

    doc = bench_doc(
        "ddms-approx-bench/v1", quick=quick,
        dims=list(dims), field_range=frange,
        exact_seconds=exact_s,
        preview={"seconds": preview_s,
                 "level": preview.approx_level,
                 "error_bound": preview.error_bound,
                 "speedup": exact_s / preview_s},
        runs=runs)
    write_bench(out_path, doc)
    print(f"wrote {out_path}: exact={exact_s*1e3:.0f}ms "
          f"preview={preview_s*1e3:.0f}ms "
          f"({exact_s/preview_s:.1f}x, bound={preview.error_bound:.3f})")
    for r in runs:
        print(f"  eps={r['epsilon_frac_of_range']:.0%} of range: "
              f"level={r['level']} bound={r['error_bound']:.4f} "
              f"{r['seconds']*1e3:.0f}ms speedup={r['speedup']:.2f}x "
              f"guarantee=checked")
    if not quick:
        at5 = next(r for r in runs
                   if r["epsilon_frac_of_range"] == 0.05)
        assert at5["speedup"] >= 2.0, \
            f"speedup {at5['speedup']:.2f}x at epsilon=5% below the 2x gate"
    return doc


def backend_bench(out_path, quick=False):
    """Sandwich back-end A/B (np reference vs jax kernels);
    BENCH_backend.json.

    Runs the full pipeline once per ``sandwich_backend`` on the 64^3
    bench field (compile caches warmed first), attributes wall time with
    the StageReport front/back split, machine-checks that the diagrams
    are bit-identical (pairs + essential classes, every dimension), and
    in full mode gates the back-end phase speedup at >= 5x."""
    import numpy as np

    from repro.core.diagram import diff_report, same_offdiagonal
    from repro.core.grid import Grid
    from repro.pipeline import PersistencePipeline, TopoRequest

    dims = (24, 24, 24) if quick else (64, 64, 64)
    g = Grid.of(*dims)
    f = _approx_bench_field(dims)
    req = TopoRequest(field=f, grid=g)

    runs, results = {}, {}
    for sb in ("jax", "np"):
        pipe = PersistencePipeline(backend="jax", sandwich_backend=sb)
        if sb == "jax":
            # warm: gradient front-end + bucketed D0 round compiles (the
            # np run reuses the shared gradient program via the plan
            # cache, so it is warm by construction)
            pipe.run(req)
        t0 = time.perf_counter()
        res = pipe.run(req)
        s = time.perf_counter() - t0
        rep = res.report
        runs[sb] = {
            "total_seconds": s,
            "front_seconds": rep.front_seconds,
            "back_seconds": rep.back_seconds,
            "stages": {c.name: c.total_seconds for c in rep.children}}
        results[sb] = res

    dn, dj = results["np"].diagram, results["jax"].diagram
    assert same_offdiagonal(dn, dj), diff_report(dn, dj, ("np", "jax"))
    for k in sorted(set(dn.pairs) | set(dj.pairs)):
        assert np.array_equal(dn.pairs[k], dj.pairs[k]), f"pairs[{k}]"
    for k in sorted(set(dn.essential) | set(dj.essential)):
        assert np.array_equal(dn.essential[k], dj.essential[k]), \
            f"essential[{k}]"

    back_speedup = runs["np"]["back_seconds"] / runs["jax"]["back_seconds"]
    doc = bench_doc(
        "ddms-backend-bench/v1", quick=quick,
        dims=list(dims),
        bit_identical=True,
        runs=runs,
        backend_speedup=back_speedup,
        end_to_end_speedup=(runs["np"]["total_seconds"]
                            / runs["jax"]["total_seconds"]))
    write_bench(out_path, doc)
    print(f"wrote {out_path}: back-end np={runs['np']['back_seconds']:.2f}s "
          f"jax={runs['jax']['back_seconds']:.2f}s "
          f"({back_speedup:.1f}x, bit-identical), "
          f"end-to-end {doc['end_to_end_speedup']:.2f}x")
    for sb in ("np", "jax"):
        st = runs[sb]["stages"]
        print(f"  {sb}: " + " ".join(
            f"{k}={v*1e3:.0f}ms" for k, v in st.items()))
    if not quick:
        assert back_speedup >= 5.0, \
            f"back-end speedup {back_speedup:.2f}x below the 5x gate"
    return doc


def _scale_point(spec, timeout=3600):
    """Run one scaling point in a subprocess (scale_worker.py): the
    forced host device count only takes effect before jax imports, so
    every point gets a fresh interpreter."""
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    worker = str(Path(__file__).parent / "scale_worker.py")
    r = subprocess.run([sys.executable, worker, json.dumps(spec)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    if r.returncode != 0:
        raise RuntimeError(f"scale worker failed for {spec}:\n"
                           f"{r.stderr[-2000:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def scale_bench(out_path, quick=False):
    """Weak/strong scaling of the sharded-streaming front-end;
    BENCH_scale.json.

    Each point runs in its own subprocess with ``--xla_force_host_
    platform_device_count=N`` so shard workers pin to distinct host
    devices.  Efficiency is *slots-normalized*: with only ``slots =
    min(N, cpu_count)`` cores, N shards can speed up at most ``slots``x,
    so weak efficiency is ``(N * T1) / (slots * TN)`` and strong
    efficiency ``T1 / (slots * TN)`` — on a 1-core box both reduce to
    "does sharding add overhead", on an N-core box to the classical
    definitions.  Timings cover the sharded *front-end* phase (the
    sandwich back-end is shard-count-independent).

    Also records the bit-identity cross-check (memmap-streamed sharded
    diagram == in-memory diagram) and, in full mode, a 256^3
    memmap-streamed sharded run plus the >= 60% weak-scaling efficiency
    gate at 4 shards."""
    import os
    import tempfile

    import numpy as np

    from repro.core.diagram import diff_report, same_offdiagonal
    from repro.core.grid import Grid
    from repro.fields import make_field
    from repro.pipeline import PersistencePipeline, TopoRequest
    from repro.stream import MemmapSource

    cpu = os.cpu_count() or 1
    shard_counts = (1, 2, 4) if quick else (1, 2, 4, 8)
    base = (32, 32, 16) if quick else (64, 64, 16)
    strong_dims = (32, 32, 32) if quick else (64, 64, 64)
    chunk_z = 8

    def efficiency(points, weak):
        t1 = points[0]["wall_s"]
        for p in points:
            n = p["n_shards"]
            slots = min(n, cpu)
            ideal = t1 * (n if weak else 1) / slots
            p["slots"] = slots
            p["efficiency"] = ideal / p["wall_s"]

    weak_points = []
    for n in shard_counts:
        dims = (base[0], base[1], base[2] * n)
        p = _scale_point({"dims": dims, "n_shards": n, "chunk_z": chunk_z,
                          "field": "wavelet", "reps": 1})
        weak_points.append(p)
        print(f"  weak  x{n}: dims={dims} wall={p['wall_s']:.2f}s "
              f"ofrac={p['overlap_fraction']}")
    efficiency(weak_points, weak=True)

    strong_points = []
    for n in shard_counts:
        p = _scale_point({"dims": strong_dims, "n_shards": n,
                          "chunk_z": chunk_z, "field": "wavelet",
                          "reps": 1})
        strong_points.append(p)
        print(f"  strong x{n}: dims={strong_dims} wall={p['wall_s']:.2f}s "
              f"ofrac={p['overlap_fraction']}")
    efficiency(strong_points, weak=False)

    # full mode: one >= 256^3 memmap-streamed sharded run — the field
    # file exists on disk only; each shard keeps ~2 ghost-extended
    # chunks resident
    memmap_large = None
    if not quick:
        big = (256, 256, 256)
        with tempfile.TemporaryDirectory() as td:
            path = os.path.join(td, "big.raw")
            MemmapSource.write(path,
                               make_field("wavelet", big, seed=0)
                               .reshape(big[::-1]))
            memmap_large = _scale_point(
                {"dims": big, "n_shards": 4, "chunk_z": 8,
                 "memmap": path, "warm": False, "reps": 1},
                timeout=7200)
        field_bytes = big[0] * big[1] * big[2] * 4
        memmap_large["field_bytes"] = field_bytes
        # out-of-core contract: <= 2 ghost-extended chunks resident per
        # shard, and well under the field itself
        assert memmap_large["peak_resident_field_bytes"] \
            <= 4 * 2 * memmap_large["max_chunk_bytes"]
        assert memmap_large["peak_resident_field_bytes"] < field_bytes / 2
        print(f"  memmap {big} x4: wall={memmap_large['wall_s']:.1f}s "
              f"resident={fmt_bytes(memmap_large['peak_resident_field_bytes'])}"
              f" of {fmt_bytes(field_bytes)}")

    # bit-identity cross-check: memmap-streamed sharded diagram vs the
    # in-memory single-device diagram, full pipeline.  It runs in this
    # process, so it comes after every worker subprocess: a parent that
    # has touched JAX holds the accelerator its children would need
    check_dims = (32, 32, 32) if quick else (64, 64, 64)
    g = Grid.of(*check_dims)
    f = make_field("wavelet", check_dims, seed=0)
    pipe = PersistencePipeline(backend="jax")
    ref = pipe.diagram(f, grid=g)
    with tempfile.TemporaryDirectory() as td:
        src = MemmapSource.write(os.path.join(td, "f.raw"),
                                 f.reshape(check_dims[::-1]))
        res = pipe.run(TopoRequest(field=src, stream=True, chunk_z=chunk_z,
                                   n_blocks=4))
    assert same_offdiagonal(res.diagram, ref.diagram), \
        diff_report(res.diagram, ref.diagram)
    for p in range(g.dim + 1):
        assert np.array_equal(res.diagram.essential_orders(p),
                              ref.diagram.essential_orders(p))
    bit_identity = {
        "dims": list(check_dims), "n_shards": int(res.stream.n_shards),
        "source": "memmap", "checked": True,
        "peak_resident_field_bytes":
            int(res.stream.peak_resident_field_bytes)}
    print(f"  bit-identity {check_dims} x{res.stream.n_shards} memmap: OK")

    doc = bench_doc(
        "ddms-scale-bench/v1", quick=quick,
        cpu_count=cpu, chunk_z=chunk_z,
        weak={"base_dims_per_shard": list(base), "points": weak_points},
        strong={"dims": list(strong_dims), "points": strong_points},
        bit_identity=bit_identity,
        memmap_large=memmap_large)
    write_bench(out_path, doc)
    print(f"wrote {out_path}: {len(weak_points)} weak + "
          f"{len(strong_points)} strong points (cpu_count={cpu})")
    for label, pts in (("weak", weak_points), ("strong", strong_points)):
        print(f"  {label}: " + " ".join(
            f"x{p['n_shards']}={p['wall_s']:.2f}s(eff {p['efficiency']:.2f})"
            for p in pts))
    if not quick:
        at4 = next(p for p in weak_points if p["n_shards"] == 4)
        assert at4["efficiency"] >= 0.60, \
            (f"weak-scaling efficiency {at4['efficiency']:.2f} at 4 shards "
             f"below the 0.60 gate")
    return doc


def obs_bench(out_path, quick=False, trace_out=None):
    """Observability layer: overhead gate + traced timeline + stall
    fault injection + exposition schema; BENCH_obs.json.

    Machine-checked properties:

    - **disabled overhead < 3%** (gated in full mode): interleaved A/B
      of the warmed in-memory pipeline with the obs layer hard-killed
      (``set_enabled(False)``) against the shipping default — enabled,
      untraced, with the **always-on flight recorder** receiving every
      stage/span event.  The enabled hot path is ``current_trace() is
      None`` checks plus ring-slot stores, so min-of-N must stay
      within 3%.
    - **the traced sharded-stream timeline**: ``TopoRequest(stream=
      True, n_blocks=4, trace=True)`` on a 32^3 field must export
      valid Perfetto ``trace_event`` JSON (schema + nesting validated)
      with >= 4 named threads, show a ``halo_recv`` span overlapping a
      ``chunk_compute`` span (the receives hide behind compute — the
      point of the eager-publish design), and produce a diagram
      bit-identical to the untraced run.
    - **stall fault injection**: the same sharded-stream run with two
      shards' slab reads wedged behind an event; a
      :class:`ProgressWatchdog` must emit a stall report naming a
      shard/halo lane *and* a flight-recorder dump within a few poll
      intervals of the deadline (gated in full mode), and the run must
      complete bit-identically once the wedge is released.
    - **exposition schema**: a ``TopoService(metrics_port=0)`` scrape
      must parse under ``parse_prometheus_text`` (cumulative
      histogram buckets closed by ``+Inf == _count``) and expose the
      dotted ``service.*`` families.

    Also snapshots the global metrics registry (plan-cache and pairing
    round counters, stream byte counters) and a live ``TopoService``
    stats sample (queue-depth gauge, batch-size / request-latency
    histogram percentiles)."""
    import tempfile
    import threading
    import urllib.request

    import numpy as np

    from repro.core.diagram import diff_report, same_offdiagonal
    from repro.core.grid import Grid
    from repro.fields import make_field
    from repro.obs import (ProgressWatchdog, global_metrics,
                           parse_prometheus_text, set_dump_dir,
                           set_enabled, spans_overlap, thread_names,
                           validate_trace_events)
    from repro.obs import flight as flight_mod
    from repro.pipeline import PersistencePipeline, TopoRequest
    from repro.serve import TopoService
    from repro.stream import ArraySource

    # ---- disabled-overhead gate -------------------------------------
    dims = (16, 16, 16) if quick else (32, 32, 32)
    reps = 3 if quick else 7
    g = Grid.of(*dims)
    f = make_field("wavelet", dims, seed=0)
    pipe = PersistencePipeline(backend="jax")
    req = TopoRequest(field=f, grid=g)
    pipe.run(req)                        # warm: compile out of the loop
    t_killed, t_normal = [], []
    try:
        for i in range(reps):            # interleaved, order alternated
            order = [(False, t_killed), (True, t_normal)]
            if i % 2:
                order.reverse()
            for enabled, sink in order:
                set_enabled(enabled)
                st, _ = timed(lambda: pipe.run(req))
                sink.append(st["min_s"])
    finally:
        set_enabled(True)
    overhead = min(t_normal) / min(t_killed) - 1.0
    print(f"  disabled-overhead: killed={min(t_killed)*1e3:.2f}ms "
          f"normal={min(t_normal)*1e3:.2f}ms overhead={overhead*100:.2f}%")

    # ---- traced sharded-stream timeline -----------------------------
    sdims = (32, 32, 32)
    sg = Grid.of(*sdims)
    sf = make_field("wavelet", sdims, seed=1)
    src = ArraySource(sf.reshape(sdims[::-1]))
    sreq = TopoRequest(field=src, stream=True, chunk_z=4, n_blocks=4,
                       trace=True)
    ref = pipe.run(sreq.replace(trace=False))    # warm + untraced ref
    overlapped, names, res, tdoc = False, {}, None, None
    for attempt in range(3):   # thread scheduling can serialize a tiny
        # run; the overlap is a property of the design, so retry
        res = pipe.run(sreq)
        tdoc = res.trace.to_dict()
        validate_trace_events(tdoc)
        names = thread_names(tdoc)
        overlapped = spans_overlap(tdoc, "halo_recv", "chunk_compute")
        if overlapped:
            break
    assert len(names) >= 4, f"expected >= 4 named threads, got {names}"
    assert overlapped, \
        "no halo_recv span overlaps any chunk_compute span in 3 runs"
    assert same_offdiagonal(res.diagram, ref.diagram), \
        diff_report(res.diagram, ref.diagram, ("traced", "untraced"))
    for p in range(sg.dim + 1):
        assert np.array_equal(res.diagram.essential_orders(p),
                              ref.diagram.essential_orders(p))
    trace_path = trace_out or str(
        Path(out_path).with_name(Path(out_path).stem + "_trace.trace.json"))
    res.trace.to_perfetto(trace_path)
    n_spans = sum(1 for ev in tdoc["traceEvents"] if ev.get("ph") == "X")
    span_names = sorted({ev["name"] for ev in tdoc["traceEvents"]
                         if ev.get("ph") == "X"})
    print(f"  trace: {n_spans} spans on {len(names)} named threads -> "
          f"{trace_path} (halo_recv x chunk_compute overlap: OK, "
          f"bit-identical: OK)")

    # ---- fault injection: stalled shard -> watchdog + flight dump ---
    release = threading.Event()
    stall_z0 = sdims[2] // 2             # wedge the upper two shards

    class StallSource(ArraySource):
        def read_slab(self, z0, z1):
            if z0 >= stall_z0:
                release.wait()
            return super().read_slab(z0, z1)

    wd_deadline = 0.25
    dump_dir = tempfile.mkdtemp(prefix="obs_bench_flight_")
    set_dump_dir(dump_dir)
    flight_mod._LAST_DUMP.clear()
    wd = ProgressWatchdog(deadline_s=wd_deadline, poll_s=0.05)
    stall_res = {}

    def stalled_run():
        stall_res["res"] = pipe.run(
            sreq.replace(field=StallSource(sf.reshape(sdims[::-1])),
                         trace=False))
    t0 = time.perf_counter()
    try:
        with wd:
            runner = threading.Thread(target=stalled_run,
                                      name="stalled-run")
            runner.start()
            while not wd.reports and time.perf_counter() - t0 < 30.0:
                time.sleep(0.01)
            detect_s = time.perf_counter() - t0
            release.set()
            runner.join(timeout=120)
    finally:
        release.set()
        set_dump_dir(None)
    assert wd.reports, "watchdog never reported the wedged shard"
    rpt = wd.reports[0]
    assert rpt["lane"].startswith(("stream.", "halo.")), rpt["lane"]
    assert rpt.get("flight_dump"), "stall fired no flight dump"
    assert all(os.path.exists(p) for p in rpt["flight_dump"])
    assert same_offdiagonal(stall_res["res"].diagram, ref.diagram), \
        "released run diverged from the clean reference"
    print(f"  stall-injection: lane {rpt['lane']!r} reported in "
          f"{detect_s*1e3:.0f}ms (deadline {wd_deadline*1e3:.0f}ms), "
          f"flight dump: {os.path.basename(rpt['flight_dump'][1])}")
    if not quick:
        assert detect_s < wd_deadline * 6 + 1.0, \
            (f"stall detected in {detect_s:.2f}s — too slow for a "
             f"{wd_deadline:.2f}s deadline")

    # ---- metrics + service sample + exposition scrape ---------------
    gm = global_metrics().snapshot()
    with TopoService(pipeline=pipe, max_batch=4, max_wait_s=0.05,
                     metrics_port=0) as svc:
        futs = [svc.submit(TopoRequest(field=make_field("wavelet", dims,
                                                        seed=s), grid=g))
                for s in range(4)]
        for fu in futs:
            fu.result(timeout=120)
        service_stats = svc.stats()
        body = urllib.request.urlopen(svc.metrics_server.url,
                                      timeout=10).read().decode()
    families = parse_prometheus_text(body)   # raises on schema breakage
    assert "service_request_latency_s" in families, sorted(families)
    lat = families["service_request_latency_s"]["samples"]
    assert lat["service_request_latency_s_count"] >= 4
    print(f"  exposition: {len(families)} families scraped + "
          f"schema-validated")

    doc = bench_doc(
        "ddms-obs-bench/v2", quick=quick,
        dims=list(dims), reps=reps,
        disabled_overhead={
            "killed_min_s": min(t_killed), "normal_min_s": min(t_normal),
            "killed_s": t_killed, "normal_s": t_normal,
            "overhead_frac": overhead, "gate": 0.03,
            "gated": not quick},
        traced_stream={
            "dims": list(sdims), "n_blocks": 4, "chunk_z": 4,
            "attempts": attempt + 1, "n_spans": n_spans,
            "span_names": span_names,
            "thread_names": sorted(names.values()),
            "halo_recv_overlaps_chunk_compute": overlapped,
            "bit_identical": True,
            "trace_path": str(trace_path)},
        stall_injection={
            "deadline_s": wd_deadline, "detect_s": detect_s,
            "lane": rpt["lane"],
            "flight_dump": [os.path.basename(p)
                            for p in rpt["flight_dump"]],
            "released_run_bit_identical": True,
            "gated": not quick},
        exposition={
            "families": len(families),
            "service_families": sorted(f for f in families
                                       if f.startswith("service_")),
            "latency_count":
                lat["service_request_latency_s_count"]},
        flight={"event_count": flight_mod.default_recorder().event_count(),
                "capacity": flight_mod.DEFAULT_CAPACITY},
        global_metrics=gm,
        service_stats=service_stats)
    write_bench(out_path, doc)
    print(f"wrote {out_path}: overhead={overhead*100:.2f}% "
          f"(gate 3%{'' if not quick else ', not gated in quick mode'}), "
          f"{len(names)} threads, stall detect={detect_s*1e3:.0f}ms, "
          f"service p50 latency="
          f"{service_stats['metrics']['request_latency_s']['p50']*1e3:.1f}ms")
    if not quick:
        assert overhead < 0.03, \
            f"tracing-disabled overhead {overhead*100:.2f}% exceeds 3%"
    return doc


def _serve_bench_fields(dims, n, seed=7):
    """``n`` distinct smooth fields of one shape: the approx-bench
    two-blob base plus a per-field low-frequency perturbation, so every
    field has its own cache key while staying coarse-level-friendly
    (degraded requests can actually be answered from a coarse level)."""
    import numpy as np
    base = _approx_bench_field(dims)
    nz, ny, nx = dims[::-1]
    z, y, x = np.meshgrid(np.linspace(0, 1, nz), np.linspace(0, 1, ny),
                          np.linspace(0, 1, nx), indexing="ij")
    out = []
    for i in range(n):
        ph = 0.37 * (i + seed)
        f = base + 0.05 * np.sin(2 * np.pi * (x + ph)) \
            * np.cos(2 * np.pi * (y - ph))
        out.append(np.ascontiguousarray(f, dtype=np.float32))
    return out


def _pctl(xs, q):
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(q * len(xs)))]


def serve_bench(out_path, quick=False):
    """Cached-serving traffic storm (repro.cache); BENCH_serve.json.

    Phases, each feeding the artifact *and* an in-benchmark gate:

    1. **miss vs hit latency** — closed-loop single requests against a
       cache-enabled ``TopoService``; cold misses compute + store, warm
       repeats decode the stored wire payload.  Full mode gates hit
       p50 at >= 10x faster than miss p50.
    2. **epsilon-aware reuse** — an epsilon request served by the
       *exact* entry stored in phase 1 (bound 0 serves any budget), and
       a looser-epsilon request served by a previously stored
       tighter-bound *approximate* entry.  Gate: both are cache hits.
    3. **progressive upgrade** — a progressive submit refines
       coarse-to-fine and each refinement tightens the cache entry in
       place; a later exact request hits the upgraded entry.
    4. **storm** — a burst of mixed requests (exact / epsilon /
       repeats) against a small ``degrade_depth`` with shedding
       disabled: under pressure deadline-less requests degrade to
       bounded-error answers.  Gates: degraded > 0, unhandled errors
       == 0, hits > 0.
    5. **shed probe** — a zero-threshold policy rejects with
       ``ServiceOverloadedError`` + retry hint (typed, not a crash).

    Every result served from the cache is validated against a fresh
    computation: exact payloads byte-identical, approximate diagrams
    within their stamped bound (``bottleneck_feasible``)."""
    import numpy as np

    from repro.approx import bottleneck_feasible
    from repro.cache import AdmissionPolicy, DiagramCache, \
        ServiceOverloadedError
    from repro.pipeline import PersistencePipeline, TopoRequest
    from repro.serve import TopoService

    dims = (16, 16, 16) if quick else (48, 48, 48)
    n_fields = 4 if quick else 8
    hit_reps = 5 if quick else 20
    storm_unique = 6 if quick else 12
    storm_total = 30 if quick else 96

    fields = _serve_bench_fields(dims, max(n_fields, storm_unique))
    pipe = PersistencePipeline(backend="jax")
    cache = DiagramCache(max_bytes=256 << 20)

    # -- phase 1: miss vs hit latency (max_wait_s=0: no batching pad) --
    miss_s, hit_s = [], []
    with TopoService(pipe, cache=cache, max_wait_s=0.0) as svc:
        svc.diagram(fields[0])                    # warm: compile
        cache.clear()
        for f in fields[:n_fields]:
            t0 = time.perf_counter()
            svc.diagram(f)
            miss_s.append(time.perf_counter() - t0)
        for _ in range(hit_reps):
            for f in fields[:n_fields]:
                t0 = time.perf_counter()
                svc.diagram(f)
                hit_s.append(time.perf_counter() - t0)
        phase1 = dict(svc.stats.as_dict())
    assert phase1["cache_hits"] == n_fields * hit_reps, phase1
    # exact-hit validation: the stored payload is byte-identical to a
    # fresh computation of the same request
    key0 = TopoRequest(field=fields[0]).cache_key()
    fresh = pipe.run(TopoRequest(field=fields[0]))
    assert cache.peek(key0).payload == fresh.to_bytes(), \
        "cached exact payload differs from a fresh computation"

    # -- phase 2: epsilon-aware reuse ----------------------------------
    frange = float(np.ptp(fields[0]))
    # 10% of range engages the coarse hierarchy at the full-mode 48^3
    # (the coarsest level's provable bound is ~7% of range there), so
    # the 20% request is answered by a stored *approximate* entry
    eps_small, eps_big = 0.10 * frange, 0.20 * frange
    reuse = {}
    with TopoService(pipe, cache=cache, max_wait_s=0.0) as svc:
        # (a) the exact phase-1 entry answers an epsilon request
        res_a = svc.diagram(TopoRequest(field=fields[0], epsilon=eps_big))
        reuse["exact_serves_epsilon"] = svc.stats.cache_hits == 1
        assert reuse["exact_serves_epsilon"], svc.stats.as_dict()
        assert res_a.error_bound in (None, 0.0)   # got the exact answer
        # (b) a tighter approximate entry answers a looser request:
        # compute+store at eps_small on an uncached field, re-ask at
        # eps_big — served from the stored entry iff its stamped bound
        # fits the looser budget
        f_new = fields[n_fields]    # never touched by phase 1
        r1 = svc.diagram(TopoRequest(field=f_new, epsilon=eps_small))
        hits_before = svc.stats.cache_hits
        r2 = svc.diagram(TopoRequest(field=f_new, epsilon=eps_big))
        reuse["tighter_bound_serves_looser"] = \
            svc.stats.cache_hits == hits_before + 1
        assert reuse["tighter_bound_serves_looser"], svc.stats.as_dict()
        reuse["stored_bound"] = r1.error_bound
        # approximate-hit validation: within the stamped bound of a
        # fresh exact computation
        exact_new = pipe.run(TopoRequest(field=f_new))
        bound = (r2.error_bound or 0.0) + 1e-9
        ok = all(bottleneck_feasible(
            r2.pairs(p, min_persistence=0),
            exact_new.pairs(p, min_persistence=0), bound)
            for p in range(3))
        assert ok, "cached approximate result violates its bound"
        reuse["bound_checked"] = ok

    # -- phase 3: progressive refinement upgrades the entry in place ---
    f_prog = _serve_bench_fields(dims, 1, seed=101)[0]
    with TopoService(pipe, cache=cache, max_wait_s=0.0) as svc:
        ups_before = cache.stats()["upgrades"]
        svc.submit(TopoRequest(field=f_prog, progressive=True)).result()
        upgrades = cache.stats()["upgrades"] - ups_before
        hits_before = svc.stats.cache_hits
        svc.diagram(f_prog)      # exact request hits the refined entry
        prog_hit = svc.stats.cache_hits == hits_before + 1
    assert upgrades > 0, "progressive refinement never tightened its entry"
    assert prog_hit, "exact request missed the fully-refined entry"

    # -- phase 4: the storm --------------------------------------------
    storm_fields = fields[:storm_unique]
    policy = AdmissionPolicy(degrade_depth=2, shed_depth=None,
                             degrade_frac=0.10)
    storm_cache = DiagramCache(max_bytes=256 << 20)
    rng = np.random.default_rng(3)
    kinds = rng.integers(0, 3, size=storm_total)      # 0 exact, 1 eps, 2 rep
    prog = set(range(0, storm_total, 16))             # sprinkle progressive
    t0 = time.perf_counter()
    with TopoService(pipe, cache=storm_cache, admission=policy) as svc:
        futs = []
        for i in range(storm_total):
            f = storm_fields[i % storm_unique]
            if i in prog:    # preview-then-refine client in the mix
                futs.append(svc.submit(
                    TopoRequest(field=f, progressive=True)))
            elif kinds[i] == 1:
                futs.append(svc.submit(
                    TopoRequest(field=f, epsilon=eps_big)))
            else:   # exact (and its repeats: the cache-hit population)
                futs.append(svc.submit(f))
        results = [ft.result() for ft in futs]    # no exception may escape
        storm_stats = dict(svc.stats.as_dict())
    storm_s = time.perf_counter() - t0
    assert storm_stats["errors"] == 0, storm_stats
    assert storm_stats["degraded"] > 0, \
        f"storm never triggered degradation: {storm_stats}"
    assert storm_stats["cache_hits"] > 0, storm_stats
    # storm validation: every result is exact-identical or within its
    # stamped bound vs a fresh exact computation of its field
    exact_by_id = {id(f): pipe.run(TopoRequest(field=f))
                   for f in storm_fields}
    checked = dict(exact=0, bounded=0)
    for i, res in enumerate(results):
        ex = exact_by_id[id(storm_fields[i % storm_unique])]
        b = res.error_bound or 0.0
        if b == 0.0:
            same = all(np.array_equal(res.pairs(p, min_persistence=0),
                                      ex.pairs(p, min_persistence=0))
                       for p in range(3))
            assert same, f"storm result {i}: exact answer differs"
            checked["exact"] += 1
        else:
            ok = all(bottleneck_feasible(res.pairs(p, min_persistence=0),
                                         ex.pairs(p, min_persistence=0),
                                         b + 1e-9)
                     for p in range(3))
            assert ok, f"storm result {i}: bound {b} violated"
            checked["bounded"] += 1

    # -- phase 5: shed probe -------------------------------------------
    shed_policy = AdmissionPolicy(degrade_depth=0, shed_depth=0)
    with TopoService(pipe, admission=shed_policy) as svc:
        try:
            svc.diagram(fields[0])
            raise AssertionError("zero-threshold policy failed to shed")
        except ServiceOverloadedError as e:
            shed = {"queue_depth": e.queue_depth,
                    "retry_after_s": e.retry_after_s,
                    "shed_count": svc.stats.shed}
    assert shed["shed_count"] == 1

    miss_p50, hit_p50 = _pctl(miss_s, 0.5), _pctl(hit_s, 0.5)
    doc = bench_doc(
        "ddms-serve-bench/v1", quick=quick,
        dims=list(dims),
        latency={"miss": {"n": len(miss_s), "p50_s": miss_p50,
                          "p99_s": _pctl(miss_s, 0.99)},
                 "hit": {"n": len(hit_s), "p50_s": hit_p50,
                         "p99_s": _pctl(hit_s, 0.99)},
                 "hit_speedup_p50": miss_p50 / hit_p50},
        epsilon_reuse=reuse,
        progressive={"upgrades": upgrades, "exact_hit_after": prog_hit},
        storm={"requests": storm_total, "unique_fields": storm_unique,
               "progressive_requests": len(prog),
               "seconds": storm_s, "stats": storm_stats,
               "hit_rate": storm_stats["cache_hits"] / storm_total,
               "validated": checked},
        shed=shed,
        cache=cache.stats())
    write_bench(out_path, doc)
    print(f"wrote {out_path}: miss p50={miss_p50*1e3:.1f}ms "
          f"hit p50={hit_p50*1e3:.2f}ms "
          f"({miss_p50/hit_p50:.0f}x); storm {storm_total} reqs in "
          f"{storm_s:.2f}s: hits={storm_stats['cache_hits']} "
          f"degraded={storm_stats['degraded']} errors=0; "
          f"validated exact={checked['exact']} bounded={checked['bounded']}")
    if not quick:
        assert miss_p50 >= 10.0 * hit_p50, \
            (f"cache hits not >= 10x faster: miss p50 {miss_p50*1e3:.2f}ms "
             f"vs hit p50 {hit_p50*1e3:.2f}ms")
        # full mode must demonstrate *approximate*-entry reuse, not just
        # exact-serves-everything (quick's tiny grid may lack a coarse
        # level that qualifies)
        assert reuse["stored_bound"], \
            "phase 2 never stored a genuinely approximate entry"
        assert checked["bounded"] > 0, \
            "storm produced no bound-checked approximate answers"
    return doc


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="results/dryrun")
    ap.add_argument("--section", default="all",
                    choices=["all", "roofline", "dryrun", "pipeline",
                             "gradient", "stream", "api", "approx",
                             "backend", "scale", "obs", "serve"])
    ap.add_argument("--out", default=None,
                    help="output path for --section "
                         "pipeline/gradient/stream/api/approx/backend")
    ap.add_argument("--quick", action="store_true",
                    help="small sizes for CI smoke "
                         "(gradient/stream/api/approx/backend/obs)")
    ap.add_argument("--trace-out", default=None,
                    help="Perfetto trace path for --section obs "
                         "(default <out>_trace.trace.json)")
    args = ap.parse_args()
    from repro.compile_cache import use_checkout_cache
    use_checkout_cache(Path(__file__).resolve().parents[1])
    if args.section == "pipeline":
        pipeline_bench(args.out or "BENCH_pipeline.json")
        return
    if args.section == "gradient":
        gradient_bench(args.out or "BENCH_gradient.json", quick=args.quick)
        return
    if args.section == "stream":
        stream_bench(args.out or "BENCH_stream.json", quick=args.quick)
        return
    if args.section == "api":
        api_bench(args.out or "BENCH_api.json", quick=args.quick)
        return
    if args.section == "approx":
        approx_bench(args.out or "BENCH_approx.json", quick=args.quick)
        return
    if args.section == "backend":
        backend_bench(args.out or "BENCH_backend.json", quick=args.quick)
        return
    if args.section == "scale":
        scale_bench(args.out or "BENCH_scale.json", quick=args.quick)
        return
    if args.section == "obs":
        obs_bench(args.out or "BENCH_obs.json", quick=args.quick,
                  trace_out=args.trace_out)
        return
    if args.section == "serve":
        serve_bench(args.out or "BENCH_serve.json", quick=args.quick)
        return
    recs = load(args.dir)
    if args.section in ("all", "dryrun"):
        print("### Dry-run status (all cells)\n")
        print(dryrun_table(recs))
        print()
    if args.section in ("all", "roofline"):
        print("### Roofline (single-pod, per device)\n")
        print(roofline_table(recs, "single"))


if __name__ == "__main__":
    main()
