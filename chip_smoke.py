#!/usr/bin/env python3
"""Smoke run of the persistence pipeline on a TPU chip.

    python3 chip_smoke.py              # one chip: phases (a), (b), (c)
    python3 chip_smoke.py --chips 4    # four chips: the sharded phase only

Drives the main path through the entry points a user calls
(``PersistencePipeline`` / ``TopoRequest`` -> ``run``, and ``TopoService``)
with the platform's default backend, which on a TPU is the fused Pallas
kernel compiled with Mosaic, and checks every diagram against an
independent computation on the same field:

(a) a 12^3 random field against the boundary-matrix reduction oracle and
    the literal ``np`` gradient;
(b) a 256^3 float32 wavelet field (16.8M vertices) against the same field
    streamed in 8-plane chunks through the XLA ``jax`` kernel;
(c) 16 requests of 64^3 fields from 4 client threads through a
    ``TopoService``: at least one batched dispatch, no retries or errors,
    and every answer equal to ``pipe.run`` on its field.

``--chips 4`` runs a 256x256x512 wavelet field through the z-slab
``shard_map`` front (``backend="shardmap", n_blocks=4``, the compiled
kernel per slab) and compares it with the same field on one chip.

Earlier lines report per-phase seconds (program compiles timed apart),
the device kind and ``peak_bytes_in_use``.  The last line is one JSON
object naming the device.  Without a TPU the script fails before any
phase.
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import numpy as np  # noqa: E402

import repro  # noqa: E402,F401  (sets the process-wide x64 mode)
from repro.core.diagram import diff_report, same_offdiagonal  # noqa: E402
from repro.core.grid import Grid  # noqa: E402
from repro.fields import make_field  # noqa: E402
from repro.pipeline import PersistencePipeline, TopoRequest  # noqa: E402


def log(**kv) -> None:
    print(json.dumps(kv), flush=True)


def peak_bytes(devices) -> list:
    return [int(d.memory_stats()["peak_bytes_in_use"]) for d in devices]


def assert_same(a, b, names) -> None:
    """Off-diagonal pairs and essential classes in every dimension."""
    assert same_offdiagonal(a, b), diff_report(a, b, names)
    for p in range(a.grid.dim + 1):
        assert np.array_equal(a.essential_orders(p), b.essential_orders(p)), \
            f"{names}: essential classes differ in dimension {p}"


def field(name: str, dims, seed: int = 0) -> np.ndarray:
    return make_field(name, dims, seed=seed).astype(np.float32)


def warm_rows(pipe, grid, batch_sizes=(1,)) -> float:
    """Compile the pipeline's rows program for each batch bucket; the
    seconds are the compile (plus one run on an inert batch)."""
    ex = pipe.compile(TopoRequest(field=np.zeros(grid.nv, np.float32),
                                  grid=grid))
    t0 = time.perf_counter()
    for b in batch_sizes:
        ex.rows_program(np.full((b, grid.nv), -1, np.int64))
    return time.perf_counter() - t0


def phase_exact(dims=(12, 12, 12)) -> None:
    """(a) platform default vs the reduction oracle and the np backend."""
    from repro.core.dms import oracle_to_diagram
    from repro.core.reduction import compute_oracle
    g = Grid.of(*dims)
    f = field("random", dims)
    pipe = PersistencePipeline()
    compile_s = warm_rows(pipe, g)
    t0 = time.perf_counter()
    res = pipe.run(TopoRequest(field=f, grid=g))
    run_s = time.perf_counter() - t0
    ref = PersistencePipeline(backend="np").run(TopoRequest(field=f, grid=g))
    orc = oracle_to_diagram(compute_oracle(g, f), g)
    assert_same(res.diagram, ref.diagram, (pipe.backend.name, "np"))
    assert same_offdiagonal(res.diagram, orc), \
        diff_report(res.diagram, orc, (pipe.backend.name, "oracle"))
    log(phase="a_exact", dims=list(dims), backend=pipe.backend.name,
        compile_s=compile_s, run_s=run_s,
        pairs={p: len(res.diagram.points_order(p)) for p in range(g.dim)},
        matches=["oracle", "np"])


def phase_volume(dims=(256, 256, 256), chunk_z: int = 8) -> None:
    """(b) one large volume through the compiled kernel vs the streamed
    XLA chunk kernel on the same chip."""
    from repro.kernels import ops
    g = Grid.of(*dims)
    f = field("wavelet", dims)
    pipe = PersistencePipeline()
    compile_s = warm_rows(pipe, g)
    t0 = time.perf_counter()
    res = pipe.run(TopoRequest(field=f, grid=g))
    run_s = time.perf_counter() - t0
    stages = {c.name: c.seconds for c in res.report.children}

    nx, ny, _ = dims
    t0 = time.perf_counter()
    ops.lower_star_rows_halo(np.full((chunk_z + 2, ny, nx), -1, np.int64))
    stream_compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    ref = PersistencePipeline(backend="jax").run(
        TopoRequest(field=f, grid=g, stream=True, chunk_z=chunk_z))
    stream_s = time.perf_counter() - t0
    assert_same(res.diagram, ref.diagram, (pipe.backend.name, "stream-jax"))
    log(phase="b_volume", dims=list(dims), backend=pipe.backend.name,
        compile_s=compile_s, run_s=run_s, stages_s=stages,
        stream_backend="jax", stream_chunk_z=chunk_z,
        stream_compile_s=stream_compile_s, stream_s=stream_s,
        pairs={p: len(res.diagram.points_order(p)) for p in range(g.dim)},
        matches=["stream-jax"])


def phase_serve(dims=(64, 64, 64), n_requests: int = 16,
                n_clients: int = 4) -> None:
    """(c) a TopoService under concurrent clients, with batching."""
    from repro.serve import TopoService
    g = Grid.of(*dims)
    fields = [field("wavelet", dims, seed=s) for s in range(n_requests)]
    pipe = PersistencePipeline()
    compile_s = warm_rows(pipe, g, batch_sizes=(1, 2, 3, 4, 6, 8))
    futures = [None] * n_requests
    t0 = time.perf_counter()
    with TopoService(pipeline=pipe, max_batch=8, max_wait_s=0.05) as svc:
        def client(c):
            for i in range(c, n_requests, n_clients):
                futures[i] = svc.submit(fields[i], grid=g)
        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        results = [fut.result(timeout=600) for fut in futures]
        stats = svc.stats.as_dict()
    serve_s = time.perf_counter() - t0
    assert stats["errors"] == 0 and stats["retried"] == 0, stats
    assert stats["max_batch"] > 1, f"no batched dispatch: {stats}"
    for i, res in enumerate(results):
        ref = pipe.run(TopoRequest(field=fields[i], grid=g))
        assert_same(res.diagram, ref.diagram, (f"served[{i}]", "run"))
    log(phase="c_serve", dims=list(dims), backend=pipe.backend.name,
        compile_s=compile_s, serve_s=serve_s, stats=stats)


def phase_sharded(dims=(256, 256, 512), n_blocks: int = 4) -> None:
    """z-slab shard_map front on ``n_blocks`` chips vs one chip.  The two
    runs go at once (the sharded one in a thread): their host back-ends
    overlap, and the chips hold both programs' buffers."""
    import jax
    g = Grid.of(*dims)
    f = field("wavelet", dims)
    sharded = PersistencePipeline(backend="shardmap", n_blocks=n_blocks,
                                  distributed=False)
    one = PersistencePipeline()
    out = {}

    def run_sharded():
        t0 = time.perf_counter()
        try:
            out["res"] = sharded.run(TopoRequest(field=f, grid=g))
        except BaseException as e:      # re-raised in the main thread
            out["error"] = e
        out["seconds"] = time.perf_counter() - t0

    worker = threading.Thread(target=run_sharded)
    worker.start()
    t0 = time.perf_counter()
    ref = one.run(TopoRequest(field=f, grid=g))
    one_s = time.perf_counter() - t0
    worker.join()
    if "error" in out:
        raise out["error"]
    res = out["res"]
    peaks = peak_bytes(jax.devices())
    assert_same(res.diagram, ref.diagram, ("shardmap", one.backend.name))
    assert all(p > 0 for p in peaks[:n_blocks]), peaks
    log(phase="sharded", dims=list(dims), n_blocks=n_blocks,
        concurrent=True, sharded_s=out["seconds"], one_chip_s=one_s,
        one_chip_backend=one.backend.name, peak_bytes_per_device=peaks,
        sharded_stages_s={c.name: c.seconds for c in res.report.children},
        one_chip_stages_s={c.name: c.seconds for c in ref.report.children},
        pairs={p: len(res.diagram.points_order(p)) for p in range(g.dim)},
        matches=["one-chip"])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 2
    from repro.compile_cache import use_checkout_cache
    log(device_kind=devices[0].device_kind, count=len(devices),
        compile_cache=use_checkout_cache(ROOT))

    phases = [phase_sharded] if args.chips == 4 else \
        [phase_exact, phase_volume, phase_serve]
    for phase in phases:
        t0 = time.perf_counter()
        phase()
        log(phase_done=phase.__name__, seconds=time.perf_counter() - t0,
            peak_bytes_in_use=peak_bytes(devices))
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
