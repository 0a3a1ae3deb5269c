"""Subprocess worker for the scaling bench (``--section scale``).

One scaling point per process: forces ``n_shards`` host devices through
XLA_FLAGS *before* importing jax (the flag only takes effect at import),
streams the sharded front-end over a synthetic or memmap source, and
prints a single JSON line with the timing + comm accounting.  Run by
``benchmarks.report.scale_bench`` — not meant to be called by hand,
though it works:

    python benchmarks/scale_worker.py '{"dims": [64, 64, 64],
        "n_shards": 4, "chunk_z": 8, "field": "wavelet"}'
"""

import json
import os
import sys


def main():
    spec = json.loads(sys.argv[1])
    n = int(spec["n_shards"])
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n} "
        + os.environ.get("XLA_FLAGS", ""))
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

    import time

    from repro.compile_cache import use_checkout_cache
    from repro.stream import (FunctionSource, MemmapSource,
                              sharded_stream_front)

    use_checkout_cache(os.path.join(os.path.dirname(__file__), ".."))
    dims = tuple(int(d) for d in spec["dims"])
    if spec.get("memmap"):
        src = MemmapSource(spec["memmap"], dims)
    else:
        src = FunctionSource.synthetic(spec.get("field", "wavelet"), dims,
                                       seed=int(spec.get("seed", 0)))
    kw = {}
    if spec.get("chunk_z"):
        kw["chunk_z"] = int(spec["chunk_z"])
    else:
        kw["chunk_budget"] = int(spec.get("chunk_budget", 64 << 20))

    if spec.get("warm", True):
        # compile every chunk shape out of the timed run
        sharded_stream_front(src, n, **kw)
    best = None
    for _ in range(int(spec.get("reps", 1))):
        t0 = time.perf_counter()
        out = sharded_stream_front(src, n, **kw)
        dt = time.perf_counter() - t0
        if best is None or dt < best[0]:
            best = (dt, out.report)
    dt, rep = best
    nv = dims[0] * dims[1] * dims[2]
    print(json.dumps({
        "n_shards": rep.n_shards, "dims": list(dims),
        "wall_s": dt, "vertices_per_s": nv / dt,
        "load_s": rep.load_s, "compute_s": rep.compute_s,
        "scatter_s": rep.scatter_s,
        "comm_s": rep.comm_s, "comm_hidden_s": rep.comm_hidden_s,
        "overlap_fraction": rep.overlap_fraction,
        "n_chunks": rep.n_chunks,
        "peak_resident_field_bytes": rep.peak_resident_field_bytes,
        "max_chunk_bytes": rep.max_chunk_bytes,
        "per_shard_peak_bytes": [s["peak_resident_field_bytes"]
                                 for s in (rep.per_shard or [])],
    }))


if __name__ == "__main__":
    main()
