"""Closed-loop driver: whole diagrams of distinct volumes, back to back.

Set-up makes a pool of fields from ``(seed, i)`` and warms up with one
whole diagram of a field outside the pool, then with the D0 round
programs of the buckets next to those the warm-up used (see
:func:`warm_d0_neighbors`).  The window runs diagrams until ``seconds``
have passed and finishes the one in flight; it ends when that diagram
completes, so the rate counts all the work over all of the window's
time.  Fields past the pool are made on the fly, so a faster program
never repeats a field.

Traffic keys: ``family``, ``dims``, ``pool``, ``check_samples``.
Configuration keys: ``pipeline`` (``PersistencePipeline`` keywords).
"""

from __future__ import annotations

import time

import numpy as np

from bench import compare, fields


def _field(state, i):
    pool = state["pool"]
    if i < len(pool):
        return pool[i]
    t = state["traffic"]
    return fields.make(t["family"], t["dims"], state["seed"], i,
                       root=state["root"])


def warm_d0_neighbors() -> list:
    """Compile (or load from the cache) the D0 round programs one bucket
    either side of each the warm-up compiled.  The round is jitted per
    (saddles, extrema) bucket, and counts differ from field to field, so
    one warm-up diagram leaves a neighbouring bucket to compile inside
    the window.  Padded no-op inputs: no saddle steps or proposes.
    Returns the buckets warmed."""
    from repro.kernels import sandwich as S
    buckets = S._D0_BUCKETS

    def near(b):
        lower = [x for x in buckets if x < b]
        if b > buckets[-1]:
            lower.append(b - buckets[-1])
        return {b, S._bucket(b + 1)} | ({max(lower)} if lower else set())

    done = set(S._D0_ROUND_CACHE)
    warmed = []
    for n0, m0 in sorted(done):
        for n in sorted(near(n0)):
            for m in sorted(near(m0)):
                if (n, m) in done:
                    continue
                done.add((n, m))
                c = np.full(n, m - 1, np.int64)
                out = S._d0_round(n, m)(
                    c, c, np.full(n, -1, np.int64), np.zeros(m, np.int64),
                    np.arange(m, dtype=np.int64),
                    np.full(m, S.NOKEY, np.int64))
                [np.asarray(a) for a in out]
                warmed.append((n, m))
    return warmed


def setup(cell, seed, seconds):
    from repro.core.grid import Grid
    from repro.pipeline import PersistencePipeline, TopoRequest
    t = cell.traffic
    dims = tuple(int(d) for d in t["dims"])
    pool = [fields.make(t["family"], dims, seed, i, root=cell.root)
            for i in range(int(t["pool"]))]
    grid = Grid.of(*dims)
    pipe = PersistencePipeline(**cell.config.get("pipeline", {}))
    warm = fields.make(t["family"], dims, seed, -1 % 2 ** 32, root=cell.root)
    pipe.run(TopoRequest(field=warm, grid=grid))
    warm_d0_neighbors()
    return {"pipe": pipe, "grid": grid, "dims": dims, "pool": pool,
            "traffic": t, "seed": seed, "root": cell.root,
            "request": TopoRequest}


def window(state, seconds, span):
    pipe, grid, Req = state["pipe"], state["grid"], state["request"]
    results = []
    stage_seconds = {}
    with span("bench.window"):
        t0 = time.perf_counter()
        while True:
            f = _field(state, len(results))
            with span("bench.diagram"):
                res = pipe.run(Req(field=f, grid=grid))
            results.append(res)
            if time.perf_counter() - t0 >= seconds:
                break
        window_s = time.perf_counter() - t0
    for res in results:
        for c in res.report.children:
            stage_seconds.setdefault(c.name, []).append(c.seconds)
    n = len(results)
    return {"attempted": n, "failed": 0, "window_s": window_s,
            "end_to_end": {"exact_vertices_per_s":
                           n * grid.nv / window_s},
            "stage_seconds": stage_seconds, "nv": grid.nv,
            "answers": results}


def samples(state, out, seed):
    """The window's diagrams to compare, drawn from the seed."""
    results = out["answers"]
    k = min(int(state["traffic"]["check_samples"]), len(results))
    rng = np.random.default_rng([int(seed) % 2 ** 64, 1])
    pick = sorted(rng.choice(len(results), size=k, replace=False).tolist())
    return [{"label": f"diagram {i}", "field": _field(state, i),
             "dims": state["dims"],
             "points": compare.program_points(results[i].diagram)}
            for i in pick]


def close(state):
    state.clear()
