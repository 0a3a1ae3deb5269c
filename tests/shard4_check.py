"""Four-slab shardmap backend checks — run as a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=4 (see test_shard4.py).

Prints one JSON line: the shardmap rows program's fields against the
host scatter of the whole grid's rows, its diagrams through
``PersistencePipeline(backend="shardmap", n_blocks=4)`` against
``compute_dms`` and ``bench/reference.py``, the plan cache's compile
count, and the error for a z extent that four slabs do not divide."""

import json
import os
import sys

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                           + os.environ.get("XLA_FLAGS", ""))
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT, HERE]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import compare  # noqa: E402
from repro.core import gradient as GR  # noqa: E402
from repro.core.dms import compute_dms  # noqa: E402
from repro.core.grid import Grid, vertex_order  # noqa: E402
from repro.fields.generators import make_field  # noqa: E402
from repro.kernels.lower_star import fused_words, host_rows  # noqa: E402
from repro.pipeline import (PersistencePipeline, TopoRequest,  # noqa: E402
                            get_backend)
from repro.pipeline.plan import default_plan_cache  # noqa: E402
from repro.pipeline.backends import _shardmap_rows_fn  # noqa: E402

FIELD_GRIDS = [(16, 12, 8), (10, 7, 12), (8, 8, 4)]
FAMILIES = ["random", "wavelet"]
DIAGRAM_DIMS = (16, 16, 16)


def fields_checks() -> dict:
    """Both families as one batch of 2 per grid: every array and dtype,
    and the critical count, against the host scatter of the rows the
    one-chip fused kernel gives for the whole grid."""
    out = {}
    for dims in FIELD_GRIDS:
        g = Grid.of(*dims)
        orders = np.stack([np.asarray(vertex_order(
            make_field(fam, dims, seed=1).reshape(-1))) for fam in FAMILIES])
        gfs, counts = _shardmap_rows_fn(g, 4)(orders)
        rows = host_rows([np.asarray(w) for w in fused_words(g, orders)],
                         g.dims[1], g.dims[0])
        host = GR.scatter_results_batch(
            g, *(r[:2 * g.nv] for r in rows), 2)
        for b, fam in enumerate(FAMILIES):
            bad = [f"{name}[{k}]" for name in ("pair_up", "pair_down", "crit")
                   for k, want in getattr(host[b], name).items()
                   if getattr(gfs[b], name)[k].dtype != want.dtype
                   or not np.array_equal(getattr(gfs[b], name)[k], want)]
            if counts[b] != sum(host[b].n_critical().values()):
                bad.append("n_critical")
            out[f"{'x'.join(map(str, dims))}-{fam}"] = bad
    return out


def words_check() -> bool:
    """The program for grids whose sids need int64 gathers the slabs'
    kernel words: they equal the one-chip kernel's words of the grid."""
    from repro.distributed.shardmap_pipeline import slab_program
    g = Grid.of(10, 7, 12)
    orders = np.asarray(vertex_order(
        make_field("random", g.dims, seed=2).reshape(-1)))[None]
    mesh = jax.make_mesh((4,), ("blocks",))
    got = slab_program(g, mesh, fields=False)(orders.astype(np.int32))
    want = fused_words(g, orders)
    return all(np.array_equal(np.asarray(a)[..., :7, :10],
                              np.asarray(b)[..., :7, :10])
               for a, b in zip(got, want))


def diagram_fields() -> dict:
    from shardmap_check import ridge_field
    dims = DIAGRAM_DIMS
    return {"random": make_field("random", dims, seed=11),
            "wavelet": make_field("wavelet", dims, seed=12),
            "ridge": ridge_field(dims, min_at_top=True)}


def diagram_checks() -> dict:
    """Points in one diagram and not the other, per field and reference,
    and what the runs counted."""
    g = Grid.of(*DIAGRAM_DIMS)
    cache = default_plan_cache()
    pipe = PersistencePipeline(backend="shardmap", n_blocks=4)
    out, stats = {}, {}
    fields = diagram_fields()
    for name, f in fields.items():
        res = pipe.run(TopoRequest(field=f, grid=g))
        got = compare.program_points(res.diagram)
        out[f"{name}-compute_dms"] = compare.mismatch(
            got, compare.program_points(compute_dms(g, f).diagram))
        out[f"{name}-reference"] = compare.mismatch(
            got, compare.reference_points(f, g.dims))
        stats[name] = {k: res.stats.get(k) for k in
                       ("device_fields", "n_blocks", "halo_bytes",
                        "d0_rounds", "d1_rounds")}
    key = (g.dims, "shardmap", 4)
    compiles = {"builds": cache.build_counts.get(key),
                "hits": cache.stats()["hits"],
                "jit_programs": cache.peek(key)._jit._cache_size()}
    # the backend's one-field gradient runs the same cached program
    order = np.asarray(vertex_order(fields["wavelet"]))
    gf = get_backend("shardmap").gradient(g, order, n_blocks=4)
    rows = host_rows([np.asarray(w) for w in fused_words(g, order)],
                     g.dims[1], g.dims[0])
    want = GR.scatter_results_batch(g, *rows, 1)[0]
    same = all(np.array_equal(getattr(gf, n)[k], v)
               for n in ("pair_up", "pair_down", "crit")
               for k, v in getattr(want, n).items())
    return {"mismatch": out, "stats": stats, "compiles": compiles,
            "gradient": {"same": same,
                         "builds": cache.build_counts.get(key)}}


def divisibility_error() -> str:
    g = Grid.of(8, 8, 6)
    try:
        PersistencePipeline(backend="shardmap", n_blocks=4).run(
            TopoRequest(field=make_field("random", g.dims, seed=0), grid=g))
    except ValueError as e:
        return str(e)
    return ""


def main():
    assert len(jax.devices()) == 4, jax.devices()
    result = {"fields": fields_checks(), "words": words_check()}
    result.update(diagram_checks())
    result["divisibility_error"] = divisibility_error()
    print(json.dumps(result))


if __name__ == "__main__":
    main()
