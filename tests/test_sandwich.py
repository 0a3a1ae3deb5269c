"""Sandwich back-end tests: np-reference vs jax-kernel parity across the
field zoo x 2D/3D x asymmetric/thin grids x streamed sources, the
dense edge keys against the per-sid formulation, the distributed D1 on
the extracted keys, the positive-highest-edge invariant on a corrupted gradient, compile-count
regression for the bucketed D0 round, and the new StageReport timing
split / `sandwich_backend` plumbing."""

import numpy as np
import pytest

from repro.core.diagram import diff_report, same_offdiagonal
from repro.core.grid import Grid
from repro.core.pairing import ExtremaPairs, pair_extrema_saddles
from repro.core.extremum_graph import ExtremumGraph
from repro.fields.generators import FIELDS, make_field
from repro.kernels.sandwich import (GradientInvariantError, TRACE_COUNTS,
                                    _rank_compress, edge_keys_kernel,
                                    pair_extrema_saddles_kernel,
                                    pair_saddle_saddle_wavefront)
from repro.pipeline import (PersistencePipeline, TopoRequest,
                            UnknownSandwichBackendError,
                            available_sandwich_backends,
                            get_sandwich_backend)
from repro.stream.chunks import pack_value_keys


def _run(field, dims, sandwich):
    pipe = PersistencePipeline("np", sandwich_backend=sandwich)
    return pipe.run(TopoRequest(field=field, grid=Grid.of(*dims)))


def _assert_identical(rn, rj, label):
    dn, dj = rn.diagram, rj.diagram
    assert same_offdiagonal(dn, dj), diff_report(dn, dj, ("np", "jax"))
    for k in sorted(set(dn.pairs) | set(dj.pairs)):
        assert np.array_equal(dn.pairs[k], dj.pairs[k]), (label, "pairs", k)
    for k in sorted(set(dn.essential) | set(dj.essential)):
        assert np.array_equal(dn.essential[k], dj.essential[k]), \
            (label, "essential", k)


# --------------------------------------------------------------------------
# parity matrix: field zoo x grids, np reference vs jax kernels
# --------------------------------------------------------------------------

GRIDS = [(6, 6, 6),      # 3-D cube
         (5, 9, 3),      # 3-D asymmetric
         (12, 10, 1),    # 2-D
         (9, 4, 1)]      # 2-D thin


@pytest.mark.parametrize("name", sorted(FIELDS))
@pytest.mark.parametrize("dims", GRIDS)
def test_parity_matrix(name, dims):
    f = make_field(name, dims, seed=3)
    rn = _run(f, dims, "np")
    rj = _run(f, dims, "jax")
    _assert_identical(rn, rj, (name, dims))


def test_parity_streamed_source():
    dims = (8, 8, 8)
    nx, ny, nz = dims
    f = make_field("wavelet", dims, seed=1).reshape(nz, ny, nx)
    out = {}
    for sb in ("np", "jax"):
        pipe = PersistencePipeline("jax", sandwich_backend=sb)
        out[sb] = pipe.run(TopoRequest(field=f, stream=True, chunk_z=3))
    _assert_identical(out["np"], out["jax"], "streamed")
    # streamed and in-memory agree too (the kernel extraction handles the
    # packed stream keys by rank compression)
    mem = _run(f, dims, "jax")
    assert same_offdiagonal(out["jax"].diagram, mem.diagram), \
        diff_report(out["jax"].diagram, mem.diagram, ("stream", "mem"))


def test_parity_distributed_engines_on_kernel_extraction():
    # distributed pairing/D1 consume the kernel-extracted CriticalInfo
    dims = (6, 6, 6)
    f = make_field("random", dims, seed=5)
    res = {}
    for sb in ("np", "jax"):
        pipe = PersistencePipeline("np", n_blocks=2, sandwich_backend=sb)
        res[sb] = pipe.run(TopoRequest(field=f, grid=Grid.of(*dims)))
    _assert_identical(res["np"], res["jax"], "distributed")


# --------------------------------------------------------------------------
# dense edge keys: shifted-slice build vs the per-sid formulation
# --------------------------------------------------------------------------

def _edge_keys_per_sid(grid, o):
    """Oracle: decode every edge sid into its two vertices and pack the
    gathered orders."""
    space = grid.sid_space(1)
    sids = np.arange(space, dtype=np.int64)
    valid = np.asarray(grid.simplex_valid(1, sids))
    keys = np.full(space, -1, dtype=np.int64)
    ov = o[np.asarray(grid.simplex_vertices(1, sids[valid]))]
    keys[sids[valid]] = (np.maximum(ov[:, 0], ov[:, 1]) << 31) \
        + np.minimum(ov[:, 0], ov[:, 1])
    return keys


def _order_of(kind, grid, rng):
    nv = grid.nv
    if kind == "permutation":
        return rng.permutation(nv).astype(np.int64)
    if kind == "max31":
        # distinct values up to the packing's limit, 2^31 - 1
        step = 2 ** 31 // (nv + 1)
        vals = (2 ** 31 - 1) - step * np.arange(nv, dtype=np.int64)
        return rng.permutation(vals)
    # streamed front: full-width packed (value, vid) keys, rank-compressed
    # as the kernel extraction does before packing edge keys
    f = rng.standard_normal(nv).astype(np.float32)
    f[: nv // 2] = f[0]                     # ties resolved by the vid
    packed = pack_value_keys(f, np.arange(nv, dtype=np.int64))
    assert int(packed.max()) >= 2 ** 31
    return _rank_compress(packed)


@pytest.mark.parametrize("kind", ("permutation", "max31", "streamed"))
@pytest.mark.parametrize("dims", GRIDS + [(7, 1, 1), (1, 1, 1), (17, 3, 2)])
def test_edge_keys_match_per_sid_formulation(dims, kind):
    grid = Grid.of(*dims)
    o = _order_of(kind, grid, np.random.default_rng(sum(dims)))
    if kind == "max31":
        assert int(o.max()) == 2 ** 31 - 1
    got = edge_keys_kernel(grid, o)
    want = _edge_keys_per_sid(grid, o)
    assert got.dtype == np.int64 and got.shape == want.shape
    assert np.array_equal(got, want)


@pytest.mark.parametrize("n_blocks", (2, 4))
def test_distributed_d1_reads_extracted_edge_keys(n_blocks, monkeypatch):
    # the token D1 pairs on the keys extraction built (one build per
    # diagram) and matches the sequential reference pipeline
    import repro.distributed.d1_rounds as d1r
    import repro.kernels.sandwich as sw
    assert not hasattr(d1r, "edge_keys_packed")
    built, seen = [], []
    build, d1 = sw.edge_keys_kernel, d1r.d1_distributed

    def spy_build(grid, o):
        built.append(build(grid, o))
        return built[-1]

    def spy_d1(grid, gf, ci, *args, **kw):
        seen.append(ci.ranks[1])
        return d1(grid, gf, ci, *args, **kw)

    monkeypatch.setattr(sw, "edge_keys_kernel", spy_build)
    monkeypatch.setattr(d1r, "d1_distributed", spy_d1)
    dims = (8, 8, 8)
    f = make_field("random", dims, seed=7)
    req = TopoRequest(field=f, grid=Grid.of(*dims))
    ref = PersistencePipeline("np", sandwich_backend="np").run(req)
    out = PersistencePipeline("np", n_blocks=n_blocks,
                              sandwich_backend="jax").run(req)
    assert len(built) == 1 and len(seen) == 1 and seen[0] is built[0]
    assert len(out.diagram.pairs[1]), "need at least one D1 pair"
    _assert_identical(ref, out, ("d1_distributed", n_blocks))


# --------------------------------------------------------------------------
# D0 kernel: synthetic-graph parity + compile-count regression
# --------------------------------------------------------------------------

def _random_graph(n, ne, seed):
    rng = np.random.default_rng(seed)
    ext = rng.choice(10 * ne, size=ne, replace=False).astype(np.int64)
    t0 = rng.integers(0, ne, size=n)
    t1 = (t0 + 1 + rng.integers(0, ne - 1, size=n)) % ne
    key = np.zeros(10 * ne, dtype=np.int64)
    key[ext] = rng.permutation(10 * ne)[:ne]
    g = ExtremumGraph(saddles=np.arange(100, 100 + n, dtype=np.int64),
                      t0=ext[t0], t1=ext[t1], ext_key=key)
    # sprinkle OMEGA terminals like the dual graph does
    g.t1 = np.where(rng.random(n) < 0.15, -2, g.t1)
    return g


@pytest.mark.parametrize("seed", range(4))
def test_d0_kernel_matches_sequential(seed):
    g = _random_graph(60, 25, seed)
    ref = pair_extrema_saddles(g)
    ker = pair_extrema_saddles_kernel(g)
    assert sorted(ker.pairs) == sorted(ref.pairs)
    assert ker.unpaired == ref.unpaired


def test_d0_kernel_empty_graph():
    g = ExtremumGraph(saddles=np.zeros(0, np.int64),
                      t0=np.zeros(0, np.int64), t1=np.zeros(0, np.int64),
                      ext_key=np.zeros(4, np.int64))
    out = pair_extrema_saddles_kernel(g)
    assert isinstance(out, ExtremaPairs)
    assert out.pairs == [] and out.unpaired == []


def test_d0_round_bucket_reuse_no_retrace():
    # two graphs whose (triplet, node) counts land in the same padding
    # bucket must share one compiled round program
    ga = _random_graph(40, 20, 11)
    gb = _random_graph(52, 22, 12)
    pair_extrema_saddles_kernel(ga)          # warm the bucket
    before = TRACE_COUNTS["d0_round"]
    pair_extrema_saddles_kernel(ga)
    pair_extrema_saddles_kernel(gb)
    assert TRACE_COUNTS["d0_round"] == before, \
        "same-bucket graphs re-traced the D0 round program"


# --------------------------------------------------------------------------
# D1 wavefront: invariant must raise on a corrupted gradient
# --------------------------------------------------------------------------

def _d1_inputs(dims=(6, 6, 6), seed=0):
    grid = Grid.of(*dims)
    f = make_field("random", dims, seed=seed)
    pipe = PersistencePipeline("np", sandwich_backend="jax")
    res = pipe.run(TopoRequest(field=f, grid=grid))
    assert len(res.diagram.pairs[1]), "need at least one D1 pair"
    # rebuild the D1 stage inputs by hand
    from repro.core.grid import vertex_order
    from repro.core.gradient import compute_gradient_np
    from repro.kernels.sandwich import extract_critical_kernel
    order = np.asarray(vertex_order(np.asarray(f).reshape(-1)))
    gf = compute_gradient_np(grid, order)
    ci = extract_critical_kernel(grid, gf, order)
    g0 = pair_extrema_saddles_kernel(
        __import__("repro.core.extremum_graph",
                   fromlist=["build_d0_graph"]).build_d0_graph(grid, gf, ci))
    d0_saddles = {s for s, _ in g0.pairs}
    from repro.kernels.sandwich import build_dual_graph_chase
    pD = pair_extrema_saddles_kernel(
        build_dual_graph_chase(grid, gf, ci, ci.crit_sids[2]))
    dual_paired = {s for s, _ in pD.pairs}
    c1 = np.asarray([int(e) for e in ci.crit_sids[1]
                     if int(e) not in d0_saddles], dtype=np.int64)
    c2 = np.asarray([int(s) for s in ci.crit_sids[2]
                     if int(s) not in dual_paired], dtype=np.int64)
    return grid, gf, ci, c1, c2


def test_wavefront_invariant_raises_on_corrupted_gradient():
    grid, gf, ci, c1, c2 = _d1_inputs()
    ok = pair_saddle_saddle_wavefront(grid, gf, ci, c1, c2)
    assert ok.pairs, "expected at least one saddle-saddle pair"
    birth = ok.pairs[0][0]
    # corrupt the filtration: drop a known birth edge from the critical
    # set, so propagation reaches an edge that is neither gradient-paired
    # upward nor claimable — the invariant must raise, not mis-pair.
    # Both the burst and the batched dispatch must enforce it.
    c1_bad = np.asarray([e for e in c1 if int(e) != birth], dtype=np.int64)
    for burst_below in (10**9, 0):
        with pytest.raises(GradientInvariantError, match="positive"):
            pair_saddle_saddle_wavefront(grid, gf, ci, c1_bad, c2,
                                         burst_below=burst_below)


def test_wavefront_small_batches_match_reference():
    # tiny batches force merges across frozen earlier batches and steals
    # within a batch; the result must not depend on the batch size
    # (burst_below=0 pins the batched path regardless of column count)
    grid, gf, ci, c1, c2 = _d1_inputs(seed=2)
    from repro.core.saddle_saddle import pair_saddle_saddle_seq
    ref = pair_saddle_saddle_seq(grid, gf, ci, c1, c2)
    for b in (1, 2, 7, 4096):
        out = pair_saddle_saddle_wavefront(grid, gf, ci, c1, c2, batch=b,
                                           burst_below=0)
        assert sorted(out.pairs) == sorted(ref.pairs), f"batch={b}"
        assert out.unpaired_edges == ref.unpaired_edges, f"batch={b}"
        assert out.unpaired_triangles == ref.unpaired_triangles, f"batch={b}"


def test_dual_chase_strategies_agree():
    # lazy / dense-chase / doubling terminal resolution must all build
    # the same dual extremum graph
    grid, gf, ci, _c1, _c2 = _d1_inputs(seed=1)
    from repro.kernels.sandwich import build_dual_graph_chase
    outs = {s: build_dual_graph_chase(grid, gf, ci, ci.crit_sids[2],
                                      strategy=s)
            for s in ("lazy", "chase", "doubling")}
    ref = outs["lazy"]
    for s, g in outs.items():
        assert np.array_equal(g.saddles, ref.saddles), s
        assert np.array_equal(g.t0, ref.t0), s
        assert np.array_equal(g.t1, ref.t1), s
    with pytest.raises(ValueError, match="unknown dual-chase strategy"):
        build_dual_graph_chase(grid, gf, ci, ci.crit_sids[2],
                               strategy="nope")


@pytest.mark.parametrize("seed", (0, 2))
def test_wavefront_burst_and_batched_paths_agree(seed):
    # the lazy-heap burst reducer and the lockstep wavefront must both
    # reproduce the sequential reference on the same inputs
    grid, gf, ci, c1, c2 = _d1_inputs(seed=seed)
    from repro.core.saddle_saddle import pair_saddle_saddle_seq
    ref = pair_saddle_saddle_seq(grid, gf, ci, c1, c2)
    burst = pair_saddle_saddle_wavefront(grid, gf, ci, c1, c2,
                                         burst_below=10**9)
    batched = pair_saddle_saddle_wavefront(grid, gf, ci, c1, c2,
                                           burst_below=0)
    for out, label in ((burst, "burst"), (batched, "batched")):
        assert sorted(out.pairs) == sorted(ref.pairs), label
        assert out.unpaired_edges == ref.unpaired_edges, label
        assert out.unpaired_triangles == ref.unpaired_triangles, label


# --------------------------------------------------------------------------
# plumbing: registry, plan, request, StageReport split
# --------------------------------------------------------------------------

def test_sandwich_registry():
    names = set(available_sandwich_backends())
    assert {"np", "jax"} <= names
    assert get_sandwich_backend("jax").name == "jax"
    with pytest.raises(UnknownSandwichBackendError,
                       match="unknown sandwich backend"):
        get_sandwich_backend("nope")
    with pytest.raises(UnknownSandwichBackendError):
        PersistencePipeline("np", sandwich_backend="nope")


def test_plan_records_sandwich_backend():
    pipe = PersistencePipeline("np")          # sandwich defaults to jax
    g = Grid.of(4, 4, 4)
    f = np.arange(g.nv, dtype=np.float64)
    plan = pipe.lower(TopoRequest(field=f, grid=g))
    assert plan.sandwich_backend == "jax"
    assert "sandwich='jax'" in plan.describe()
    assert plan.sandwich_backend in plan.key
    # a request override wins over the pipeline default
    plan_np = pipe.lower(TopoRequest(field=f, grid=g,
                                     sandwich_backend="np"))
    assert plan_np.sandwich_backend == "np"
    assert plan.key != plan_np.key


def test_stage_report_front_back_split():
    dims = (5, 5, 5)
    res = _run(make_field("random", dims, seed=0), dims, "jax")
    rep = res.report
    assert rep.front_seconds > 0
    assert rep.back_seconds > 0
    total = sum(c.total_seconds for c in rep.children)
    assert rep.front_seconds + rep.back_seconds <= total + 1e-9
    d = rep.to_dict()
    assert d["front_seconds"] == pytest.approx(rep.front_seconds)
    assert d["back_seconds"] == pytest.approx(rep.back_seconds)
