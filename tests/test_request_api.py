"""The declarative TopoRequest/Plan/DiagramResult surface.

Covers: request validation, the lower/compile AOT split and shared
PlanCache compile counts, legacy entry points as bit-identical shims
over run(), min_persistence/top_k query parity against full diagrams,
the versioned wire format round trip (1-D/2-D/3-D + streamed), and the
TopoService mixed-payload map regression."""

import warnings

import numpy as np
import pytest

from repro.core.diagram import diff_report, same_offdiagonal
from repro.core.dms import compute_dms
from repro.core.grid import Grid
from repro.fields import make_field
from repro.pipeline import (DiagramResult, PersistencePipeline, Plan,
                            PlanCache, TopoRequest, resolve_grid)
from repro.stream import ArraySource, unpack_value_keys


DIMS = (4, 4, 8)


def _field(seed=0, dims=DIMS):
    g = Grid.of(*dims)
    rng = np.random.default_rng(seed)
    return g, rng.standard_normal(g.nv)


def _assert_same(a, b, names=("A", "B")):
    assert same_offdiagonal(a, b), diff_report(a, b, names)
    for p in range(a.grid.dim + 1):
        assert np.array_equal(a.essential_orders(p), b.essential_orders(p))


# --------------------------------------------------------------------------
# request validation
# --------------------------------------------------------------------------

class TestRequestValidation:
    def test_field_required(self):
        with pytest.raises(TypeError, match="needs a field"):
            TopoRequest(field=None)

    def test_min_persistence_negative(self):
        with pytest.raises(ValueError, match="min_persistence"):
            TopoRequest(field=np.zeros(8), min_persistence=-0.1)

    def test_top_k_and_n_blocks(self):
        with pytest.raises(ValueError, match="top_k"):
            TopoRequest(field=np.zeros(8), top_k=0)
        with pytest.raises(ValueError, match="n_blocks"):
            TopoRequest(field=np.zeros(8), n_blocks=0)

    def test_both_chunk_knobs(self):
        with pytest.raises(ValueError, match="at most one"):
            TopoRequest(field=np.zeros(8), chunk_z=2, chunk_budget=1 << 20)
        with pytest.raises(ValueError, match="chunk_z"):
            TopoRequest(field=np.zeros(8), chunk_z=0)
        with pytest.raises(ValueError, match="chunk_budget"):
            TopoRequest(field=np.zeros(8), chunk_budget=-1)

    def test_homology_dims_bounds(self):
        with pytest.raises(ValueError, match="not be empty"):
            TopoRequest(field=np.zeros(8), homology_dims=())
        with pytest.raises(ValueError, match=r"\[0, 3\]"):
            TopoRequest(field=np.zeros(8), homology_dims=(4,))
        # normalized: sorted, deduplicated
        r = TopoRequest(field=np.zeros(8), homology_dims=(2, 0, 2))
        assert r.homology_dims == (0, 2)

    def test_homology_dims_exceed_grid(self):
        g2 = Grid.of(6, 6)     # 2-D grid: dim-3 classes cannot exist
        with pytest.raises(ValueError, match="exceed the grid dimension"):
            TopoRequest(field=np.zeros(g2.nv), grid=g2,
                        homology_dims=(0, 3)).resolve()

    def test_flat_field_needs_grid(self):
        with pytest.raises(ValueError, match="cannot infer the grid"):
            TopoRequest(field=np.zeros(10)).resolve()

    def test_field_grid_shape_conflicts(self):
        """Regression: an explicit grid contradicting the field shape
        (same or different nv) must be a named error, not a silently
        wrong-topology diagram or a deep reshape failure."""
        f = np.zeros((6, 6, 6))
        with pytest.raises(ValueError, match="conflict with the field"):
            TopoRequest(field=f, grid=Grid.of(4, 9, 6)).resolve()  # same nv
        with pytest.raises(ValueError, match="conflict with the field"):
            TopoRequest(field=f, grid=Grid.of(4, 4, 4)).resolve()
        with pytest.raises(ValueError, match="216 values.*64 vertices"):
            TopoRequest(field=np.zeros(216), grid=Grid.of(4, 4, 4)).resolve()
        TopoRequest(field=f, grid=Grid.of(6, 6, 6)).resolve()  # consistent

    def test_stream_false_vs_source(self):
        src = ArraySource(np.zeros((4, 4, 4), np.float32))
        with pytest.raises(ValueError, match="stream=False conflicts"):
            TopoRequest(field=src, stream=False).resolve()

    def test_chunk_knobs_need_streaming(self):
        g, f = _field()
        with pytest.raises(ValueError, match="only apply to streamed"):
            TopoRequest(field=f, grid=g, stream=False, chunk_z=2).resolve()

    def test_resolve_infers_and_is_idempotent(self):
        g, f = _field()
        shaped = f.reshape(g.dims[::-1])
        r = TopoRequest(field=shaped).resolve()
        assert r.grid.dims == g.dims
        assert r.resolve() is r
        assert resolve_grid(shaped).dims == g.dims
        src = ArraySource(np.zeros((3, 4, 5), np.float32))
        assert resolve_grid(src).dims == (5, 4, 3)
        assert TopoRequest(field=src).is_stream
        assert TopoRequest(field=f, grid=g, chunk_z=2).is_stream


# --------------------------------------------------------------------------
# lower / compile: plans and the shared cache
# --------------------------------------------------------------------------

class TestLowerCompile:
    def test_plan_is_inspectable_and_hashable(self):
        g, f = _field()
        pipe = PersistencePipeline(backend="jax")
        plan = pipe.lower(TopoRequest(field=f, grid=g))
        assert isinstance(plan, Plan)
        assert plan.dims == g.dims and plan.backend == "jax"
        assert plan.stage_names == ("order", "gradient", "extract_sort",
                                    "d0", "d_top", "d1")
        assert hash(plan) == hash(pipe.lower(TopoRequest(field=f, grid=g)))
        assert "jax" in plan.describe() and "in-memory" in plan.describe()

    def test_request_overrides_pipeline_defaults(self):
        g, f = _field()
        pipe = PersistencePipeline(backend="np")
        plan = pipe.lower(TopoRequest(field=f, grid=g, backend="jax",
                                      n_blocks=4))
        assert plan.backend == "jax"
        assert plan.n_blocks == 4 and plan.distributed  # n_blocks>1 implies
        plan = pipe.lower(TopoRequest(field=f, grid=g))
        assert plan.backend == "np" and not plan.distributed

    def test_stage_chain_restriction(self):
        g, f = _field()
        pipe = PersistencePipeline(backend="np")
        low = lambda **kw: pipe.lower(TopoRequest(field=f, grid=g, **kw))
        assert low(homology_dims=(0,)).stage_names[-1] == "d0"
        assert low(homology_dims=(0, 3)).stage_names[-2:] == ("d0", "d_top")
        assert low(homology_dims=(1,)).stage_names[-3:] == \
            ("d0", "d_top", "d1")

    def test_streamed_plan(self):
        src = ArraySource(np.zeros((8, 4, 4), np.float32))
        pipe = PersistencePipeline(backend="jax")
        plan = pipe.lower(TopoRequest(field=src, chunk_z=2))
        assert plan.streamed and plan.chunk_z == 2
        assert plan.stage_names[0] == "gradient"
        with pytest.raises(ValueError, match="streamed"):
            PersistencePipeline(backend="np").lower(TopoRequest(field=src))

    def test_one_compile_per_shape_backend_blocks(self):
        """The acceptance counter: repeated + batched requests of one
        (dims, backend, n_blocks) build the rows program exactly once."""
        g = Grid.of(*DIMS)
        rng = np.random.default_rng(1)
        cache = PlanCache()
        pipe = PersistencePipeline(backend="jax", plan_cache=cache)
        for seed in range(3):                       # repeated singles
            pipe.run(TopoRequest(field=rng.standard_normal(g.nv), grid=g))
        pipe.run_batch([TopoRequest(field=rng.standard_normal(g.nv), grid=g)
                        for _ in range(3)])         # and a batch
        key = (g.dims, "jax", 1)
        assert cache.build_counts[key] == 1
        st = cache.stats()
        assert st["compiles"] == 1      # the rows program alone
        assert st["hits"] >= 3      # every later lookup of it

    def test_plan_cache_builds_outside_lock(self):
        """A slow build of one key must not block lookups of other keys,
        and concurrent builders of one key compile exactly once."""
        import threading
        import time as _t
        cache = PlanCache()
        built = []

        def slow():
            built.append(1)
            _t.sleep(0.2)
            return "slow"

        t = threading.Thread(
            target=lambda: cache.get_or_build(("slow",), slow))
        t.start()
        _t.sleep(0.05)
        t0 = _t.perf_counter()
        assert cache.get_or_build(("fast",), lambda: "fast") == "fast"
        assert _t.perf_counter() - t0 < 0.1, "fast key blocked on slow build"
        vals = []
        ts = [threading.Thread(target=lambda: vals.append(
            cache.get_or_build(("slow",), slow))) for _ in range(3)]
        for x in ts:
            x.start()
        t.join()
        for x in ts:
            x.join()
        assert vals == ["slow"] * 3
        assert cache.build_counts[("slow",)] == 1 and len(built) == 1
        # a failed build releases waiters and allows a rebuild
        with pytest.raises(RuntimeError, match="nope"):
            cache.get_or_build(("bad",), lambda: (_ for _ in ()).throw(
                RuntimeError("nope")))
        assert cache.get_or_build(("bad",), lambda: "ok") == "ok"

    def test_plan_cache_eviction_and_stats(self):
        cache = PlanCache(maxsize=2)
        for i in range(4):
            cache.get_or_build(("k", i), lambda i=i: i)
        assert len(cache) == 2 and cache.stats()["evictions"] == 2
        assert ("k", 3) in cache and ("k", 0) not in cache
        # build_counts is pruned with evicted entries (bounded in the
        # process-wide singleton); the lifetime total lives in compiles
        assert set(cache.build_counts) == {("k", 2), ("k", 3)}
        assert cache.stats()["compiles"] == 4
        cache.clear()
        assert len(cache) == 0
        with pytest.raises(ValueError, match="maxsize"):
            PlanCache(maxsize=0)

    def test_plan_cache_empty_is_truthy(self):
        """Regression: PlanCache defines __len__, so an *empty* cache
        used to be falsy — `cache or default()` silently swapped a
        fresh isolated cache for the shared one.  __bool__ pins
        truthiness independent of size."""
        from repro.pipeline import default_plan_cache
        cache = PlanCache()
        assert len(cache) == 0 and bool(cache) is True
        assert (cache or default_plan_cache()) is cache
        # the guards this used to force are gone: an empty cache passed
        # to the pipeline / compile is used, not replaced
        pipe = PersistencePipeline(backend="jax", plan_cache=cache)
        assert pipe.plan_cache is cache
        g, f = _field()
        pipe.lower(TopoRequest(field=f, grid=g)).compile(cache)
        assert len(cache) > 0

    def test_unregistered_backend_instance(self):
        """Regression: a Backend *instance* that was never registered
        (test double / locally-built) must work end to end — lower,
        compile, and the stage config all use the held instance."""
        import dataclasses as dc
        from repro.pipeline import get_backend
        g, f = _field(seed=2)
        be = dc.replace(get_backend("np"), name="custom_unregistered")
        pipe = PersistencePipeline(backend=be)
        plan = pipe.lower(TopoRequest(field=f, grid=g))
        assert plan.backend == "custom_unregistered"
        res = pipe.run(TopoRequest(field=f, grid=g))
        _assert_same(compute_dms(g, f).diagram, res.diagram,
                     ("np", "custom"))

    def test_source_grid_dims_conflict(self):
        """Regression: an explicit grid that contradicts a FieldSource's
        own dims must be rejected at resolve(), not die deep in the
        streamed kernels (or silently compute the wrong complex)."""
        src = ArraySource(np.zeros((8, 4, 4), np.float32))   # dims (4,4,8)
        with pytest.raises(ValueError, match="conflict with the "
                                             "FieldSource"):
            TopoRequest(field=src, grid=Grid.of(8, 4, 4)).resolve()
        # matching grid is fine, and flat arrays stream via the grid dims
        TopoRequest(field=src, grid=Grid.of(4, 4, 8)).resolve()
        g = Grid.of(4, 4, 6)
        f = make_field("random", g.dims, seed=1)
        res = PersistencePipeline(backend="jax").run(
            TopoRequest(field=f.astype(np.float32), grid=g, stream=True,
                        chunk_z=2))
        _assert_same(
            PersistencePipeline(backend="jax").run(
                TopoRequest(field=f, grid=g)).diagram,
            res.diagram, ("in-memory", "flat-streamed"))

    def test_shadowing_backend_instance_gets_own_program(self):
        """Regression: a Backend instance that *shares a name* with a
        registry entry must not exchange compiled rows programs with it
        through the shared cache."""
        import dataclasses as dc
        from repro.pipeline import get_backend
        g, f = _field(seed=2)
        cache = PlanCache()
        reg = PersistencePipeline(backend="jax", plan_cache=cache)
        ex_reg = reg.compile(TopoRequest(field=f, grid=g))
        shadow = dc.replace(get_backend("jax"), name="jax")
        pipe = PersistencePipeline(backend=shadow, plan_cache=cache)
        ex_shadow = pipe.compile(TopoRequest(field=f, grid=g))
        assert ex_shadow.rows_program is not ex_reg.rows_program
        # and memoized per instance: no rebuild on the next compile
        assert pipe.compile(TopoRequest(field=f, grid=g)).rows_program \
            is ex_shadow.rows_program
        _assert_same(compute_dms(g, f).diagram,
                     pipe.run(TopoRequest(field=f, grid=g)).diagram)

    def test_streamed_run_compiles_nothing(self):
        """Regression: the streamed path drives its own per-chunk
        kernels — run() must not build the batched rows program."""
        dims = (5, 5, 8)
        f = make_field("wavelet", dims, seed=0)
        cache = PlanCache()
        pipe = PersistencePipeline(backend="jax", plan_cache=cache)
        pipe.run(TopoRequest(field=ArraySource(f.reshape(dims[::-1])),
                             chunk_z=3))
        assert ((5, 5, 8), "jax", 1) not in cache.build_counts

    def test_options_alongside_request_rejected(self):
        g, f = _field()
        pipe = PersistencePipeline(backend="np")
        with pytest.raises(TypeError, match="inside the TopoRequest"):
            pipe.run(TopoRequest(field=f, grid=g), grid=g)


# --------------------------------------------------------------------------
# legacy entry points == run() (the parity matrix), warning-free
# --------------------------------------------------------------------------

class TestShimParity:
    @pytest.mark.parametrize("backend,n_blocks", [("np", 1), ("jax", 1),
                                                  ("jax", 4)])
    def test_diagram_routes_through_run(self, backend, n_blocks):
        g, f = _field(seed=3)
        pipe = PersistencePipeline(backend=backend, n_blocks=n_blocks)
        with warnings.catch_warnings():
            warnings.simplefilter("error", DeprecationWarning)
            a = pipe.diagram(f, grid=g)
            b = pipe.run(TopoRequest(field=f, grid=g))
        _assert_same(a.diagram, b.diagram, ("shim", "run"))
        assert a.stats.keys() == b.stats.keys()
        assert a.plan == b.plan

    def test_diagrams_routes_through_run_batch(self):
        g = Grid.of(*DIMS)
        rng = np.random.default_rng(7)
        fields = [rng.standard_normal(g.nv) for _ in range(3)]
        pipe = PersistencePipeline(backend="jax")
        with warnings.catch_warnings():
            warnings.simplefilter("error", DeprecationWarning)
            shim = pipe.diagrams(fields, grid=g)
            runs = pipe.run_batch(
                [TopoRequest(field=f, grid=g) for f in fields])
        for a, b in zip(shim, runs):
            _assert_same(a.diagram, b.diagram, ("shim", "run_batch"))
            assert a.stats["batch_size"] == b.stats["batch_size"] == 3

    def test_diagram_stream_routes_through_run(self):
        dims = (5, 5, 8)
        f = make_field("wavelet", dims, seed=0)
        src = ArraySource(f.reshape(dims[::-1]))
        pipe = PersistencePipeline(backend="jax")
        with warnings.catch_warnings():
            warnings.simplefilter("error", DeprecationWarning)
            a = pipe.diagram_stream(src, chunk_z=3)
            b = pipe.run(TopoRequest(field=src, chunk_z=3))
        _assert_same(a.diagram, b.diagram, ("shim", "run"))
        assert a.stream.n_chunks == b.stream.n_chunks == 3

    def test_topo_service_routes_through_run(self):
        from repro.serve import TopoService
        g, f = _field(seed=5)
        ref = compute_dms(g, f).diagram
        with warnings.catch_warnings():
            warnings.simplefilter("error", DeprecationWarning)
            with TopoService(backend="jax", max_wait_s=0.02) as svc:
                res = svc.submit(f, grid=g).result(timeout=120)
                via_req = svc.submit(
                    TopoRequest(field=f, grid=g)).result(timeout=120)
        _assert_same(ref, res.diagram, ("ref", "service"))
        _assert_same(ref, via_req.diagram, ("ref", "service-request"))
        assert res.plan is not None     # went through lower/compile/run

    def test_wrappers_route_through_run(self):
        from repro.core.ddms import compute_ddms_sim
        g, f = _field(seed=6)
        a = compute_dms(g, f)
        b = compute_ddms_sim(g, f, n_blocks=2)
        _assert_same(a.diagram, b.diagram, ("dms", "ddms"))


# --------------------------------------------------------------------------
# result queries
# --------------------------------------------------------------------------

class TestResultQueries:
    @pytest.fixture(scope="class")
    def res(self):
        dims = (6, 6, 8)
        g = Grid.of(*dims)
        f = make_field("wavelet", dims, seed=0)
        pipe = PersistencePipeline(backend="jax")
        return f, pipe.run(TopoRequest(field=f, grid=g)), g

    def test_pairs_match_full_diagram(self, res):
        f, r, g = res
        for p in range(g.dim):
            full = r.diagram.points_value(p, np.asarray(f).reshape(-1))
            q = r.pairs(p)
            assert {tuple(x) for x in q} == {tuple(x) for x in full}

    def test_min_persistence_parity(self, res):
        f, r, g = res
        full = r.diagram.points_value(0, np.asarray(f).reshape(-1))
        for t in (0.05, 0.2, 1.0):
            manual = full[(full[:, 1] - full[:, 0]) >= t]
            q = r.pairs(0, min_persistence=t)
            assert {tuple(x) for x in q} == {tuple(x) for x in manual}, t

    def test_top_k_parity(self, res):
        f, r, g = res
        full = r.pairs(0)
        pers = full[:, 1] - full[:, 0]
        assert np.all(np.diff(pers) <= 0)        # sorted descending
        for k in (1, 3, 10 ** 6):
            q = r.pairs(0, top_k=k)
            assert np.array_equal(q, full[:k])

    def test_order_space_and_request_defaults(self, res):
        f, r, g = res
        q = r.pairs(0, space="order", min_persistence=2)
        assert q.dtype == np.int64
        assert np.all(q[:, 1] - q[:, 0] >= 2)
        with pytest.raises(ValueError, match="space"):
            r.pairs(0, space="nope")
        # request-level defaults drive the queries
        pipe = PersistencePipeline(backend="jax")
        r2 = pipe.run(TopoRequest(field=f, grid=g, top_k=2,
                                  min_persistence=0.05))
        assert len(r2.pairs(0)) <= 2
        assert np.array_equal(r2.pairs(0),
                              r.pairs(0, min_persistence=0.05, top_k=2))

    def test_betti_and_essential(self, res):
        f, r, g = res
        assert r.betti() == r.diagram.betti()
        assert np.array_equal(r.essential(0, space="order"),
                              r.diagram.essential_orders(0))

    def test_homology_restriction(self, res):
        f, r, g = res
        pipe = PersistencePipeline(backend="jax")
        r0 = pipe.run(TopoRequest(field=f, grid=g, homology_dims=(0,)))
        assert [c.name for c in r0.report.children] == \
            ["order", "gradient", "extract_sort", "d0"]
        assert np.array_equal(r0.pairs(0), r.pairs(0))
        assert r0.betti() == {0: r.betti()[0]}
        with pytest.raises(ValueError, match="not computed"):
            r0.pairs(1)

    def test_include_report_false(self, res):
        f, _, g = res
        pipe = PersistencePipeline(backend="jax")
        r = pipe.run(TopoRequest(field=f, grid=g, include_report=False))
        assert r.report is None and r.stats    # flat stats survive


# --------------------------------------------------------------------------
# wire format
# --------------------------------------------------------------------------

def _roundtrip_exact(res):
    blob = res.to_bytes()
    back = DiagramResult.from_bytes(blob)
    a, b = res.arrays(), back.arrays()
    assert set(a) == set(b)
    for k in a:
        assert a[k].dtype == b[k].dtype, k
        assert a[k].shape == b[k].shape, k
        assert a[k].tobytes() == b[k].tobytes(), k   # bit-exact
    assert back.betti() == res.betti()
    assert back.grid_dims == res.grid_dims
    assert DiagramResult.from_bytes(back.to_bytes()).arrays().keys() \
        == a.keys()
    return back


class TestWireFormat:
    @pytest.mark.parametrize("dims", [(16, 1, 1), (9, 7, 1), (5, 4, 6)])
    def test_roundtrip_bit_exact(self, dims):
        g = Grid.of(*dims)
        f = make_field("random", dims, seed=2)
        res = PersistencePipeline(backend="jax").run(
            TopoRequest(field=f, grid=g))
        back = _roundtrip_exact(res)
        for p in range(g.dim):
            assert np.array_equal(back.pairs(p), res.pairs(p))

    def test_roundtrip_streamed(self):
        dims = (5, 5, 9)
        f = make_field("wavelet", dims, seed=0)
        res = PersistencePipeline(backend="jax").run(
            TopoRequest(field=ArraySource(f.reshape(dims[::-1])),
                        chunk_z=3))
        back = _roundtrip_exact(res)
        assert np.array_equal(back.pairs(0, top_k=5), res.pairs(0, top_k=5))

    def test_wire_preserves_query_defaults(self):
        """Regression: a decoded payload must answer pairs() exactly
        like the live result, including the request's top_k /
        min_persistence defaults."""
        dims = (6, 6, 8)
        g = Grid.of(*dims)
        f = make_field("wavelet", dims, seed=0)
        res = PersistencePipeline(backend="jax").run(
            TopoRequest(field=f, grid=g, top_k=3, min_persistence=0.05))
        back = DiagramResult.from_bytes(res.to_bytes())
        assert np.array_equal(back.pairs(0), res.pairs(0))
        assert len(back.pairs(0)) <= 3
        assert np.array_equal(back.pairs(0, top_k=None, min_persistence=0),
                              res.pairs(0, top_k=None, min_persistence=0))

    def test_value_default_not_applied_in_order_space(self):
        """Regression: the request's value-space min_persistence must
        not filter order-space (integer) queries."""
        g, f = _field(seed=17)
        res = PersistencePipeline(backend="np").run(
            TopoRequest(field=f, grid=g, min_persistence=10.0))
        assert len(res.pairs(0)) == 0                  # value space: all cut
        full = PersistencePipeline(backend="np").run(
            TopoRequest(field=f, grid=g))
        assert np.array_equal(res.pairs(0, space="order"),
                              full.pairs(0, space="order"))

    def test_bad_payloads(self):
        g, f = _field()
        res = PersistencePipeline(backend="np").run(
            TopoRequest(field=f, grid=g))
        blob = res.to_bytes()
        with pytest.raises(ValueError, match="magic"):
            DiagramResult.from_bytes(b"NOPE" + blob[4:])
        with pytest.raises(ValueError, match="newer than supported"):
            DiagramResult.from_bytes(blob[:4] + b"\xff\x7f" + blob[6:])
        with pytest.raises(ValueError, match="trailing"):
            DiagramResult.from_bytes(blob + b"\x00")

    def test_unpack_value_keys_inverts_pack(self):
        from repro.stream import pack_value_keys
        rng = np.random.default_rng(0)
        vals = rng.standard_normal(64).astype(np.float32)
        vals[:4] = [0.0, -0.0, np.inf, -np.inf]
        keys = pack_value_keys(vals, np.arange(64, dtype=np.int64))
        out = unpack_value_keys(keys)
        # exact except -0.0, which folds onto +0.0 by design
        assert np.array_equal(out, np.where(vals == 0, np.float32(0), vals))


# --------------------------------------------------------------------------
# TopoService: mixed payloads, per-request grids, wire mode
# --------------------------------------------------------------------------

class TestServiceMixed:
    def test_map_mixed_sources_and_grids(self):
        """Regression: map() takes ndarray/FieldSource/TopoRequest mixes
        and per-request grids, like submit() does."""
        from repro.serve import TopoService
        dims = (5, 5, 8)
        g = Grid.of(*dims)
        f = make_field("wavelet", dims, seed=0)
        ref = compute_dms(g, f).diagram
        src = ArraySource(f.reshape(dims[::-1]))
        with TopoService(backend="jax", max_batch=4,
                         max_wait_s=0.05) as svc:
            out = svc.map([f, src, TopoRequest(field=f, grid=g, top_k=3)],
                          grid=[g, None, None])
            st = svc.stats.as_dict()
        assert st["requests"] == 3 and st["stream_requests"] == 1
        for res in out:
            _assert_same(ref, res.diagram, ("ref", "mixed-map"))
        assert out[1].stream is not None
        assert len(out[2].pairs(0)) <= 3

    def test_map_accepts_generators(self):
        """Regression: map() must not require len() on its input."""
        from repro.serve import TopoService
        g, f = _field(seed=15)
        with TopoService(backend="np", max_wait_s=0.02) as svc:
            out = svc.map((f for _ in range(2)), grid=g)
        assert len(out) == 2
        _assert_same(out[0].diagram, out[1].diagram)

    def test_map_grid_length_mismatch(self):
        from repro.serve import TopoService
        g, f = _field()
        with TopoService(backend="np") as svc:
            with pytest.raises(ValueError, match="per-request grids"):
                svc.map([f, f], grid=[g])

    def test_option_requests_batch_together(self):
        from repro.serve import TopoService
        g = Grid.of(*DIMS)
        rng = np.random.default_rng(11)
        fields = [rng.standard_normal(g.nv) for _ in range(4)]
        refs = [compute_dms(g, f).diagram for f in fields]
        with TopoService(backend="jax", max_batch=8,
                         max_wait_s=0.1) as svc:
            # different *result-only* options must not split the batch
            out = svc.map([TopoRequest(field=f, grid=g, top_k=4 + i)
                           for i, f in enumerate(fields)])
            st = svc.stats.as_dict()
        for i, (ref, res) in enumerate(zip(refs, out)):
            _assert_same(ref, res.diagram, ("ref", "req-batch"))
            assert len(res.pairs(0)) <= 4 + i
        assert st["batched_requests"] >= 2   # coalesced via run_batch

    def test_wire_mode(self):
        from repro.serve import TopoService
        g, f = _field(seed=9)
        ref = PersistencePipeline(backend="jax").run(
            TopoRequest(field=f, grid=g))
        with TopoService(backend="jax", wire=True,
                         max_wait_s=0.05) as svc:
            payloads = svc.map([f, f], grid=g)
        for blob in payloads:
            assert isinstance(blob, bytes)
            back = DiagramResult.from_bytes(blob)
            assert back.betti() == ref.betti()
            assert np.array_equal(back.pairs(0), ref.pairs(0))
