"""Tests for the out-of-core streaming engine (repro.stream).

The headline contract: ``PersistencePipeline.diagram_stream`` is
bit-identical to the in-memory ``diagram`` (off-diagonal pairs AND
essential classes) while the front-end never holds more than ~2
ghost-extended chunks of field data — asserted against the
``StreamReport`` byte accounting, not logs."""

import os

import numpy as np
import pytest

from repro.core.diagram import diff_report, same_offdiagonal
from repro.core.grid import Grid, vertex_order
from repro.fields import FIELDS, make_field, make_field_chunk
from repro.pipeline import PersistencePipeline
from repro.stream import (ArraySource, FunctionSource, MemmapSource,
                          SparseOrder, as_source, pack_value_keys,
                          plan_chunks, ranks_for_vids, stream_front)


def vol(f, dims):
    nx, ny, nz = Grid.of(*dims).dims
    return np.asarray(f, np.float32).reshape(nz, ny, nx)


def assert_same_diagram(res, ref, g):
    assert same_offdiagonal(res.diagram, ref.diagram), \
        diff_report(res.diagram, ref.diagram)
    for p in range(g.dim + 1):
        assert np.array_equal(res.diagram.essential_orders(p),
                              ref.diagram.essential_orders(p))


# --------------------------------------------------------------------------
# decomposition + keys
# --------------------------------------------------------------------------

class TestChunks:
    def test_plan_covers_grid_with_ghosts(self):
        for dims, cz in (((4, 4, 32), 5), ((3, 3, 7), 3), ((5, 5, 4), 9)):
            nz = dims[2]
            chunks = plan_chunks(dims, chunk_z=cz)
            assert chunks[0].zlo == 0 and chunks[-1].zhi == nz
            for a, b in zip(chunks, chunks[1:]):
                assert a.zhi == b.zlo
            for c in chunks:
                assert c.glo == max(0, c.zlo - 1)
                assert c.ghi == min(nz, c.zhi + 1)

    def test_plan_budget_knob(self):
        dims = (8, 8, 32)
        plane = 8 * 8 * 4
        chunks = plan_chunks(dims, chunk_budget=6 * plane)
        assert chunks[0].nz == 4          # 4 owned + 2 ghost planes fit
        assert all(c.load_bytes(dims) <= 6 * plane for c in chunks)
        # tiny budgets still make progress (1 plane per chunk)
        assert plan_chunks(dims, chunk_budget=1)[0].nz == 1

    def test_plan_arg_validation(self):
        with pytest.raises(ValueError, match="exactly one"):
            plan_chunks((4, 4, 4))
        with pytest.raises(ValueError, match="exactly one"):
            plan_chunks((4, 4, 4), chunk_z=2, chunk_budget=100)

    def test_packed_keys_match_vertex_order(self):
        rng = np.random.default_rng(0)
        f = rng.standard_normal(4000).astype(np.float32)
        f[100:120] = 1.5          # exact ties -> vid tie-break
        f[7] = -0.0
        f[9] = 0.0                # signed-zero tie
        keys = pack_value_keys(f, np.arange(len(f), dtype=np.int64))
        assert (keys >= 0).all()  # never collides with the -1 sentinel
        perm = np.argsort(keys)
        order = np.empty(len(f), np.int64)
        order[perm] = np.arange(len(f))
        assert np.array_equal(order, np.asarray(vertex_order(f)))

    def test_ranks_for_vids_chunked_counting(self):
        rng = np.random.default_rng(1)
        f = rng.standard_normal(3000).astype(np.float32)
        keys = pack_value_keys(f, np.arange(len(f), dtype=np.int64))
        order = np.asarray(vertex_order(f))
        q = rng.integers(0, len(f), size=64)
        assert np.array_equal(ranks_for_vids(keys, q, slab=257), order[q])


# --------------------------------------------------------------------------
# sources
# --------------------------------------------------------------------------

class TestSources:
    dims = (5, 4, 9)

    def test_array_source_slabs(self):
        f = make_field("wavelet", self.dims, seed=0)
        src = ArraySource(vol(f, self.dims))
        assert src.dims == self.dims
        got = src.read_slab(2, 5)
        assert np.array_equal(got, vol(f, self.dims)[2:5])
        with pytest.raises(IndexError):
            src.read_slab(5, 20)

    def test_array_source_rejects_float64(self):
        with pytest.raises(TypeError, match="float32"):
            ArraySource(np.zeros((4, 4, 4)))

    def test_memmap_source_round_trip(self, tmp_path):
        f = make_field("random", self.dims, seed=3)
        path = os.path.join(tmp_path, "field.f32")
        src = MemmapSource.write(path, vol(f, self.dims))
        assert np.array_equal(src.read_slab(0, 9), vol(f, self.dims))
        assert np.array_equal(src.read_slab(3, 6), vol(f, self.dims)[3:6])

    def test_function_source_shape_check(self):
        src = FunctionSource(lambda a, b: np.zeros((b - a, 2, 2), np.float32),
                             (5, 4, 9))
        with pytest.raises(ValueError, match="shape"):
            src.read_slab(0, 2)

    def test_as_source(self):
        f = vol(make_field("wavelet", self.dims, seed=0), self.dims)
        assert isinstance(as_source(f), ArraySource)
        src = ArraySource(f)
        assert as_source(src) is src
        with pytest.raises(TypeError):
            as_source("not a field")


class TestFieldChunks:
    """make_field_chunk(name, ...) == the make_field slice, every field."""

    @pytest.mark.parametrize("name", sorted(FIELDS))
    def test_chunks_match_full_field(self, name):
        dims = (6, 5, 11)
        full = vol(make_field(name, dims, seed=4), dims)
        for zlo, zhi in ((0, 11), (0, 4), (4, 8), (8, 11), (5, 6)):
            got = make_field_chunk(name, dims, 4, zlo, zhi)
            assert np.array_equal(got, full[zlo:zhi]), (name, zlo, zhi)

    def test_synthetic_source(self):
        dims = (4, 4, 8)
        src = FunctionSource.synthetic("truss", dims, seed=2)
        full = vol(make_field("truss", dims, seed=2), dims)
        assert np.array_equal(src.read_slab(3, 7), full[3:7])


# --------------------------------------------------------------------------
# streamed front-end: accounting + bit-identical gradient
# --------------------------------------------------------------------------

class TestStreamFront:
    def test_peak_resident_bounded_by_two_chunks(self):
        dims = (8, 8, 32)
        f = make_field("random", dims, seed=0)
        out = stream_front(ArraySource(vol(f, dims)),
                           chunk_z=4)
        rep = out.report
        assert rep.n_chunks == 8
        # the double-buffer contract: never more than the compute chunk
        # plus the prefetch chunk (each with its ghost planes)
        assert rep.peak_resident_field_bytes <= 2 * rep.max_chunk_bytes
        # and genuinely out-of-core: a fraction of the full field
        field_bytes = Grid.of(*dims).nv * 4
        assert rep.peak_resident_field_bytes < field_bytes / 2
        assert rep.total_loaded_bytes >= field_bytes  # every plane read
        assert rep.wall_s > 0 and rep.load_s > 0 and rep.compute_s > 0

    def test_streamed_gradient_equals_in_memory(self):
        dims = (6, 7, 10)
        g = Grid.of(*dims)
        f = make_field("backpack", dims, seed=1)
        from repro.core.gradient import compute_gradient
        gf_ref = compute_gradient(g, np.asarray(vertex_order(f)),
                                  backend="jax")
        out = stream_front(ArraySource(vol(f, dims)),
                           chunk_z=3)
        for k in gf_ref.crit:
            assert np.array_equal(out.gf.crit[k], gf_ref.crit[k]), k
        for k in gf_ref.pair_up:
            assert np.array_equal(out.gf.pair_up[k], gf_ref.pair_up[k]), k
        for k in gf_ref.pair_down:
            assert np.array_equal(out.gf.pair_down[k], gf_ref.pair_down[k])

    def test_sparse_order_guards_unregistered(self):
        keys = pack_value_keys(np.arange(10, dtype=np.float32),
                               np.arange(10, dtype=np.int64))
        so = SparseOrder.from_keys(keys, np.array([2, 5, 7]))
        assert len(so) == 10
        assert np.array_equal(so[np.array([5, 2])], np.array([5, 2]))
        with pytest.raises(KeyError, match="not registered"):
            so[np.array([3])]


# --------------------------------------------------------------------------
# end-to-end parity: diagram_stream == diagram
# --------------------------------------------------------------------------

REFS = {}


def ref_diagram(name, dims):
    key = (name, dims)
    if key not in REFS:
        f = make_field(name, dims, seed=0)
        REFS[key] = (f, PersistencePipeline(backend="jax")
                     .diagram(f, grid=Grid.of(*dims)))
    return REFS[key]


class TestDiagramStreamParity:
    """The acceptance matrix: >=3 field types at 32^3 and an asymmetric
    grid, two chunk sizes, one forcing >= 4 chunks."""

    @pytest.mark.parametrize("name", ["wavelet", "random", "elevation"])
    @pytest.mark.parametrize("chunk_z", [8, 5])
    def test_parity_32cubed(self, name, chunk_z):
        dims = (32, 32, 32)
        g = Grid.of(*dims)
        f, ref = ref_diagram(name, dims)
        res = PersistencePipeline(backend="jax").diagram_stream(
            ArraySource(vol(f, dims)), chunk_z=chunk_z)
        assert res.stream.n_chunks >= 4
        assert res.stream.peak_resident_field_bytes \
            <= 2 * res.stream.max_chunk_bytes
        assert_same_diagram(res, ref, g)

    @pytest.mark.parametrize("name", ["isabel", "magnetic", "truss"])
    @pytest.mark.parametrize("chunk_z", [6, 3])
    def test_parity_asymmetric(self, name, chunk_z):
        dims = (10, 6, 17)
        g = Grid.of(*dims)
        f, ref = ref_diagram(name, dims)
        res = PersistencePipeline(backend="jax").diagram_stream(
            ArraySource(vol(f, dims)), chunk_z=chunk_z)
        assert res.stream.n_chunks >= 3
        assert_same_diagram(res, ref, g)

    def test_parity_pallas_fused(self):
        dims = (6, 5, 12)
        g = Grid.of(*dims)
        f, ref = ref_diagram("wavelet", dims)
        res = PersistencePipeline(backend="pallas").diagram_stream(
            ArraySource(vol(f, dims)), chunk_z=4)
        assert_same_diagram(res, ref, g)

    def test_parity_2d_grid(self):
        dims = (12, 9, 1)
        g = Grid.of(*dims)
        f, ref = ref_diagram("random", dims)
        res = PersistencePipeline(backend="jax").diagram_stream(
            ArraySource(vol(f, dims)), chunk_z=1)
        assert_same_diagram(res, ref, g)

    def test_parity_memmap_and_function_sources(self, tmp_path):
        dims = (7, 6, 12)
        g = Grid.of(*dims)
        f, ref = ref_diagram("isabel", dims)
        pipe = PersistencePipeline(backend="jax")
        src = MemmapSource.write(os.path.join(tmp_path, "f.raw"),
                                 vol(f, dims))
        assert_same_diagram(pipe.diagram_stream(src, chunk_z=5), ref, g)
        fsrc = FunctionSource.synthetic("isabel", dims, seed=0)
        assert_same_diagram(pipe.diagram_stream(fsrc, chunk_z=4), ref, g)

    def test_parity_distributed_backend(self):
        dims = (6, 5, 12)
        g = Grid.of(*dims)
        f, ref = ref_diagram("wavelet", dims)
        res = PersistencePipeline(backend="jax", n_blocks=4,
                                  distributed=True).diagram_stream(
            ArraySource(vol(f, dims)), chunk_z=4)
        assert_same_diagram(res, ref, g)
        assert res.stats.get("d1_rounds") is not None

    def test_chunk_budget_default_and_knob(self):
        dims = (6, 6, 16)
        g = Grid.of(*dims)
        f, ref = ref_diagram("magnetic", dims)
        pipe = PersistencePipeline(backend="jax")
        src = ArraySource(vol(f, dims))
        res = pipe.diagram_stream(src)          # default 64 MiB budget
        assert res.stream.n_chunks == 1
        res = pipe.diagram_stream(src, chunk_budget=6 * 6 * 4 * 6)
        assert res.stream.n_chunks == 4
        assert_same_diagram(res, ref, g)

    def test_non_streamed_backend_raises(self):
        f = vol(make_field("wavelet", (4, 4, 4), seed=0), (4, 4, 4))
        with pytest.raises(ValueError, match="streamed"):
            PersistencePipeline(backend="np").diagram_stream(
                ArraySource(f), chunk_z=2)

    def test_report_nested_into_stage_report(self):
        dims = (5, 5, 8)
        f, _ = ref_diagram("wavelet", dims)
        res = PersistencePipeline(backend="jax").diagram_stream(
            ArraySource(vol(f, dims)), chunk_z=3)
        stages = {c.name: c for c in res.report.children}
        grad = stages["gradient"]
        assert {"load", "compute", "scatter"} <= \
            {c.name for c in grad.children}
        assert grad.counters["chunks"] == 3
        assert grad.counters["peak_resident_field_bytes"] \
            == res.stream.peak_resident_field_bytes
        assert "rank_translate" in stages
        # flat view carries the stream counters too
        assert res.stats["chunks"] == 3


# --------------------------------------------------------------------------
# serving sources
# --------------------------------------------------------------------------

class TestServiceStreaming:
    def test_topo_service_accepts_sources(self):
        from repro.serve import TopoService
        dims = (5, 5, 8)
        g = Grid.of(*dims)
        f, ref = ref_diagram("wavelet", dims)
        with TopoService(backend="jax", max_batch=4) as svc:
            fut = svc.submit(FunctionSource.synthetic("wavelet", dims,
                                                      seed=0))
            res = fut.result(timeout=120)
            assert svc.stats.stream_requests == 1
        assert res.stream is not None
        assert_same_diagram(res, ref, g)
