"""Tests for the overlapped sharded-streaming engine (repro.stream.sharded).

The headline contract: a streamed request with ``n_blocks > 1`` runs
every shard over its own z-slab chunk-by-chunk — never more than ~2
ghost-extended chunks of field data resident *per shard* — exchanges
boundary key planes through the double-buffered :class:`HaloExchange`,
and still produces diagrams bit-identical to the in-memory single-device
path (off-diagonal pairs AND essential classes).  Comm accounting
(``comm_seconds`` / ``overlap_fraction``) must surface through the
:class:`StreamReport` and the :class:`StageReport`."""

import os
import threading

import numpy as np
import pytest

from repro.core.diagram import diff_report, same_offdiagonal
from repro.core.grid import Grid, vertex_order
from repro.fields import make_field
from repro.pipeline import PersistencePipeline, TopoRequest
from repro.pipeline.stages import StageReport
from repro.stream import (ArraySource, HaloExchange, HaloExchangeTimeout,
                          MemmapSource, pack_value_keys, plan_chunks,
                          plan_shards, sharded_stream_front)


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
# shard planning + windowed chunking
# --------------------------------------------------------------------------

class TestPlanShards:
    def test_near_even_contiguous_cover(self):
        for nz, ns in ((32, 4), (17, 4), (9, 2), (7, 7), (100, 8)):
            slabs = plan_shards(nz, ns)
            assert slabs[0][0] == 0 and slabs[-1][1] == nz
            for (_, a1), (b0, _) in zip(slabs, slabs[1:]):
                assert a1 == b0
            sizes = [z1 - z0 for z0, z1 in slabs]
            assert sum(sizes) == nz
            assert max(sizes) - min(sizes) <= 1

    def test_clamped_to_one_plane_per_shard(self):
        slabs = plan_shards(3, 8)
        assert len(slabs) == 3
        assert all(z1 - z0 == 1 for z0, z1 in slabs)

    def test_rejects_bad_count(self):
        with pytest.raises(ValueError, match="n_shards"):
            plan_shards(16, 0)

    def test_windowed_chunks_cover_slab_with_shard_halos(self):
        dims = (4, 4, 32)
        for (z0, z1) in plan_shards(32, 4):
            chunks = plan_chunks(dims, chunk_z=3, window=(z0, z1),
                                 halo_below=z0 > 0, halo_above=z1 < 32)
            assert chunks[0].zlo == z0 and chunks[-1].zhi == z1
            for a, b in zip(chunks, chunks[1:]):
                assert a.zhi == b.zlo
            # source reads stay inside the shard window: the boundary
            # ghost planes arrive through the halo exchange instead
            for c in chunks:
                assert z0 <= c.glo and c.ghi <= z1
            assert chunks[0].halo_below == (z0 > 0)
            assert chunks[-1].halo_above == (z1 < 32)
            # interior chunk boundaries never need the exchange
            for c in chunks[1:-1]:
                assert not c.halo_below and not c.halo_above


# --------------------------------------------------------------------------
# halo exchange primitive
# --------------------------------------------------------------------------

class TestHaloExchange:
    def test_publish_recv_round_trip(self):
        ex = HaloExchange(3)
        plane = np.arange(12, dtype=np.int64)
        ex.publish(1, "last", plane)
        got = ex.recv(1, "last", timeout=1.0)
        assert np.array_equal(got, plane)

    def test_recv_blocks_until_published(self):
        ex = HaloExchange(2)
        plane = np.arange(6, dtype=np.int64)
        t = threading.Timer(0.05, lambda: ex.publish(0, "last", plane))
        t.start()
        try:
            assert np.array_equal(ex.recv(0, "last", timeout=5.0), plane)
        finally:
            t.join()

    def test_recv_timeout_raises(self):
        ex = HaloExchange(2)
        with pytest.raises(HaloExchangeTimeout, match="shard 1"):
            ex.recv(1, "first", timeout=0.05)


# --------------------------------------------------------------------------
# sharded front-end: bit-identical gradient + resident/comm accounting
# --------------------------------------------------------------------------

class TestShardedStreamFront:
    def test_gradient_and_keys_equal_in_memory(self):
        dims = (6, 7, 20)
        g = Grid.of(*dims)
        f = make_field("backpack", dims, seed=1)
        from repro.core.gradient import compute_gradient
        gf_ref = compute_gradient(g, np.asarray(vertex_order(f)),
                                  backend="jax")
        out = sharded_stream_front(ArraySource(vol(f, dims)), 4,
                                   chunk_z=3)
        for k in gf_ref.crit:
            assert np.array_equal(out.gf.crit[k], gf_ref.crit[k]), k
        for k in gf_ref.pair_up:
            assert np.array_equal(out.gf.pair_up[k], gf_ref.pair_up[k]), k
        for k in gf_ref.pair_down:
            assert np.array_equal(out.gf.pair_down[k], gf_ref.pair_down[k])
        ref_keys = pack_value_keys(vol(f, dims),
                                   np.arange(g.nv, dtype=np.int64))
        assert np.array_equal(out.keys, ref_keys)

    def test_per_shard_residency_and_comm_accounting(self):
        dims = (8, 8, 40)
        f = make_field("random", dims, seed=0)
        out = sharded_stream_front(ArraySource(vol(f, dims)), 4,
                                   chunk_z=3)
        rep = out.report
        assert rep.n_shards == 4
        assert len(rep.per_shard) == 4
        for st in rep.per_shard:
            # the double-buffer contract, per shard: compute chunk +
            # prefetch chunk, each with its ghost planes
            assert st["peak_resident_field_bytes"] \
                <= 2 * st["max_chunk_bytes"], st
            assert st["n_chunks"] >= 3
        # interior shards publish 2 planes, edge shards 1 -> 2*(ns-1)
        assert sum(st["halo_planes"] for st in rep.per_shard) == 6
        assert rep.comm_s > 0
        assert rep.overlap_fraction is not None
        assert 0.0 <= rep.overlap_fraction <= 1.0
        assert rep.comm_hidden_s <= rep.comm_s + 1e-9
        # every owned plane read once + one halo-publish plane per edge
        field_bytes = Grid.of(*dims).nv * 4
        assert rep.total_loaded_bytes >= field_bytes

    def test_single_shard_degrades_to_plain_streaming(self):
        dims = (5, 4, 9)
        f = make_field("wavelet", dims, seed=0)
        out = sharded_stream_front(ArraySource(vol(f, dims)), 1,
                                   chunk_z=4)
        assert out.report.n_shards == 1
        assert out.report.comm_s == 0.0
        assert out.report.overlap_fraction is None


# --------------------------------------------------------------------------
# end-to-end parity matrix: sharded-streamed == in-memory
# --------------------------------------------------------------------------

REFS = {}


def ref_diagram(name, dims):
    key = (name, dims)
    if key not in REFS:
        f = make_field(name, dims, seed=0)
        REFS[key] = (f, PersistencePipeline(backend="jax")
                     .diagram(f, grid=Grid.of(*dims)))
    return REFS[key]


def run_sharded(f, dims, n_shards, chunk_z=3, source=None, **req_kw):
    src = ArraySource(vol(f, dims)) if source is None else source
    return PersistencePipeline(backend="jax").run(
        TopoRequest(field=src, stream=True, chunk_z=chunk_z,
                    n_blocks=n_shards, **req_kw))


class TestShardedParity:
    """The acceptance matrix: field zoo x {2, 4} shards on asymmetric and
    thin grids, resident memory bounded per shard."""

    @pytest.mark.parametrize("name", ["wavelet", "random", "elevation"])
    @pytest.mark.parametrize("n_shards", [2, 4])
    def test_parity_asymmetric(self, name, n_shards):
        dims = (8, 5, 24)
        g = Grid.of(*dims)
        f, ref = ref_diagram(name, dims)
        res = run_sharded(f, dims, n_shards)
        assert res.stream.n_shards == n_shards
        for st in res.stream.per_shard:
            assert st["peak_resident_field_bytes"] \
                <= 2 * st["max_chunk_bytes"], st
        assert res.stream.overlap_fraction is not None
        assert_same_diagram(res, ref, g)

    @pytest.mark.parametrize("name", ["isabel", "truss"])
    @pytest.mark.parametrize("n_shards", [2, 4])
    def test_parity_thin_grid(self, name, n_shards):
        dims = (4, 3, 33)
        g = Grid.of(*dims)
        f, ref = ref_diagram(name, dims)
        res = run_sharded(f, dims, n_shards)
        assert_same_diagram(res, ref, g)

    def test_parity_uneven_slabs(self):
        # nz = 17 over 4 shards: slab sizes 5,4,4,4 (plan_shards extras)
        dims = (10, 6, 17)
        g = Grid.of(*dims)
        f, ref = ref_diagram("magnetic", dims)
        res = run_sharded(f, dims, 4, chunk_z=2)
        assert res.stream.n_shards == 4
        assert_same_diagram(res, ref, g)

    def test_parity_memmap_source(self, tmp_path):
        dims = (7, 6, 24)
        g = Grid.of(*dims)
        f, ref = ref_diagram("isabel", dims)
        src = MemmapSource.write(os.path.join(tmp_path, "f.raw"),
                                 vol(f, dims))
        res = run_sharded(f, dims, 4, chunk_z=3, source=src)
        assert res.stream.n_shards == 4
        assert_same_diagram(res, ref, g)

    def test_shards_clamped_to_z_extent(self):
        dims = (6, 5, 3)
        g = Grid.of(*dims)
        f, ref = ref_diagram("random", dims)
        res = run_sharded(f, dims, 8, chunk_z=1)
        assert res.stream.n_shards == 3
        assert_same_diagram(res, ref, g)

    @pytest.mark.slow
    def test_parity_32cubed_4_shards(self):
        dims = (32, 32, 32)
        g = Grid.of(*dims)
        f, ref = ref_diagram("wavelet", dims)
        res = run_sharded(f, dims, 4, chunk_z=4)
        # 4 concurrent shards, each double-buffered: the global peak is
        # bounded by 2 ghost-extended chunks per shard
        assert res.stream.peak_resident_field_bytes \
            <= res.stream.n_shards * 2 * res.stream.max_chunk_bytes
        assert_same_diagram(res, ref, g)


# --------------------------------------------------------------------------
# plan lowering + report surfacing
# --------------------------------------------------------------------------

class TestPlanAndReport:
    def test_describe_names_the_composed_engine(self):
        pipe = PersistencePipeline(backend="jax")
        f = np.zeros((8, 4, 4), np.float32)
        plan = pipe.lower(TopoRequest(field=ArraySource(f), stream=True,
                                      chunk_z=2, n_blocks=4))
        assert "sharded-streamed x4" in plan.describe()
        assert "overlapped halo exchange" in plan.describe()
        solo = pipe.lower(TopoRequest(field=ArraySource(f), stream=True,
                                      chunk_z=2))
        assert "sharded-streamed" not in solo.describe()

    def test_shardmap_backend_remaps_to_composed_engine(self):
        # the shardmap backend has no streamed kernels; a streamed +
        # sharded request must lower to the composed engine instead of
        # raising (the pre-composition behavior)
        pipe = PersistencePipeline(backend="shardmap")
        f = np.zeros((8, 4, 4), np.float32)
        plan = pipe.lower(TopoRequest(field=ArraySource(f), stream=True,
                                      chunk_z=2, n_blocks=2))
        assert plan.backend == "jax"
        assert plan.n_blocks == 2
        assert plan.streamed

    def test_stage_report_comm_properties(self):
        root = StageReport("pipeline")
        grad = root.child("gradient")
        grad.seconds = 2.0
        comm = grad.child("comm")
        comm.seconds = 0.5
        comm.count(comm_total_s=0.5, comm_hidden_s=0.4)
        assert root.comm_seconds == pytest.approx(0.5)
        assert root.overlap_fraction == pytest.approx(0.8)
        d = root.to_dict()
        assert d["comm_seconds"] == pytest.approx(0.5)
        assert d["overlap_fraction"] == pytest.approx(0.8)

    def test_stage_report_no_comm_is_none(self):
        root = StageReport("pipeline")
        root.child("gradient").seconds = 1.0
        assert root.comm_seconds == 0.0
        assert root.overlap_fraction is None
        assert "overlap_fraction" not in root.to_dict()

    def test_run_report_carries_comm_split(self):
        dims = (6, 5, 16)
        f, _ = ref_diagram("wavelet", dims)
        res = run_sharded(f, dims, 4, chunk_z=2)
        assert res.report.comm_seconds > 0
        ofrac = res.report.overlap_fraction
        assert ofrac is not None and 0.0 <= ofrac <= 1.0
        d = res.report.to_dict()
        assert d["comm_seconds"] > 0
