"""Double-buffered out-of-core streaming of the gradient front-end.

The jax analogue of the paper's dedicated communication thread (Sec.
V-C): a one-slot loader thread prefetches chunk ``i+1`` from the
:class:`~repro.stream.chunks.FieldSource` while the device computes the
lower-star gradient of chunk ``i``, so host I/O and kernel time overlap
and at most **two** ghost-extended chunks of field data are ever
resident.  Per chunk:

1. the loader reads the ghost-extended z-slab (float32 planes);
2. the slab is packed into rank-free ``(value, vid)`` int64 keys
   (:func:`~repro.stream.chunks.pack_value_keys`) — no global argsort,
   no dense rank array, zero cross-chunk communication;
3. the halo-extended key volume goes straight into the XLA chunk
   kernel (``repro.kernels.ops.lower_star_rows_halo``, the one-jit jnp
   program — the only kernel that takes the int64 keys), which returns
   packed gradient rows for the owned vertices;
4. the rows scatter into global gradient arrays through the cached
   row→sid offset tables (``GR.scatter_rows_chunk``) and the owned keys
   land in the dense key array handed to the back-end.

The back-end consumes the key array *as* the vertex order (every
downstream comparison — critical ranks, elder rule, D1 propagation — is
order-isomorphism invariant), and :class:`SparseOrder` translates keys
back to true global ranks only for the handful of vertices the final
diagram touches, via a chunked counting pass (:func:`ranks_for_vids`).
The global vertex order is never materialized.

All byte/second accounting lands in a :class:`StreamReport`, the record
the resident-memory acceptance test asserts against (not logging).
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from repro.core import gradient as GR
from repro.core.grid import Grid
from repro.obs import flight as _flight
from repro.obs import watchdog as _watchdog
from repro.obs.metrics import global_metrics
from repro.obs.trace import maybe_span

from .chunks import Chunk, FieldSource, pack_value_keys, plan_chunks


# --------------------------------------------------------------------------
# accounting
# --------------------------------------------------------------------------

@dataclass
class StreamReport:
    """Machine-readable accounting of one streamed front-end run.

    ``peak_resident_field_bytes`` counts ghost-extended field slabs
    *reserved simultaneously* (the compute slab plus the prefetch slab) —
    the number the out-of-core contract bounds by ~2 chunks + ghosts; in
    a sharded run it is the concurrent total across shards, with the
    per-shard peaks in ``per_shard``.  ``key_bytes`` is the dense int64
    key array handed to the back-end (the per-vertex residue the current
    in-memory back-end still needs; see docs/pipeline.md for the full
    memory model).

    ``comm_s`` totals the halo-exchange work of a sharded run (boundary
    plane publishes plus neighbor-plane waits); ``comm_hidden_s`` is the
    part that ran inside the loader thread while the device computed,
    and ``overlap_fraction = comm_hidden_s / comm_s`` (None when the run
    had no communication) is the comm-hiding figure of merit."""

    dims: tuple = ()
    backend: str = ""
    n_chunks: int = 0
    chunk_z: int = 0
    max_chunk_bytes: int = 0
    peak_resident_field_bytes: int = 0
    total_loaded_bytes: int = 0
    key_bytes: int = 0
    load_s: float = 0.0
    compute_s: float = 0.0
    scatter_s: float = 0.0
    wall_s: float = 0.0
    overlap_s: float = 0.0
    n_shards: int = 1
    comm_s: float = 0.0
    comm_hidden_s: float = 0.0
    overlap_fraction: Optional[float] = None
    per_shard: List[dict] = field(default_factory=list)

    def to_dict(self) -> dict:
        return {k: (list(v) if isinstance(v, tuple) else v)
                for k, v in self.__dict__.items()}


class _Resident:
    """Running/peak byte counter for reserved field slabs (thread-safe:
    sharded runs reserve from every shard worker concurrently)."""

    def __init__(self):
        self.cur = 0
        self.peak = 0
        self._lock = threading.Lock()

    def add(self, n: int) -> None:
        with self._lock:
            self.cur += n
            self.peak = max(self.peak, self.cur)

    def release(self, n: int) -> None:
        with self._lock:
            self.cur -= n


# --------------------------------------------------------------------------
# streamed front-end
# --------------------------------------------------------------------------

@dataclass
class StreamResult:
    """Front-end handoff: dense gradient + key array + accounting."""

    gf: GR.GradientField
    keys: np.ndarray          # (nv,) int64 rank-free keys (back-end order)
    report: StreamReport
    chunks: List[Chunk] = field(default_factory=list)

    def values_for_vids(self, vids) -> np.ndarray:
        """Field values of ``vids``, recovered from the packed keys —
        the field itself was never materialized (-0.0 reads as +0.0)."""
        from .chunks import unpack_value_keys
        return unpack_value_keys(self.keys[np.asarray(vids, np.int64)])


def _ext_volume(keys_slab: np.ndarray, c: Chunk, dims,
                halo_lo: Optional[np.ndarray] = None,
                halo_hi: Optional[np.ndarray] = None) -> np.ndarray:
    """(nzl+2, ny, nx) halo key volume of chunk ``c`` (-1 at the grid
    boundary).  At a *shard* boundary the ghost plane was not loaded from
    the source: it is the neighbor's boundary key plane received through
    the halo exchange (``halo_lo`` / ``halo_hi``)."""
    nx, ny, nz = dims
    k3 = keys_slab.reshape(c.ghi - c.glo, ny, nx)
    if c.halo_below:
        lo = np.asarray(halo_lo, np.int64).reshape(1, ny, nx)
    elif c.glo < c.zlo:
        lo = k3[:1]
    else:
        lo = np.full((1, ny, nx), -1, np.int64)
    if c.halo_above:
        hi = np.asarray(halo_hi, np.int64).reshape(1, ny, nx)
    elif c.ghi > c.zhi:
        hi = k3[-1:]
    else:
        hi = np.full((1, ny, nx), -1, np.int64)
    return np.concatenate([lo, k3[c.zlo - c.glo: c.zhi - c.glo], hi], axis=0)


def stream_front(source: FieldSource, *,
                 chunk_z: Optional[int] = None,
                 chunk_budget: Optional[int] = None,
                 stage_report=None) -> StreamResult:
    """Run the lower-star gradient over ``source`` chunk by chunk.

    Chunks run the XLA chunk kernel (``lower_star_rows_halo``).  Exactly
    one of ``chunk_z`` (owned planes per chunk) / ``chunk_budget`` (bytes of
    loaded field per chunk) selects the decomposition.  ``stage_report``,
    if given, is a ``StageReport`` that receives load/compute/scatter
    child timings and the headline counters."""
    from repro.kernels import ops

    grid = Grid.of(*source.dims)
    nx, ny, nz = grid.dims
    plane = nx * ny
    chunks = plan_chunks(grid.dims, chunk_z=chunk_z,
                         chunk_budget=chunk_budget)

    gf = GR.alloc_gradient(grid)
    offsets = GR.row_sid_offsets(grid)
    keys = np.empty(grid.nv, dtype=np.int64)
    rep = StreamReport(
        dims=grid.dims, backend="jax", n_chunks=len(chunks),
        chunk_z=chunks[0].nz,
        max_chunk_bytes=max(c.load_bytes(grid.dims) for c in chunks),
        key_bytes=keys.nbytes)
    res = _Resident()
    # worker threads cannot see the run's thread-local activation —
    # they capture the Trace (or None) from the stage report instead
    tr = getattr(stage_report, "trace", None)

    def load(c: Chunk):
        t0 = time.perf_counter()
        with maybe_span(tr, "chunk_load", zlo=c.zlo, zhi=c.zhi):
            slab = source.read_slab(c.glo, c.ghi)
        return slab, time.perf_counter() - t0

    t_wall = time.perf_counter()
    # a loader-thread failure surfaces at fut.result(): any escaping
    # exception leaves a flight dump, and the chunk loop beats a
    # watchdog lane so a silent wedge (a blocking source) gets named
    with _flight.dump_on_error("stream.scheduler"), \
            _watchdog.lane("stream.chunks"), \
            ThreadPoolExecutor(max_workers=1,
                               thread_name_prefix="stream-loader") as pool:
        res.add(chunks[0].load_bytes(grid.dims))
        fut = pool.submit(load, chunks[0])
        for i, c in enumerate(chunks):
            _watchdog.progress("stream.chunks")
            slab, dt = fut.result()
            rep.load_s += dt
            rep.total_loaded_bytes += slab.nbytes
            if i + 1 < len(chunks):
                # double buffer: reserve + prefetch the next chunk while
                # this one computes (the "communication thread")
                res.add(chunks[i + 1].load_bytes(grid.dims))
                fut = pool.submit(load, chunks[i + 1])

            t0 = time.perf_counter()
            with maybe_span(tr, "chunk_compute", zlo=c.zlo, zhi=c.zhi):
                vids = np.arange(c.glo * plane, c.ghi * plane,
                                 dtype=np.int64)
                kslab = pack_value_keys(slab, vids)
                ext = _ext_volume(kslab, c, grid.dims)
                rows = [np.asarray(r) for r in
                        ops.lower_star_rows_halo(ext)]
            rep.compute_s += time.perf_counter() - t0

            t0 = time.perf_counter()
            with maybe_span(tr, "chunk_scatter", zlo=c.zlo, zhi=c.zhi):
                v0 = c.vid0(grid.dims)
                GR.scatter_rows_chunk(grid, gf, rows[0], rows[1], rows[2],
                                      rows[3], v0, offsets=offsets)
                keys[v0: v0 + c.nz * plane] = \
                    kslab[(c.zlo - c.glo) * plane:
                          (c.zlo - c.glo) * plane + c.nz * plane]
            rep.scatter_s += time.perf_counter() - t0
            res.release(c.load_bytes(grid.dims))
            del slab, kslab, ext, rows

    rep.wall_s = time.perf_counter() - t_wall
    rep.peak_resident_field_bytes = res.peak
    serial = rep.load_s + rep.compute_s + rep.scatter_s
    rep.overlap_s = max(0.0, serial - rep.wall_s)
    mx = global_metrics()
    mx.counter("stream.chunks").inc(rep.n_chunks)
    mx.counter("stream.loaded_bytes").inc(rep.total_loaded_bytes)

    if stage_report is not None:
        for name in ("load", "compute", "scatter"):
            ch = stage_report.child(name)
            ch.seconds = getattr(rep, name + "_s")
        stage_report.count(
            chunks=rep.n_chunks,
            peak_resident_field_bytes=rep.peak_resident_field_bytes,
            loaded_bytes=rep.total_loaded_bytes,
            max_chunk_bytes=rep.max_chunk_bytes,
            overlap_s=rep.overlap_s)
    return StreamResult(gf, keys, rep, chunks)


# --------------------------------------------------------------------------
# key -> rank translation for the final diagram
# --------------------------------------------------------------------------

def ranks_for_vids(keys: np.ndarray, vids: np.ndarray,
                   slab: int = 1 << 20) -> np.ndarray:
    """Exact global ranks of ``vids`` under the (value, vid) order.

    rank(v) = #{u : key[u] < key[v]} — computed by counting against the
    key array one O(slab) piece at a time (sort the piece, one
    ``searchsorted`` per piece), so no global argsort/permutation is ever
    built.  Keys are injective, so these ranks equal
    ``vertex_order(f)[vids]`` bit-for-bit."""
    vids = np.asarray(vids, dtype=np.int64)
    qk = keys[vids]
    counts = np.zeros(len(vids), dtype=np.int64)
    for lo in range(0, len(keys), slab):
        counts += np.searchsorted(np.sort(keys[lo:lo + slab]), qk,
                                  side="left")
    return counts


class SparseOrder:
    """Array-like vertex order defined only at registered vertices.

    Stands in for the dense ``order`` array on a streamed
    :class:`~repro.core.diagram.Diagram`: fancy-indexing (``order[vids]``)
    answers exact global ranks for the critical-simplex vertices the
    diagram touches and raises ``KeyError`` elsewhere — by construction
    the streamed pipeline never needs the rest."""

    def __init__(self, nv: int, vids: np.ndarray, ranks: np.ndarray):
        srt = np.argsort(vids)
        self.nv = int(nv)
        self._vids = np.asarray(vids, dtype=np.int64)[srt]
        self._ranks = np.asarray(ranks, dtype=np.int64)[srt]

    @classmethod
    def from_keys(cls, keys: np.ndarray, vids: np.ndarray) -> "SparseOrder":
        vids = np.unique(np.asarray(vids, dtype=np.int64))
        return cls(len(keys), vids, ranks_for_vids(keys, vids))

    def __len__(self) -> int:
        return self.nv

    def __getitem__(self, idx) -> np.ndarray:
        a = np.asarray(idx, dtype=np.int64)
        pos = np.searchsorted(self._vids, a)
        pc = np.clip(pos, 0, max(len(self._vids) - 1, 0))
        if len(self._vids) == 0 or not (self._vids[pc] == a).all():
            missing = np.unique(
                a[(len(self._vids) == 0)
                  | (self._vids[pc] != a)]) if a.size else a
            raise KeyError(
                f"SparseOrder: rank not registered for vertices "
                f"{missing[:8].tolist()}{'...' if missing.size > 8 else ''}")
        return self._ranks[pc].reshape(a.shape)


def diagram_vertices(grid: Grid, pairs: Dict[int, np.ndarray],
                     essential: Dict[int, np.ndarray]) -> np.ndarray:
    """All vertex ids the final diagram will ever look up: the vertices
    of every paired and essential critical simplex."""
    vs = []
    for p, pr in pairs.items():
        if len(pr):
            vs.append(np.asarray(
                grid.simplex_vertices(p, pr[:, 0])).reshape(-1))
            vs.append(np.asarray(
                grid.simplex_vertices(p + 1, pr[:, 1])).reshape(-1))
    for p, es in essential.items():
        es = np.asarray(es)
        if len(es):
            vs.append(np.asarray(grid.simplex_vertices(p, es)).reshape(-1))
    if not vs:
        return np.zeros(0, dtype=np.int64)
    return np.unique(np.concatenate(vs))
