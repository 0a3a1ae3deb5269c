"""Device-level DDMS front-end under ``shard_map`` (paper Sec. III/IV).

The scalar field is z-slab decomposed over a mesh axis; each device runs:

  1. *Array preconditioning*: distributed sample sort -> global vertex ranks
     (``repro.distributed.order``) or rank-free keys (beyond-paper);
  2. one-plane halo exchange of ranks (``lax.ppermute``) — the ghost layer;
  3. the lower-star gradient on its own vertices (jnp oracle or Pallas);
  4. successor construction by pure index arithmetic from the packed rows:
     vertex -> next vertex (descending v-path), tet -> next tet (dual
     ascending path, OMEGA at the compactified boundary).  Tets whose base
     lies in the below-ghost plane belong to the neighbor (lowest-base
     ownership, paper Sec. II-B) and their successors are shipped down —
     the only ghost-simplex exchange the pipeline needs;
  5. trace resolution: local pointer doubling, then *ring resolution* —
     boundary-plane resolution tables rotate around the mesh ring and
     cross-slab pointers substitute through them.  This is the
     bulk-synchronous analogue of the paper's compute-until-ghost /
     exchange / resume rounds (Sec. IV-A); cross-block pointers always land
     in a first/last slab plane, so the table family is closed;
  6. emission of capacity-padded extremum-graph triplet buffers for D0 and
     the dual diagram — the interface to the self-correcting pairing.

Everything is fixed-shape and jit-able: this is the program the multi-pod
dry-run lowers and the roofline analysis measures.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gradient as GR
from repro.core import grid as G
from repro.kernels import ref as REF
from repro.kernels.lower_star import (fields_from_words,
                                      fused_rows_from_halo_volume, halo_words,
                                      lower_star_gradient_pallas)
from repro.obs import flight as _flight
from .order import rankfree_keys, sample_sort_ranks

OMEGA = -2


class CritCapacityError(RuntimeError):
    """A device found more critical edges/triangles than its fixed-shape
    triplet buffers can hold.  Raised by :func:`run_front` (never a
    silent truncation); carries the observed peak and the capacity so
    callers can rerun with an explicit ``crit_cap``."""

    def __init__(self, observed: int, cap: int, dims, n_blocks: int):
        self.observed = int(observed)
        self.cap = int(cap)
        super().__init__(
            f"critical-simplex count {self.observed} exceeds the triplet "
            f"buffer capacity {self.cap} on at least one device (dims="
            f"{tuple(dims)}, n_blocks={n_blocks}); pass crit_cap="
            f"{self.observed} (or higher) to run_front/FrontConfig")


@dataclass(frozen=True)
class FrontConfig:
    dims: Tuple[int, int, int]        # global (nx, ny, nz)
    n_blocks: int
    axis_name: object = "blocks"      # one name or tuple of names
    # triplet buffer capacity per device; None auto-sizes from the grid
    # (overflow always *raises* CritCapacityError, never truncates)
    crit_cap: Optional[int] = None
    # resolution ring rotations; None derives a convergence bound from
    # n_blocks + plane size and early-exits on stationarity
    ring_rotations: Optional[int] = None
    gradient_backend: str = "jax"     # "jax" | "fused" | "pallas"
    gradient_chunk: Optional[int] = None  # vertices per chunk (memory knob)
    use_sample_sort: bool = True
    sort_slack: float = 2.0
    # split the gradient into interior planes (purely local) + the two
    # boundary planes (need the ppermute halo) so XLA overlaps the
    # collective with the interior kernel; output is bit-identical
    overlap_comm: bool = True

    @property
    def nz_local(self) -> int:
        nx, ny, nz = self.dims
        if self.n_blocks < 1:
            raise ValueError(f"n_blocks must be >= 1, got {self.n_blocks}")
        if nz % self.n_blocks != 0:
            raise ValueError(
                f"nz={nz} does not divide evenly over n_blocks="
                f"{self.n_blocks} (dims={self.dims}); choose a block count "
                f"dividing the z extent")
        return nz // self.n_blocks

    @property
    def plane(self) -> int:
        return self.dims[0] * self.dims[1]

    @property
    def nv_local(self) -> int:
        return self.nz_local * self.plane

    @property
    def crit_capacity(self) -> int:
        """Resolved triplet buffer capacity: the explicit ``crit_cap``,
        else sized from the slab (a lower-star emits at most a few
        critical cells per vertex; overflow raises, never truncates)."""
        if self.crit_cap is not None:
            return self.crit_cap
        return min(7 * self.nv_local, max(4096, self.nv_local))

    def ring_rotation_count(self, ent_per_vertex: int = 1) -> int:
        """Rotations guaranteeing ring-resolution convergence.

        Each rotation substitutes through *rotation-start snapshots* of
        every block's boundary tables and then re-doubles locally, so
        resolved prefixes double per rotation (parallel pointer jumping
        on the boundary graph).  V-paths are strictly descending — they
        visit each boundary-plane entity at most once — so chain length
        across boundaries is bounded by the total boundary entries
        ``2 * (n_blocks - 1) * plane * ent``, and ``ceil(log2(.)) + 1``
        rotations suffice.  The old hard-coded 3 silently under-resolved
        zigzag chains crossing more than ~8 slab boundaries."""
        if self.ring_rotations is not None:
            return self.ring_rotations
        boundary = 2 * max(1, self.n_blocks - 1) * self.plane \
            * max(1, ent_per_vertex)
        return max(3, int(np.ceil(np.log2(boundary))) + 1)


# -- mesh-axis helpers (single name or tuple; z is split over all of them) --

def _axis_size(ax):
    if isinstance(ax, tuple):
        n = 1
        for a in ax:
            n *= jax.lax.axis_size(a)
        return n
    return jax.lax.axis_size(ax)


def _axis_index(ax):
    if isinstance(ax, tuple):
        idx = jax.lax.axis_index(ax[0])
        for a in ax[1:]:
            idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
        return idx
    return jax.lax.axis_index(ax)


def _ring_perm(n, up: bool, wrap: bool):
    if up:
        p = [(i, i + 1) for i in range(n - 1)]
        return p + ([(n - 1, 0)] if wrap else [])
    p = [(i + 1, i) for i in range(n - 1)]
    return p + ([(0, n - 1)] if wrap else [])


def _ppshift(x, ax, up: bool, wrap: bool = False):
    """Shift x by one block along the (possibly multi-axis) ring; edge
    devices receive zeros unless wrap."""
    n = _axis_size(ax)
    name = ax[0] if isinstance(ax, tuple) and len(ax) == 1 else ax
    return jax.lax.ppermute(x, name, _ring_perm(n, up, wrap))


# --------------------------------------------------------------------------
# generic ring resolution of successor tables
# --------------------------------------------------------------------------

def _double_table(table, lo, n_local, iters):
    """True pointer doubling: T <- T o T wherever entries point locally.
    O(log chain length) iterations resolve every local chain."""
    def body(_, t):
        is_loc = (t >= lo) & (t < lo + n_local)
        idx = jnp.clip(t - lo, 0, n_local - 1)
        return jnp.where(is_loc, t[idx], t)
    return jax.lax.fori_loop(0, iters, body, table)


def _lookup(vals, table, lo, n_local):
    """One substitution of vals through a locally-resolved table."""
    is_loc = (vals >= lo) & (vals < lo + n_local)
    idx = jnp.clip(vals - lo, 0, n_local - 1)
    return jnp.where(is_loc, table[idx], vals)


def ring_resolve(cfg: FrontConfig, table, ent_per_vertex: int, queries):
    """Fully resolve a sharded successor table + extra query pointers.

    table: (n_local,) global-space successor values for the entities based
    in my slab (terminal entries point to themselves; OMEGA < 0 passes).
    queries: (q,) pointers to resolve through the global table.
    Returns (resolved_table, resolved_queries, unresolved_count).
    """
    ax = cfg.axis_name
    nb = cfg.n_blocks
    me = _axis_index(ax)
    P = cfg.plane * ent_per_vertex
    n_local = cfg.nv_local * ent_per_vertex
    lo = me.astype(jnp.int64) * n_local
    log_iters = int(np.ceil(np.log2(max(2, n_local)))) + 1

    table = _double_table(table, lo, n_local, log_iters)
    queries = _lookup(queries, table, lo, n_local)

    if nb > 1:
        def substitute(vals, tabs, owner):
            own_lo = owner.astype(jnp.int64) * n_local
            off = vals - own_lo
            in_first = (off >= 0) & (off < P)
            in_last = (off >= n_local - P) & (off < n_local)
            idx_f = jnp.clip(off, 0, P - 1)
            idx_l = jnp.clip(off - (n_local - P), 0, P - 1)
            out = jnp.where(in_first, tabs[0][idx_f], vals)
            out = jnp.where(in_last, tabs[1][idx_l], out)
            return out

        def one_rotation(state):
            table, queries = state
            old_t, old_q = table, queries
            tabs = jnp.stack([table[:P], table[n_local - P:]])
            owner = me
            def step(_, st):
                table, queries, tabs, owner = st
                table = substitute(table, tabs, owner)
                queries = substitute(queries, tabs, owner)
                tabs = _ppshift(tabs, ax, up=True, wrap=True)
                owner = (owner - 1) % nb
                return (table, queries, tabs, owner)
            table, queries, _, _ = jax.lax.fori_loop(
                0, nb, step, (table, queries, tabs, owner))
            # chains may have re-entered my slab: settle locally again
            table = _double_table(table, lo, n_local, log_iters)
            queries = _lookup(queries, table, lo, n_local)
            changed = (table != old_t).sum() + (queries != old_q).sum()
            return table, queries, changed

        max_rot = cfg.ring_rotation_count(ent_per_vertex)
        if cfg.ring_rotations is not None:
            # explicit count: fixed rotations (legacy behavior, still
            # reports unresolved chains through the stationarity count)
            changed = jnp.int64(0)
            for _ in range(max_rot):
                table, queries, changed = one_rotation((table, queries))
            unresolved = jax.lax.psum(changed, cfg.axis_name)
        else:
            # derived bound + stationarity early exit: rotate until no
            # entry moved anywhere on the ring (the psum makes the loop
            # condition globally uniform, so every device takes the
            # same number of rotations — no collective mismatch)
            def cond(st):
                _, _, changed_g, r = st
                return (changed_g > 0) & (r < max_rot)

            def body(st):
                table, queries, _, r = st
                table, queries, changed = one_rotation((table, queries))
                return (table, queries,
                        jax.lax.psum(changed, cfg.axis_name), r + 1)

            table, queries, unresolved, _ = jax.lax.while_loop(
                cond, body, (table, queries, jnp.int64(1), jnp.int64(0)))
            # stationary <=> resolved: a locally-doubled table entry only
            # maps a value to itself if it is terminal, so any unresolved
            # chain keeps advancing; the loop only stops early once a full
            # rotation moved nothing, hence unresolved > 0 here means the
            # convergence bound itself was exceeded.
    else:
        unresolved = jnp.int64(0)
    return table, queries, unresolved


# --------------------------------------------------------------------------
# the per-device program
# --------------------------------------------------------------------------

def _rank_bound(cfg: FrontConfig) -> Optional[int]:
    """Static exclusive bound on rank values (None for rank-free keys).

    Dense sample-sort ranks live in [0, nv_global); rank-free keys are
    full-width int64 and admit no narrowing or key packing."""
    if not cfg.use_sample_sort:
        return None
    nx, ny, nz = cfg.dims
    return nx * ny * nz


def halo_exchange(cfg: FrontConfig, r3):
    """The ring neighbours' boundary rank planes of my z-slab (inside
    shard_map): ``r3`` is (..., nz_local, ny, nx); returns ``(below,
    above)``, each (..., 1, ny, nx) in ``r3``'s dtype: the last plane of
    the slab below and the first plane of the slab above, -1 at the
    global boundary.  Two one-plane ``ppermute``s under the ``halo``
    scope."""
    ax = cfg.axis_name
    me = _axis_index(ax)
    with jax.named_scope("halo"):
        below = _ppshift(r3[..., -1:, :, :], ax, up=True)
        above = _ppshift(r3[..., :1, :, :], ax, up=False)
    fill = jnp.asarray(-1, r3.dtype)
    below = jnp.where(me > 0, below, fill)
    above = jnp.where(me < cfg.n_blocks - 1, above, fill)
    return below, above


def slab_words(cfg: FrontConfig, ranks):
    """The fused kernel's words of my z-slab (inside shard_map).

    ranks: (B, nv_local) int32 global vertex ranks of my slab for a batch
    of B fields.  The exchanged planes are exactly the z halo the fused
    kernel reads, so the kernel runs on the halo-extended slab directly;
    no (nv_local, 27) neighbour tensor is built.  Returns the (status,
    partner) words, each (B, nz_local, 19, ny8, nx128) int32."""
    nx, ny, _ = cfg.dims
    r3 = ranks.reshape(ranks.shape[0], cfg.nz_local, ny, nx)
    below, above = halo_exchange(cfg, r3)
    return halo_words(jnp.concatenate([below, r3, above], axis=1),
                      rank_bound=_rank_bound(cfg))


def slab_fields(cfg: FrontConfig, grid, ranks):
    """The dense gradient fields of my z-slab, built on my chip (inside
    shard_map): the sids whose base vertex lies in my slab, numbered
    globally (``sid = vid * T_k + t``).

    A simplex based in my last plane can have its top vertex, and so the
    row that writes it, in the first plane of the slab above: that
    plane's words come down the ring by one more one-plane ``ppermute``
    (all NOT_L above the grid).  Sending 2 x 19 int32 words per vertex
    of a plane costs microseconds on the interconnect, where a second
    halo plane would make the kernel pair one more plane of the slab.

    Returns ``fields_from_words``' ``(pair_up, pair_down, crit,
    n_critical)`` for my slab, the (B,) critical counts summed over the
    ring."""
    nx, ny, _ = cfg.dims
    ax = cfg.axis_name
    words = slab_words(cfg, ranks)
    with jax.named_scope("halo"):
        ghost = [_ppshift(w[:, :1, :, :ny, :nx], ax, up=False)
                 for w in words]
    z0 = _axis_index(ax) * cfg.nz_local
    pair_up, pair_down, crit, n_crit = fields_from_words(
        words, grid, z0=z0, ghost=ghost)
    with jax.named_scope("halo"):
        n_crit = jax.lax.psum(n_crit, ax)
    return pair_up, pair_down, crit, n_crit


def slab_program(grid, mesh, *, fields: bool = True):
    """The jitted ``shard_map`` of :func:`slab_fields` (or, with
    ``fields=False``, :func:`slab_words`) over a one-axis ``mesh``: (B,
    nv) int32 ranks, sharded on the vertex axis into z-slabs, to the
    fields sharded on their leading axis and the replicated critical
    counts (or the words, sharded on the z axis)."""
    from jax.sharding import PartitionSpec as P
    ax = mesh.axis_names[0]
    cfg = FrontConfig(tuple(grid.dims), mesh.size, axis_name=ax)
    cfg.nz_local  # eager divisibility check
    slabs = P(None, ax)

    def dev_fn(o):  # (B, nv_local) ranks of my slab
        return slab_fields(cfg, grid, o) if fields else slab_words(cfg, o)

    return jax.jit(jax.shard_map(
        dev_fn, mesh=mesh, in_specs=slabs,
        out_specs=(P(ax),) * 3 + (P(),) if fields else slabs,
        check_vma=False))


def halo_gradient(cfg: FrontConfig, ranks):
    """Halo-exchange the boundary rank planes with the ring neighbors and
    run the lower-star gradient on the local slab (inside shard_map):
    the gradient step of :func:`front_device_fn` (``run_front``).  The
    pipeline's ``shardmap`` backend runs :func:`slab_fields` instead.

    ranks: (nv_local,) global vertex ranks of my z-slab.
    Returns (nbrs, (status, partner, vstat, vpart)): the (nv_local, 27)
    neighbor-order tensor, which the triplet-key extraction of
    ``front_device_fn`` reads at the critical simplices, and the packed
    gradient rows.

    The ``"fused"`` backend runs the kernel on the halo-extended slab.
    With ``cfg.overlap_comm`` the other kernels split the work so the
    collective hides behind compute: the ``ppermute`` is issued first,
    the interior planes ``[1, nz_local - 1)`` (whose 27-neighborhoods are
    purely local) are processed from the un-extended slab, and only the
    two boundary planes consume the received halo (via 3-plane
    sub-volumes).  The row functions are per-vertex maps, so the
    stitched result is bit-identical to the monolithic path — but XLA's
    scheduler is now free to run the interior gradient while the halo is
    in flight.
    """
    nx, ny, _ = cfg.dims
    nzl, plane, nvl = cfg.nz_local, cfg.plane, cfg.nv_local
    r3 = ranks.reshape(nzl, ny, nx)
    # issue the collectives first: nothing below depends on them until
    # the boundary-plane stitches at the very end of the overlap path
    below, above = halo_exchange(cfg, r3)
    from repro.core.grid import Grid
    if not cfg.overlap_comm or nzl < 3 or cfg.gradient_backend == "fused":
        # monolithic path: the fused kernel wants the whole halo volume,
        # and slabs under 3 planes have no comm-free interior
        ext = jnp.concatenate([below, r3, above], axis=0)
        eg = Grid.of(nx, ny, nzl + 2)
        nbrs_ext = GR.neighbor_orders(eg, ext.reshape(-1), xp=jnp)
        nbrs = nbrs_ext.reshape(nzl + 2, plane, 27)[1:-1].reshape(nvl, 27)
        return nbrs, _gradient_rows(cfg, nbrs, ranks, ext=ext)

    # interior planes: complete neighborhoods inside the local slab
    eg_int = Grid.of(nx, ny, nzl)
    nbrs_int = GR.neighbor_orders(eg_int, ranks, xp=jnp) \
        .reshape(nzl, plane, 27)[1:-1].reshape(-1, 27)
    rows_int = _gradient_rows(cfg, nbrs_int, ranks[plane: nvl - plane])

    # boundary planes: 3-plane sub-volumes around the received halo
    eg_b = Grid.of(nx, ny, 3)

    def boundary(vol3, own):
        nb_ = GR.neighbor_orders(eg_b, vol3.reshape(-1), xp=jnp) \
            .reshape(3, plane, 27)[1]
        return nb_, _gradient_rows(cfg, nb_, own)

    nbrs_lo, rows_lo = boundary(jnp.concatenate([below, r3[:2]]),
                                ranks[:plane])
    nbrs_hi, rows_hi = boundary(jnp.concatenate([r3[-2:], above]),
                                ranks[nvl - plane:])

    nbrs = jnp.concatenate([nbrs_lo, nbrs_int, nbrs_hi], axis=0)
    rows = tuple(jnp.concatenate(parts, axis=0)
                 for parts in zip(rows_lo, rows_int, rows_hi))
    return nbrs, rows


def _gradient_rows(cfg: FrontConfig, nbrs, ov, ext=None):
    rb = _rank_bound(cfg)
    if cfg.gradient_backend == "fused" and ext is not None:
        return fused_rows_from_halo_volume(ext, rank_bound=rb)
    if rb is not None and rb < 2 ** 31:
        nbrs = nbrs.astype(jnp.int32)
        ov = ov.astype(jnp.int32)
    if cfg.gradient_backend == "pallas":
        return lower_star_gradient_pallas(nbrs, ov, rank_bound=rb)
    if cfg.gradient_chunk is None:
        return REF.lower_star_gradient_jnp(nbrs, ov, rank_bound=rb)
    n = nbrs.shape[0]
    c = cfg.gradient_chunk
    npad = -(-n // c) * c
    nb_ = jnp.pad(nbrs, ((0, npad - n), (0, 0)), constant_values=-1)
    op = jnp.pad(ov, (0, npad - n))
    outs = jax.lax.map(
        lambda ab: REF.lower_star_gradient_jnp(ab[0], ab[1], rank_bound=rb),
        (nb_.reshape(npad // c, c, 27), op.reshape(npad // c, c)))
    return tuple(o.reshape((npad,) + o.shape[2:])[:n] for o in outs)


def _row_tables():
    """Packed-row helper constants as jnp arrays."""
    shift = GR.PACKED["row_shift"].astype(np.int64)     # (74,3)
    rtype = GR.PACKED["row_type"].astype(np.int64)      # (74,)
    oth = GR.PACKED["others"].astype(np.int64)          # (74,3) nbr idx
    return jnp.asarray(shift), jnp.asarray(rtype), jnp.asarray(oth)


def front_device_fn(cfg: FrontConfig, f_slab):
    """Runs inside shard_map.  f_slab: (nz_local, ny, nx) float32."""
    nx, ny, nz = cfg.dims
    nzl, plane, nvl = cfg.nz_local, cfg.plane, cfg.nv_local
    ax = cfg.axis_name
    me = _axis_index(ax)
    nb = cfg.n_blocks
    has_above = me < nb - 1
    gid0 = me.astype(jnp.int64) * nvl

    fl = f_slab.reshape(-1)
    gids = gid0 + jnp.arange(nvl, dtype=jnp.int64)

    # ---- 1. global order -------------------------------------------------
    if cfg.use_sample_sort and nb > 1:
        ranks, overflow = sample_sort_ranks(fl, gids, ax, nb,
                                            slack=cfg.sort_slack)
    elif cfg.use_sample_sort:
        key = jnp.argsort(jnp.argsort(rankfree_keys(fl, gids)))
        ranks, overflow = key.astype(jnp.int64), jnp.asarray(False)
    else:
        ranks, overflow = rankfree_keys(fl, gids), jnp.asarray(False)

    # ---- 2+3. halo exchange of ranks, gradient on own vertices -------------
    nbrs, (status, partner, vstat, vpart) = halo_gradient(cfg, ranks)

    SHIFT, RTYPE, OTH = _row_tables()
    vx = gids % nx
    vy = (gids // nx) % ny
    vz = gids // plane                                   # global z

    def other_vid(rows, m):
        """Global vid of the m-th 'other' vertex of packed row `rows` at
        each of my vertices."""
        o = OTH[rows, m]                                 # nbr index 0..26
        dx = o % 3 - 1
        dy = (o // 3) % 3 - 1
        dz = o // 9 - 1
        return (vx + dx) + nx * (vy + dy) + (jnp.int64(nx) * ny) * (vz + dz)

    # ---- 4a. vertex successors (descending v-paths) -----------------------
    vp = jnp.maximum(vpart, 0).astype(jnp.int64)
    succ_v = jnp.where(vstat == GR.TAIL, other_vid(vp, 0), gids)

    # ---- 4b. tet successors (ascending dual paths) ------------------------
    # For every dim-3 row with a result at my vertices, compute the tet's
    # global sid and its successor; scatter into a table covering bases
    # [gid0 - plane, gid0 + nvl), then ship the ghost segment down.
    T3, T2 = G.NTYPES[3], G.NTYPES[2]
    off3 = GR.ROW_OFF[3]
    rows3 = jnp.arange(off3, off3 + G.NSTAR[3])
    st3 = status[:, off3:]                               # (nvl, 24)
    pr3 = partner[:, off3:]

    def rows_gsid(rows_const, k):
        """Global sid of row r (vector of row ids, one per vertex) dim k."""
        sh = SHIFT[rows_const]                            # (...,3)
        t = RTYPE[rows_const]
        bx = vx - sh[..., 0]
        by = vy - sh[..., 1]
        bz = vz - sh[..., 2]
        return (bx + nx * (by + jnp.int64(ny) * bz)) * G.NTYPES[k] + t

    # vectorize over the 24 tet rows
    def per_row3(r):
        row = rows3[r]
        st = st3[:, r]
        tet = rows_gsid(jnp.full(nvl, row, jnp.int64), 3)
        # paired face triangle (HEAD rows)
        prow = jnp.maximum(pr3[:, r], 0).astype(jnp.int64)
        tri = rows_gsid(prow, 2)
        # other cofacet of tri: via COFACES[2] with *global* validity
        tri_base = tri // T2
        tri_t = tri % T2
        cof = jnp.asarray(G.COFACES[2].astype(np.int64))[tri_t]  # (nvl,NC,4)
        cbx = (tri_base % nx)[:, None] + cof[..., 1]
        cby = ((tri_base // nx) % ny)[:, None] + cof[..., 2]
        cbz = (tri_base // plane)[:, None] + cof[..., 3]
        span = jnp.asarray(G.SPAN[3].astype(np.int64))[
            jnp.maximum(cof[..., 0], 0)]
        ok = (cof[..., 0] >= 0) \
            & (cbx >= 0) & (cbx + span[..., 0] <= nx - 1) \
            & (cby >= 0) & (cby + span[..., 1] <= ny - 1) \
            & (cbz >= 0) & (cbz + span[..., 2] <= nz - 1)
        csid = (cbx + nx * (cby + jnp.int64(ny) * cbz)) * T3 + cof[..., 0]
        other = jnp.where(ok & (csid != tet[..., None]), csid, -1)
        nxt = other.max(axis=-1)                          # -1 if none
        nxt = jnp.where(nxt < 0, jnp.int64(OMEGA), nxt)
        succ = jnp.where(st == GR.CRIT, tet,
                         jnp.where(st == GR.HEAD, nxt, jnp.int64(-3)))
        return tet, succ

    tets, tsucc = jax.vmap(per_row3, out_axes=1)(jnp.arange(G.NSTAR[3]))
    tets = tets.reshape(-1)
    tsucc = tsucc.reshape(-1)
    # scatter into [gid0-plane, gid0+nvl) * T3 (+1 dump)
    tab_lo = (gid0 - plane) * T3
    tab_n = (nvl + plane) * T3
    idx = jnp.where(tsucc != -3, tets - tab_lo, tab_n)
    idx = jnp.clip(idx, 0, tab_n)
    ttab = jnp.full(tab_n + 1, -3, dtype=jnp.int64).at[idx].set(
        jnp.where(tsucc != -3, tsucc, -3))
    ttab = ttab[:tab_n]
    # ship ghost segment (first plane*T3 entries) down to its owner
    ghost = ttab[: plane * T3]
    recv = _ppshift(ghost, ax, up=False)                 # from me+1
    seg = ttab[nvl * T3:]
    merged = jnp.where((recv != -3) & has_above, recv, seg)
    ttab = ttab.at[nvl * T3:].set(merged)
    tet_table = ttab[plane * T3:]                        # my nvl*T3 entries
    # unset entries (-3) are tets never processed (invalid or ghost-only):
    # point them at OMEGA so chases cannot wander
    tet_table = jnp.where(tet_table == -3, jnp.int64(OMEGA), tet_table)

    # ---- 5a. critical edges -> D0 triplets ---------------------------------
    cap = cfg.crit_capacity
    st1 = status[:, :G.NSTAR[1]]
    crit1 = (st1 == GR.CRIT)
    v_rep = jnp.broadcast_to(gids[:, None], crit1.shape)
    rows1 = jnp.broadcast_to(jnp.arange(G.NSTAR[1])[None, :], crit1.shape)
    flat1 = crit1.reshape(-1)
    e_v = v_rep.reshape(-1)
    e_r = rows1.reshape(-1)
    eidx = jnp.nonzero(flat1, size=cap, fill_value=len(flat1) - 1)[0]
    n_ce = flat1.sum()
    ce_v = e_v[eidx]
    ce_row = e_r[eidx].astype(jnp.int64)
    # the other endpoint + key (hi = rank of max vertex = my vertex)
    ou = OTH[ce_row, 0]
    dx = ou % 3 - 1
    dy = (ou // 3) % 3 - 1
    dz = ou // 9 - 1
    ce_u = (ce_v % nx + dx) + nx * (((ce_v // nx) % ny + dy)
                                    + jnp.int64(ny) * (ce_v // plane + dz))
    key_hi = ranks[jnp.clip(ce_v - gid0, 0, nvl - 1)]
    lo_nbr = nbrs[jnp.clip(ce_v - gid0, 0, nvl - 1), ou]
    ekey = jnp.stack([key_hi, lo_nbr], axis=1)           # (cap,2)
    valid_e = jnp.arange(cap) < n_ce

    # ---- 5b. critical triangles -> dual triplets ---------------------------
    st2 = status[:, GR.ROW_OFF[2]: GR.ROW_OFF[2] + G.NSTAR[2]]
    crit2 = (st2 == GR.CRIT)
    flat2 = crit2.reshape(-1)
    rows2 = jnp.broadcast_to(
        jnp.arange(GR.ROW_OFF[2], GR.ROW_OFF[2] + G.NSTAR[2])[None, :],
        crit2.shape).reshape(-1)
    t_v = jnp.broadcast_to(gids[:, None], crit2.shape).reshape(-1)
    tidx = jnp.nonzero(flat2, size=cap, fill_value=len(flat2) - 1)[0]
    n_ct = flat2.sum()
    ct_v = t_v[tidx]
    ct_row = rows2[tidx].astype(jnp.int64)
    vloc = jnp.clip(ct_v - gid0, 0, nvl - 1)
    o1 = nbrs[vloc, OTH[ct_row, 0]]
    o2 = nbrs[vloc, OTH[ct_row, 1]]
    tkey = jnp.stack([ranks[vloc], jnp.maximum(o1, o2), jnp.minimum(o1, o2)],
                     axis=1)                              # (cap,3) desc key
    # triangle global sid + its two cofacet tets (global validity)
    sh = SHIFT[ct_row]
    tbx = ct_v % nx - sh[:, 0]
    tby = (ct_v // nx) % ny - sh[:, 1]
    tbz = ct_v // plane - sh[:, 2]
    tri_t = RTYPE[ct_row]
    cof = jnp.asarray(G.COFACES[2].astype(np.int64))[tri_t]  # (cap,NC,4)
    cbx = tbx[:, None] + cof[..., 1]
    cby = tby[:, None] + cof[..., 2]
    cbz = tbz[:, None] + cof[..., 3]
    span = jnp.asarray(G.SPAN[3].astype(np.int64))[jnp.maximum(cof[..., 0], 0)]
    ok = (cof[..., 0] >= 0) \
        & (cbx >= 0) & (cbx + span[..., 0] <= nx - 1) \
        & (cby >= 0) & (cby + span[..., 1] <= ny - 1) \
        & (cbz >= 0) & (cbz + span[..., 2] <= nz - 1)
    csid = (cbx + nx * (cby + jnp.int64(ny) * cbz)) * T3 + cof[..., 0]
    csid = jnp.where(ok, csid, -1)
    # compact to exactly two slots (a triangle has <= 2 cofacets)
    first = jnp.argmax(ok, axis=1)
    okc = ok.at[jnp.arange(cap), first].set(False)
    second = jnp.argmax(okc, axis=1)
    cof0 = jnp.where(ok.any(1), csid[jnp.arange(cap), first],
                     jnp.int64(OMEGA))
    cof1 = jnp.where(okc.any(1), csid[jnp.arange(cap), second],
                     jnp.int64(OMEGA))
    valid_t = jnp.arange(cap) < n_ct

    # ---- 6. resolve all traces --------------------------------------------
    # padding rows must not wander: mask them to OMEGA before resolving
    succ_v64 = succ_v.astype(jnp.int64)
    vq = jnp.where(jnp.concatenate([valid_e, valid_e]),
                   jnp.concatenate([ce_v, ce_u]), jnp.int64(OMEGA))
    _, vq_res, un_v = ring_resolve(cfg, succ_v64, 1, vq)
    t0 = vq_res[:cap]
    t1 = vq_res[cap:]
    tq = jnp.where(jnp.concatenate([valid_t, valid_t]),
                   jnp.concatenate([cof0, cof1]), jnp.int64(OMEGA))
    _, tq_res, un_t = ring_resolve(cfg, tet_table, T3, tq)
    s0 = tq_res[:cap]
    s1 = tq_res[cap:]

    ncrit = jnp.stack([
        jax.lax.psum((vstat == GR.CRIT).sum(), ax),
        jax.lax.psum(n_ce, ax),
        jax.lax.psum(n_ct, ax),
        jax.lax.psum((st3 == GR.CRIT).sum(), ax)])
    # buffer overflow detection: the largest per-device critical count,
    # checked host-side against the capacity (raise, never truncate)
    crit_peak = jax.lax.pmax(jnp.maximum(n_ce, n_ct), ax)

    return dict(
        ranks=ranks, overflow=overflow,
        d0_key=ekey, d0_t0=t0, d0_t1=t1, d0_valid=valid_e,
        d0_sid_v=ce_v, d0_row=ce_row,
        dual_key=tkey, dual_t0=s0, dual_t1=s1, dual_valid=valid_t,
        dual_sid_v=ct_v, dual_row=ct_row,
        ncrit=ncrit, unresolved=un_v + un_t, crit_peak=crit_peak,
        vstat=vstat, vpart=vpart, status=status, partner=partner,
    )


# --------------------------------------------------------------------------
# host-side driver
# --------------------------------------------------------------------------

def run_front(dims, f, n_blocks: int, mesh=None, **cfg_kw):
    """Execute the front-end under shard_map on ``n_blocks`` devices.
    Returns numpy outputs (triplet buffers, ranks, stats)."""
    from jax.sharding import PartitionSpec as P

    cfg = FrontConfig(tuple(dims), n_blocks, axis_name="blocks", **cfg_kw)
    cfg.nz_local  # eager divisibility check: fail with dims/blocks named
    if mesh is None:
        mesh = jax.make_mesh((n_blocks,), ("blocks",))

    def dev_fn(f_slab):
        return front_device_fn(cfg, f_slab)

    fn = jax.shard_map(dev_fn, mesh=mesh, in_specs=P("blocks"),
                       out_specs=_front_out_specs(), check_vma=False)
    out = jax.jit(fn)(jnp.asarray(np.asarray(f).reshape(-1), jnp.float32))
    out = {k: np.asarray(v) for k, v in out.items()}
    peak = int(out["crit_peak"])
    if peak > cfg.crit_capacity:
        err = CritCapacityError(peak, cfg.crit_capacity, cfg.dims, n_blocks)
        _flight.crash_dump("crit_capacity", exc=err)
        raise err
    return cfg, out


def _front_out_specs():
    from jax.sharding import PartitionSpec as P
    rep = {"overflow", "ncrit", "unresolved", "crit_peak"}
    keys = ["ranks", "overflow", "d0_key", "d0_t0", "d0_t1", "d0_valid",
            "d0_sid_v", "d0_row", "dual_key", "dual_t0", "dual_t1",
            "dual_valid", "dual_sid_v", "dual_row", "ncrit", "unresolved",
            "crit_peak", "vstat", "vpart", "status", "partner"]
    return {k: (P() if k in rep else P("blocks")) for k in keys}


def _vrow_to_sid(dims, v, row, k):
    """(vertex, packed row) -> global simplex sid (numpy)."""
    nx, ny, nz = dims
    sh = GR.PACKED["row_shift"].astype(np.int64)[row]
    t = GR.PACKED["row_type"].astype(np.int64)[row]
    bx = v % nx - sh[:, 0]
    by = (v // nx) % ny - sh[:, 1]
    bz = v // (nx * ny) - sh[:, 2]
    return (bx + nx * (by + ny * bz)) * G.NTYPES[k] + t


def front_triplets(dims, out):
    """Extract (saddle sid, t0, t1) triplet lists from front outputs."""
    d0v = out["d0_valid"].astype(bool)
    sid0 = _vrow_to_sid(dims, out["d0_sid_v"][d0v],
                        out["d0_row"][d0v].astype(np.int64), 1)
    key0 = out["d0_key"][d0v]
    t0, t1 = out["d0_t0"][d0v], out["d0_t1"][d0v]
    dv = out["dual_valid"].astype(bool)
    # dual_row stores packed rows (14..49); _vrow_to_sid indexes the packed
    # tables directly
    sidd = _vrow_to_sid(dims, out["dual_sid_v"][dv],
                        out["dual_row"][dv].astype(np.int64), 2)
    keyd = out["dual_key"][dv]
    s0, s1 = out["dual_t0"][dv], out["dual_t1"][dv]
    return (sid0, key0, t0, t1), (sidd, keyd, s0, s1)
