"""Discrete gradient computation (Robins et al. ProcessLowerStars).

Paper Sec. II-C / III: the discrete gradient is computed *per vertex* by
pairing the simplices of each lower star — embarrassingly parallel, the most
time-consuming DMS/DDMS step, and the step that maps onto the TPU VPU.

Two implementations with a proven-equivalent formulation:

- ``compute_gradient_np``  — literal Robins pseudocode with priority queues
  (heapq), the paper-faithful reference.
- ``compute_gradient_jax`` — branchless *masked recomputation* form: the PQ
  memberships are pure functions of the current pairing state
  (``PQone == available & n_unpaired_faces == 1``,
  ``PQzero == available & n_unpaired_faces == 0``), so each pop is a masked
  lexicographic argmin over a fixed 74-row table.  ``vmap`` over vertices,
  ``lax.while_loop`` per vertex.  This is the TPU adaptation: priority queues
  (a CPU idiom) become lane-parallel masked reductions.

Equivalence sketch (asserted by tests): in the literal algorithm, a simplex
enters PQone exactly when one of its faces is consumed, which happens exactly
when its unpaired-face count drops to 1 while it is still available; edges
always have 0 unpaired faces once the vertex is paired; any available simplex
with count 0 must previously have passed through count 1 (counts drop by at
most one per pairing event) and would have been moved to PQzero.  Hence both
queue memberships are recomputable, and pop order (min by the lexicographic
G-order) is identical.

Packed tables (concat layout over star rows): rows 0..13 = edges,
14..49 = triangles, 50..73 = tetrahedra.  Every row's data is derived from the
27-neighborhood (offsets in {-1,0,1}^3) of the vertex, so the only input is
``nbr_orders``: the (nv, 27) tensor of neighbor vertex orders (-1 outside the
grid).  That tensor is produced by a pure stencil gather — the memory-bound
pre-pass — and the pairing itself is compute-local, which is exactly the shape
a Pallas kernel wants (see ``repro.kernels.lower_star``).
"""

from __future__ import annotations

import functools
import heapq
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from . import grid as G
from .grid import Grid

# --------------------------------------------------------------------------
# Packed star tables (concat layout over dims 1..3)
# --------------------------------------------------------------------------

NROWS = G.NSTAR[1] + G.NSTAR[2] + G.NSTAR[3]  # 74
ROW_OFF = {1: 0, 2: G.NSTAR[1], 3: G.NSTAR[1] + G.NSTAR[2]}  # {1:0, 2:14, 3:50}

# offset -> index into the 27-neighborhood (x fastest)
def _nbr_index(off: np.ndarray) -> int:
    return int((off[0] + 1) + 3 * (off[1] + 1) + 9 * (off[2] + 1))


def _build_packed() -> Dict[str, np.ndarray]:
    row_dim = np.zeros(NROWS, dtype=np.int8)
    # neighbor indices of the "other" vertices of each row (pad -1)
    others = np.full((NROWS, 3), -1, dtype=np.int8)
    # faces-containing-v of each row, as packed row indices (pad -1)
    fid = np.full((NROWS, 3), -1, dtype=np.int8)
    # star table refs for scattering results back to global sids
    row_type = np.zeros(NROWS, dtype=np.int8)
    row_shift = np.zeros((NROWS, 3), dtype=np.int8)
    for k in (1, 2, 3):
        off = ROW_OFF[k]
        for r in range(G.NSTAR[k]):
            row = off + r
            row_dim[row] = k
            t, j = divmod(r, k + 1)
            row_type[row] = t
            row_shift[row] = G.STAR[k][r, 1:]
            for m in range(k):
                others[row, m] = _nbr_index(G.OTHERS[k][r, m])
            if k >= 2:
                for m in range(k):
                    fid[row, m] = ROW_OFF[k - 1] + int(G.STAR_FACES[k][r, m])
    return dict(row_dim=row_dim, others=others, fid=fid,
                row_type=row_type, row_shift=row_shift)


PACKED = _build_packed()

# status codes
NOT_L, AVAIL, TAIL, HEAD, CRIT = 0, 1, 2, 3, 4


# --------------------------------------------------------------------------
# Neighbor-order tensor (the stencil pre-pass)
# --------------------------------------------------------------------------

def neighbor_orders(grid: Grid, order, xp=np):
    """(nv, 27) orders of the 27-neighborhood of every vertex; -1 outside."""
    nx, ny, nz = grid.dims
    o3 = order.reshape(nz, ny, nx)  # z slowest (vid = x + nx*(y + ny*z))
    if xp is np:
        pad = np.full((nz + 2, ny + 2, nx + 2), -1, dtype=order.dtype)
        pad[1:-1, 1:-1, 1:-1] = o3
    else:
        pad = xp.pad(o3, 1, constant_values=-1)
    cols = []
    for dz in (-1, 0, 1):
        for dy in (-1, 0, 1):
            for dx in (-1, 0, 1):
                cols.append(pad[1 + dz: 1 + dz + nz,
                                1 + dy: 1 + dy + ny,
                                1 + dx: 1 + dx + nx])
    stacked = xp.stack(cols, axis=-1)  # (nz,ny,nx,27) ordered x fastest
    # reorder list: we appended with dx fastest inner — but _nbr_index uses
    # (dx+1) + 3*(dy+1) + 9*(dz+1), i.e. dx fastest -> consistent.
    return stacked.reshape(grid.nv, 27)


# --------------------------------------------------------------------------
# Literal Robins reference (priority queues)
# --------------------------------------------------------------------------

def _row_key(nbrs: np.ndarray, row: int) -> Tuple[int, int, int]:
    """Lexicographic G-key of a star row: other-vertex orders, sorted
    descending, padded with -1 (the shared max vertex v is dropped)."""
    oth = PACKED["others"][row]
    vals = sorted((int(nbrs[i]) for i in oth if i >= 0), reverse=True)
    while len(vals) < 3:
        vals.append(-1)
    return tuple(vals)


def _row_in_l(nbrs: np.ndarray, ov: int, row: int) -> bool:
    oth = PACKED["others"][row]
    for i in oth:
        if i < 0:
            continue
        o = int(nbrs[i])
        if o < 0 or o >= ov:
            return False
    return True


def _process_lower_star_ref(nbrs: np.ndarray, ov: int):
    """Literal ProcessLowerStars for one vertex.  Returns (status, partner,
    vstatus, vpartner): status/partner over the 74 packed rows."""
    status = np.zeros(NROWS, dtype=np.int8)
    partner = np.full(NROWS, -1, dtype=np.int8)
    in_l = [_row_in_l(nbrs, ov, r) for r in range(NROWS)]
    for r in range(NROWS):
        if in_l[r]:
            status[r] = AVAIL
    edges = [r for r in range(G.NSTAR[1]) if in_l[r]]
    if not edges:
        return status, partner, CRIT, -1

    def nuf(row: int) -> Tuple[int, int]:
        """(count, last) of available faces-containing-v of a row."""
        c, last = 0, -1
        for f in PACKED["fid"][row]:
            if f >= 0 and status[f] == AVAIL:
                c += 1
                last = int(f)
        return c, last

    delta = min(edges, key=lambda r: _row_key(nbrs, r))
    vstatus, vpartner = TAIL, delta
    status[delta] = HEAD
    partner[delta] = -2  # paired with the vertex itself

    pqzero: List[Tuple[Tuple[int, int, int], int]] = []
    pqone: List[Tuple[Tuple[int, int, int], int]] = []
    for r in edges:
        if r != delta:
            heapq.heappush(pqzero, (_row_key(nbrs, r), r))
    # cofaces of delta with one unpaired face
    for r in range(NROWS):
        if status[r] == AVAIL and nuf(r)[0] == 1 and delta in PACKED["fid"][r]:
            heapq.heappush(pqone, (_row_key(nbrs, r), r))

    def push_cofaces(*rows: int):
        for r in range(NROWS):
            if status[r] != AVAIL:
                continue
            if nuf(r)[0] == 1 and any(x in PACKED["fid"][r] for x in rows):
                heapq.heappush(pqone, (_row_key(nbrs, r), r))

    while pqone or pqzero:
        while pqone:
            _, alpha = heapq.heappop(pqone)
            if status[alpha] != AVAIL:
                continue  # stale
            c, face = nuf(alpha)
            if c == 0:
                heapq.heappush(pqzero, (_row_key(nbrs, alpha), alpha))
                continue
            # pair(face, alpha)
            status[alpha] = HEAD
            partner[alpha] = face
            status[face] = TAIL
            partner[face] = alpha
            push_cofaces(alpha, face)
        if pqzero:
            _, gamma = heapq.heappop(pqzero)
            if status[gamma] != AVAIL:
                continue  # stale (was paired meanwhile)
            status[gamma] = CRIT
            push_cofaces(gamma)
    return status, partner, vstatus, vpartner


# --------------------------------------------------------------------------
# Masked-recomputation form (numpy version; the jnp twin lives in
# repro.kernels.ref / repro.kernels.lower_star)
# --------------------------------------------------------------------------

def _process_lower_star_masked(nbrs: np.ndarray, ov: int):
    """Same output as the literal reference, queue-free (see module doc)."""
    status = np.zeros(NROWS, dtype=np.int8)
    partner = np.full(NROWS, -1, dtype=np.int8)
    keys = np.stack([_row_key(nbrs, r) for r in range(NROWS)]).astype(np.int64)
    for r in range(NROWS):
        if _row_in_l(nbrs, ov, r):
            status[r] = AVAIL
    if not (status[: G.NSTAR[1]] == AVAIL).any():
        return status, partner, CRIT, -1

    def lexmin(mask: np.ndarray) -> int:
        idx = np.nonzero(mask)[0]
        return int(idx[np.lexsort((keys[idx, 2], keys[idx, 1], keys[idx, 0]))[0]])

    delta = lexmin((status == AVAIL)
                   & (np.arange(NROWS) < G.NSTAR[1]))
    vstatus, vpartner = TAIL, delta
    status[delta] = HEAD
    partner[delta] = -2

    fid = PACKED["fid"]
    while True:
        avail = status == AVAIL
        nuf = ((fid >= 0) & avail[np.maximum(fid, 0)]).sum(axis=1)
        m1 = avail & (nuf == 1)
        if m1.any():
            alpha = lexmin(m1)
            fr = fid[alpha]
            face = int(fr[(fr >= 0) & avail[np.maximum(fr, 0)]][0])
            status[alpha] = HEAD
            partner[alpha] = face
            status[face] = TAIL
            partner[face] = alpha
            continue
        m0 = avail & (nuf == 0)
        if not m0.any():
            break
        gamma = lexmin(m0)
        status[gamma] = CRIT
    return status, partner, vstatus, vpartner


# --------------------------------------------------------------------------
# Gradient field container + scatter
# --------------------------------------------------------------------------

@dataclass
class GradientField:
    """Dense discrete gradient over the implicit complex.

    ``pair_up[k][sid]``  = sid of the (k+1)-simplex pairing sid as tail (-1)
    ``pair_down[k][sid]``= sid of the (k-1)-simplex pairing sid as head (-1)
    ``crit[k][sid]``     = critical mask (only meaningful on valid sids)
    """

    grid: Grid
    pair_up: Dict[int, np.ndarray]
    pair_down: Dict[int, np.ndarray]
    crit: Dict[int, np.ndarray]

    def critical_sids(self, k: int) -> np.ndarray:
        return np.nonzero(self.crit[k])[0]

    def n_critical(self) -> Dict[int, int]:
        return {k: int(self.crit[k].sum()) for k in self.crit}


@functools.lru_cache(maxsize=64)
def row_sid_offsets(grid: Grid) -> Dict[int, np.ndarray]:
    """Per-grid row -> sid linear offset tables.

    The sid of packed star row ``r`` (dim k) at vertex ``v`` is an affine
    function of v:  ``sid = v * NTYPES[k] + off[k][r_local]`` where
    ``off[k][r] = row_type[r] - lin(row_shift[r]) * NTYPES[k]`` and ``lin``
    is the vid linearization.  One tiny (S_k,) table per dimension turns
    the whole result scatter into flat index arithmetic — no per-row
    coordinate decomposition, no Python loop over vertices or batches.
    """
    nx, ny, _ = grid.dims
    out: Dict[int, np.ndarray] = {}
    for k in (1, 2, 3):
        rows = slice(ROW_OFF[k], ROW_OFF[k] + G.NSTAR[k])
        sh = PACKED["row_shift"][rows].astype(np.int64)
        t = PACKED["row_type"][rows].astype(np.int64)
        lin = sh[:, 0] + nx * (sh[:, 1] + ny * sh[:, 2])
        out[k] = t - lin * G.NTYPES[k]
    return out


def sid_dtype(grid: Grid, k: int):
    """Smallest signed integer dtype that indexes dim-k sid space."""
    return np.int32 if grid.sid_space(k) < 2 ** 31 else np.int64


def scatter_results_batch(grid: Grid, status: np.ndarray, partner: np.ndarray,
                          vstatus: np.ndarray, vpartner: np.ndarray,
                          B: int = 1) -> List[GradientField]:
    """Turn packed rows of B stacked same-grid fields into GradientFields.

    status/partner are (B*nv, 74), vstatus/vpartner (B*nv,).  All dims and
    all batch elements scatter through flat index arithmetic on the cached
    row->sid offset tables — the only Python loop is over the <= 3 simplex
    dimensions.  Pair/crit arrays are int32 whenever the sid space fits
    (it always does below ~180M vertices), halving gradient-field memory.
    On the fused kernel the device builds the same arrays
    (``kernels.lower_star.fields_from_words``); this scatter is their
    oracle and serves the other kernels.
    """
    nv = grid.nv
    d = grid.dim
    off = row_sid_offsets(grid)
    N = B * nv

    space = {k: grid.sid_space(k) for k in range(d + 1)}
    # flat (B, sid_space) planes; per-field views are split at the end.
    # A pair array for dim k STORES sids of the adjacent dimension, so its
    # dtype is gated on that dimension's space (e.g. pair_up[1] holds
    # dim-2 sids spanning 12*nv even though its length is only 7*nv)
    pair_up = {k: np.full(B * space[k], -1, dtype=sid_dtype(grid, k + 1))
               for k in range(d)}
    pair_down = {k: np.full(B * space[k], -1, dtype=sid_dtype(grid, k - 1))
                 for k in range(1, d + 1)}
    crit = {k: np.zeros(B * space[k], dtype=bool) for k in range(d + 1)}

    crit[0][:] = vstatus == CRIT
    # vertex-edge pairs: vertex sid space == vid space, so the flat pair_up
    # destination of vertex i IS i; the edge sid needs only the offset table
    vv = np.nonzero(vstatus == TAIL)[0]
    if len(vv):
        es = (vv % nv) * G.NTYPES[1] + off[1][vpartner[vv]]
        pair_up[0][vv] = es
        pair_down[1][(vv // nv) * space[1] + es] = vv % nv

    for k in range(1, d + 1):
        st = status[:, ROW_OFF[k]: ROW_OFF[k] + G.NSTAR[k]]   # (N, S_k)
        vs, rs = np.nonzero(st == CRIT)
        if len(vs):
            sids = (vs % nv) * G.NTYPES[k] + off[k][rs]
            crit[k][(vs // nv) * space[k] + sids] = True
        # head side: rows with status HEAD know their face partner; every
        # pair has exactly one head, so this covers all vectors of dim >= 1
        vs, rs = np.nonzero(st == HEAD)
        if len(vs):
            p = partner[vs, ROW_OFF[k] + rs].astype(np.int64)
            if k == 1:
                # partner -2 means paired with the vertex itself (handled
                # above via vstatus); nothing else is legal for dim-1 heads
                assert (p == -2).all(), "dim-1 head must pair with vertex"
            else:
                head_sid = (vs % nv) * G.NTYPES[k] + off[k][rs]
                face_sid = ((vs % nv) * G.NTYPES[k - 1]
                            + off[k - 1][p - ROW_OFF[k - 1]])
                b = vs // nv
                pair_down[k][b * space[k] + head_sid] = face_sid
                pair_up[k - 1][b * space[k - 1] + face_sid] = head_sid

    out = []
    for b in range(B):
        out.append(GradientField(
            grid,
            {k: pair_up[k][b * space[k]:(b + 1) * space[k]]
             for k in pair_up},
            {k: pair_down[k][b * space[k]:(b + 1) * space[k]]
             for k in pair_down},
            {k: crit[k][b * space[k]:(b + 1) * space[k]] for k in crit}))
    return out


def _scatter_results(grid: Grid, status: np.ndarray, partner: np.ndarray,
                     vstatus: np.ndarray, vpartner: np.ndarray) -> GradientField:
    """Single-field view of :func:`scatter_results_batch`."""
    [gf] = scatter_results_batch(grid, status, partner,
                                 np.asarray(vstatus), np.asarray(vpartner))
    return gf


def alloc_gradient(grid: Grid) -> GradientField:
    """Empty dense gradient arrays for incremental (chunked) scatter.

    Every pair entry starts -1 and every critical flag 0; chunk scatters
    (:func:`scatter_rows_chunk`) fill them in.  Dtypes match
    :func:`scatter_results_batch` so streamed and in-memory fields are
    structurally identical."""
    d = grid.dim
    pair_up = {k: np.full(grid.sid_space(k), -1, dtype=sid_dtype(grid, k + 1))
               for k in range(d)}
    pair_down = {k: np.full(grid.sid_space(k), -1,
                            dtype=sid_dtype(grid, k - 1))
                 for k in range(1, d + 1)}
    crit = {k: np.zeros(grid.sid_space(k), dtype=bool) for k in range(d + 1)}
    return GradientField(grid, pair_up, pair_down, crit)


def scatter_rows_chunk(grid: Grid, gf: GradientField, status: np.ndarray,
                       partner: np.ndarray, vstatus: np.ndarray,
                       vpartner: np.ndarray, v0: int,
                       offsets: Optional[Dict[int, np.ndarray]] = None
                       ) -> None:
    """Scatter the packed rows of one vertex chunk into global arrays.

    status/partner are (nc, 74) for the ``nc`` vertices [v0, v0 + nc) in
    vid order (a z-slab).  Because a simplex belongs to the lower star of
    exactly one vertex (its order-maximal one), chunks never write the
    same sid twice — streaming the chunks in any order rebuilds exactly
    the single-shot :func:`scatter_results_batch` result.  Simplices
    *based* in a neighboring slab (row shift crossing the chunk floor)
    land there via the same flat index arithmetic; ``gf`` is dense over
    the whole grid."""
    off = row_sid_offsets(grid) if offsets is None else offsets
    d = grid.dim
    vstatus = np.asarray(vstatus)
    vpartner = np.asarray(vpartner)

    gf.crit[0][v0:v0 + len(vstatus)] = vstatus == CRIT
    vv = np.nonzero(vstatus == TAIL)[0]
    if len(vv):
        vg = vv + v0
        es = vg * G.NTYPES[1] + off[1][vpartner[vv]]
        gf.pair_up[0][vg] = es
        gf.pair_down[1][es] = vg

    for k in range(1, d + 1):
        st = status[:, ROW_OFF[k]: ROW_OFF[k] + G.NSTAR[k]]   # (nc, S_k)
        vs, rs = np.nonzero(st == CRIT)
        if len(vs):
            gf.crit[k][(vs + v0) * G.NTYPES[k] + off[k][rs]] = True
        vs, rs = np.nonzero(st == HEAD)
        if len(vs):
            p = partner[vs, ROW_OFF[k] + rs].astype(np.int64)
            if k == 1:
                assert (p == -2).all(), "dim-1 head must pair with vertex"
            else:
                head_sid = (vs + v0) * G.NTYPES[k] + off[k][rs]
                face_sid = ((vs + v0) * G.NTYPES[k - 1]
                            + off[k - 1][p - ROW_OFF[k - 1]])
                gf.pair_down[k][head_sid] = face_sid
                gf.pair_up[k - 1][face_sid] = head_sid


def compute_gradient_np(grid: Grid, order: np.ndarray,
                        masked: bool = False) -> GradientField:
    """Reference gradient: literal Robins (or the masked form) per vertex."""
    nbrs = np.asarray(neighbor_orders(grid, order))
    nv = grid.nv
    status = np.zeros((nv, NROWS), dtype=np.int8)
    partner = np.full((nv, NROWS), -1, dtype=np.int8)
    vstatus = np.zeros(nv, dtype=np.int8)
    vpartner = np.full(nv, -1, dtype=np.int8)
    fn = _process_lower_star_masked if masked else _process_lower_star_ref
    for v in range(nv):
        s, p, vs, vp = fn(nbrs[v], int(order[v]))
        status[v], partner[v], vstatus[v], vpartner[v] = s, p, vs, vp
    return _scatter_results(grid, status, partner, vstatus, vpartner)


def compute_gradient(grid: Grid, order, backend: str = "jax") -> GradientField:
    """Vectorized gradient via the kernels package (jnp or Pallas)."""
    from repro.kernels import ops
    status, partner, vstatus, vpartner = ops.lower_star_gradient(
        grid, order, backend=backend)
    return _scatter_results(grid, np.asarray(status), np.asarray(partner),
                            np.asarray(vstatus), np.asarray(vpartner))


# --------------------------------------------------------------------------
# Validity checks (used by property tests)
# --------------------------------------------------------------------------

def check_gradient_valid(grid: Grid, gf: GradientField, order: np.ndarray):
    """Assert discrete-vector-field validity + lower-star locality."""
    d = grid.dim
    for k in range(d + 1):
        valid = np.asarray(grid.simplex_valid(k, np.arange(grid.sid_space(k))))
        up = gf.pair_up.get(k)
        down = gf.pair_down.get(k)
        cr = gf.crit[k]
        # every valid simplex is exactly one of: critical, tail, head
        n_roles = cr.astype(int)
        if up is not None:
            n_roles = n_roles + (up >= 0)
        if down is not None:
            n_roles = n_roles + (down >= 0)
        assert (n_roles[valid] == 1).all(), f"dim {k}: role violation"
        assert (n_roles[~valid] == 0).all(), f"dim {k}: invalid simplex used"
        # pairing is an involution and respects incidence + lower stars
        if up is not None:
            sids = np.nonzero(up >= 0)[0]
            heads = up[sids]
            assert (gf.pair_down[k + 1][heads] == sids).all()
            faces = np.asarray(grid.simplex_faces(k + 1, heads))
            assert (faces == sids[:, None]).any(axis=1).all(), \
                f"dim {k}: pair not incident"
            mv_t = np.asarray(grid.simplex_max_vertex(k, sids, order))
            mv_h = np.asarray(grid.simplex_max_vertex(k + 1, heads, order))
            assert (mv_t == mv_h).all(), f"dim {k}: pair leaves lower star"
    # Euler characteristic from critical counts
    chi = sum((-1) ** k * int(gf.crit[k].sum()) for k in range(d + 1))
    assert chi == 1, f"critical Euler characteristic {chi} != 1"
