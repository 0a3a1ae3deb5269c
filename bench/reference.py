"""Plain persistence reference for the benchmark's correctness check.

Computes the persistence diagram of the lower-star filtration of a
float32 scalar field on a 3-D regular grid, in every homology dimension,
from the definitions alone.  It imports nothing of the program under
test.

Semantics (what the program states it computes):

- Vertex ``v = x + nx * (y + ny * z)``; the injective vertex order ranks
  vertices by ``(f, v)`` ascending.
- The grid is cut into simplices by the Freudenthal (Kuhn) triangulation:
  a simplex is a chain ``u < u + s1 < u + s1 + s2 < ...`` whose steps are
  non-zero 0/1 offsets with disjoint supports.  So 7 edge types, 12
  triangle types and 6 tetrahedron types per base vertex; every unit
  cube is cut along its (0,0,0)-(1,1,1) diagonal.
- A simplex enters the filtration with its highest vertex.  A diagram
  point is (order of the birth simplex's highest vertex, order of the
  death simplex's highest vertex); points on the diagonal are dropped.

Method (textbook persistence with the standard shortcuts, none of them
the program's):

1. Refine the filtration simplex-wise by the lexicographic order of each
   simplex's vertex orders, largest first.  Its *apparent pairs* (a
   simplex whose youngest facet has it as oldest cofacet) are
   persistence pairs that need no reduction, all on the diagonal, and
   they form an acyclic discrete gradient (Bauer and Roll, "Gradient-like
   flows and self-indexing in filtrations", 2022).  They are found with
   whole-array NumPy operations.
2. The unpaired ("critical") simplices span a Morse complex with the
   same persistence.  D0 is union-find over critical edges whose ends
   follow the vertex-to-edge gradient down to minima; D2 is union-find
   over critical triangles in reverse order on the dual graph (critical
   tetrahedra plus the outside), whose ends follow the
   triangle-to-tetrahedron gradient.
3. D1 is the left-to-right Z/2 column reduction of the remaining
   critical triangles (those that do not create a 2-cycle), each
   column's boundary taken through the gradient paths, rows being
   critical edges.
"""

from __future__ import annotations

import heapq
import itertools
from typing import Dict

import numpy as np

_UNIT = {0: (1, 0, 0), 1: (0, 1, 0), 2: (0, 0, 1)}
# edge types: the 7 non-zero 0/1 offsets (dx, dy, dz)
EDGE_TYPES = [s for s in itertools.product((0, 1), repeat=3) if any(s)]
_EDGE_INDEX = {d: i for i, d in enumerate(EDGE_TYPES)}


def _add(a, b):
    return tuple(int(x) + int(y) for x, y in zip(a, b))


def _disjoint(a, b):
    return not any(x and y for x, y in zip(a, b))


# triangle types: ordered pairs (a, b) of disjoint non-zero offsets;
# vertices u, u + a, u + a + b
TRI_TYPES = [(a, b) for a in EDGE_TYPES for b in EDGE_TYPES
             if _disjoint(a, b)]
_TRI_INDEX = {t: i for i, t in enumerate(TRI_TYPES)}
# tetrahedron types: axis permutations; vertices u, u+e1, u+e1+e2, u+111
TET_TYPES = list(itertools.permutations((0, 1, 2)))
_TET_INDEX = {p: i for i, p in enumerate(TET_TYPES)}

CRIT, DOWN, UP = 0, 1, 2      # unpaired / paired with a facet / a cofacet


class _Grid:
    def __init__(self, dims):
        self.nx, self.ny, self.nz = (int(d) for d in dims)
        self.nv = self.nx * self.ny * self.nz
        z, y, x = np.meshgrid(np.arange(self.nz), np.arange(self.ny),
                              np.arange(self.nx), indexing="ij")
        self.x, self.y, self.z = x.ravel(), y.ravel(), z.ravel()

    def vid(self, s):
        """Vertex-id offset of a (dx, dy, dz) step."""
        return s[0] + self.nx * (s[1] + self.ny * s[2])

    def inside(self, s, lo=(0, 0, 0)):
        """Mask of base vertices u with u + lo >= 0 and u + s in the grid."""
        return ((self.x + lo[0] >= 0) & (self.y + lo[1] >= 0)
                & (self.z + lo[2] >= 0)
                & (self.x + s[0] < self.nx) & (self.y + s[1] < self.ny)
                & (self.z + s[2] < self.nz))

    def at(self, arr, s, big):
        """arr[u + s] for every base u (``big`` where u + s is outside)."""
        out = np.full(self.nv, big, dtype=arr.dtype)
        m = self.inside(s)
        idx = np.nonzero(m)[0]
        out[idx] = arr[idx + self.vid(s)]
        return out


def vertex_order(f: np.ndarray) -> np.ndarray:
    """Rank of each vertex by (f, vertex id) ascending."""
    f = np.asarray(f).reshape(-1)
    perm = np.argsort(f, kind="stable")
    order = np.empty(f.size, dtype=np.int64)
    order[perm] = np.arange(f.size, dtype=np.int64)
    return order


def persistence(f: np.ndarray, dims) -> Dict[str, Dict[int, np.ndarray]]:
    """Diagram of the lower-star filtration of ``f`` on a grid ``dims``.

    Returns ``{"pairs": {p: (n, 2) int64 sorted rows of (birth order,
    death order)}, "essential": {p: sorted birth orders}}`` for p = 0..3.
    """
    g = _Grid(dims)
    nv = g.nv
    order = vertex_order(f)
    perm = np.argsort(order)              # order -> vertex id
    big = np.int64(nv)                    # "outside" marker for orders

    # ---- vertices and edges: the steepest lower neighbour --------------
    n_e = len(EDGE_TYPES) * nv
    e_o1 = np.full(n_e, -1, np.int64)     # highest vertex order, -1 invalid
    e_o2 = np.full(n_e, -1, np.int64)
    e_top = np.zeros(n_e, np.int64)       # vertex id of the highest vertex
    e_bot = np.zeros(n_e, np.int64)
    lowest_below = np.full(nv, big, np.int64)   # min lower-neighbour order
    for i, d in enumerate(EDGE_TYPES):
        ob = g.at(order, d, big)
        m = ob < big
        u = np.nonzero(m)[0]
        a, b = order[u], ob[u]
        sl = i * nv + u
        up = a > b
        e_o1[sl] = np.where(up, a, b)
        e_o2[sl] = np.where(up, b, a)
        e_top[sl] = np.where(up, u, u + g.vid(d))
        e_bot[sl] = np.where(up, u + g.vid(d), u)
        np.minimum.at(lowest_below, e_top[sl], e_o2[sl])
    e_valid = e_o1 >= 0
    e_kind = np.full(n_e, CRIT, np.int8)
    vert_paired = lowest_below < big
    down = e_valid & (e_o2 == lowest_below[e_top])
    e_kind[down] = DOWN
    # descent: each non-minimum vertex points at its steepest lower
    # neighbour; pointer jumping takes every vertex to its minimum
    nxt = np.where(vert_paired, perm[np.minimum(lowest_below, nv - 1)],
                   np.arange(nv))
    minimum_of = _jump(nxt)

    # ---- triangles: pair with their youngest edge where apparent -------
    n_t = len(TRI_TYPES) * nv
    t_o = np.full((3, n_t), -1, np.int64)       # sorted orders, high first
    t_young = np.full(n_t, -1, np.int64)        # youngest facet edge id
    t_edges = np.full((3, n_t), -1, np.int64)
    lowest_third = np.full(n_e, big, np.int64)  # per edge: min 3rd vertex
    for i, (a, b) in enumerate(TRI_TYPES):
        ab = _add(a, b)
        o2v = g.at(order, ab, big)
        u = np.nonzero(o2v < big)[0]
        vo = np.stack([order[u], order[u + g.vid(a)], o2v[u]])
        # facet j drops vertex j: (u+a, u+a+b) | (u, u+a+b) | (u, u+a)
        fe = np.stack([_EDGE_INDEX[b] * nv + u + g.vid(a),
                       _EDGE_INDEX[ab] * nv + u,
                       _EDGE_INDEX[a] * nv + u])
        lo = np.argmin(vo, axis=0)
        srt = -np.sort(-vo, axis=0)
        sl = i * nv + u
        t_o[:, sl] = srt
        t_edges[:, sl] = fe
        t_young[sl] = fe[lo, np.arange(u.size)]
        np.minimum.at(lowest_third, t_young[sl], srt[2])
    t_valid = t_o[0] >= 0
    t_kind = np.full(n_t, CRIT, np.int8)
    tri_down = t_valid & (t_o[2] == lowest_third[np.maximum(t_young, 0)])
    t_kind[tri_down] = DOWN
    # the edge side of those pairs, and each such edge's other two edges
    paired_t = np.nonzero(tri_down)[0]
    pe = t_young[paired_t]
    if np.any(e_kind[pe] != CRIT):
        raise AssertionError("apparent pairs overlap on edges")
    e_kind[pe] = UP
    e_up_tri = np.full(n_e, -1, np.int64)
    e_up_tri[pe] = paired_t

    # ---- tetrahedra: pair with their youngest triangle where apparent --
    n_T = len(TET_TYPES) * nv
    T_top = np.full(n_T, -1, np.int64)          # highest vertex order
    T_young = np.full(n_T, -1, np.int64)        # youngest facet triangle
    T_lo = np.zeros(n_T, np.int8)               # which vertex is lowest
    T_low = np.full(n_T, big, np.int64)         # lowest vertex order
    lowest_fourth = np.full(n_t, big, np.int64)
    for i, p in enumerate(TET_TYPES):
        e1, e2, e3 = (_UNIT[k] for k in p)
        s1, s12 = e1, _add(e1, e2)
        s123 = (1, 1, 1)
        o3v = g.at(order, s123, big)
        u = np.nonzero(o3v < big)[0]
        vo = np.stack([order[u], order[u + g.vid(s1)],
                       order[u + g.vid(s12)], o3v[u]])
        ft = np.stack([
            _TRI_INDEX[(e2, e3)] * nv + u + g.vid(e1),
            _TRI_INDEX[(s12, e3)] * nv + u,
            _TRI_INDEX[(e1, _add(e2, e3))] * nv + u,
            _TRI_INDEX[(e1, e2)] * nv + u])
        lo = np.argmin(vo, axis=0)
        sl = i * nv + u
        T_top[sl] = vo.max(axis=0)
        T_young[sl] = ft[lo, np.arange(u.size)]
        T_lo[sl] = lo
        T_low[sl] = vo.min(axis=0)
        np.minimum.at(lowest_fourth, T_young[sl], vo.min(axis=0))
    T_valid = T_top >= 0
    T_kind = np.full(n_T, CRIT, np.int8)
    tet_down = T_valid & (T_low == lowest_fourth[np.maximum(T_young, 0)])
    T_kind[tet_down] = DOWN
    pt = T_young[tet_down]
    if np.any(t_kind[pt] != CRIT):
        raise AssertionError("apparent pairs overlap on triangles")
    t_kind[pt] = UP
    # dual descent: a paired tetrahedron steps across its paired facet
    outside = n_T
    tnext = np.arange(n_T + 1)
    paired_T = np.nonzero(tet_down)[0]
    tnext[paired_T] = _tet_across(g, paired_T, T_lo[paired_T])
    top_of = _jump(tnext)

    # ---- D0: union-find over critical edges ----------------------------
    crit_e = np.nonzero(e_valid & (e_kind == CRIT))[0]
    crit_e = crit_e[np.argsort(e_o1[crit_e] * nv + e_o2[crit_e],
                               kind="stable")]
    ends_a = minimum_of[e_top[crit_e]].tolist()
    ends_b = minimum_of[e_bot[crit_e]].tolist()
    parent: Dict[int, int] = {}
    birth: Dict[int, int] = {}
    d0 = []
    positive_e = set()
    o1_list = e_o1[crit_e].tolist()
    for k, e in enumerate(crit_e.tolist()):
        a, b = ends_a[k], ends_b[k]
        ra, rb = _find(parent, a), _find(parent, b)
        if ra == rb:
            positive_e.add(e)
            continue
        ba = birth.get(ra, int(order[ra]))
        bb = birth.get(rb, int(order[rb]))
        if ba < bb:
            ra, rb, ba, bb = rb, ra, bb, ba
        # ra is the younger component: it dies here
        d0.append((ba, o1_list[k]))
        parent[ra] = rb
        birth[rb] = bb
    minima = np.nonzero(~vert_paired)[0]
    roots = {_find(parent, int(m)) for m in minima.tolist()}
    ess0 = sorted(birth.get(r, int(order[r])) for r in roots)

    # ---- D2: union-find over critical triangles on the dual graph ------
    crit_t = np.nonzero(t_valid & (t_kind == CRIT))[0]
    crit_t = crit_t[np.lexsort((t_o[2, crit_t], t_o[1, crit_t],
                                t_o[0, crit_t]))]
    cof = _tri_cofaces(g, crit_t, T_valid)
    ends = np.where(cof >= 0, top_of[np.maximum(cof, 0)], outside)
    parent = {}
    birth = {outside: nv + 1}
    d2 = []
    negative_t = []
    t_hi = t_o[0, crit_t].tolist()
    ea, eb = ends[0].tolist(), ends[1].tolist()
    for k in range(len(crit_t) - 1, -1, -1):
        ra, rb = _find(parent, ea[k]), _find(parent, eb[k])
        if ra == rb:
            negative_t.append(k)
            continue
        ba = birth[ra] if ra in birth else int(T_top[ra])
        bb = birth[rb] if rb in birth else int(T_top[rb])
        if ba > bb:
            ra, rb, ba, bb = rb, ra, bb, ba
        # ra is the younger (lower) component of the reversed sweep
        d2.append((t_hi[k], ba))
        parent[ra] = rb
        birth[rb] = bb
    negative_t.reverse()

    # ---- D1: reduction of the remaining critical triangles -------------
    d1, ess1 = _reduce_d1(crit_t[negative_t], t_edges, t_o[0], e_o1, e_o2,
                          e_kind, e_up_tri, positive_e, nv)

    def pts(rows):
        a = np.asarray(rows, dtype=np.int64).reshape(-1, 2)
        a = a[a[:, 0] != a[:, 1]]
        return a[np.lexsort((a[:, 1], a[:, 0]))]

    return {"pairs": {0: pts(d0), 1: pts(d1), 2: pts(d2),
                      3: np.zeros((0, 2), np.int64)},
            "essential": {0: np.asarray(ess0, np.int64),
                          1: np.asarray(sorted(ess1), np.int64),
                          2: np.zeros(0, np.int64),
                          3: np.zeros(0, np.int64)}}


def _reduce_d1(cols, t_edges, t_top, e_o1, e_o2, e_kind, e_up_tri,
               positive_e, nv):
    """Z/2 reduction of triangle columns over critical edges.

    Edges are numbered by their rank in the simplex-wise order.  A
    column's boundary is followed through the edge-to-triangle gradient
    (a heap, youngest edge first, equal entries cancelling) until only
    critical edges remain; those form its row support."""
    valid = np.nonzero(e_o1 >= 0)[0]
    by_rank = valid[np.argsort(e_o1[valid] * nv + e_o2[valid],
                               kind="stable")]
    rank = np.full(e_o1.size, -1, np.int64)
    rank[by_rank] = np.arange(by_rank.size, dtype=np.int64)
    kind_r = e_kind[by_rank]
    # each gradient edge's two other edges in its paired triangle
    up = np.nonzero(kind_r == UP)[0]
    tri_r = rank[t_edges[:, e_up_tri[by_rank[up]]]].T        # (n_up, 3)
    others = tri_r[tri_r != up[:, None]].reshape(-1, 2)
    oth = np.full((2, by_rank.size), -1, np.int64)
    oth[:, up] = others.T
    kind_b = kind_r.astype(np.uint8).tobytes()
    oth_a, oth_b = memoryview(oth[0]), memoryview(oth[1])
    pop, push = heapq.heappop, heapq.heappush

    def boundary(t):
        heap = [-int(r) for r in rank[t_edges[:, t]]]
        heapq.heapify(heap)
        out = set()
        while heap:
            item = pop(heap)
            par = 1
            while heap and heap[0] == item:
                pop(heap)
                par ^= 1
            if not par:
                continue
            r = -item
            kind = kind_b[r]
            if kind == CRIT:
                out.add(r)
            elif kind == UP:
                push(heap, -oth_a[r])
                push(heap, -oth_b[r])
        return out

    positive_r = set(rank[np.fromiter(positive_e, np.int64,
                                      len(positive_e))].tolist())
    pivots: Dict[int, set] = {}
    pairs = []
    for t in cols.tolist():
        chain = boundary(t)
        while chain:
            low = max(chain)
            other = pivots.get(low)
            if other is None:
                break
            chain ^= other
        if not chain:
            raise AssertionError("a triangle that kills no cycle was "
                                 "expected to create none")
        if low not in positive_r:
            raise AssertionError("a column's pivot is a D0 edge")
        pivots[low] = chain
        pairs.append((int(e_o1[by_rank[low]]), int(t_top[t])))
    ess = [int(e_o1[by_rank[r]]) for r in positive_r if r not in pivots]
    return pairs, ess


def _find(parent, a):
    root = a
    while root in parent:
        root = parent[root]
    while a != root:
        nxt = parent[a]
        parent[a] = root
        a = nxt
    return root


def _jump(nxt: np.ndarray) -> np.ndarray:
    """Follow pointers to their fixed points (pointer jumping)."""
    cur = nxt.copy()
    for _ in range(64):
        nn = cur[cur]
        if np.array_equal(nn, cur):
            return cur
        cur = nn
    raise AssertionError("gradient paths do not end: not acyclic")


def _tet_across(g, tets, facet):
    """The tetrahedron across facet ``facet`` (the one dropping vertex
    ``facet``) of each tetrahedron, or the outside id."""
    nv = g.nv
    ptype, u = np.divmod(tets, nv)
    out = np.full(tets.size, len(TET_TYPES) * nv, np.int64)
    x, y, z = g.x[u], g.y[u], g.z[u]
    for i, p in enumerate(TET_TYPES):
        for j in range(4):
            sel = (ptype == i) & (facet == j)
            if not np.any(sel):
                continue
            p1, p2, p3 = p
            if j == 0:
                shift, q = _UNIT[p1], (p2, p3, p1)
            elif j == 3:
                shift, q = tuple(-c for c in _UNIT[p3]), (p3, p1, p2)
            elif j == 1:
                shift, q = (0, 0, 0), (p2, p1, p3)
            else:
                shift, q = (0, 0, 0), (p1, p3, p2)
            bx, by, bz = x[sel] + shift[0], y[sel] + shift[1], \
                z[sel] + shift[2]
            ok = ((bx >= 0) & (by >= 0) & (bz >= 0) & (bx < g.nx - 1)
                  & (by < g.ny - 1) & (bz < g.nz - 1))
            ids = _TET_INDEX[q] * nv + bx + g.nx * (by + g.ny * bz)
            out[np.nonzero(sel)[0][ok]] = ids[ok]
    return out


def _tri_cofaces(g, tris, T_valid):
    """(2, n) ids of the tetrahedra on either side of each triangle, -1
    where the side is outside the grid."""
    nv = g.nv
    ttype, u = np.divmod(tris, nv)
    out = np.full((2, tris.size), -1, np.int64)
    x, y, z = g.x[u], g.y[u], g.z[u]
    for i, (a, b) in enumerate(TRI_TYPES):
        sel = np.nonzero(ttype == i)[0]
        if sel.size == 0:
            continue
        cands = []
        if sum(a) + sum(b) == 3:
            # the two-axis step splits either way inside one cube
            if sum(a) == 2:
                ax = [k for k in range(3) if a[k]]
                bk = [k for k in range(3) if b[k]][0]
                cands = [((0, 0, 0), (ax[0], ax[1], bk)),
                         ((0, 0, 0), (ax[1], ax[0], bk))]
            else:
                ak = [k for k in range(3) if a[k]][0]
                bx_ = [k for k in range(3) if b[k]]
                cands = [((0, 0, 0), (ak, bx_[0], bx_[1])),
                         ((0, 0, 0), (ak, bx_[1], bx_[0]))]
        else:
            ak = [k for k in range(3) if a[k]][0]
            bk = [k for k in range(3) if b[k]][0]
            c = 3 - ak - bk
            cands = [(tuple(-v for v in _UNIT[c]), (c, ak, bk)),
                     ((0, 0, 0), (ak, bk, c))]
        for side, (shift, q) in enumerate(cands):
            bx = x[sel] + shift[0]
            by = y[sel] + shift[1]
            bz = z[sel] + shift[2]
            ok = ((bx >= 0) & (by >= 0) & (bz >= 0) & (bx < g.nx - 1)
                  & (by < g.ny - 1) & (bz < g.nz - 1))
            ids = _TET_INDEX[q] * nv + bx + g.nx * (by + g.ny * bz)
            out[side, sel[ok]] = ids[ok]
    if np.any((out >= 0) & ~T_valid[np.maximum(out, 0)]):
        raise AssertionError("a triangle's coface is not a tetrahedron")
    return out
