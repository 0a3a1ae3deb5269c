"""Batched sandwich back-end: the D0/D_{d-1}/D1 pairing phases as kernels.

The sequential references (``core.pairing``, ``core.saddle_saddle``,
``core.critical``) run the paper's "sandwich" (Sec. II-F) as host-side
Python with dict/set state — O(pairs) interpreter work that dominates
once the gradient front-end is compiled.  This module re-expresses the
whole back-end as array programs:

- :func:`extract_critical_kernel` — critical extraction without the
  dense per-dimension lexsort.  Every later stage only *compares* ranks
  (never decodes them), so any order-isomorphic injective key works:
  vertex ranks are the vertex order itself, edge ranks are the packed
  ``o_max * 2^31 + o_min`` key (the ``repro.stream`` trick), and
  triangle/tet ranks are computed *among critical simplices only* — the
  only places they are ever compared.  Streamed fronts hand in full-
  width int64 key fields; those are rank-compressed first (one argsort
  over the vertices) so the packing always fits.
- :func:`pair_extrema_saddles_kernel` — the elder-rule Union-Find as
  pointer jumping: the self-correcting round fixpoint of
  ``repro.distributed.pairing_rounds`` (age-filtered find + oldest-
  saddle-wins, provably equal to the sequential Alg. 1) restated as a
  single jitted round program: ``lax.while_loop`` pointer chase, masked
  winner selection by scatter-min, bucket-padded shapes so nearby graph
  sizes reuse one compiled program.
- :func:`build_dual_graph_chase` — the dual extremum graph with the
  stable-set terminals resolved *from the saddle cofacets only*
  (:func:`repro.core.tracing.resolve_chase`) instead of pointer-doubling
  the entire dense tet space.
- :func:`pair_saddle_saddle_wavefront` — D1 homologous propagation as a
  wavefront over *all* active columns at once.  Columns are padded,
  key-sorted edge lists ((C, W) int arrays, -1 padding at the front so
  the pivot is always the last slot); one round gathers every active
  pivot, applies the gradient-pair expansions as a batched
  concat-sort-cancel XOR, and resolves critical pivots through an
  optimistic claim table with steals (lowest filtration rank wins, the
  displaced column reopens and merges the winner) — the Nigmetov-style
  self-correction the paper's distributed D1 uses, in lockstep form.
  Columns are admitted in rank-bucketed batches, so memory stays
  bounded and earlier batches can only ever be merged from, never
  stolen from.

Everything here is bit-compatible with the sequential oracles: same
pairs, same essential classes, for every field/grid (the parity matrix
in ``tests/test_sandwich.py`` asserts it).  The positive-highest-edge
invariant of ``core.saddle_saddle`` is enforced as a raised
:class:`GradientInvariantError` rather than an ``assert`` — a malformed
gradient must fail loudly, not silently mis-pair.
"""

from __future__ import annotations

import heapq
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.core.critical import CriticalInfo
from repro.core.extremum_graph import ExtremumGraph
from repro.core.gradient import GradientField
from repro.core.grid import FACES, NTYPES, Grid
from repro.core.pairing import ExtremaPairs
from repro.core.saddle_saddle import SaddleSaddlePairs
from repro.core.tracing import OMEGA, resolve_chase, resolve_doubling, \
    tet_successors
from repro.obs import flight as _flight
from repro.obs.metrics import global_metrics
from repro.obs.trace import current_trace, maybe_span

NOKEY = np.int64(np.iinfo(np.int64).max)    # "unassigned" representative tag
NEG_INF = np.int64(np.iinfo(np.int64).min)  # pad-slot comparison key


class GradientInvariantError(ValueError):
    """A 1-cycle's highest edge must be *positive* (it created the
    cycle): propagation reaching a negative edge — one that died in D0
    or was paired with a vertex — means the gradient field is
    inconsistent with the filtration.  The sequential reference asserts
    this; the kernel path raises it."""


# --------------------------------------------------------------------------
# Critical extraction without the dense lexsort
# --------------------------------------------------------------------------

def _rank_compress(order: np.ndarray) -> np.ndarray:
    """Dense [0, nv) ranks of an injective int64 key field (one argsort;
    order-isomorphic by construction)."""
    perm = np.argsort(order, kind="stable")
    out = np.empty(len(order), dtype=np.int64)
    out[perm] = np.arange(len(order), dtype=np.int64)
    return out


def edge_keys_kernel(grid: Grid, o: np.ndarray) -> np.ndarray:
    """Dense packed edge comparison key ``o_max * 2^31 + o_min`` per edge
    sid (requires ``o < 2^31``); ``-1`` on invalid sids.  Sorts exactly
    like the reference lexicographic edge rank."""
    space = grid.sid_space(1)
    sids = np.arange(space, dtype=np.int64)
    valid = np.asarray(grid.simplex_valid(1, sids))
    keys = np.full(space, -1, dtype=np.int64)
    vv = np.asarray(grid.simplex_vertices(1, sids[valid]))
    ov = o[vv]
    keys[sids[valid]] = (np.maximum(ov[:, 0], ov[:, 1]) << 31) \
        + np.minimum(ov[:, 0], ov[:, 1])
    return keys


def extract_critical_kernel(grid: Grid, gf: GradientField,
                            order: np.ndarray) -> CriticalInfo:
    """Critical extraction with order-isomorphic ranks.

    The reference ``extract_critical`` lexsorts *every valid simplex* of
    every dimension — the single most expensive back-end step.  All
    consumers only ever compare ranks: dimension 0 and 1 comparisons
    happen on arbitrary simplices (so those keys stay dense), dimensions
    >= 2 are only compared among *critical* simplices (graph build, D1
    processing order) — so only the critical ones are ranked.  Output is
    a drop-in :class:`CriticalInfo`: identical ``crit_sids`` sequences,
    rank arrays that sort identically wherever the pipeline compares
    them."""
    order = np.asarray(order).reshape(-1)
    # streamed fronts pass full-width packed (value, vid) keys; compress
    # them to [0, nv) so the edge-key packing below always fits
    o = order if order.size == 0 or int(order.max()) < 2 ** 31 \
        else _rank_compress(order)
    crit_sids: Dict[int, np.ndarray] = {}
    ranks: Dict[int, np.ndarray] = {}
    for k in range(grid.dim + 1):
        cs = gf.critical_sids(k)
        if k == 0:
            # the vertex rank IS the vertex order (rank-compressed)
            ranks[0] = o.astype(np.int64)
        elif k == 1:
            ranks[1] = edge_keys_kernel(grid, o)
        else:
            # rank among critical simplices only — the only comparisons
            # that ever happen in dimensions >= 2
            keys = np.asarray(grid.simplex_key(k, cs, o)) if len(cs) \
                else np.zeros((0, k + 1), np.int64)
            perm = np.lexsort(tuple(keys[:, c]
                                    for c in range(k, -1, -1)))
            rk = np.full(grid.sid_space(k), -1, dtype=np.int64)
            rk[cs[perm]] = np.arange(len(cs), dtype=np.int64)
            ranks[k] = rk
        crit_sids[k] = cs[np.argsort(ranks[k][cs], kind="stable")]
    return CriticalInfo(grid, order, crit_sids, ranks)


# --------------------------------------------------------------------------
# D0 pairing: pointer-jumping fixpoint (jitted round, bucket-padded)
# --------------------------------------------------------------------------

_D0_BUCKETS = (64, 256, 1024, 4096, 16384, 65536)

# trace-time side effect: counts how many distinct (n_pad, m_pad) round
# programs were compiled — the bucket-reuse regression tests probe this
TRACE_COUNTS = {"d0_round": 0}


def _bucket(n: int) -> int:
    for b in _D0_BUCKETS:
        if b >= n:
            return b
    return -(-n // _D0_BUCKETS[-1]) * _D0_BUCKETS[-1]


_D0_ROUND_CACHE: Dict[Tuple[int, int], object] = {}


def _d0_round(n_pad: int, m_pad: int):
    """One jitted self-correcting round over padded shapes.

    The round is the pure function of ``repro.distributed
    .pairing_rounds``: age-filtered find (follow rep links only while
    the assigning saddle is older), per-triplet proposals, oldest-
    saddle-wins rebuild — here the rebuild is a scatter-min winner
    selection instead of a host-side stable sort."""
    key = (n_pad, m_pad)
    fn = _D0_ROUND_CACHE.get(key)
    if fn is not None:
        return fn
    import jax
    import jax.numpy as jnp

    def round_fn(c0, c1, skey, ekey, rep, repkey):
        TRACE_COUNTS["d0_round"] += 1   # fires once per trace
        cur = jnp.stack([c0, c1], axis=1)              # (n_pad, 2)

        def cond(cur):
            return (repkey[cur] < skey[:, None]).any()

        def body(cur):
            step = repkey[cur] < skey[:, None]
            return jnp.where(step, rep[cur], cur)

        cur = jax.lax.while_loop(cond, body, cur)
        r0, r1 = cur[:, 0], cur[:, 1]
        prop = r0 != r1
        younger = ekey[r0] >= ekey[r1]
        die = jnp.where(younger, r0, r1)
        live = jnp.where(younger, r1, r0)
        # oldest proposing saddle wins each extremum: scatter-min of the
        # saddle keys, then the winners are the triplets whose key won
        win = jnp.full(m_pad, NOKEY, jnp.int64) \
            .at[die].min(jnp.where(prop, skey, NOKEY))
        is_win = prop & (win[die] == skey)
        tgt = jnp.where(is_win, die, m_pad)            # m_pad = dropped
        new_rep = jnp.arange(m_pad, dtype=jnp.int64) \
            .at[tgt].set(live, mode="drop")
        new_repkey = jnp.full(m_pad, NOKEY, jnp.int64) \
            .at[tgt].set(skey, mode="drop")
        new_pair = jnp.full(m_pad, -1, jnp.int64) \
            .at[tgt].set(jnp.arange(n_pad, dtype=jnp.int64), mode="drop")
        return new_rep, new_repkey, new_pair

    fn = jax.jit(round_fn)
    _D0_ROUND_CACHE[key] = fn
    return fn


def _compact_nodes_vec(t0: np.ndarray, t1: np.ndarray):
    """Map extremum ids (+ OMEGA) to compact [0, ne]; OMEGA -> ne.
    Vectorized (searchsorted) version of the distributed engine's
    dict-based compaction."""
    nodes = np.unique(np.concatenate([t0, t1]))
    nodes = nodes[nodes != OMEGA]
    ne = len(nodes)

    def remap(a: np.ndarray) -> np.ndarray:
        om = a == OMEGA
        safe = np.where(om, nodes[0] if ne else 0, a)
        return np.where(om, ne, np.searchsorted(nodes, safe))

    return nodes, remap(t0), remap(t1), ne


def pair_extrema_saddles_kernel(g: ExtremumGraph) -> ExtremaPairs:
    """Elder-rule pairing as a pointer-jumping fixpoint (same result as
    the sequential ``pair_extrema_saddles``, same as the distributed
    ``pairing_fixpoint`` — which is the convergence proof)."""
    n = len(g.saddles)
    if n == 0:
        return ExtremaPairs([], [])
    nodes, c0, c1, ne = _compact_nodes_vec(np.asarray(g.t0),
                                           np.asarray(g.t1))
    m = ne + 1                                # + the OMEGA slot
    n_pad, m_pad = _bucket(n), _bucket(m + 1)
    skey = np.full(n_pad, -1, dtype=np.int64)  # pads never step/propose
    skey[:n] = np.arange(n, dtype=np.int64)
    ekey = np.zeros(m_pad, dtype=np.int64)
    ekey[:ne] = np.asarray(g.ext_key)[nodes]
    ekey[ne] = -(2 ** 62)                      # OMEGA: oldest, never dies
    c0p = np.full(n_pad, m_pad - 1, dtype=np.int64)
    c1p = np.full(n_pad, m_pad - 1, dtype=np.int64)
    c0p[:n], c1p[:n] = c0, c1

    round_fn = _d0_round(n_pad, m_pad)
    rep = np.arange(m_pad, dtype=np.int64)
    repkey = np.full(m_pad, NOKEY, dtype=np.int64)
    pair = np.full(m_pad, -1, dtype=np.int64)
    tr = current_trace()
    n_rounds = 0
    while True:
        n_rounds += 1
        with maybe_span(tr, "d0_round", round=n_rounds):
            new_rep, new_repkey, new_pair = (
                np.asarray(a) for a in round_fn(c0p, c1p, skey, ekey,
                                                rep, repkey))
        if (np.array_equal(new_rep, rep) and np.array_equal(new_pair, pair)
                and np.array_equal(new_repkey, repkey)):
            break
        rep, repkey, pair = new_rep, new_repkey, new_pair
    global_metrics().counter("pairing.d0_rounds").inc(n_rounds)

    e_idx = np.nonzero(pair[:ne] >= 0)[0]
    saddles = np.asarray(g.saddles)[pair[e_idx]]
    pairs = [(int(s), int(t)) for s, t in zip(saddles, nodes[e_idx])]
    mask = np.ones(ne, dtype=bool)
    mask[e_idx] = False
    unpaired = [int(x) for x in nodes[mask]]   # nodes are unique-sorted
    return ExtremaPairs(pairs, unpaired)


# --------------------------------------------------------------------------
# Dual extremum graph with chase-based terminal resolution
# --------------------------------------------------------------------------

def _chase_lazy(grid: Grid, gf: GradientField,
                starts: np.ndarray) -> np.ndarray:
    """Follow ascending dual v-paths computing successors on demand.

    ``tet_successors`` walks the *entire* dense tet space up front —
    wasted work when only a few stable-set terminals are needed.  Here
    each hop derives the successor for just the current frontier (the
    cofacet of each tet's exit triangle), so the cost is O(frontier x
    path length) with no dense pass at all."""
    d = grid.dim
    pd = np.asarray(gf.pair_down[d]).astype(np.int64)
    cur = np.asarray(starts, dtype=np.int64).copy()
    while True:
        ok = cur >= 0
        tau = np.where(ok, pd[np.maximum(cur, 0)], -1)
        mov = tau >= 0                  # unpaired (critical) tets stay
        if not mov.any():
            return cur
        cof = np.asarray(grid.simplex_cofaces(d - 1, tau[mov]))
        src = cur[mov]
        other = np.full(len(src), OMEGA, dtype=np.int64)
        for c in range(cof.shape[1]):
            cc = cof[:, c]
            take = (cc >= 0) & (cc != src) & (other == OMEGA)
            other[take] = cc[take]
        cur = cur.copy()
        cur[mov] = other


def build_dual_graph_chase(grid: Grid, gf: GradientField, ci: CriticalInfo,
                           saddles: np.ndarray, *,
                           strategy: str = "auto") -> ExtremumGraph:
    """``build_dual_graph`` with the stable-set terminals resolved only
    from the saddle cofacets (chase on the few needed start tets)
    instead of pointer-doubling the whole dense tet space.

    ``strategy`` picks the terminal resolution: ``"lazy"`` (per-hop
    successor computation, no dense pass), ``"chase"`` (dense successor
    array, hop per round), ``"doubling"`` (dense + pointer doubling),
    or ``"auto"`` to choose by frontier size."""
    d = grid.dim
    sig = saddles[np.argsort(-ci.ranks[d - 1][saddles], kind="stable")]
    cof = (np.asarray(grid.simplex_cofaces(d - 1, sig)) if len(sig)
           else np.zeros((0, 2), np.int64))
    t = np.full((len(sig), 2), OMEGA, dtype=np.int64)
    cnt = np.zeros(len(sig), dtype=np.int64)
    for i in range(cof.shape[1] if len(sig) else 0):
        cc = cof[:, i]
        ok = cc >= 0
        if (ok & (cnt >= 2)).any():
            raise ValueError("non-manifold cofacet count")
        put0 = ok & (cnt == 0)
        put1 = ok & (cnt == 1)
        t[put0, 0] = cc[put0]
        t[put1, 1] = cc[put1]
        cnt += ok
    starts = t[t >= 0]
    if len(starts):
        uniq, inv = np.unique(starts, return_inverse=True)
        if strategy == "auto":
            if len(uniq) * 8 > grid.sid_space(d):
                strategy = "doubling"          # dense wins on huge fronts
            elif len(uniq) <= 4096:
                strategy = "lazy"
            else:
                strategy = "chase"
        if strategy == "doubling":
            term = resolve_doubling(tet_successors(grid, gf))
            t[t >= 0] = term[starts]
        elif strategy == "lazy":
            t[t >= 0] = _chase_lazy(grid, gf, uniq)[inv]
        elif strategy == "chase":
            succ = tet_successors(grid, gf)
            t[t >= 0] = resolve_chase(succ, uniq)[inv]
        else:
            raise ValueError(f"unknown dual-chase strategy {strategy!r}")
    keep = t[:, 0] != t[:, 1]
    key = -ci.ranks[d]
    return ExtremumGraph(sig[keep], t[keep, 0], t[keep, 1], key)


# --------------------------------------------------------------------------
# D1: wavefront reduction over sparse hole-tolerant columns
# --------------------------------------------------------------------------

def _xor_sorted(rows: np.ndarray, keys: np.ndarray, add: np.ndarray,
                addk: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Batched symmetric difference of edge-set rows (-1 = hole).

    Operands carry their comparison keys (holes at ``NEG_INF``), so one
    stable ascending argsort both sweeps holes to the row head and makes
    equal entries adjacent for the mod-2 cancellation (each operand is a
    set and edge keys are injective, so multiplicity is at most 2).
    Cancelled slots become holes in place; the caller re-compacts the
    rows right-aligned so the pivot stays in the last column."""
    a = np.concatenate([rows, add], axis=1)
    k = np.concatenate([keys, addk], axis=1)
    idx = np.argsort(k, axis=1, kind="stable")
    a = np.take_along_axis(a, idx, axis=1)
    k = np.take_along_axis(k, idx, axis=1)
    eq = (k[:, 1:] == k[:, :-1]) & (a[:, 1:] >= 0)
    rm = np.zeros(a.shape, dtype=bool)
    rm[:, 1:] |= eq
    rm[:, :-1] |= eq
    a[rm] = -1
    k[rm] = NEG_INF
    return a, k


def _pair_d1_burst(grid: Grid, pair_up1: np.ndarray, is_c1: np.ndarray,
                   erank: np.ndarray,
                   order_c2: np.ndarray) -> Tuple[np.ndarray, int, int]:
    """Sequential lazy-heap reduction for small column counts.

    With only a handful of columns the lockstep wavefront pays its
    per-round vectorization overhead thousands of times for rows that
    never get wide enough to amortize it; chasing each column to its
    next critical pivot with a lazy binary heap (entries carry
    multiplicity, mod-2 cancellation happens on pop, as in Ripser's
    lazy columns) is orders of magnitude cheaper there.  Columns are
    processed in filtration order, so a claim can never be stolen and
    the result is exactly the sequential reduction's."""
    nx, ny, _ = grid.dims
    ntri = NTYPES[2]
    nedg = NTYPES[1]
    ftab = [[(int(e[0]), int(e[1]), int(e[2]), int(e[3])) for e in row]
            for row in FACES[2]]

    def faces3(sid: int) -> List[int]:
        base, t = divmod(sid, ntri)
        x = base % nx
        r = base // nx
        y = r % ny
        z = r // ny
        return [((x + dx) + nx * ((y + dy) + ny * (z + dz))) * nedg + ft
                for ft, dx, dy, dz in ftab[t]]

    n2 = len(order_c2)
    claim: Dict[int, int] = {}
    stored: Dict[int, List[Tuple[int, int]]] = {}
    pair_edge = np.full(n2, -1, dtype=np.int64)
    expansions = 0
    rounds = 0
    for g in range(n2):
        h = [(-int(erank[e]), e) for e in faces3(int(order_c2[g]))]
        heapq.heapify(h)
        while True:
            piv = None
            while h:                     # pop max, cancelling mod-2 pairs
                k = heapq.heappop(h)
                if h and h[0] == k:
                    heapq.heappop(h)
                    continue
                piv = k
                break
            if piv is None:
                break                    # boundary vanished: essential
            rounds += 1
            e = piv[1]
            up = int(pair_up1[e])
            if up >= 0:
                expansions += 1
                for f in faces3(up):     # XOR ∂V(e); the popped e cancels
                    if f != e:
                        heapq.heappush(h, (-int(erank[f]), f))
                continue
            if not is_c1[e]:
                err = GradientInvariantError(
                    f"D1 propagation reached edge sid {e}, which is "
                    f"neither gradient-paired upward nor an unpaired "
                    f"critical edge: a 1-cycle's highest edge must be "
                    f"positive — the gradient field is inconsistent")
                _flight.crash_dump("gradient_invariant", exc=err)
                raise err
            holder = claim.get(e)
            if holder is None:
                claim[e] = g
                pair_edge[g] = e
                stored[g] = h            # pivot excluded: a merge cancels
                break                    # it by never re-adding it
            expansions += 1
            for entry in stored[holder]:
                heapq.heappush(h, entry)
    return pair_edge, expansions, rounds


def pair_saddle_saddle_wavefront(grid: Grid, gf: GradientField,
                                 ci: CriticalInfo, c1: np.ndarray,
                                 c2: np.ndarray, *,
                                 batch: int = 4096,
                                 burst_below: int = 512
                                 ) -> SaddleSaddlePairs:
    """D1 homologous propagation, all columns advancing per round.

    ``c1``: unpaired critical edges; ``c2``: unpaired critical triangles
    (sid arrays).  Bit-identical pairs/essential classes to
    ``pair_saddle_saddle_seq``; the ``expansions`` counter counts
    expansion *and* merge ops (as the sequential reference does), and a
    ``rounds`` attribute records the round count (lockstep rounds, or
    pivot steps on the burst path).

    Fewer than ``burst_below`` columns dispatch to the sequential
    lazy-heap burst reducer (:func:`_pair_d1_burst`) — lockstep
    vectorization only pays off once enough columns advance together.
    On the batched path each column row is kept ascending-sorted by
    edge key with holes at the *front*, and its comparison keys are
    cached in a parallel matrix: the pivot is always ``rows[:, -1]``
    (no gather, no argmax), only the rows touched by an XOR get
    re-sorted, and the post-cancel compaction is a counting scatter
    rather than a second sort."""
    erank = ci.ranks[1]
    trank = ci.ranks[2]
    c1 = np.asarray(c1, dtype=np.int64)
    c2 = np.asarray(c2, dtype=np.int64)
    n2 = len(c2)
    E = grid.sid_space(1)
    is_c1 = np.zeros(E, dtype=bool)
    if len(c1):
        is_c1[c1] = True
    pair_up1 = np.asarray(gf.pair_up[1]).astype(np.int64)
    order_c2 = c2[np.argsort(trank[c2], kind="stable")]

    if n2 < burst_below:
        pair_edge, expansions, rounds = _pair_d1_burst(
            grid, pair_up1, is_c1, erank, order_c2)
        return _d1_result(order_c2, c1, pair_edge, expansions, rounds)

    # expansion-face table: one dense gather instead of a per-round
    # simplex_faces call.  Building it walks the whole triangle space,
    # so it only pays off with enough columns to amortize (skipped on
    # huge grids too — ~200 MB at 128^3)
    T = grid.sid_space(2)
    tri_faces = None
    if n2 >= 256 and T <= (1 << 23):
        tri_faces = np.asarray(
            grid.simplex_faces(2, np.arange(T, dtype=np.int64)),
            dtype=np.int64)

    def faces_of(tris: np.ndarray) -> np.ndarray:
        if tri_faces is not None:
            return tri_faces[tris]
        return np.asarray(grid.simplex_faces(2, tris), dtype=np.int64)

    claim = np.full(E, -1, dtype=np.int64)      # edge -> global column
    win = np.full(E, NOKEY, dtype=np.int64)     # contest scratch, reused
    stored: List[Optional[np.ndarray]] = [None] * n2
    pair_edge = np.full(n2, -1, dtype=np.int64)
    expansions = 0
    rounds = 0
    tr = current_trace()

    for lo in range(0, n2, batch):
        hi = min(lo + batch, n2)
        C = hi - lo
        rows = faces_of(order_c2[lo:hi])         # (C, 3)
        keys = erank[rows]
        srt = np.argsort(keys, axis=1, kind="stable")
        rows = np.take_along_axis(rows, srt, axis=1)
        keys = np.take_along_axis(keys, srt, axis=1)
        nlive = np.full(C, 3, dtype=np.int64)    # live entries per row
        active = np.ones(C, dtype=bool)
        while True:
            # work only on the active rows: the wavefront narrows to a
            # long tail of deep columns, and touching retired rows every
            # round would dominate the whole pass
            idx = np.nonzero(active)[0]
            if len(idx) == 0:
                break
            rounds += 1
            # round spans bracket manually (Trace.complete): the body
            # exits through several continue paths
            _rt0 = time.perf_counter() if tr is not None else 0.0
            piv = rows[idx, -1]                  # sorted rows: pivot last
            mx = keys[idx, -1]
            # -- retirement: column vanished -> essential 2-class -------
            empty = mx == NEG_INF
            if empty.any():
                active[idx[empty]] = False
                idx, piv = idx[~empty], piv[~empty]
                if len(idx) == 0:
                    if tr is not None:
                        tr.complete("d1_round", _rt0, round=rounds)
                    continue
            # -- classify the live pivots ------------------------------
            up = pair_up1[piv]
            expand = up >= 0
            crit = ~expand
            ex_rows = idx[expand]
            mg_rows = np.zeros(0, dtype=np.int64)
            mg_bounds: List[np.ndarray] = []
            if crit.any():
                bad = ~is_c1[piv[crit]]
                if bad.any():
                    e = int(piv[crit][bad][0])
                    err = GradientInvariantError(
                        f"D1 propagation reached edge sid {e}, which is "
                        f"neither gradient-paired upward nor an unpaired "
                        f"critical edge: a 1-cycle's highest edge must be "
                        f"positive — the gradient field is inconsistent")
                    _flight.crash_dump("gradient_invariant", exc=err)
                    raise err
                # -- critical pivots: merge / contest ------------------
                crit_rows = idx[crit]
                cpiv = piv[crit]
                holder = claim[cpiv]             # global index or -1
                mine = crit_rows + lo            # global index of each
                merge = (holder >= 0) & (holder < mine)
                contest = ~merge                 # unclaimed, or stealable
                # contest winner per pivot: the lowest-rank (= lowest
                # global index) column wins; the others wait a round
                if contest.any():
                    cand_rows = crit_rows[contest]
                    cand_piv = cpiv[contest]
                    win[cand_piv] = NOKEY        # reset only touched slots
                    np.minimum.at(win, cand_piv, cand_rows + lo)
                    is_win = win[cand_piv] == cand_rows + lo
                    wrows = cand_rows[is_win]
                    wpiv = cand_piv[is_win]
                    # steal: the displaced (younger) holder reopens; next
                    # round it sees the new claim and merges the winner
                    old = claim[wpiv]
                    reopen = old[old >= 0]
                    reopen = reopen[(reopen >= lo) & (reopen < hi)]
                    if len(reopen):
                        active[reopen - lo] = True
                        pair_edge[reopen] = -1
                    claim[wpiv] = wrows + lo
                    pair_edge[wrows + lo] = wpiv
                    active[wrows] = False        # provisionally retired
                mg_rows = crit_rows[merge]
                for gidx in claim[cpiv[merge]]:
                    b = stored[gidx] if gidx < lo else rows[gidx - lo]
                    mg_bounds.append(b[b >= 0])
            # -- apply the XOR ops (expansions + merges) in one batch --
            op_rows = np.concatenate([ex_rows, mg_rows]) \
                if len(mg_rows) else ex_rows
            if len(op_rows) == 0:
                if tr is not None:
                    tr.complete("d1_round", _rt0, round=rounds)
                continue                         # contest losers wait
            expansions += len(op_rows)
            ne = len(ex_rows)
            aw = max([3] + [len(b) for b in mg_bounds])
            add = np.full((len(op_rows), aw), -1, dtype=np.int64)
            if ne:
                add[:ne, :3] = faces_of(up[expand])
            for r, b in enumerate(mg_bounds):
                add[ne + r, :len(b)] = b
            if len(mg_bounds):
                addk = np.where(add >= 0, erank[np.maximum(add, 0)],
                                NEG_INF)
            else:
                addk = erank[add]                # pure expansions: no holes
            a, k = _xor_sorted(rows[op_rows], keys[op_rows], add, addk)
            # -- re-compact right-aligned into the (maybe grown) width --
            m = a >= 0
            cnt = m.cumsum(axis=1)
            live = cnt[:, -1]
            W = rows.shape[1]
            lmax = int(live.max()) if len(live) else 0
            if lmax > W:                         # grow geometrically so
                Wn = max(lmax, 2 * W)            # the copies amortize
                gr = np.full((C, Wn), -1, dtype=np.int64)
                gr[:, Wn - W:] = rows
                gk = np.full((C, Wn), NEG_INF, dtype=np.int64)
                gk[:, Wn - W:] = keys
                rows, keys, W = gr, gk, Wn
            # counting scatter with a trash slot: live entries land right-
            # aligned in columns 1..W, holes all land in the (discarded)
            # column 0 — no nonzero() pass over the whole op block
            dest = np.where(m, (W + 1 - live)[:, None] + cnt - 1, 0)
            na = np.full((len(op_rows), W + 1), -1, dtype=np.int64)
            nk = np.full((len(op_rows), W + 1), NEG_INF, dtype=np.int64)
            ar = np.arange(len(op_rows))[:, None]
            na[ar, dest] = a
            nk[ar, dest] = k
            rows[op_rows] = na[:, 1:]
            keys[op_rows] = nk[:, 1:]
            nlive[op_rows] = live
            # -- shrink once the peak has passed: per-round sort cost
            # tracks the *current* widest row, not the historical peak --
            wide = int(nlive.max())
            if W > 8 and 2 * wide <= W:
                Wn = max(wide, 4)
                rows = rows[:, W - Wn:].copy()
                keys = keys[:, W - Wn:].copy()
            if tr is not None:
                tr.complete("d1_round", _rt0, round=rounds)
        # batch done: freeze the claim-holding boundaries (later batches
        # can merge them but — being younger — can never steal them)
        for r in range(C):
            g = lo + r
            if pair_edge[g] >= 0:
                row = rows[r]
                stored[g] = row[row >= 0].copy()

    return _d1_result(order_c2, c1, pair_edge, expansions, rounds)


def _d1_result(order_c2: np.ndarray, c1: np.ndarray, pair_edge: np.ndarray,
               expansions: int, rounds: int) -> SaddleSaddlePairs:
    paired = pair_edge >= 0
    pairs = [(int(pair_edge[g]), int(order_c2[g]))
             for g in np.nonzero(paired)[0]]
    unpaired_tri = [int(order_c2[g]) for g in np.nonzero(~paired)[0]]
    claimed = set(int(e) for e, _ in pairs)
    unpaired_edges = sorted(int(x) for x in c1 if int(x) not in claimed)
    out = SaddleSaddlePairs(pairs, unpaired_edges, unpaired_tri,
                            expansions)
    out.rounds = rounds
    global_metrics().counter("pairing.d1_rounds").inc(rounds)
    return out
