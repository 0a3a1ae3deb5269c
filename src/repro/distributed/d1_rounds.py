"""Distributed saddle-saddle pairing (paper Sec. V, Alg. 5/6) — token-based
round-synchronous engine with the global-local boundary structure.

Faithful structure (paper -> here):

- *global-local boundary*: per block, the set of boundary edges it owns
  (``local``); plus the (n_props, n_blocks) table of the highest boundary
  edge key per block (``gmax``) — the "global boundary".
- *computation token*: ``owner[i]`` — only that block expands propagation i
  this round; tokens travel to the block holding the global max edge.
- *anticipation* (Sec. V-B): the owner keeps expanding locally up to
  ``budget`` steps even while the global max is remote, but never pairs or
  steals an edge unless its key dominates every remote column ("not pairing
  the potential simplex c ensures the propagation never expands too far").
- *self-correction* (Alg. 5 l.20-27): reaching an edge already paired to an
  older propagation merges boundaries; an older propagation steals the edge
  from a younger one, which is reactivated and resumes (merging next round).
- messages: edge additions to neighbor-owned edges (XOR toggles), merge
  broadcasts, gmax column updates, token transfers — applied at round
  boundaries in deterministic order (the paper's ordering properties (i)/(ii)
  hold because rounds are bulk-synchronous here).
- ``gmax`` columns may *overestimate* after merges/toggles (the paper merges
  global boundaries by taking per-process maxima, which survives XOR
  cancellation); a token arriving at a block whose true max is lower simply
  corrects the column and forwards the token — safe, costs extra hops.

The round loop is bulk-synchronous SPMD (the TPU adaptation of the paper's
MPI message cycles; the dedicated communication thread of Sec. V-C maps to
XLA async collectives and is a no-op here).  Outcome equals the sequential
Alg. 2/3 result for any block count / budget — asserted by tests.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set, Tuple

import numpy as np

from repro.core.critical import CriticalInfo
from repro.core.gradient import GradientField
from repro.core.grid import Grid
from repro.core.saddle_saddle import SaddleSaddlePairs, _tri_boundary
from repro.obs import watchdog as _watchdog
from repro.obs.metrics import global_metrics
from repro.obs.trace import current_trace, maybe_span


NEG_INF = -(2 ** 62)


@dataclass
class D1Stats:
    rounds: int = 0
    token_hops: int = 0
    expansions: int = 0
    merges: int = 0
    steals: int = 0
    addition_msgs: int = 0


class _Block:
    """Per-block state of the simulation (one MPI rank / TPU device)."""

    def __init__(self, bid: int):
        self.bid = bid
        self.local: Dict[int, Set[int]] = {}          # prop -> owned edges
        self.pair_of_edge: Dict[int, int] = {}        # owned edge -> prop
        self.inbox_add: List[Tuple[int, int]] = []    # (prop, edge sid)
        self.inbox_merge: List[Tuple[int, int]] = []  # (dst prop, src prop)

    def toggle(self, prop: int, e: int):
        s = self.local.setdefault(prop, set())
        if e in s:
            s.remove(e)
        else:
            s.add(e)

    def local_max(self, prop: int, ekey: np.ndarray) -> int:
        s = self.local.get(prop)
        if not s:
            return NEG_INF
        return max(int(ekey[e]) for e in s)


def d1_distributed(grid: Grid, gf: GradientField, ci: CriticalInfo,
                   c1: np.ndarray, c2: np.ndarray, n_blocks: int,
                   anticipation: bool = True,
                   budget: Optional[int] = None) -> Tuple[SaddleSaddlePairs,
                                                          D1Stats]:
    """Block-parallel D1.  ``n_blocks`` z-slabs; ``budget`` = anticipation
    step budget per round (paper default: 0.01% of local triangles, min 1).
    ``anticipation=False`` gives the paper's *Basic* version (Sec. V-A)."""
    nz = grid.dims[2] if grid.dim == 3 else grid.dims[grid.dim - 1]
    stats = D1Stats()
    nv_plane = grid.nv // max(grid.dims[2], 1) if grid.dim == 3 else None

    # ---- ownership: z-slab of the base vertex --------------------------
    zsplit = np.linspace(0, grid.dims[2], n_blocks + 1).astype(int) \
        if grid.dim == 3 else None
    assert grid.dim == 3, "distributed D1 is a 3-D procedure"

    def block_of_vertex(v: int) -> int:
        z = v // (grid.dims[0] * grid.dims[1])
        return int(np.searchsorted(zsplit, z, side="right") - 1)

    def block_of_edge(e: int) -> int:
        import repro.core.grid as G
        return block_of_vertex(e // G.NTYPES[1])

    def block_of_tri(t: int) -> int:
        import repro.core.grid as G
        return block_of_vertex(t // G.NTYPES[2])

    # edge keys are compared, never decoded: the extraction's dense edge
    # ranks (packed keys or lexsort ranks) sort like the lexicographic
    # order, and only valid edges are ever read, all above NEG_INF
    ekey = ci.ranks[1]
    trank = ci.ranks[2]
    c1_set = {int(x) for x in c1}
    n2 = len(c2)
    c2 = np.asarray(sorted((int(x) for x in c2), key=lambda s: trank[s]),
                    dtype=np.int64)
    if budget is None:
        budget = max(1, grid.n_simplices(2) // (10000 * n_blocks))

    blocks = [_Block(b) for b in range(n_blocks)]
    gmax = np.full((n2, n_blocks), NEG_INF, dtype=np.int64)
    owner = np.array([block_of_tri(int(s)) for s in c2], dtype=np.int64)
    active = np.ones(n2, dtype=bool)
    pair_edge = np.full(n2, -1, dtype=np.int64)

    # initial boundaries (∂ sigma): additions routed to edge owners
    for i, s in enumerate(c2):
        for e in _tri_boundary(grid, int(s)):
            b = block_of_edge(e)
            blocks[b].inbox_add.append((i, e))
            gmax[i, b] = max(gmax[i, b], int(ekey[e]))

    def expand(i: int, blk: _Block) -> Optional[Tuple[int, str]]:
        """Run propagation i at its token owner.  Returns (dest, why) if the
        token must move, None if the propagation retired this round."""
        steps = 0
        while True:
            lmax = blk.local_max(i, ekey)
            rmax_col = int(np.max(np.delete(gmax[i], blk.bid))) \
                if n_blocks > 1 else NEG_INF
            gmax[i, blk.bid] = lmax
            if lmax == NEG_INF and rmax_col == NEG_INF:
                active[i] = False          # boundary vanished: essential
                return None
            if lmax == NEG_INF or (not anticipation and lmax < rmax_col):
                return (int(np.argmax(gmax[i])), "basic")
            if steps >= budget and lmax < rmax_col:
                return (int(np.argmax(gmax[i])), "budget")
            tau = max(blk.local.get(i, ()), key=lambda e: int(ekey[e]))
            up = int(gf.pair_up[1][tau])
            if up >= 0:
                # triangle-paired: XOR the apparent pair's boundary.  This is
                # legal even when a remote column dominates (anticipation) —
                # XOR expansion commutes.
                stats.expansions += 1
                steps += 1
                for e in _tri_boundary(grid, up):
                    b = block_of_edge(e)
                    if b == blk.bid:
                        blk.toggle(i, e)
                    else:
                        blocks[b].inbox_add.append((i, e))
                        gmax[i, b] = max(gmax[i, b], int(ekey[e]))
                        stats.addition_msgs += 1
                continue
            if int(ekey[tau]) < rmax_col:
                # local max is not the cycle max: it may legally be a
                # negative edge (vertex-paired or a D0 death) that the true
                # max's expansions will cancel — pausing here is the only
                # safe move (pair/steal/merge need the *global* max).
                return (int(np.argmax(gmax[i])), "defer-pair")
            # tau dominates globally: the max edge of a 1-cycle is positive,
            # so a critical tau is necessarily D0-unpaired (cf. saddle_saddle)
            assert tau in c1_set, "negative edge dominates a 1-cycle"
            j = blk.pair_of_edge.get(tau, -1)
            if j < 0:
                blk.pair_of_edge[tau] = i
                pair_edge[i] = tau
                active[i] = False          # token parks here
                return None
            if trank[c2[j]] < trank[c2[i]]:
                # tau belongs to an older propagation: merge its boundary
                stats.merges += 1
                for b in range(n_blocks):
                    if b == blk.bid:
                        for e in list(blocks[b].local.get(j, ())):
                            blk.toggle(i, e)
                    else:
                        blocks[b].inbox_merge.append((i, j))
                    gmax[i, b] = max(gmax[i, b], gmax[j, b])
                continue
            # steal: i is older — tau re-pairs with i, j resumes here
            stats.steals += 1
            blk.pair_of_edge[tau] = i
            pair_edge[i] = tau
            pair_edge[j] = -1
            active[j] = True
            owner[j] = blk.bid
            active[i] = False
            return None

    tr = current_trace()   # grabbed once: the loop runs on one thread
    while True:
        stats.rounds += 1
        _watchdog.progress("pairing.d1")    # round heartbeat
        with maybe_span(tr, "d1_round", round=stats.rounds):
            # ---- apply messages (deterministic order), refresh gmax ----
            for blk in blocks:
                touched = set()
                for i, e in blk.inbox_add:
                    blk.toggle(i, e)
                    touched.add(i)
                blk.inbox_add = []
                for i, j in blk.inbox_merge:
                    for e in list(blk.local.get(j, ())):
                        blk.toggle(i, e)
                    touched.add(i)
                blk.inbox_merge = []
                for i in touched:
                    gmax[i, blk.bid] = blk.local_max(i, ekey)
            # ---- token owners expand (ownership snapshot: tokens travel
            # as messages, so transfers take effect only next round — the
            # paper processes boundary updates strictly before tokens,
            # Sec. V-A) --------------------------------------------------
            moved = False
            owner_snapshot = owner.copy()
            active_snapshot = active.copy()
            for blk in blocks:
                for i in range(n2):
                    if active_snapshot[i] and owner_snapshot[i] == blk.bid:
                        res = expand(i, blk)
                        if res is not None:
                            dest, _ = res
                            if dest != blk.bid:
                                stats.token_hops += 1
                                moved = True
                            owner[i] = dest
        if not active.any():
            break
        if not moved:
            # all active propagations are waiting on messages already applied
            # next round; if nothing is in flight either, we are stuck
            in_flight = any(blk.inbox_add or blk.inbox_merge
                            for blk in blocks)
            if not in_flight:
                continue_possible = False
                for blk in blocks:
                    for i in range(n2):
                        if active[i] and owner[i] == blk.bid:
                            continue_possible = True
                assert continue_possible, "D1 rounds deadlocked"
    global_metrics().counter("pairing.d1_rounds").inc(stats.rounds)

    pairs = []
    for blk in blocks:
        for e, i in blk.pair_of_edge.items():
            if pair_edge[i] == e:
                pairs.append((int(e), int(c2[i])))
    paired_edges = {e for e, _ in pairs}
    paired_tris = {t for _, t in pairs}
    unpaired_edges = sorted(c1_set - paired_edges)
    unpaired_tris = sorted(set(int(x) for x in c2) - paired_tris)
    return SaddleSaddlePairs(sorted(pairs), unpaired_edges, unpaired_tris,
                             stats.expansions), stats
