"""Composable DMS/DDMS stages + structured stage reporting.

The paper's pipeline is a fixed chain (Sec. II-F / III):

    order -> gradient -> critical extraction -> D0 -> D_{d-1} -> D1

The *front-end* (order, gradient, extraction) is identical for the
sequential and the distributed algorithm; only the *back-end* pairing
engines differ (Union-Find vs the round-synchronous self-correcting
fixpoint; sequential homologous propagation vs the token-based D1).
This module expresses each link of the chain as a stage object operating
on a shared :class:`PipelineState`, so `compute_dms` / `compute_ddms_sim`
and the `PersistencePipeline` facade all run the *same* code and only
select engines through the config.

Timings and algorithm counters land in a :class:`StageReport` — a
nestable, machine-readable record replacing the ad-hoc ``stats`` dicts
the two drivers used to hand-roll.  ``StageReport.flat()`` reproduces
the legacy flat key space (``order``, ``gradient``, ``d1_rounds``, ...)
so existing consumers keep working.

Since the observability PR the report is **span-backed**: every
``stage()`` context is also a :class:`repro.obs.trace.Span` when a
trace is active (``TopoRequest(trace=True)`` — the pipeline activates
the trace thread-locally, and reports created inside the activation
window bind to it automatically).  The public shape (``name`` /
``seconds`` / ``counters`` / ``children``, ``flat()``, ``to_dict()``)
is unchanged; the trace adds wall-clock timestamps and thread identity
on top, exported via ``result.trace.to_perfetto(path)``.  Every stage
is also a ``stage.<name>`` annotation on the ``jax.profiler`` timeline,
traced or not.
"""

from __future__ import annotations

import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from repro.obs.trace import Trace, current_trace, maybe_span

from repro.core.critical import CriticalInfo
from repro.core.diagram import Diagram
from repro.core.dms import _as_pairs
from repro.core.extremum_graph import build_d0_graph
from repro.core.gradient import GradientField
from repro.core.grid import Grid, vertex_order


# --------------------------------------------------------------------------
# StageReport
# --------------------------------------------------------------------------

# the wall-time attribution split: the *front-end* (order + gradient) is
# what PR 2 kernelized; everything from critical extraction on is the
# *sandwich back-end* this registry selects an implementation for
FRONT_STAGE_NAMES = ("order", "gradient")
BACK_STAGE_NAMES = ("extract_sort", "d0", "d_top", "d1")
# halo-exchange stages of the sharded-streaming front-end (nested under
# the gradient stage); their counters carry the comm-hiding split
COMM_STAGE_NAMES = ("comm",)

@dataclass
class StageReport:
    """Structured per-stage record: wall time, counters, nested children.

    Span-backed: when a :class:`repro.obs.trace.Trace` is attached
    (explicitly, or inherited from the thread's active trace at
    construction), every ``stage()`` context also records a span —
    same name, same interval, stage counters as span attributes — so
    the report tree and the Perfetto timeline are two views of one
    measurement."""

    name: str
    seconds: float = 0.0
    counters: Dict[str, float] = field(default_factory=dict)
    children: List["StageReport"] = field(default_factory=list)
    trace: Optional[Trace] = field(default=None, repr=False, compare=False)

    def __post_init__(self):
        if self.trace is None:
            self.trace = current_trace()

    def child(self, name: str) -> "StageReport":
        r = StageReport(name, trace=self.trace)
        self.children.append(r)
        return r

    @contextmanager
    def stage(self, name: str):
        """Open (and time) a child stage.  The interval is a
        :func:`repro.obs.trace.maybe_span`: a span with the stage's
        counters when traced, a flight-recorder event otherwise, and a
        ``stage.<name>`` annotation on the profiler timeline on both."""
        r = self.child(name)
        with maybe_span(self.trace, name) as sp:
            t0 = time.perf_counter()
            try:
                yield r
            finally:
                r.seconds += time.perf_counter() - t0
                if sp is not None:
                    sp.args.update(r.counters)

    def count(self, **counters) -> None:
        for k, v in counters.items():
            self.counters[k] = self.counters.get(k, 0) + v

    @property
    def total_seconds(self) -> float:
        return self.seconds if self.seconds else \
            sum(c.total_seconds for c in self.children)

    def _named_seconds(self, names) -> float:
        return sum(c.total_seconds for c in self.children
                   if c.name in names)

    @property
    def front_seconds(self) -> float:
        """Front-end wall time (order + gradient child stages)."""
        return self._named_seconds(FRONT_STAGE_NAMES)

    @property
    def back_seconds(self) -> float:
        """Sandwich back-end wall time (extract_sort + d0 + d_top + d1)."""
        return self._named_seconds(BACK_STAGE_NAMES)

    def _counter_sum(self, key: str) -> float:
        return float(self.counters.get(key, 0.0)) + \
            sum(c._counter_sum(key) for c in self.children)

    @property
    def comm_seconds(self) -> float:
        """Halo-exchange wall time of a sharded run: ``comm`` stages,
        summed recursively (comm nests under the gradient stage)."""
        return self._named_seconds(COMM_STAGE_NAMES) + \
            sum(c.comm_seconds for c in self.children
                if c.name not in COMM_STAGE_NAMES)

    @property
    def overlap_fraction(self) -> Optional[float]:
        """Fraction of halo-exchange time hidden behind compute
        (``comm_hidden_s / comm_total_s`` over all nested comm stages);
        ``None`` when the run had no communication."""
        total = self._counter_sum("comm_total_s")
        return self._counter_sum("comm_hidden_s") / total \
            if total > 0 else None

    def flat(self) -> Dict[str, float]:
        """Legacy flat stats dict: stage names -> seconds (nested names are
        dot-joined), all counters merged at top level under their own keys."""
        out: Dict[str, float] = {}

        def visit(r: "StageReport", prefix: str) -> None:
            for c in r.children:
                out[prefix + c.name] = c.seconds
                visit(c, prefix + c.name + ".")
            out.update(r.counters)

        visit(self, "")
        return out

    def to_dict(self) -> dict:
        """Nested machine-readable form (BENCH_pipeline.json)."""
        out = {"name": self.name, "seconds": self.seconds,
               "counters": dict(self.counters),
               "children": [c.to_dict() for c in self.children]}
        if self.children:
            out["front_seconds"] = self.front_seconds
            out["back_seconds"] = self.back_seconds
            comm = self.comm_seconds
            if comm > 0:
                out["comm_seconds"] = comm
                out["overlap_fraction"] = self.overlap_fraction
        return out


# --------------------------------------------------------------------------
# Pipeline state
# --------------------------------------------------------------------------

@dataclass
class PipelineState:
    """Everything a stage may read or produce, threaded through the chain."""

    grid: Grid
    f: np.ndarray
    order: Optional[np.ndarray] = None
    gf: Optional[GradientField] = None
    ci: Optional[CriticalInfo] = None
    pairs: Dict[int, np.ndarray] = field(default_factory=dict)
    essential: Dict[int, np.ndarray] = field(default_factory=dict)
    # inter-stage sets: saddles consumed by D0 / the dual diagram
    d0_saddles: set = field(default_factory=set)
    dual_saddles: Optional[np.ndarray] = None
    dual_paired_saddles: set = field(default_factory=set)

    def diagram(self) -> Diagram:
        return Diagram(self.grid, self.order, self.pairs, self.essential)


# --------------------------------------------------------------------------
# Front-end stages (shared by DMS and DDMS)
# --------------------------------------------------------------------------

class OrderStage:
    """Global injective vertex order (Array Preconditioning, Sec. III)."""

    name = "order"

    def run(self, state: PipelineState, cfg, rep: StageReport) -> None:
        state.f = np.asarray(state.f).reshape(-1)
        state.order = np.asarray(vertex_order(state.f))


class GradientStage:
    """Discrete gradient via the configured backend (registry dispatch)."""

    name = "gradient"

    def run(self, state: PipelineState, cfg, rep: StageReport) -> None:
        state.gf = cfg.backend.gradient(state.grid, state.order,
                                        n_blocks=cfg.n_blocks)
        rep.count(n_critical=sum(state.gf.n_critical().values()))


def sandwich_of(cfg):
    """The config's sandwich back-end (``np`` reference for configs
    predating the knob, e.g. hand-built test doubles)."""
    sb = getattr(cfg, "sandwich", None)
    if sb is None:
        from .backends import get_sandwich_backend
        sb = get_sandwich_backend("np")
    return sb


class CriticalStage:
    """Critical extraction + per-dimension rank sort (sandwich back-end
    dispatch: reference dense lexsort vs the kernel's isomorphic-rank
    extraction)."""

    name = "extract_sort"

    def run(self, state: PipelineState, cfg, rep: StageReport) -> None:
        state.ci = sandwich_of(cfg).extract(state.grid, state.gf,
                                            state.order)


# --------------------------------------------------------------------------
# Back-end stages (engine selected by the config)
# --------------------------------------------------------------------------

def _pair_graph(g, cfg, rep: StageReport, prefix: str):
    """Run the configured extremum-saddle pairing engine on a graph."""
    if cfg.distributed:
        from repro.distributed.pairing_rounds import pairing_fixpoint
        p, st = pairing_fixpoint(g, collect_stats=True)
        rep.count(**{prefix + "_rounds": st.rounds})
        if prefix == "d0":
            rep.count(d0_corrections=st.corrections)
        return p
    return sandwich_of(cfg).pair_d0(g)


class D0Stage:
    """D0 on the primal extremum graph (minimum-saddle pairs)."""

    name = "d0"

    def run(self, state: PipelineState, cfg, rep: StageReport) -> None:
        grid, ci = state.grid, state.ci
        if grid.dim >= 1:
            g0 = build_d0_graph(grid, state.gf, ci)
            p0 = _pair_graph(g0, cfg, rep, "d0")
            state.pairs[0] = _as_pairs([(e, s) for (s, e) in p0.pairs])
            paired_v = {e for _, e in p0.pairs}
            state.essential[0] = np.asarray(
                sorted(set(map(int, ci.crit_sids[0])) - paired_v),
                dtype=np.int64)
            state.d0_saddles = {s for s, _ in p0.pairs}
        else:
            state.pairs[0] = _as_pairs([])
            state.essential[0] = np.asarray(
                [int(x) for x in ci.crit_sids[0]], dtype=np.int64)


class DualStage:
    """D_{d-1} on the dual graph (saddle-maximum pairs) + essential[d]."""

    name = "d_top"

    def run(self, state: PipelineState, cfg, rep: StageReport) -> None:
        grid, ci = state.grid, state.ci
        d = grid.dim
        if d >= 2:
            if d == 2:
                state.dual_saddles = np.asarray(
                    [int(e) for e in ci.crit_sids[1]
                     if int(e) not in state.d0_saddles], dtype=np.int64)
            else:
                state.dual_saddles = ci.crit_sids[d - 1]
            gD = sandwich_of(cfg).build_dual(grid, state.gf, ci,
                                             state.dual_saddles)
            pD = _pair_graph(gD, cfg, rep, "d_top")
            state.pairs[d - 1] = _as_pairs(pD.pairs)
            state.essential[d] = np.asarray(
                sorted(set(map(int, ci.crit_sids[d]))
                       - {e for _, e in pD.pairs}), dtype=np.int64)
            state.dual_paired_saddles = {s for s, _ in pD.pairs}
        elif d == 1:
            state.essential[1] = np.asarray(
                sorted(set(map(int, ci.crit_sids[1])) - state.d0_saddles),
                dtype=np.int64)


class D1Stage:
    """D1 by homologous propagation on the unpaired leftovers (3-D)."""

    name = "d1"

    def run(self, state: PipelineState, cfg, rep: StageReport) -> None:
        grid, ci = state.grid, state.ci
        d = grid.dim
        if d == 3:
            c1 = np.asarray(
                [int(e) for e in ci.crit_sids[1]
                 if int(e) not in state.d0_saddles], dtype=np.int64)
            c2 = np.asarray(
                [int(s) for s in ci.crit_sids[2]
                 if int(s) not in state.dual_paired_saddles], dtype=np.int64)
            if cfg.distributed:
                from repro.distributed.d1_rounds import d1_distributed
                ss, st1 = d1_distributed(
                    grid, state.gf, ci, c1, c2, cfg.n_blocks,
                    anticipation=cfg.anticipation, budget=cfg.budget)
                rep.count(d1_rounds=st1.rounds, d1_token_hops=st1.token_hops,
                          d1_expansions=st1.expansions, d1_merges=st1.merges,
                          d1_steals=st1.steals)
            else:
                ss = sandwich_of(cfg).pair_d1(grid, state.gf, ci, c1, c2)
                rep.count(d1_expansions=ss.expansions)
                if hasattr(ss, "rounds"):
                    rep.count(d1_rounds=ss.rounds)
            state.pairs[1] = _as_pairs(ss.pairs)
            state.essential[1] = np.asarray(ss.unpaired_edges,
                                            dtype=np.int64)
            state.essential[2] = np.asarray(ss.unpaired_triangles,
                                            dtype=np.int64)
        elif d == 2:
            state.essential[1] = np.asarray(
                sorted({int(s) for s in state.dual_saddles}
                       - state.dual_paired_saddles), dtype=np.int64)


FRONT_STAGES = (OrderStage(), GradientStage(), CriticalStage())
BACK_STAGES = (D0Stage(), DualStage(), D1Stage())
ALL_STAGES = FRONT_STAGES + BACK_STAGES


def run_stages(state: PipelineState, cfg, report: StageReport,
               stages=ALL_STAGES) -> PipelineState:
    """Run a stage chain over ``state``, timing each into ``report``."""
    for st in stages:
        with report.stage(st.name) as rep:
            st.run(state, cfg, rep)
    return state
