"""`PersistencePipeline` — one declarative front door for diagrams.

    from repro.pipeline import PersistencePipeline, TopoRequest

    pipe = PersistencePipeline(backend="jax")
    res  = pipe.run(TopoRequest(field=f, grid=g, top_k=50))
    ress = pipe.run_batch([TopoRequest(field=f) for f in fields])

Every path — in-memory, batched, streamed (out-of-core), distributed —
dispatches through one resolver with an explicit AOT split mirroring
jax:

    request --lower--> Plan --compile--> Executable --execute--> result

``lower`` resolves the request against the pipeline defaults into an
inspectable, hashable :class:`~repro.pipeline.plan.Plan` (backend,
engines, stage chain, streamed/in-memory decomposition); ``compile``
binds the compiled batched-rows program (ranks to gradient fields) via
the shared, evictable :class:`~repro.pipeline.plan.PlanCache` (one
compile per ``(dims, backend, n_blocks)`` across repeated and batched
requests).  Results are queryable :class:`~repro.pipeline.result
.DiagramResult`s with a versioned wire format.

``diagram`` / ``diagrams`` / ``diagram_stream`` remain as thin shims
over ``run`` (bit-identical output), as do ``compute_dms`` /
``compute_ddms_sim`` in ``repro.core``.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import List, Optional, Sequence, Union

import numpy as np

from repro.core.diagram import Diagram
from repro.core.grid import Grid, vertex_order
from repro.obs.trace import Trace, current_trace, maybe_span, trace_active

from .backends import (Backend, SandwichBackend, default_backend_name,
                       get_backend, get_sandwich_backend)
from .plan import Executable, Plan, PlanCache, default_plan_cache
from .request import TopoRequest, strip_field
from .result import DiagramResult, PipelineResult  # noqa: F401  (re-export)
from .stages import (ALL_STAGES, FRONT_STAGES, PipelineState, StageReport,
                     run_stages, sandwich_of)

_STAGES_BY_NAME = {st.name: st for st in ALL_STAGES}


@dataclass(frozen=True)
class PipelineConfig:
    """Resolved execution config handed to every stage."""

    backend: Backend
    n_blocks: int = 1
    distributed: bool = False       # round-synchronous pairing + token D1
    anticipation: bool = True       # D1 anticipation (Sec. V-B)
    budget: Optional[int] = None    # D1 anticipation step budget
    # the sandwich back-end running the pairing phases; None means the
    # "np" reference (configs predating the knob keep their behavior)
    sandwich: Optional[SandwichBackend] = None

    def __post_init__(self):
        if self.n_blocks < 1:
            raise ValueError(
                f"n_blocks must be >= 1, got {self.n_blocks}")


def _back_stage_names(grid_dim: int, homology_dims) -> tuple:
    """Resolve the back-end stage chain for the requested dimensions.

    D0 always runs (it is cheap and its saddle set feeds the dual
    stage); the dual and D1 engines are dropped when no requested
    dimension needs their output."""
    dims = set(homology_dims)
    names = ["d0"]
    need_d1 = (grid_dim == 3 and bool(dims & {1, 2})) \
        or (grid_dim == 2 and 1 in dims)
    need_dual = (grid_dim >= 2 and bool(dims & {grid_dim - 1, grid_dim})) \
        or (grid_dim == 3 and need_d1) or grid_dim == 1
    if need_dual:
        names.append("d_top")
    if need_d1 or grid_dim <= 1:
        names.append("d1")
    return tuple(names)


class PersistencePipeline:
    """Staged DMS/DDMS executor over a registered backend.

    Parameters
    ----------
    backend : registry name ("np", "jax", "pallas", "shardmap") or a
        :class:`Backend` instance — the default for requests that do
        not name one.  None picks it from the platform: the compiled
        Pallas kernel on a TPU, the ``np`` reference elsewhere.
    n_blocks : z-slab block count for the distributed engines.
    distributed : use the round-synchronous self-correcting pairing and
        the token-based D1 (the DDMS back-end).  Defaults to
        ``n_blocks > 1``.
    anticipation, budget : D1 engine knobs (distributed only).
    sandwich_backend : which back-end runs the pairing phases (critical
        extraction, D0, dual, D1): ``"jax"`` (default) selects the
        batched kernels of ``repro.kernels.sandwich``, ``"np"`` the
        sequential reference oracles.  Output is bit-identical.
    plan_cache : the compiled-artifact cache; defaults to the
        process-wide shared :func:`default_plan_cache`.
    """

    def __init__(self, backend: Union[str, Backend, None] = None, *,
                 n_blocks: int = 1,
                 distributed: Optional[bool] = None,
                 anticipation: bool = True, budget: Optional[int] = None,
                 sandwich_backend: Optional[str] = None,
                 plan_cache: Optional[PlanCache] = None):
        if backend is None:
            backend = default_backend_name()
        be = backend if isinstance(backend, Backend) else get_backend(backend)
        sb = sandwich_backend if sandwich_backend is not None else "jax"
        self.config = PipelineConfig(
            backend=be, n_blocks=n_blocks,
            distributed=(n_blocks > 1) if distributed is None else distributed,
            anticipation=anticipation, budget=budget,
            sandwich=sb if isinstance(sb, SandwichBackend)
            else get_sandwich_backend(sb))
        self.plan_cache = plan_cache or default_plan_cache()

    # -- helpers -----------------------------------------------------------

    @property
    def backend(self) -> Backend:
        return self.config.backend

    @property
    def _programs(self) -> "_ProgramsView":
        """Legacy view of the shared :class:`PlanCache` under the old
        per-pipeline ``_programs`` keys (kept for probes/tests)."""
        return _ProgramsView(self.plan_cache)

    def _get_backend(self, name: str) -> Backend:
        """Resolve a plan's backend name, preferring the pipeline's own
        held instance (which may be an unregistered Backend object)."""
        if name == self.config.backend.name:
            return self.config.backend
        return get_backend(name)

    def _as_request(self, request, grid=None, **options) -> TopoRequest:
        if isinstance(request, TopoRequest):
            if grid is not None or options:
                raise TypeError(
                    "pass options inside the TopoRequest, not alongside it")
            return request
        return TopoRequest(field=request, grid=grid, **options)

    # -- AOT split: lower / compile ----------------------------------------

    def lower(self, request: Union[TopoRequest, np.ndarray], grid=None,
              **options) -> Plan:
        """Resolve a request against this pipeline's defaults into an
        inspectable, hashable :class:`Plan` (no field data touched
        beyond grid inference, nothing compiled)."""
        return self._lower_resolved(
            self._as_request(request, grid, **options).resolve())

    def _lower_resolved(self, req: TopoRequest) -> Plan:
        """``lower`` for a request ``resolve()`` already validated."""
        cfg = self.config
        backend = req.backend if req.backend is not None else cfg.backend.name
        n_blocks = req.n_blocks if req.n_blocks is not None else cfg.n_blocks
        if req.distributed is not None:
            distributed = req.distributed
        elif req.n_blocks is not None:
            distributed = req.n_blocks > 1
        else:
            distributed = cfg.distributed
        anticipation = req.anticipation if req.anticipation is not None \
            else cfg.anticipation
        budget = req.budget if req.budget is not None else cfg.budget
        if req.sandwich_backend is not None:
            sandwich = get_sandwich_backend(req.sandwich_backend).name
        else:
            sandwich = cfg.sandwich.name if cfg.sandwich is not None \
                else "jax"
        be = self._get_backend(backend)
        streamed = req.is_stream
        if streamed and not be.caps.streamed:
            if be.caps.jittable:
                # the chunks carry rank-free int64 keys, which only the XLA
                # "jax" chunk kernel takes; a sharded backend becomes the
                # composed sharded-streaming engine (host-thread shard
                # workers streaming their z-slabs through that kernel,
                # exchanging boundary key planes)
                backend, be = "jax", self._get_backend("jax")
            else:
                from .backends import available_backends
                ok = sorted(n for n, b in available_backends().items()
                            if b.caps.streamed)
                raise ValueError(
                    f"backend {backend!r} has no streamed kernel; "
                    f"streaming backends: {ok}")
        g = req.grid
        hdims = req.homology_dims if req.homology_dims is not None \
            else tuple(range(g.dim + 1))
        front = tuple(st.name for st in FRONT_STAGES)
        if streamed:
            front = ("gradient", "extract_sort")
        return Plan(dims=g.dims, backend=backend, n_blocks=n_blocks,
                    distributed=distributed, anticipation=anticipation,
                    budget=budget, streamed=streamed,
                    chunk_z=req.chunk_z, chunk_budget=req.chunk_budget,
                    homology_dims=hdims,
                    stage_names=front + _back_stage_names(g.dim, hdims),
                    epsilon=req.epsilon, deadline_s=req.deadline_s,
                    progressive=req.progressive,
                    sandwich_backend=sandwich)

    def compile(self, request, grid=None, **options) -> Executable:
        """``lower`` + bind compiled artifacts via the shared cache."""
        return self._compile(self.lower(request, grid, **options))

    def _compile(self, plan: Plan) -> Executable:
        return plan.compile(self.plan_cache,
                            backend=self._get_backend(plan.backend))

    # -- the one resolver --------------------------------------------------

    def run(self, request: Union[TopoRequest, np.ndarray], grid=None,
            **options) -> DiagramResult:
        """Execute one request end to end (in-memory or streamed).

        Accepts a :class:`TopoRequest`, or an ndarray/``FieldSource``
        plus keyword options which are packed into one."""
        req = self._as_request(request, grid, **options).resolve()
        if req.is_approx:
            return self._run_approx(req)
        plan = self._lower_resolved(req)
        if req.trace:
            # activate a fresh Trace for this thread; every StageReport
            # created under it auto-binds (stages.py), deep layers hook
            # in via current_trace(), and engine worker threads capture
            # it from their stage_report — see repro.obs
            with trace_active(Trace()):
                return self._run_planned(req, plan)
        return self._run_planned(req, plan)

    def _run_planned(self, req: TopoRequest, plan: Plan) -> DiagramResult:
        if plan.streamed:
            # the streamed front-end drives its own per-chunk kernels;
            # the batched rows program would be compiled for nothing
            return self._run_stream(req, plan)
        return self._run_memory(req, plan, self._compile(plan))

    def run_batch(self, requests: Sequence[Union[TopoRequest, np.ndarray]]
                  ) -> List[DiagramResult]:
        """Execute a batch, amortizing compiled programs across requests.

        Same-plan, same-shape in-memory groups run the stencil-gather +
        lower-star pairing front-end as ONE (B*nv)-vertex dispatch on
        batch-capable backends; everything else falls back to per-
        request ``run``.  Results come back in submission order."""
        reqs = [self._as_request(r).resolve() for r in requests]
        if not reqs:
            return []
        plans = [self._lower_resolved(r) for r in reqs]
        groups: dict = {}
        for i, (req, plan) in enumerate(zip(reqs, plans)):
            groups.setdefault((plan.key, req.field_shape), []).append(i)
        out: List[Optional[DiagramResult]] = [None] * len(reqs)
        for idxs in groups.values():
            plan = plans[idxs[0]]
            if any(reqs[i].trace for i in idxs):
                # a trace is per-run, not part of the Plan identity —
                # traced requests serve one by one so each gets its own
                # timeline (the shared plan cache still amortizes)
                for i in idxs:
                    out[i] = self.run(reqs[i])
                continue
            if plan.is_approx:
                # approximation picks its level per field (the bound is
                # data-dependent), so these serve one by one — each
                # level still amortizes through the shared plan cache
                for i in idxs:
                    out[i] = self._run_approx(reqs[i])
                continue
            if plan.streamed:
                for i in idxs:
                    out[i] = self._run_stream(reqs[i], plan)
                continue
            ex = self._compile(plan)
            if len(idxs) == 1 or ex.rows_program is None:
                for i in idxs:
                    out[i] = self._run_memory(reqs[i], plan, ex)
                continue
            for i, res in zip(idxs, self._run_group(
                    [reqs[i] for i in idxs], plan, ex)):
                out[i] = res
        return out

    # -- execution paths ---------------------------------------------------

    def _run_approx(self, req: TopoRequest) -> DiagramResult:
        """Bounded-error / progressive path (``repro.approx``): picks a
        hierarchy level for ``epsilon`` requests, walks coarse-to-fine
        for ``progressive`` / ``deadline_s`` ones (returning the final,
        tightest result — ``repro.approx.refine`` yields the
        intermediates, ``TopoService`` serves them as previews)."""
        from repro.approx.engine import approximate
        from repro.approx.progressive import approximate_progressive
        if req.progressive or req.deadline_s is not None:
            return approximate_progressive(self, req)
        return approximate(self, req)

    def _cfg(self, plan: Plan) -> PipelineConfig:
        return PipelineConfig(
            backend=self._get_backend(plan.backend), n_blocks=plan.n_blocks,
            distributed=plan.distributed, anticipation=plan.anticipation,
            budget=plan.budget,
            sandwich=get_sandwich_backend(plan.sandwich_backend))

    def _stages(self, plan: Plan, names) -> tuple:
        return tuple(_STAGES_BY_NAME[n] for n in names)

    def _finish(self, state: PipelineState, report: StageReport,
                req: TopoRequest, plan: Plan, cfg: PipelineConfig,
                stream=None, diagram: Optional[Diagram] = None,
                values_fn=None) -> DiagramResult:
        if cfg.distributed:
            report.count(n_blocks=cfg.n_blocks)
        dg = diagram if diagram is not None else state.diagram()
        if values_fn is None:
            f = np.asarray(state.f).reshape(-1)
            values_fn = (lambda vids: f[vids]) if f.size else None
        res = DiagramResult(
            dg, report.flat(), report if req.include_report else None,
            stream=stream, request=strip_field(req), plan=plan,
            trace=report.trace, _values_fn=values_fn)
        # materialize the canonical query arrays now (tiny — critical
        # simplices only) so the result does not pin the full field /
        # dense key array for its lifetime
        res.arrays()
        res._values_fn = None
        return res

    def _run_memory(self, req: TopoRequest, plan: Plan,
                    ex: Executable) -> DiagramResult:
        if ex.rows_program is not None:
            # the compiled rows program IS the single-field gradient
            # (a B=1 bucket): one code path for singles and batches
            return self._run_group([req], plan, ex)[0]
        cfg = self._cfg(plan)
        state = PipelineState(req.grid, np.asarray(req.field))
        report = StageReport("pipeline")
        run_stages(state, cfg, report,
                   stages=self._stages(plan, plan.stage_names))
        return self._finish(state, report, req, plan, cfg)

    def _run_group(self, reqs: List[TopoRequest], plan: Plan,
                   ex: Executable) -> List[DiagramResult]:
        """Batched front-end: one compiled rows program over the stacked
        batch, then per-request back-ends."""
        cfg = self._cfg(plan)
        grid = reqs[0].grid
        B = len(reqs)
        reports = [StageReport("pipeline") for _ in reqs]
        states = [PipelineState(grid, np.asarray(r.field)) for r in reqs]

        # order per field (cheap, numpy) — timed per report
        for state, report in zip(states, reports):
            with report.stage("order"):
                state.f = np.asarray(state.f).reshape(-1)
                state.order = np.asarray(vertex_order(state.f))

        # one batched gradient dispatch for the whole batch; the program
        # returns the fields and their critical counts, built on the
        # device (device_fields 1) or scattered on the host (0); a
        # sharded program also counts n_blocks and halo_bytes
        counters = ex.rows_program.counters
        tr = current_trace()
        t0 = time.perf_counter()
        with maybe_span(tr, "gradient", batch_size=B, **counters):
            gfs, n_crit = ex.rows_program(
                np.stack([s.order for s in states]))
        dt = (time.perf_counter() - t0) / B
        for state, report, gf, n in zip(states, reports, gfs, n_crit):
            rep = report.child("gradient")
            rep.seconds = dt
            rep.count(n_critical=n, batch_size=B, **counters)
            state.gf = gf

        # per-request critical extraction + back-end
        rest = self._stages(plan, ("extract_sort",)
                            + plan.stage_names[len(FRONT_STAGES):])
        out = []
        for req, state, report in zip(reqs, states, reports):
            run_stages(state, cfg, report, stages=rest)
            out.append(self._finish(state, report, req, plan, cfg))
        return out

    def _run_stream(self, req: TopoRequest, plan: Plan) -> DiagramResult:
        """Out-of-core path: chunked front-end on rank-free keys, back-
        end on the stitched critical set, SparseOrder rank recovery.
        ``n_blocks > 1`` selects the overlapped sharded-streaming engine
        (every shard streams its z-slab; halo exchange double-buffered
        against chunk compute) — output stays bit-identical."""
        from repro.stream import (SparseOrder, as_source, diagram_vertices,
                                  sharded_stream_front, stream_front)

        cfg = self._cfg(plan)
        # the explicit grid carries the dims for flat-array sources
        # (resolve() already rejected source/grid dim conflicts)
        src = as_source(req.field, dims=req.grid.dims)
        grid = req.grid
        chunk_z, chunk_budget = plan.chunk_z, plan.chunk_budget
        if chunk_z is None and chunk_budget is None:
            chunk_budget = 64 << 20
        report = StageReport("pipeline")

        with report.stage("gradient") as rep:
            if plan.n_blocks > 1:
                out = sharded_stream_front(
                    src, plan.n_blocks, chunk_z=chunk_z,
                    chunk_budget=chunk_budget, stage_report=rep)
            else:
                out = stream_front(src, chunk_z=chunk_z,
                                   chunk_budget=chunk_budget,
                                   stage_report=rep)
            rep.count(n_critical=sum(out.gf.n_critical().values()))

        # the back-end compares orders, never their absolute values, so
        # the dense key array stands in for the vertex order verbatim
        state = PipelineState(grid, np.zeros(0, np.float32),
                              order=out.keys, gf=out.gf)
        with report.stage("extract_sort"):
            state.ci = sandwich_of(cfg).extract(grid, out.gf, out.keys)
        run_stages(state, cfg, report,
                   stages=self._stages(plan, plan.stage_names[2:]))

        # exact global ranks, but only for the vertices the diagram
        # touches (chunked counting pass — still no global argsort)
        with report.stage("rank_translate"):
            order = SparseOrder.from_keys(
                out.keys, diagram_vertices(grid, state.pairs,
                                           state.essential))
        dg = Diagram(grid, order, state.pairs, state.essential)
        return self._finish(
            state, report, req, plan, cfg, stream=out.report, diagram=dg,
            values_fn=out.values_for_vids)

    # -- legacy entry points (thin shims over run) -------------------------

    def diagram(self, f, grid: Optional[Grid] = None) -> DiagramResult:
        """Persistence diagram of one scalar field (shim over ``run``)."""
        return self.run(TopoRequest(field=f, grid=grid))

    def diagram_stream(self, source, *, chunk_z: Optional[int] = None,
                       chunk_budget: Optional[int] = None) -> DiagramResult:
        """Persistence diagram of a field served chunk-by-chunk (shim
        over ``run`` with ``stream=True``).

        ``source`` is a :class:`repro.stream.FieldSource` (in-memory
        array, ``np.memmap`` file, or on-demand generator) — the field
        is never materialized as one array; at most ~2 chunks of field
        data are resident (asserted by ``result.stream``).  Output is
        bit-identical to :meth:`diagram` on the same field.  Requires a
        backend with the ``streamed`` capability."""
        return self.run(TopoRequest(field=source, stream=True,
                                    chunk_z=chunk_z,
                                    chunk_budget=chunk_budget))

    def diagrams(self, fields: Sequence, grid: Optional[Grid] = None
                 ) -> List[DiagramResult]:
        """Diagrams of a batch of same-shape fields (shim over
        ``run_batch``; same-shape is the legacy contract)."""
        fields = list(fields)
        if not fields:
            return []
        shapes = {np.asarray(f).shape for f in fields}
        if len(shapes) > 1:
            raise ValueError(
                f"diagrams() needs same-shape fields, got {sorted(shapes)}")
        return self.run_batch(
            [TopoRequest(field=f, grid=grid) for f in fields])


class _ProgramsView:
    """Mapping adapter exposing the shared PlanCache under the legacy
    ``pipe._programs`` keys: ``(dims, backend, n_blocks)`` -> rows
    program."""

    def __init__(self, cache: PlanCache):
        self._cache = cache

    def __contains__(self, key) -> bool:
        return key in self._cache

    def __getitem__(self, key):
        return self._cache.peek(key)
