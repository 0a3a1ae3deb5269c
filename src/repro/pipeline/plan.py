"""AOT split of the pipeline, mirroring jax: ``lower`` then ``compile``.

``PersistencePipeline.lower(request)`` resolves a :class:`TopoRequest`
against the pipeline's defaults into a :class:`Plan` — the *decision
record*: grid decomposition, backend, pairing engines, streamed or
in-memory execution, and the exact stage chain (stages whose outputs
the request does not ask for are dropped, e.g. ``homology_dims=(0,)``
on a 3-D grid skips the D1 engine).  Plans are frozen, hashable, and
inspectable (``describe()``) without touching field data.

``Plan.compile()`` binds the compiled artifact — the backend's batched
rows program, ranks to gradient fields — through a shared, evictable
:class:`PlanCache` (this replaces the ad-hoc per-pipeline ``_programs``
dict).  Compiled programs are keyed
by ``(dims, backend, n_blocks)``: two plans differing only in result
options or engine knobs share one compile, which is the compile-count
contract the regression tests assert.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Callable, Dict, Optional, Tuple

from repro.core.grid import Grid

from repro.obs.metrics import global_metrics

from .backends import Backend, get_backend


# --------------------------------------------------------------------------
# PlanCache — shared, evictable compiled-artifact cache
# --------------------------------------------------------------------------

# process-wide plan-cache counters (repro.obs), aggregated across every
# PlanCache instance — the per-instance ints below stay the per-cache
# source of truth for stats()/tests
_M_HITS = global_metrics().counter("plan_cache.hits")
_M_MISSES = global_metrics().counter("plan_cache.misses")
_M_EVICTIONS = global_metrics().counter("plan_cache.evictions")
_M_COMPILES = global_metrics().counter("plan_cache.compiles")

class PlanCache:
    """LRU cache of compiled plan artifacts, shared across pipelines.

    Entries are built once per key by the supplied builder; ``maxsize``
    bounds the number of resident artifacts (compiled programs hold
    device executables — evicting the least recently used keeps
    long-running services from accumulating every shape they ever saw).
    Thread-safe: the serving worker and client threads share one cache.
    """

    def __init__(self, maxsize: int = 128):
        if maxsize < 1:
            raise ValueError(f"maxsize must be >= 1, got {maxsize}")
        self.maxsize = maxsize
        self._entries: "OrderedDict[tuple, object]" = OrderedDict()
        self._lock = threading.RLock()
        self._building: Dict[tuple, threading.Event] = {}
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.compiles = 0
        # key -> how many times the builder ran for it while resident
        # (compile counter; stays at 1 per key unless the entry was
        # evicted and rebuilt).  Pruned with its entry on eviction so
        # the process-wide singleton stays bounded; ``compiles`` keeps
        # the lifetime total.
        self.build_counts: Dict[tuple, int] = {}

    def get_or_build(self, key: tuple, builder: Callable[[], object]):
        """Return the cached entry, building it once if absent.

        The builder (a trace/compile, possibly seconds) runs *outside*
        the cache lock: concurrent lookups of other keys never block on
        it, and concurrent builders of the same key wait on a per-key
        event so each key still compiles exactly once."""
        while True:
            with self._lock:
                if key in self._entries:
                    self.hits += 1
                    _M_HITS.inc()
                    self._entries.move_to_end(key)
                    return self._entries[key]
                pending = self._building.get(key)
                if pending is None:
                    self._building[key] = threading.Event()
                    self.misses += 1
                    _M_MISSES.inc()
                    break
            pending.wait()     # someone else is building this key
        try:
            out = builder()
        except BaseException:
            with self._lock:
                self._building.pop(key).set()  # let waiters retry/raise
            raise
        with self._lock:
            self._entries[key] = out
            self.compiles += 1
            _M_COMPILES.inc()
            self.build_counts[key] = self.build_counts.get(key, 0) + 1
            while len(self._entries) > self.maxsize:
                old, _ = self._entries.popitem(last=False)
                self.build_counts.pop(old, None)
                self.evictions += 1
                _M_EVICTIONS.inc()
            self._building.pop(key).set()
        return out

    def __bool__(self) -> bool:
        # a cache is always truthy, even when empty: ``__len__`` alone
        # would make `cache or default_plan_cache()` silently discard a
        # fresh isolated cache (the falsiness footgun the `is None`
        # guards used to work around)
        return True

    def __contains__(self, key: tuple) -> bool:
        with self._lock:
            return key in self._entries

    def peek(self, key: tuple):
        """Read without building (KeyError if absent); no LRU touch."""
        with self._lock:
            return self._entries[key]

    def __len__(self) -> int:
        return len(self._entries)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self.build_counts.clear()

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return dict(size=len(self._entries), hits=self.hits,
                        misses=self.misses, evictions=self.evictions,
                        compiles=self.compiles)


_DEFAULT_CACHE = PlanCache()
_MEMO_LOCK = threading.Lock()   # guards per-instance backend rows memos


def default_plan_cache() -> PlanCache:
    """The process-wide shared cache used when a pipeline gets none."""
    return _DEFAULT_CACHE


# --------------------------------------------------------------------------
# Plan / Executable
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class Plan:
    """Resolved execution plan: everything decided, nothing compiled.

    Frozen and hashable — ``plan.key`` is the full identity,
    ``plan.compile_key`` the (coarser) compiled-program identity."""

    dims: Tuple[int, ...]                 # grid vertex dims (nx, ny, nz)
    backend: str                          # registry name
    n_blocks: int
    distributed: bool
    anticipation: bool
    budget: Optional[int]
    streamed: bool
    chunk_z: Optional[int] = None
    chunk_budget: Optional[int] = None
    homology_dims: Tuple[int, ...] = ()
    stage_names: Tuple[str, ...] = ()
    # approximation knobs (repro.approx): the plan records them so the
    # resolver can route to the hierarchy engine and batches never mix
    # approximate with exact execution
    epsilon: Optional[float] = None
    deadline_s: Optional[float] = None
    progressive: bool = False
    # which sandwich back-end runs the pairing phases (critical
    # extraction, D0, dual, D1): "jax" batched kernels (default) or the
    # "np" sequential reference oracle
    sandwich_backend: str = "jax"

    @property
    def key(self) -> tuple:
        return (self.dims, self.backend, self.n_blocks, self.distributed,
                self.anticipation, self.budget, self.streamed,
                self.chunk_z, self.chunk_budget, self.homology_dims,
                self.epsilon, self.deadline_s, self.progressive,
                self.sandwich_backend)

    @property
    def is_approx(self) -> bool:
        """Whether execution routes through ``repro.approx``."""
        return self.epsilon is not None or self.progressive \
            or self.deadline_s is not None

    @property
    def compile_key(self) -> tuple:
        """Compiled artifacts are shared at this granularity: one compile
        per (dims, backend, n_blocks) regardless of result options."""
        return (self.dims, self.backend, self.n_blocks)

    @property
    def result_key(self) -> tuple:
        """The plan facets that determine result *content*: grid dims +
        homology dims.  Backend, sandwich engine, sharding, streaming
        and chunking are excluded — their diagrams are bit-identical
        (the repo-wide parity contract), which is why the diagram cache
        (``repro.cache``) serves across all of them from one entry;
        approximation knobs are excluded too, because epsilon is a
        lookup-time predicate on the stored entry's ``error_bound``,
        not part of the identity.  The request-level analogue (adding
        the field fingerprint and query defaults) is
        ``TopoRequest.cache_key()``."""
        return (self.dims, self.homology_dims)

    @property
    def grid(self) -> Grid:
        return Grid.of(*self.dims)

    def describe(self) -> str:
        """Human-readable one-plan summary (inspectable AOT artifact)."""
        if self.streamed and self.n_blocks > 1:
            # the composed engine: every shard streams its z-slab, the
            # boundary-plane halo exchange is double-buffered against
            # chunk compute (comm_seconds / overlap_fraction land in the
            # StageReport of the run)
            mode = (f"sharded-streamed x{self.n_blocks} "
                    f"(overlapped halo exchange)")
        elif self.streamed:
            mode = "streamed"
        else:
            mode = "in-memory"
        engine = "distributed" if self.distributed else "sequential"
        approx = ""
        if self.is_approx:
            knobs = [f"epsilon={self.epsilon}"] \
                if self.epsilon is not None else []
            if self.progressive:
                knobs.append("progressive")
            if self.deadline_s is not None:
                knobs.append(f"deadline_s={self.deadline_s}")
            approx = f", approx({', '.join(knobs)})"
        return (f"Plan(dims={self.dims}, backend={self.backend!r}, "
                f"{mode}, {engine} back-end, "
                f"sandwich={self.sandwich_backend!r}, "
                f"n_blocks={self.n_blocks}, "
                f"homology_dims={self.homology_dims}{approx}, "
                f"stages={' -> '.join(self.stage_names)})")

    def compile(self, cache: Optional[PlanCache] = None,
                backend: Optional[Backend] = None) -> "Executable":
        """Bind the compiled artifact (the batched rows program) through
        ``cache`` (the shared default if None).

        ``backend`` overrides the registry lookup — the pipeline passes
        its own held instance so unregistered :class:`Backend` objects
        (test doubles, locally-built backends) keep working."""
        cache = cache or default_plan_cache()
        be = get_backend(self.backend) if backend is None else backend
        grid = self.grid
        rows_program = None
        if be.batched_rows is not None:
            try:
                registered = get_backend(self.backend)
            except Exception:
                registered = None
            if be is registered:
                rows_program = cache.get_or_build(
                    self.compile_key,
                    lambda: be.batched_rows(grid, self.n_blocks))
            else:
                # an unregistered (or shadowing same-named) Backend
                # instance must never exchange compiled programs with
                # the registry entry through the shared cache — memoize
                # on the instance itself instead (one lock is fine:
                # unregistered-backend compiles are rare)
                with _MEMO_LOCK:
                    memo = getattr(be, "_rows_memo", None)
                    if memo is None:
                        memo = {}
                        object.__setattr__(be, "_rows_memo", memo)
                    if self.compile_key not in memo:
                        memo[self.compile_key] = be.batched_rows(
                            grid, self.n_blocks)
                    rows_program = memo[self.compile_key]
        return Executable(plan=self, backend=be,
                          rows_program=rows_program, cache=cache)


@dataclass(frozen=True)
class Executable:
    """A plan with its compiled artifacts bound, ready to execute.

    ``rows_program`` is the backend's jitted ``orders (B, nv) ->
    (GradientFields, critical counts)`` program (None for the ``np``
    backend): on the fused kernel, on one chip or slab by slab over a
    ring (``shardmap``), it builds the fields on the device, on the
    others it scatters packed rows on the host (``backends._rows_fn``,
    ``backends._shardmap_rows_fn``).  It comes out of the shared
    :class:`PlanCache`, so repeated and batched requests of one ``(dims,
    backend, n_blocks)`` reuse a single compile."""

    plan: Plan
    backend: Backend
    rows_program: Optional[Callable] = None
    cache: PlanCache = field(default_factory=default_plan_cache, repr=False,
                             compare=False)
