"""Backend registry: name -> gradient implementation + capability flags.

Replaces the string-typed ``gradient_backend`` if/else ladders that used
to live in ``compute_dms`` / ``compute_ddms_sim`` / ``kernels.ops``.  A
backend bundles:

- ``gradient(grid, order, *, n_blocks=1)`` -> :class:`GradientField`;
- an optional *batched rows* program ``batched_rows(grid, n_blocks)``
  returning a compiled ``orders (B, nv) -> (GradientFields, critical
  counts)`` function; ``Plan.compile`` binds it through the shared
  :class:`~repro.pipeline.plan.PlanCache` (one compile per ``(dims,
  backend, n_blocks)``) and ``PersistencePipeline.run_batch`` uses it to
  amortize the stencil-gather pre-pass over a batch of same-shape
  requests;
- capability flags (``jittable`` / ``sharded`` / ``batched`` /
  ``fused`` / ``streamed``) that ``lower()`` and the serving layer use
  to pick execution strategies (a streamed plan requires ``streamed``).

Registered backends:

- ``np``             — literal Robins reference with priority queues;
- ``jax``            — branchless masked-recomputation form; the stencil
  gather and pairing compile as one jit program (packed int64 keys,
  int32 ranks);
- ``pallas``         — the *fused* Pallas lower-star kernel: the
  27-point gather runs inside the kernel over the z-planes and y-tiles
  around each tile (Mosaic-compiled on a TPU, interpreted elsewhere);
- ``pallas_prepass`` — the original im2col pre-pass + vertex-tiled
  Pallas kernel, kept as a fallback and cross-check;
- ``shardmap``       — the z-slab front-end over ``n_blocks`` devices:
  one cached ``shard_map`` rows program per ``(dims, n_blocks)`` in
  which each device exchanges int32 rank planes with its ring
  neighbours (``ppermute``), runs the fused kernel on its slab and
  builds its slab's fields, the next slab's first plane of words
  arriving as a ghost plane (``repro.distributed.shardmap_pipeline
  .slab_fields``).

Batched rows programs are jitted end to end and their *batch dimension
is bucket-padded* (see ``_bucket_batch``), so nearby batch sizes reuse
one compiled program instead of re-tracing per distinct B.

``register_backend`` is the extension point later scaling PRs (async
collectives, multi-host, remote caches) plug into.

The platform picks the default: :func:`default_backend_name` is the
compiled ``pallas`` kernel on a TPU and the ``np`` reference elsewhere.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, Optional

import numpy as np

from repro.core import gradient as GR
from repro.core.gradient import GradientField
from repro.core.grid import Grid
from repro.obs.trace import current_trace, maybe_span


class UnknownBackendError(KeyError):
    """Raised for a backend name absent from the registry."""


class UnknownSandwichBackendError(KeyError):
    """Raised for a sandwich back-end name absent from the registry."""


@dataclass(frozen=True)
class BackendCaps:
    jittable: bool = False   # gradient program is jit-compiled
    sharded: bool = False    # runs under shard_map over a device mesh
    batched: bool = False    # supports one-shot batched packed-row programs
    fused: bool = False      # stencil gather fused into the kernel (no
    #                          materialized (nv, 27) im2col tensor)
    streamed: bool = False   # kernel accepts per-chunk halo volumes with
    #                          rank-free keys (out-of-core front-end,
    #                          PersistencePipeline.diagram_stream)


@dataclass(frozen=True)
class Backend:
    """One gradient/pairing implementation behind the common protocol."""

    name: str
    gradient: Callable[..., GradientField]
    caps: BackendCaps = field(default_factory=BackendCaps)
    description: str = ""
    # optional: (grid, n_blocks) -> compiled fn(orders (B, nv) int64) ->
    # (fields, critical counts); see _rows_fn, _shardmap_rows_fn
    batched_rows: Optional[Callable[[Grid, int], Callable]] = None


_REGISTRY: Dict[str, Backend] = {}


def register_backend(backend: Backend, overwrite: bool = False) -> Backend:
    if backend.name in _REGISTRY and not overwrite:
        raise ValueError(f"backend {backend.name!r} already registered")
    _REGISTRY[backend.name] = backend
    return backend


def get_backend(name: str) -> Backend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise UnknownBackendError(
            f"unknown backend {name!r}; registered backends: "
            f"{sorted(_REGISTRY)}") from None


def available_backends() -> Dict[str, Backend]:
    return dict(_REGISTRY)


def default_backend_name() -> str:
    """The gradient backend a pipeline runs when none is named: the
    Pallas kernel where it compiles (a TPU), the literal reference
    elsewhere."""
    from repro.kernels.lower_star import interpret_mode
    return "np" if interpret_mode() else "pallas"


# --------------------------------------------------------------------------
# Sandwich back-ends: the D0 / D_{d-1} / D1 pairing phases
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class SandwichBackend:
    """One implementation of the sandwich back-end phases.

    The gradient front-end is selected by :class:`Backend`; everything
    after it — critical extraction, the D0 elder-rule pairing, the dual
    graph build, and the D1 saddle-saddle reduction — is selected here.
    ``np`` is the sequential reference (the bit-exactness oracle);
    ``jax`` is the batched kernel path of ``repro.kernels.sandwich``
    (pointer-jumping D0, chase-resolved dual graph, wavefront D1) and
    the pipeline default."""

    name: str
    extract: Callable      # (grid, gf, order)          -> CriticalInfo
    pair_d0: Callable      # (ExtremumGraph)            -> ExtremaPairs
    build_dual: Callable   # (grid, gf, ci, saddles)    -> ExtremumGraph
    pair_d1: Callable      # (grid, gf, ci, c1, c2)     -> SaddleSaddlePairs
    description: str = ""


_SANDWICH_REGISTRY: Dict[str, SandwichBackend] = {}


def register_sandwich_backend(backend: SandwichBackend,
                              overwrite: bool = False) -> SandwichBackend:
    if backend.name in _SANDWICH_REGISTRY and not overwrite:
        raise ValueError(
            f"sandwich backend {backend.name!r} already registered")
    _SANDWICH_REGISTRY[backend.name] = backend
    return backend


def get_sandwich_backend(name: str) -> SandwichBackend:
    try:
        return _SANDWICH_REGISTRY[name]
    except KeyError:
        raise UnknownSandwichBackendError(
            f"unknown sandwich backend {name!r}; registered: "
            f"{sorted(_SANDWICH_REGISTRY)}") from None


def available_sandwich_backends() -> Dict[str, SandwichBackend]:
    return dict(_SANDWICH_REGISTRY)


def _register_sandwich_backends() -> None:
    from repro.core.critical import extract_critical
    from repro.core.extremum_graph import build_dual_graph
    from repro.core.pairing import pair_extrema_saddles
    from repro.core.saddle_saddle import pair_saddle_saddle_seq
    from repro.kernels.sandwich import (build_dual_graph_chase,
                                        extract_critical_kernel,
                                        pair_extrema_saddles_kernel,
                                        pair_saddle_saddle_wavefront)
    register_sandwich_backend(SandwichBackend(
        name="np", extract=extract_critical,
        pair_d0=pair_extrema_saddles, build_dual=build_dual_graph,
        pair_d1=pair_saddle_saddle_seq,
        description="sequential reference back-end (Union-Find dicts + "
                    "per-triangle set-XOR); the bit-exactness oracle"))
    register_sandwich_backend(SandwichBackend(
        name="jax", extract=extract_critical_kernel,
        pair_d0=pair_extrema_saddles_kernel,
        build_dual=build_dual_graph_chase,
        pair_d1=pair_saddle_saddle_wavefront,
        description="batched kernel back-end: jitted pointer-jumping D0, "
                    "chase-resolved dual graph, wavefront D1 columns"))


_register_sandwich_backends()


# --------------------------------------------------------------------------
# np — literal Robins reference (priority queues)
# --------------------------------------------------------------------------

def _gradient_np(grid: Grid, order, *, n_blocks: int = 1) -> GradientField:
    return GR.compute_gradient_np(grid, np.asarray(order))


# --------------------------------------------------------------------------
# jax / pallas — vectorized kernels (shared batched-row machinery)
# --------------------------------------------------------------------------

_BATCH_BUCKETS = (1, 2, 3, 4, 6, 8, 12, 16, 24, 32)


def _bucket_batch(B: int) -> int:
    """Smallest padding bucket >= B (then multiples of 32)."""
    for b in _BATCH_BUCKETS:
        if b >= B:
            return b
    return -(-B // 32) * 32


def _rows_fn(grid: Grid, kernel: str) -> Callable:
    """orders (B, nv) -> the batch's B :class:`GradientField`s and their
    critical-simplex counts.

    The stencil gather and the per-vertex pairing are both vertex-local,
    so a batch of B same-shape fields is just a (B*nv)-vertex problem —
    one compiled program, one dispatch.  The whole device program is
    jitted for every kernel, and the batch dimension is bucket-padded
    with inert all(-1) fields so nearby batch sizes share one compiled
    program.  On the fused kernel the program goes on from the kernel's
    words to the dense fields themselves (``fields_from_words``), so the
    host only cuts them into per-field views; the other kernels, and a
    fused grid whose sids need int64, return packed rows that the host
    scatters (``GR.scatter_results_batch``).  ``device_fields`` says
    which: 1 when the fields come from the device.
    """
    import jax
    import jax.numpy as jnp
    from repro.kernels import ref as REF
    from repro.kernels.lower_star import (device_fields_fit,
                                          fields_from_words, fused_words,
                                          lower_star_gradient_pallas)

    on_device = kernel == "pallas" and device_fields_fit(grid)

    def fn(orders):  # (Bp, nv) rank fields
        if kernel == "pallas":
            # fused path: gather happens inside the kernel, the batch is a
            # leading grid dimension — no (B*nv, 27) tensor materializes
            words = fused_words(grid, orders)
            return fields_from_words(words, grid) if on_device else words
        o = orders.astype(jnp.int32) if grid.nv < 2 ** 31 else orders
        nbrs = jax.vmap(
            lambda oo: GR.neighbor_orders(grid, oo, xp=jnp))(o)
        flat_nbrs = nbrs.reshape(-1, 27)
        flat_ov = o.reshape(-1)
        if kernel == "pallas_prepass":
            return lower_star_gradient_pallas(flat_nbrs, flat_ov,
                                              rank_bound=grid.nv)
        return REF.lower_star_gradient_jnp(flat_nbrs, flat_ov,
                                           rank_bound=grid.nv)

    return _rows_program(grid, jax.jit(fn), jnp.asarray,
                         on_device=on_device, words=kernel == "pallas")


def _rows_program(grid: Grid, jfn, put, *, on_device: bool, words: bool,
                  n_blocks: int = 1, counters: Optional[dict] = None
                  ) -> Callable:
    """The host side of a jitted rows program: pad the (B, nv) orders to
    the batch bucket, ``put`` them on the device, run ``jfn``, copy its
    outputs to the host, then cut the device's fields into per-field
    views (``on_device``), or scatter its packed rows (``words``: the
    fused kernel's words, unpacked first).  Each step is a sub-span of
    the gradient stage.  ``counters`` (with ``device_fields``) go on the
    stage's report and span."""
    import jax
    from repro.kernels.lower_star import host_rows

    def wrapped(orders):
        tr = current_trace()
        orders = np.asarray(orders)
        B = orders.shape[0]
        with maybe_span(tr, "gradient.h2d"):
            Bp = _bucket_batch(B)
            if Bp != B:
                # all(-1) pad fields: every simplex fails the lower-star
                # test, so the padded lanes retire after one loop
                # iteration
                orders = np.concatenate([orders, np.full(
                    (Bp - B, orders.shape[1]), -1, orders.dtype)])
            orders = jax.block_until_ready(put(orders))
        with maybe_span(tr, "gradient.kernel"):
            out = jax.block_until_ready(jfn(orders))
        with maybe_span(tr, "gradient.d2h"):
            out = jax.device_get(out)
        if on_device:
            with maybe_span(tr, "gradient.scatter"):
                return _field_views(grid, out, B, n_blocks)
        if words:
            with maybe_span(tr, "gradient.unpack"):
                out = host_rows(out, grid.dims[1], grid.dims[0])
        with maybe_span(tr, "gradient.scatter"):
            n = B * grid.nv
            gfs = GR.scatter_results_batch(grid, *(r[:n] for r in out), B)
            return gfs, [sum(gf.n_critical().values()) for gf in gfs]

    wrapped._jit = jfn  # compile-cache probe for the recompile tests
    wrapped.counters = dict(device_fields=int(on_device), **(counters or {}))
    return wrapped


def _field_views(grid: Grid, fields, B: int, n_blocks: int = 1):
    """Per-field views of ``fields_from_words`` output copied to the
    host: each array's first ``B * sid_space(k)`` elements are the B
    fields' flat sid arrays back to back (the rest is bucket padding).
    Slab-sharded output holds ``n_blocks`` such arrays of one z-slab
    each, end to end; a field's slabs are contiguous in its sid layout,
    so with one field and no padding every view is still a view."""
    pair_up, pair_down, crit, n_crit = fields

    def cut(arrays):
        out = {}
        for k, a in arrays.items():
            n = grid.sid_space(k) // n_blocks
            a = a.reshape(n_blocks, -1)[:, :B * n].reshape(n_blocks, B, n)
            out[k] = a.swapaxes(0, 1).reshape(B, -1)
        return out

    up, down, cr = cut(pair_up), cut(pair_down), cut(crit)
    gfs = [GradientField(grid, {k: a[b] for k, a in up.items()},
                         {k: a[b] for k, a in down.items()},
                         {k: a[b] for k, a in cr.items()})
           for b in range(B)]
    return gfs, [int(c) for c in n_crit[:B]]


def _make_kernel_gradient(kernel: str) -> Callable:
    def _gradient(grid: Grid, order, *, n_blocks: int = 1) -> GradientField:
        return GR.compute_gradient(grid, order, backend=kernel)
    return _gradient


# --------------------------------------------------------------------------
# shardmap — device-level z-slab front-end (mesh ring + halo exchange)
# --------------------------------------------------------------------------

def _shardmap_rows_fn(grid: Grid, n_blocks: int) -> Callable:
    """orders (B, nv) -> the batch's :class:`GradientField`s and critical
    counts, with the grid split into ``n_blocks`` z-slabs over a ring of
    devices: one ``jax.jit(shard_map(...))`` per ``(dims, n_blocks)``,
    bound through the plan cache like ``_rows_fn``.

    The host casts the ranks to int32 and puts each slab on its device.
    Each device exchanges its boundary rank planes with its ring
    neighbours, runs the fused kernel on its halo-extended slab and
    builds the fields of the sids based in its slab, the rows of its
    last plane's simplices taken from the words of the next slab's
    first plane (``shardmap_pipeline.slab_fields``).  The fields come
    back sharded on the slab axis and the host cuts per-field views, as
    on one chip.  A grid whose sids need int64 (``device_fields_fit``)
    gathers the slabs' words and scatters them on the host instead.
    Counters: ``n_blocks`` and ``halo_bytes``, the bytes a device sends
    per field (two rank planes, and the two word planes of the ghost
    plane)."""
    import jax
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.distributed.shardmap_pipeline import slab_program
    from repro.kernels.lower_star import WORDS, device_fields_fit

    devices = jax.devices()
    if n_blocks > len(devices):
        raise ValueError(
            f"shardmap backend needs {n_blocks} devices, have "
            f"{len(devices)} (set XLA_FLAGS="
            "--xla_force_host_platform_device_count=N)")
    if grid.nv >= 2 ** 31:
        raise ValueError(f"the fused kernel takes int32 ranks; {grid.dims} "
                         f"has {grid.nv} vertices")
    on_device = device_fields_fit(grid)
    mesh = jax.make_mesh((n_blocks,), ("blocks",)) \
        if n_blocks == len(devices) \
        else Mesh(np.asarray(devices[:n_blocks]), ("blocks",))
    jfn = slab_program(grid, mesh, fields=on_device)
    sharding = NamedSharding(mesh, P(None, "blocks"))

    def put(orders):  # int32 ranks, each device's slab on the device
        return jax.device_put(orders.astype(np.int32, copy=False),
                              sharding)

    planes = 2 + (2 * WORDS if on_device else 0)
    halo = 0 if n_blocks == 1 else planes * grid.dims[0] * grid.dims[1] * 4
    return _rows_program(grid, jfn, put, on_device=on_device, words=True,
                         n_blocks=n_blocks,
                         counters=dict(n_blocks=n_blocks, halo_bytes=halo))


def _gradient_shardmap(grid: Grid, order, *,
                       n_blocks: int = 1) -> GradientField:
    """One field through the cached shardmap rows program."""
    from .plan import default_plan_cache
    prog = default_plan_cache().get_or_build(
        (grid.dims, "shardmap", n_blocks),
        lambda: _shardmap_rows_fn(grid, n_blocks))
    gfs, _ = prog(np.asarray(order).reshape(1, -1))
    return gfs[0]


# --------------------------------------------------------------------------
# registration
# --------------------------------------------------------------------------

register_backend(Backend(
    name="np", gradient=_gradient_np,
    caps=BackendCaps(),
    description="literal Robins ProcessLowerStars (heapq reference)"))

register_backend(Backend(
    name="jax", gradient=_make_kernel_gradient("jax"),
    caps=BackendCaps(jittable=True, batched=True, streamed=True),
    description="branchless masked-recomputation form, jit-compiled",
    batched_rows=lambda grid, n_blocks=1: _rows_fn(grid, "jax")))

register_backend(Backend(
    name="pallas", gradient=_make_kernel_gradient("pallas"),
    caps=BackendCaps(jittable=True, batched=True, fused=True),
    description="fused Pallas lower-star kernel (Mosaic on a TPU)",
    batched_rows=lambda grid, n_blocks=1: _rows_fn(grid, "pallas")))

register_backend(Backend(
    name="pallas_prepass", gradient=_make_kernel_gradient("pallas_prepass"),
    caps=BackendCaps(jittable=True, batched=True),
    description="im2col pre-pass + vertex-tiled Pallas kernel (fallback)",
    batched_rows=lambda grid, n_blocks=1: _rows_fn(grid,
                                                   "pallas_prepass")))

register_backend(Backend(
    name="shardmap", gradient=_gradient_shardmap,
    caps=BackendCaps(jittable=True, sharded=True, batched=True, fused=True),
    description="shard_map z-slab front-end: ppermute halo exchange, the "
                "fused kernel and the fields on each device",
    batched_rows=_shardmap_rows_fn))
