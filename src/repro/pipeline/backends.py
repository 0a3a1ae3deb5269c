"""Backend registry: name -> gradient implementation + capability flags.

Replaces the string-typed ``gradient_backend`` if/else ladders that used
to live in ``compute_dms`` / ``compute_ddms_sim`` / ``kernels.ops``.  A
backend bundles:

- ``gradient(grid, order, *, n_blocks=1)`` -> :class:`GradientField`;
- an optional *batched rows* program ``batched_rows(grid)`` returning a
  compiled ``orders (B, nv) -> packed rows`` function; ``Plan.compile``
  binds it through the shared :class:`~repro.pipeline.plan.PlanCache`
  (one compile per ``(dims, backend, n_blocks)``) and
  ``PersistencePipeline.run_batch`` uses it to amortize the
  stencil-gather pre-pass over a batch of same-shape requests;
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
- ``shardmap``       — the device-level z-slab front-end: ``shard_map``
  over a mesh ring with one-plane ``ppermute`` halo exchange of ranks,
  the same program ``repro.distributed.shardmap_pipeline`` runs at
  scale.

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
    # optional: grid -> compiled fn(orders (B, nv) int64) -> packed rows
    batched_rows: Optional[Callable[[Grid], Callable]] = None


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
    """orders (B, nv) -> packed rows over the flattened batch.

    The stencil gather and the per-vertex pairing are both vertex-local,
    so a batch of B same-shape fields is just a (B*nv)-vertex problem —
    one compiled program, one dispatch.  The whole device program is
    jitted for every kernel (the fused kernel's words are unpacked on the
    host after it), and the batch dimension is bucket-padded with inert
    all(-1) fields so nearby batch sizes share one compiled program.
    """
    import jax
    import jax.numpy as jnp
    from repro.kernels import ref as REF
    from repro.kernels.lower_star import (fused_words, host_rows,
                                          lower_star_gradient_pallas)

    def fn(orders):  # (Bp, nv) rank fields
        if kernel == "pallas":
            # fused path: gather happens inside the kernel, the batch is a
            # leading grid dimension — no (B*nv, 27) tensor materializes;
            # the packed words are unpacked on the host
            return fused_words(grid, orders)
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

    jfn = jax.jit(fn)

    def wrapped(orders):
        orders = jnp.asarray(orders)
        B = orders.shape[0]
        Bp = _bucket_batch(B)
        if Bp != B:
            # all(-1) pad fields: every simplex fails the lower-star test,
            # so the padded lanes retire after one loop iteration
            pad = jnp.full((Bp - B, orders.shape[1]), -1, orders.dtype)
            orders = jnp.concatenate([orders, pad])
        rows = jfn(orders)
        if kernel == "pallas":
            rows = host_rows(rows, grid.dims[1], grid.dims[0])
        n = B * grid.nv
        return tuple(r[:n] for r in rows)

    wrapped._jit = jfn  # compile-cache probe for the recompile tests
    return wrapped


def _scatter_batch(grid: Grid, rows, B: int, offsets=None):
    """Split flattened-batch packed rows back into B GradientFields.

    Fully vectorized: one flat index-arithmetic scatter over all dims and
    all batch elements (see ``GR.scatter_results_batch``)."""
    status, partner, vstat, vpart = (np.asarray(r) for r in rows)
    return GR.scatter_results_batch(grid, status, partner, vstat, vpart,
                                    B, offsets=offsets)


def _make_kernel_gradient(kernel: str) -> Callable:
    def _gradient(grid: Grid, order, *, n_blocks: int = 1) -> GradientField:
        return GR.compute_gradient(grid, order, backend=kernel)
    return _gradient


# --------------------------------------------------------------------------
# shardmap — device-level z-slab front-end (mesh ring + halo exchange)
# --------------------------------------------------------------------------

def _gradient_shardmap(grid: Grid, order, *,
                       n_blocks: int = 1) -> GradientField:
    """Lower-star gradient under ``shard_map``: each device owns a z-slab,
    exchanges its boundary rank planes with ring neighbors (``ppermute``),
    and runs the kernel on its own vertices — the gradient step of
    ``repro.distributed.shardmap_pipeline.front_device_fn``.  The per-slab
    kernel is the compiled fused Pallas kernel on a TPU and the XLA
    ``jax`` kernel elsewhere."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.distributed.shardmap_pipeline import (FrontConfig,
                                                     halo_gradient)
    from repro.kernels.lower_star import interpret_mode

    n_dev = len(jax.devices())
    if n_blocks > n_dev:
        raise ValueError(
            f"shardmap backend needs {n_blocks} devices, have {n_dev} "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    cfg = FrontConfig(grid.dims, n_blocks,
                      gradient_backend="jax" if interpret_mode() else "fused")
    cfg.nz_local  # eager divisibility check
    mesh = jax.make_mesh((n_blocks,), ("blocks",))

    def dev_fn(o_slab):  # (nv_local,) int64 ranks of my slab
        _, rows = halo_gradient(cfg, o_slab)
        return rows

    fn = jax.shard_map(dev_fn, mesh=mesh, in_specs=P("blocks"),
                       out_specs=P("blocks"), check_vma=False)
    o = jnp.asarray(np.asarray(order).reshape(-1), jnp.int64)
    status, partner, vstat, vpart = jax.jit(fn)(o)
    return GR._scatter_results(grid, np.asarray(status), np.asarray(partner),
                               np.asarray(vstat), np.asarray(vpart))


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
    batched_rows=lambda grid: _rows_fn(grid, "jax")))

register_backend(Backend(
    name="pallas", gradient=_make_kernel_gradient("pallas"),
    caps=BackendCaps(jittable=True, batched=True, fused=True),
    description="fused Pallas lower-star kernel (Mosaic on a TPU)",
    batched_rows=lambda grid: _rows_fn(grid, "pallas")))

register_backend(Backend(
    name="pallas_prepass", gradient=_make_kernel_gradient("pallas_prepass"),
    caps=BackendCaps(jittable=True, batched=True),
    description="im2col pre-pass + vertex-tiled Pallas kernel (fallback)",
    batched_rows=lambda grid: _rows_fn(grid, "pallas_prepass")))

register_backend(Backend(
    name="shardmap", gradient=_gradient_shardmap,
    caps=BackendCaps(jittable=True, sharded=True),
    description="shard_map z-slab front-end with ppermute halo exchange"))
