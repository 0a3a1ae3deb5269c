"""jit'd wrappers around the gradient kernels.

``backend``:
- ``"jax"``            — pure-jnp oracle (ref.py); the 27-point stencil
  gather and the pairing loop compile as ONE jit program (XLA fuses the
  gather, so no (nv, 27) tensor round-trips through HBM), with int32
  ranks and packed int64 keys whenever the grid allows.
- ``"pallas"``         — the fused Pallas kernel: the gather happens
  inside the kernel from the z-planes and y-tiles around each tile
  (compiled with Mosaic on a TPU, interpreted elsewhere).
- ``"pallas_prepass"`` — the original im2col pre-pass + vertex-tiled
  Pallas kernel, kept as a fallback and oracle cross-check.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import gradient as GR
from repro.core.grid import Grid
from . import ref as REF
from .lower_star import (fused_lower_star_gradient_pallas,
                         lower_star_gradient_pallas)

BACKENDS = ("jax", "pallas", "pallas_prepass")


def neighbor_orders_jnp(grid: Grid, order):
    return GR.neighbor_orders(grid, jnp.asarray(order), xp=jnp)


@functools.partial(jax.jit, static_argnums=(0,))
def _jax_full_rows(grid: Grid, order):
    """Gather + pairing fused by XLA in one compiled program."""
    o = order.astype(jnp.int32) if grid.nv < 2 ** 31 else order
    nbrs = GR.neighbor_orders(grid, o, xp=jnp)
    return REF.lower_star_gradient_jnp(nbrs, o, rank_bound=grid.nv)


def jax_rows_cache_size() -> int:
    """Compiled-program count of the jnp rows path (recompile probe)."""
    return _jax_full_rows._cache_size()


def gradient_hbm_model(dims, rank_bytes=None):
    """Modeled HBM gather traffic in bytes/vertex for each front-end path.

    Rank width follows the code: int32 when ``nv < 2**31`` (always for
    our grids; the pre-PR int64 pre-pass was 216+216+8 = 440 B/vertex).

    - ``prepass``: the im2col pre-pass materializes a (nv, 27) rank
      tensor (27*w B/vertex written), the kernel reads it back, and the
      order field is read once: 27*w + 27*w + w (= 220 B/vertex at w=4).
    - ``fused``: each (8, x) tile reads the 3 z-planes x 3 y-tiles
      around it, x padded to the 128-lane tile and y to 8 rows:
      9*w*pad B/vertex (36 B/vertex at w=4 when x is a multiple of 128).
    """
    nx, ny, nz = dims
    if rank_bytes is None:
        rank_bytes = 4.0 if nx * ny * nz < 2 ** 31 else 8.0
    w = float(rank_bytes)
    pad = (-(-nx // 128) * 128 / nx) * (-(-ny // 8) * 8 / ny)
    return {"prepass": 27 * w + 27 * w + w, "fused": 9 * w * pad}


@jax.jit
def _halo_rows_jax(ext):
    """Gather + pairing for the owned slab of a halo-extended key volume.

    ext: (nzl+2, ny, nx) order/key volume whose first/last z-planes are
    ghosts (-1 at the global boundary).  Jitted per shape; rank-free int64
    keys compare exactly like dense ranks, so ``rank_bound=None``."""
    nzh, ny, nx = ext.shape
    eg = Grid.of(nx, ny, nzh)
    nbrs = GR.neighbor_orders(eg, ext.reshape(-1), xp=jnp)
    nbrs = nbrs.reshape(nzh, ny * nx, 27)[1:-1].reshape(-1, 27)
    ov = ext[1:-1].reshape(-1)
    return REF.lower_star_gradient_jnp(nbrs, ov, rank_bound=None)


def lower_star_rows_halo(ext):
    """Packed gradient rows for one halo-extended z-slab (streaming entry).

    The out-of-core scheduler (``repro.stream``) calls this once per
    chunk: ``ext`` is the chunk's (nzl+2, ny, nx) packed-key volume with
    exchanged/loaded ghost planes (-1 outside the grid).  Keys are
    *rank-free* — full-width int64, which the int32 Pallas kernels do not
    take — so the XLA ``jax`` kernel is the one streaming kernel."""
    return _halo_rows_jax(jnp.asarray(ext))


def lower_star_gradient(grid: Grid, order, backend: str = "jax",
                        tile: int = 1024):
    """Compute per-vertex packed gradient rows for the whole grid."""
    order = jnp.asarray(order)
    if backend == "jax":
        return _jax_full_rows(grid, order)
    if backend == "pallas":
        return fused_lower_star_gradient_pallas(grid, order)
    if backend == "pallas_prepass":
        nbrs = neighbor_orders_jnp(grid, order)
        return lower_star_gradient_pallas(nbrs, order, tile=tile,
                                          rank_bound=grid.nv)
    raise ValueError(f"unknown backend {backend!r}; expected {BACKENDS}")
