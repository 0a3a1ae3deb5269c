"""Pallas kernel validation: interpret-mode vs the pure-jnp ref oracle.

Sweeps grid shapes, dtypes and tile sizes per the kernel contract.  The ref
oracle itself is validated against the literal priority-queue Robins
implementation in test_gradient.py, closing the chain of trust.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.grid import Grid, vertex_order
from repro.kernels import ops
from repro.kernels.lower_star import lower_star_gradient_pallas
from repro.kernels.ref import lower_star_gradient_jnp


SHAPES = [(16,), (7, 5), (9, 4), (4, 4, 4), (5, 3, 2), (3, 6, 4)]


@pytest.mark.parametrize("dims", SHAPES)
@pytest.mark.parametrize("dtype", [np.int32, np.int64])
def test_pallas_matches_ref(dims, dtype):
    g = Grid.of(*dims)
    rng = np.random.default_rng(hash(dims) % 2**31)
    f = rng.standard_normal(g.nv)
    order = vertex_order(f).astype(dtype)
    nbrs = ops.neighbor_orders_jnp(g, jnp.asarray(order))
    ref = lower_star_gradient_jnp(nbrs, jnp.asarray(order))
    # int64 ranks reach the int32 kernel through the rank bound
    got = lower_star_gradient_pallas(nbrs, jnp.asarray(order), tile=128,
                                     rank_bound=g.nv)
    for a, b, name in zip(ref, got, ["status", "partner", "vstat", "vpart"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)


@pytest.mark.parametrize("tile", [64, 128, 256])
def test_pallas_tile_sweep(tile):
    g = Grid.of(6, 5, 3)
    rng = np.random.default_rng(tile)
    f = rng.standard_normal(g.nv)
    order = vertex_order(f)
    nbrs = ops.neighbor_orders_jnp(g, jnp.asarray(order))
    ref = lower_star_gradient_jnp(nbrs, jnp.asarray(order))
    got = lower_star_gradient_pallas(nbrs, jnp.asarray(order), tile=tile,
                                     rank_bound=g.nv)
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("kernel", ["prepass", "fused"])
def test_pallas_refuses_unbounded_int64(kernel):
    """64-bit keys with no rank bound never reach a Pallas kernel."""
    from repro.kernels.lower_star import fused_rows_from_halo_volume
    g = Grid.of(4, 3, 2)
    order = jnp.asarray(vertex_order(np.arange(g.nv, dtype=float)),
                        jnp.int64)
    with pytest.raises(TypeError, match="int32"):
        if kernel == "prepass":
            nbrs = ops.neighbor_orders_jnp(g, order)
            lower_star_gradient_pallas(nbrs, order)
        else:
            fused_rows_from_halo_volume(order.reshape(2, 3, 4))


def test_interpret_mode_follows_platform():
    """Interpret mode is the platform's call: on the CPU the kernels are
    interpreted and the pipeline default stays the np reference."""
    import jax
    from repro.kernels.lower_star import interpret_mode
    from repro.pipeline import PersistencePipeline
    from repro.pipeline.backends import default_backend_name
    assert jax.default_backend() == "cpu"
    assert interpret_mode() is True
    assert default_backend_name() == "np"
    assert PersistencePipeline().backend.name == "np"


def test_pallas_backend_end_to_end():
    """Full gradient through the pallas backend equals the literal ref."""
    from repro.core.gradient import compute_gradient, compute_gradient_np
    g = Grid.of(5, 4, 3)
    rng = np.random.default_rng(0)
    f = rng.standard_normal(g.nv)
    order = vertex_order(f)
    a = compute_gradient_np(g, order)
    b = compute_gradient(g, order, backend="pallas")
    for k in a.pair_up:
        assert np.array_equal(a.pair_up[k], b.pair_up[k])
    for k in a.crit:
        assert np.array_equal(a.crit[k], b.crit[k])
