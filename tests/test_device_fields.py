"""Gradient fields built on the device from the fused kernel's words
(``kernels.lower_star.fields_from_words``): parity with the host scatter
(``core.gradient.scatter_results_batch``) on the rows of the same words,
the exactness of the MXU interleave, the pipeline's ``device_fields``
counter, and the absence of scatter and gather ops in the program."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gradient as GR
from repro.core.diagram import diff_report, same_offdiagonal
from repro.core.grid import Grid, vertex_order
from repro.fields.generators import make_field
from repro.kernels.lower_star import (_interleave, device_fields_fit,
                                      fields_from_words, fused_words,
                                      host_rows)
from repro.pipeline import PersistencePipeline, TopoRequest
from repro.pipeline.backends import _bucket_batch, _field_views


def _orders(dims, B, family, seed=0):
    """(B, nv) rank fields, padded with all(-1) fields to the pipeline's
    batch bucket."""
    g = Grid.of(*dims)
    o = [np.asarray(vertex_order(make_field(family, dims, seed + b)
                                 .reshape(-1))) for b in range(B)]
    o += [np.full(g.nv, -1, np.int64)] * (_bucket_batch(B) - B)
    return g, np.stack(o)


def _assert_fields_equal(dev, host, tag):
    for name in ("pair_up", "pair_down", "crit"):
        a, b = getattr(dev, name), getattr(host, name)
        assert set(a) == set(b), f"{tag} {name} dims"
        for k in b:
            assert a[k].dtype == b[k].dtype, f"{tag} {name}[{k}] dtype"
            np.testing.assert_array_equal(a[k], b[k],
                                          err_msg=f"{tag} {name}[{k}]")


# 3-D grids whose y / x are not multiples of 8 / 128, 2-D (nz = 1, and a
# y-z slab with nx = 1) and 1-D grids
PARITY = [((5, 7, 9), 1, "random"), ((5, 7, 9), 3, "random"),
          ((9, 13, 17), 1, "random"), ((9, 13, 17), 3, "wavelet"),
          ((9, 13, 17), 5, "random"), ((12, 10, 6), 3, "wavelet"),
          ((9, 4), 1, "random"), ((9, 4), 3, "wavelet"),
          ((7, 5, 1), 3, "random"), ((1, 5, 6), 3, "random"),
          ((16,), 3, "random")]


@pytest.mark.parametrize("dims,B,family", PARITY,
                         ids=[f"{'x'.join(map(str, d))}-B{b}-{f}"
                              for d, b, f in PARITY])
def test_fields_from_words_match_host_scatter(dims, B, family):
    g, orders = _orders(dims, B, family)
    words = fused_words(g, orders)
    dev = jax.jit(lambda w: fields_from_words(w, g))(words)
    dev_gfs, dev_counts = _field_views(g, jax.device_get(dev), B)
    rows = host_rows([np.asarray(w) for w in words], g.dims[1], g.dims[0])
    host_gfs = GR.scatter_results_batch(
        g, *(r[:B * g.nv] for r in rows), B)
    for b in range(B):
        _assert_fields_equal(dev_gfs[b], host_gfs[b], f"{dims} field {b}")
        assert dev_counts[b] == sum(host_gfs[b].n_critical().values())
    assert np.asarray(dev[3]).dtype == np.int32


@pytest.mark.parametrize("T", [6, 7, 12])
def test_interleave_is_exact_on_the_int32_range(T):
    """Every value a pair array can hold, -1 up to the largest sid
    2**31 - 2, and the flags come through the bfloat16 MXU interleave
    unchanged."""
    rng = np.random.default_rng(T)
    shape = (2, 3, 5, 7)
    ints = [rng.integers(-1, 2 ** 31 - 1, size=shape, dtype=np.int32)
            for _ in range(T)]
    ints[0].flat[:3] = (-1, 2 ** 31 - 2, 2 ** 24 + 1)
    flags = [rng.random(shape) < 0.5 for _ in range(T)]
    for planes in (ints, flags):
        want = np.stack(planes, axis=-1).reshape(-1)
        got = np.asarray(_interleave([jnp.asarray(p) for p in planes]))
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(got.reshape(-1)[:want.size], want)


def test_fields_program_has_no_scatter_or_gather():
    """The device fields are shifts, selects and a matrix product: the
    lowered program holds no scatter and no gather."""
    g = Grid.of(9, 13, 17)
    words = jax.eval_shape(lambda o: fused_words(g, o),
                           jax.ShapeDtypeStruct((3, g.nv), jnp.int32))
    text = jax.jit(lambda w: fields_from_words(w, g)).lower(words).as_text()
    assert "scatter" not in text and "gather" not in text


def test_device_fields_fit_follows_the_sid_space():
    assert device_fields_fit(Grid.of(128, 128, 128))
    assert device_fields_fit(Grid.of(512, 512, 512))
    assert not device_fields_fit(Grid.of(1024, 1024, 256))  # 12·nv >= 2**31


@pytest.mark.parametrize("backend,expect", [("pallas", 1), ("jax", 0),
                                            ("pallas_prepass", 0)])
def test_device_fields_counter(backend, expect):
    g = Grid.of(4, 5, 6)
    res = PersistencePipeline(backend=backend).run(
        TopoRequest(field=make_field("random", g.dims, seed=3), grid=g))
    assert res.stats["device_fields"] == expect


def test_pallas_pipeline_matches_np_on_12_cubed():
    """The default pipeline on the fused kernel, fields built on the
    device, gives the np backend's diagram on a 12^3 field."""
    g = Grid.of(12, 12, 12)
    f = make_field("random", g.dims, seed=7)
    a = PersistencePipeline(backend="pallas").run(TopoRequest(field=f,
                                                              grid=g))
    b = PersistencePipeline(backend="np").run(TopoRequest(field=f, grid=g))
    assert a.stats["device_fields"] == 1
    assert same_offdiagonal(a.diagram, b.diagram), \
        diff_report(a.diagram, b.diagram, ("pallas", "np"))
    for p in range(g.dim + 1):
        assert np.array_equal(a.diagram.essential_orders(p),
                              b.diagram.essential_orders(p))
