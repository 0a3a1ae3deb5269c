"""The plain reference against the program's boundary-matrix oracle and
pipeline, and the control (bfloat16 fields) against the reference."""

from __future__ import annotations

import numpy as np
import pytest

from bench import compare, fields, reference

GRIDS = [(4, 4, 4), (5, 4, 3), (6, 6, 6), (7, 5, 6), (3, 9, 2)]


def _program_points(diagram):
    return compare.program_points(diagram)


@pytest.mark.parametrize("dims", GRIDS)
@pytest.mark.parametrize("family", ["random", "wavelet-seeded"])
def test_reference_matches_reduction_oracle(dims, family):
    from repro.core.dms import oracle_to_diagram
    from repro.core.grid import Grid
    from repro.core.reduction import compute_oracle
    for i in range(2):
        f = fields.make(family, dims, 11, i)
        g = Grid.of(*dims)
        orc = oracle_to_diagram(compute_oracle(g, f), g)
        ref = reference.persistence(f, dims)
        assert compare.mismatch(ref, _program_points(orc)) == 0


def test_reference_matches_pipeline_with_ties():
    """Ties in the field are broken by vertex id on both sides."""
    from repro.core.grid import Grid
    from repro.pipeline import PersistencePipeline, TopoRequest
    dims = (9, 7, 8)
    f = np.round(fields.make("random", dims, 3, 0), 1)
    res = PersistencePipeline(backend="np").run(
        TopoRequest(field=f, grid=Grid.of(*dims)))
    ref = reference.persistence(f, dims)
    assert len(ref["pairs"][1]) > 0
    assert compare.mismatch(ref, _program_points(res.diagram)) == 0


def test_essential_classes_of_a_cube():
    ref = reference.persistence(fields.make("random", (5, 5, 5), 1, 0),
                                (5, 5, 5))
    assert ref["essential"][0].tolist() == [0]
    assert all(len(ref["essential"][p]) == 0 for p in (1, 2, 3))


@pytest.mark.parametrize("family,dims", [("wavelet-seeded", (16, 16, 16)),
                                         ("random", (10, 10, 10))])
def test_control_fails_the_comparison(family, dims):
    """The control (reference on the bfloat16-rounded field) must read
    as not correct against the float32 reference."""
    for i in range(3):
        f = fields.make(family, dims, 2 ** 31 + 9, i)
        assert compare.mismatch(compare.control_points(f, dims),
                                compare.reference_points(f, dims)) > 0


def test_mismatch_counts_multisets():
    a = {"pairs": {0: np.array([[1, 5], [1, 5]])}, "essential": {0: [0]}}
    b = {"pairs": {0: np.array([[1, 5]])}, "essential": {0: [0]}}
    c = {"pairs": {0: np.array([[1, 5]])}, "essential": {0: [0, 3]}}
    assert compare.mismatch(a, a) == 0
    assert compare.mismatch(a, b) == 1
    assert compare.mismatch(b, c) == 1


def test_fields_depend_on_seed_and_index_only():
    a = fields.make("wavelet-seeded", (5, 6, 7), 2 ** 33 + 1, 4)
    b = fields.make("wavelet-seeded", (5, 6, 7), 2 ** 33 + 1, 4)
    c = fields.make("wavelet-seeded", (5, 6, 7), 2 ** 33 + 1, 5)
    assert a.dtype == np.float32 and a.shape == (5 * 6 * 7,)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    with pytest.raises(FileNotFoundError):
        fields.make("nope", (2, 2, 2), 0, 0)


def _value_pairs(field, points):
    v = np.sort(field)
    return {p: sorted(map(tuple, v[np.asarray(points["pairs"][p], np.int64)
                                   .reshape(-1, 2)].tolist()))
            for p in range(4)}


def test_random_fields_are_one_draw_in_another_layout():
    """Every seed gets the same draw for field i, in a layout that keeps
    the triangulation: the same values, the same diagram in value
    space, and more than one layout over a few seeds."""
    dims = (6, 6, 6)
    fs = [fields.make("random", dims, s, 3) for s in range(2 ** 40, 2 ** 40 + 8)]
    assert len({f.tobytes() for f in fs}) > 1
    assert all(np.array_equal(np.sort(f), np.sort(fs[0])) for f in fs)
    want = _value_pairs(fs[0], reference.persistence(fs[0], dims))
    for f in fs[1:]:
        assert _value_pairs(f, reference.persistence(f, dims)) == want
    assert not np.array_equal(fields.make("random", dims, 5, 3),
                              fields.make("random", dims, 5, 4))
