"""The comparison that decides ``correct``.

Diagrams are compared in order space (birth order, death order), off the
diagonal, in every homology dimension, plus the essential classes of
every dimension: the comparison ``chip_smoke.assert_same`` makes.  The
number compared is how many points differ (multiset symmetric
difference), summed over dimensions; its limit is 0.
"""

from __future__ import annotations

from collections import Counter

import numpy as np

from . import reference

DIMS = range(4)


def program_points(diagram) -> dict:
    """Order-space points of a program ``Diagram``."""
    pairs, ess = {}, {}
    for p in DIMS:
        a = np.asarray(diagram.points_order(p), np.int64).reshape(-1, 2)
        pairs[p] = a[np.lexsort((a[:, 1], a[:, 0]))]
        ess[p] = np.sort(np.asarray(diagram.essential_orders(p), np.int64))
    return {"pairs": pairs, "essential": ess}


def mismatch(a: dict, b: dict) -> int:
    """Points (and essential classes) in one diagram and not the other."""
    n = 0
    for p in DIMS:
        n += _sym_diff(map(tuple, _rows(a["pairs"], p)),
                       map(tuple, _rows(b["pairs"], p)))
        n += _sym_diff(np.asarray(a["essential"].get(p, []), np.int64).tolist(),
                       np.asarray(b["essential"].get(p, []), np.int64).tolist())
    return n


def _rows(pairs: dict, p: int) -> list:
    return np.asarray(pairs.get(p, np.zeros((0, 2))), np.int64) \
        .reshape(-1, 2).tolist()


def _sym_diff(a, b) -> int:
    ca, cb = Counter(a), Counter(b)
    return sum(((ca - cb) + (cb - ca)).values())


def reference_points(field: np.ndarray, dims) -> dict:
    return reference.persistence(np.asarray(field, np.float32), dims)


def control_points(field: np.ndarray, dims) -> dict:
    """The control: the reference on the field rounded to bfloat16, the
    precision below the float32 the configurations state."""
    import ml_dtypes
    low = np.asarray(field, np.float32).astype(ml_dtypes.bfloat16)
    return reference.persistence(low.astype(np.float32), dims)
