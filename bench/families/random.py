"""I.i.d. standard normal values, the paper's worst case (DDMS
arXiv:2505.21266 Sec. VI-A, Random).

The work a random field makes varies from field to field (at 64³ the D1
stage by about a tenth), so fields drawn from the run's seed would let
the seed change the work of a window.  Field ``i`` is therefore the same
draw for every seed, laid out by one of the symmetries of the grid that
keep its Freudenthal triangulation (a permutation of axes of equal
length, with or without reversing all three), picked by ``(seed, i)``:
the diagram and the work are those of the draw, and the input differs
from seed to seed.
"""

import itertools

import numpy as np

from bench.fields import rng

DRAW_SEED = 0x5EED


def make(dims, seed, i):
    nx, ny, nz = dims
    f = rng(DRAW_SEED, i).standard_normal(nx * ny * nz) \
        .astype(np.float32).reshape(nz, ny, nx)
    perms = [p for p in itertools.permutations(range(3))
             if all(f.shape[a] == f.shape[b] for a, b in enumerate(p))]
    pick = np.random.default_rng([int(seed) % 2 ** 64, int(i), 1])
    f = f.transpose(perms[pick.integers(len(perms))])
    if pick.integers(2):
        f = f[::-1, ::-1, ::-1]
    return np.ascontiguousarray(f).reshape(-1)
