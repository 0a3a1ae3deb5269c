"""Fields the traffic mixes draw from, made from a seed.

Each field family is a file of its own, ``bench/families/<family>.py``,
found by the name a traffic mix gives.  Field ``i`` of a run with seed
``s`` depends on ``(s, i)`` alone.  Values are float32 in vertex order
``x + nx * (y + ny * z)``.  The families are copies kept with the
benchmark, so that the yardstick cannot move with the program.
"""

from __future__ import annotations

from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent


def rng(seed: int, i: int) -> np.random.Generator:
    """The generator of field ``i`` under run seed ``seed``."""
    return np.random.default_rng([int(seed) % 2 ** 64, int(i)])


def make(family: str, dims, seed: int, i: int, root: Path = ROOT
         ) -> np.ndarray:
    from bench import registry
    return registry.family(root, family).make(
        tuple(int(d) for d in dims), seed, i)
