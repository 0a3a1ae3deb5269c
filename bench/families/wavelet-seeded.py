"""The repo's ``wavelet`` field (frequencies 12/10/8 under a Gaussian
envelope, DDMS arXiv:2505.21266 Sec. VI-A) with one phase per axis drawn
from the seed: every seed is a different field with the same number of
features."""

import numpy as np

from bench.fields import rng


def make(dims, seed, i):
    nx, ny, nz = dims
    px, py, pz = rng(seed, i).uniform(0.0, 2 * np.pi, 3)
    x = np.arange(nx) / max(nx - 1, 1)
    y = np.arange(ny) / max(ny - 1, 1)
    z = np.arange(nz) / max(nz - 1, 1)
    # separable: cos(12x+px) cos(10y+py) cos(8z+pz) exp(-2 |p - c|^2)
    fx = np.cos(12 * x + px) * np.exp(-2 * (x - .5) ** 2)
    fy = np.cos(10 * y + py) * np.exp(-2 * (y - .5) ** 2)
    fz = np.cos(8 * z + pz) * np.exp(-2 * (z - .5) ** 2)
    f = fz[:, None, None] * fy[None, :, None] * fx[None, None, :]
    return f.astype(np.float32).reshape(-1)
