"""The initial colouring of a block of processes: process ``p`` draws its
``H x W`` colours from ``numpy.random.default_rng((seed, p))``. Kept apart
from the reference so that worker processes import numpy alone."""
import numpy as np


def block(seed: int, start: int, stop: int, H: int, W: int,
          n_colors: int) -> np.ndarray:
    out = np.empty((stop - start, H, W), np.int32)
    for i, p in enumerate(range(start, stop)):
        out[i] = np.random.default_rng((seed, p)).integers(
            0, n_colors, size=(H, W))
    return out
