"""Graph colouring by CFL (Leith et al. 2012): each process holds an
``H x W`` block of the global grid's nodes, its four halos are the
neighbouring blocks' edge rows, and a node in conflict with any neighbour
decays its colour's probability by ``b``, spreads that mass over the other
colours and resamples; others keep their colour with probability one.
"""
import dataclasses
import math
import multiprocessing
import os
from concurrent.futures import ProcessPoolExecutor

import jax.numpy as jnp
import numpy as np

from reference import STREAM_APP, hash_uniform

#: populations from which the initial colours are drawn in parallel
PARALLEL_FROM = 4096


def block_shape(simels: int):
    a = int(math.sqrt(simels))
    while simels % a:
        a -= 1
    return a, simels // a


def initial_colours(seed: int, n: int, H: int, W: int, n_colors: int):
    """Each process's block of colours, drawn from ``default_rng((seed, p))``.
    A large population is drawn by a few worker processes, which import
    numpy alone and end before this returns."""
    from colours import block
    workers = min(8, os.cpu_count() or 1) if n >= PARALLEL_FROM else 1
    if workers == 1:
        return block(seed, 0, n, H, W, n_colors)
    cuts = np.linspace(0, n, workers + 1).astype(int)
    ctx = multiprocessing.get_context("spawn")
    with ProcessPoolExecutor(workers, mp_context=ctx) as pool:
        parts = [pool.submit(block, seed, a, b, H, W, n_colors)
                 for a, b in zip(cuts[:-1], cuts[1:])]
        return np.concatenate([f.result() for f in parts])


@dataclasses.dataclass(frozen=True)
class App:
    H: int
    W: int
    n_colors: int
    b: float

    @classmethod
    def from_config(cls, c: dict) -> "App":
        H, W = block_shape(c["simels_per_process"])
        return cls(H, W, c["n_colors"], c["b"])

    @property
    def L(self):
        """Payload words: the longer side of the block."""
        return max(self.H, self.W)

    def init(self, seed: int, n: int):
        """(state, halo) before the first window: every halo holds the
        process's own facing edge row."""
        colors = jnp.asarray(initial_colours(seed, n, self.H, self.W,
                                             self.n_colors))
        probs = jnp.full((n, self.H, self.W, self.n_colors),
                         1.0 / self.n_colors, jnp.float32)
        state = dict(colors=colors, probs=probs)
        return state, self.rows(state)

    def rows(self, state):
        """(n, 4, L) first row, last row, first column, last column,
        zero-padded to L."""
        colors, L = state["colors"], self.L
        H, W = colors.shape[1:]
        pad_w, pad_h = ((0, 0), (0, L - W)), ((0, 0), (0, L - H))
        return jnp.stack([jnp.pad(colors[:, 0, :], pad_w),
                          jnp.pad(colors[:, -1, :], pad_w),
                          jnp.pad(colors[:, :, 0], pad_h),
                          jnp.pad(colors[:, :, -1], pad_h)], axis=1)

    def step(self, state, halo, steps, seed, pids):
        """The CFL update of every block against its halos."""
        H, W, C, b = self.H, self.W, self.n_colors, self.b
        colors, probs = state["colors"], state["probs"]
        up = jnp.concatenate([halo[:, 0, :W][:, None, :], colors[:, :-1]], 1)
        down = jnp.concatenate([colors[:, 1:], halo[:, 1, :W][:, None, :]], 1)
        left = jnp.concatenate([halo[:, 2, :H][:, :, None], colors[:, :, :-1]],
                               2)
        right = jnp.concatenate([colors[:, :, 1:], halo[:, 3, :H][:, :, None]],
                                2)
        conflict = ((colors == up) | (colors == down) | (colors == left)
                    | (colors == right))
        onehot = (colors[..., None] == jnp.arange(C)).astype(jnp.float32)
        fail_p = (1 - b) * probs + b * (1 - onehot) / (C - 1)
        probs = jnp.where(conflict[..., None], fail_p, onehot)
        cell = (pids[:, None, None] * np.int32(H * W)
                + jnp.arange(H * W, dtype=jnp.int32).reshape(H, W))
        u = hash_uniform(seed, STREAM_APP, steps[:, None, None],
                         cell)[..., None]
        cdf = jnp.cumsum(probs, axis=-1)
        drawn = jnp.minimum((u > cdf).sum(-1), C - 1)
        return dict(colors=jnp.where(conflict, drawn, colors), probs=probs)

    def quality(self, state) -> float:
        """Conflicting neighbour pairs on the global grid the blocks tile."""
        colors = np.asarray(state["colors"])
        n, H, W = colors.shape
        gh, gw = block_shape(n)     # the process grid, as near square
        full = colors.reshape(gh, gw, H, W).transpose(0, 2, 1, 3).reshape(
            gh * H, gw * W)
        return float((full == np.roll(full, 1, 0)).sum()
                     + (full == np.roll(full, 1, 1)).sum())
