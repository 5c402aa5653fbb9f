"""The near-square 2-D torus: each process's four neighbours up, down, left
and right on a ``gh x gw`` grid, numbered row-major.

Ducts are kept by receiver, one per neighbour in ascending order of the
neighbour's number. The canonical id of the directed edge ``s -> d``
enumerates edges source by source, each source's receivers in ascending
order: ``4 s + `` the rank of ``d`` among ``s``'s neighbours.

What a duct needs from its sender comes by rolling the grid toward the
sender's direction, with no gather.
"""
import math

import jax.numpy as jnp
import numpy as np

# neighbour directions, and each one's opposite: the direction in which a
# neighbour sees the process
UP, DOWN, LEFT, RIGHT = range(4)
REVERSE = (DOWN, UP, RIGHT, LEFT)


def grid(n: int):
    """(rows, columns) of the most nearly square grid of ``n``."""
    a = int(math.sqrt(n))
    while n % a:
        a -= 1
    return a, n // a


def tables(n: int) -> dict:
    """(n, 4) tables: ``src`` the sender of each duct, ``rev`` the flat
    index ``4 s + j'`` of the duct back from the receiver into the sender,
    ``eid`` the canonical id of the duct's edge, ``slot_dir`` the
    direction of each duct's sender and ``dir_slot`` the duct fed from each
    direction."""
    gh, gw = grid(n)
    if gh < 3 or gw < 3:
        raise ValueError(f"torus of {n} processes is {gh}x{gw}; both sides "
                         "must be at least 3 for four distinct neighbours")
    r, c = np.divmod(np.arange(n), gw)
    by_dir = np.stack([((r - 1) % gh) * gw + c, ((r + 1) % gh) * gw + c,
                       r * gw + (c - 1) % gw, r * gw + (c + 1) % gw], axis=1)
    slot_dir = np.argsort(by_dir, axis=1, kind="stable")
    src = np.take_along_axis(by_dir, slot_dir, axis=1)
    # rank of each receiver among its sender's neighbours
    rank = np.argmax(src[src] == np.arange(n)[:, None, None], axis=2)
    return dict(src=src, rev=4 * src + rank, eid=4 * src + rank,
                slot_dir=slot_dir, dir_slot=np.argsort(slot_dir, axis=1))


def _toward(x, k):
    """Each process's neighbour's value of ``x`` in direction ``k``."""
    g = x.reshape(grid(x.shape[0]) + x.shape[1:])
    shift, axis = ((1, 0), (-1, 0), (1, 1), (-1, 1))[k]
    return jnp.roll(g, shift, axis).reshape(x.shape)


def from_sender(tabs, x):
    """(n, ...) per process -> (n, 4, ...): each duct's sender's value."""
    n, slot_dir = x.shape[0], tabs["slot_dir"]
    out = jnp.zeros((n, 4) + x.shape[1:], x.dtype)
    for k in range(4):
        hit = (slot_dir == k).reshape((n, 4) + (1,) * (x.ndim - 1))
        out = jnp.where(hit, _toward(x, k)[:, None], out)
    return out


def of_reverse(tabs, x):
    """(n, 4) per duct -> (n, 4): the value of each duct's reverse duct,
    the one from its receiver back into its sender."""
    slot_dir, dir_slot = tabs["slot_dir"], tabs["dir_slot"]
    by_dir = jnp.stack([
        jnp.sum(jnp.where(dir_slot[:, k, None] == jnp.arange(4), x, 0),
                axis=1) for k in range(4)], axis=1)
    out = jnp.zeros_like(x)
    for k in range(4):
        out = jnp.where(slot_dir == k,
                        _toward(by_dir[:, REVERSE[k]], k)[:, None], out)
    return out
