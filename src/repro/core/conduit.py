"""In-graph (SPMD) Conduit: best-effort neighbor exchange over mesh axes.

The TPU-native analogue of the paper's Inlet/Outlet ducts (DESIGN.md §2):
channels are double-buffered, so under ``BEST_EFFORT`` a fragment consumes the
values its neighbors sent on the *previous* step while the current
``ppermute`` is scheduled concurrently with compute — communication leaves the
critical path at the cost of one step of staleness, exactly the best-effort
trade.  Under ``BARRIER_EVERY_STEP`` the fresh values are consumed in-step
(BSP).  Designed for use inside ``shard_map`` bodies (see apps/graphcolor).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.modes import AsyncMode


def ring_perm(n: int, shift: int):
    return [(i, (i + shift) % n) for i in range(n)]


def ring_exchange(x, axis_name: str, shift: int = 1):
    """Rotate ``x`` around the ring: device i receives device (i - shift)'s
    value (i.e. values travel ``shift`` steps forward)."""
    n = lax.axis_size(axis_name)
    return lax.ppermute(x, axis_name, ring_perm(n, shift))


@dataclasses.dataclass(frozen=True)
class Conduit:
    """Best-effort channel over one mesh axis (ring topology).

    ``directions`` maps a name to a ring shift, e.g. {"fwd": +1, "bwd": -1}.
    State (the staleness buffers) is an ordinary pytree the caller threads
    through its step loop / scan carry.
    """

    axis_name: str
    directions: Dict[str, int]
    mode: AsyncMode = AsyncMode.BEST_EFFORT

    def init_buffers(self, example) -> Dict[str, jax.Array]:
        return {d: jnp.zeros_like(example) for d in self.directions}

    def exchange(self, value, buffers, *, flush=None) -> Tuple[dict, dict]:
        """One communication phase.

        value: the local payload to publish to every neighbor.
        buffers: previously received payloads (from ``init_buffers``/last call).
        flush: optional bool scalar — modes 1/2 consume fresh values when set.

        Returns (received, new_buffers): what this fragment should consume
        now, and the buffers to carry forward.
        """
        if self.mode == AsyncMode.NO_COMM:
            return buffers, buffers

        fresh = {d: ring_exchange(value, self.axis_name, s)
                 for d, s in self.directions.items()}

        if self.mode == AsyncMode.BARRIER_EVERY_STEP:
            return fresh, fresh
        if self.mode == AsyncMode.BEST_EFFORT:
            # consume stale, publish fresh: the permute's consumer is the
            # *next* step, so the scheduler overlaps it with this step's work
            return buffers, fresh
        # rolling / fixed barrier: stale between barriers, fresh at barriers
        assert flush is not None, "modes 1/2 need a flush predicate"
        received = jax.tree.map(
            lambda f, b: jnp.where(flush, f, b), fresh, buffers)
        return received, fresh


def torus_conduits(axis_names: Tuple[str, str], mode: AsyncMode):
    """N/S/E/W conduits for a 2-D toroidal fragment grid.

    ``received["north"]`` is the payload of the neighbor one row up
    (device i-1 along the row axis => shift +1), etc.
    """
    row = Conduit(axis_names[0], {"north": +1, "south": -1}, mode)
    col = Conduit(axis_names[1], {"west": +1, "east": -1}, mode)
    return row, col
