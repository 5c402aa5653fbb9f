"""Shared window-phase core for the vectorized engines (DESIGN.md §11).

Both JAX engines — the single-device windowed-time engine
(``runtime/engine_jax.py``) and the mesh-sharded engine
(``runtime/engine_sharded.py``) — advance the population through the same
lockstep-window phases:

  drain      pop every duct ring's available FIFO prefix, merge the
             freshest payloads into the (n, 4, L) halos, bump the
             receiver-side QoS counters
  compute    the application's actual batched step, masked by activity
  send       best-effort push attempt per out-edge (drop iff the ring is
             full, latency stamp), sender-side QoS counters
  stage      the dense layout's eager send decision (ring writes ride
             into the next window's fused ``duct_window`` pass)
  close      QoS snapshot write (masked select), termination, barrier
             bookkeeping and the virtual-time advance

Before this module existed each engine reimplemented all of them; now the
engines are thin compositions.  What stays engine-specific is exactly the
distribution machinery: the sharded engine's mesh/shard_map plumbing,
packed-ppermute boundary exchange, and *where* its barrier-release
reductions run (a :class:`MeshRelease` over the shard axis instead of
:data:`LOCAL_RELEASE`).  The phases themselves are row-count agnostic:
the unsharded engine passes full-population tables, the sharded engine
passes its shard's sentinel-padded slices, and both trace to the same
operation sequence — which is why ``tests/test_engine_conformance.py``
can pin every registry engine to the event-engine oracle bitwise.

All stochastic draws are counter-based splitmix-style hashes (the
in-graph twin of ``runtime/faults.py``'s splitmix64 streams — same
distributions, different bit streams), keyed by *original* pid and
*canonical* edge id so trajectories are a pure function of
``(config, seed)`` regardless of layout, scheduler, or shard count.
"""
from __future__ import annotations

import math
from typing import Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.modes import AsyncMode
from repro.core.qos import QosReport
from repro.kernels.duct_exchange.ops import (
    dense_halo_select,
    dense_stage,
    duct_commit,
    duct_drain,
    duct_send,
    duct_window,
)
from repro.runtime.faults import STREAM_FLAP, STREAM_LOSS  # noqa: F401
from repro.runtime.simulator import SimResult

#: the ``jax.named_scope`` of each window phase: every operation a phase
#: emits carries the name in its ``op_name`` metadata, so a device trace
#: splits the chunk program's time by phase (the snapshot write nests
#: inside the close)
DRAIN, COMPUTE, SEND, CLOSE, SNAPSHOT, COMMIT = (
    "window.drain", "window.compute", "window.send", "window.close",
    "window.snapshot", "window.commit")


#: modes whose processes stop at a barrier and wait for a global release
BARRIER_MODES = (AsyncMode.BARRIER_EVERY_STEP, AsyncMode.ROLLING_BARRIER,
                 AsyncMode.FIXED_BARRIER)

# ---------------------------------------------------------------------------
# Counter-based RNG: splitmix-style 32-bit finalizer chains, pure functions
# of their integer keys.
# ---------------------------------------------------------------------------
_GOLDEN = np.uint32(0x9E3779B9)

# stream tags keep independent draws independent
STREAM_STEP, STREAM_STALL, STREAM_LAT, STREAM_APP, STREAM_MUT = 1, 2, 3, 4, 5


def _mix32(x: jax.Array) -> jax.Array:
    """32-bit splitmix-style finalizer (lowbias32 constants)."""
    x = (x ^ (x >> np.uint32(16))) * np.uint32(0x7FEB352D)
    x = (x ^ (x >> np.uint32(15))) * np.uint32(0x846CA68B)
    return x ^ (x >> np.uint32(16))


def hash_u32(*keys) -> jax.Array:
    """Combine integer keys (arrays broadcast) into one hashed uint32."""
    h = _GOLDEN
    for k in keys:
        k = jnp.asarray(k).astype(jnp.uint32)
        h = _mix32(h ^ (k + _GOLDEN + (h << np.uint32(6)) +
                        (h >> np.uint32(2))))
    return h


def hash_uniform(*keys) -> jax.Array:
    """Deterministic uniform in (0, 1) from integer keys."""
    h = hash_u32(*keys)
    return ((h >> np.uint32(8)).astype(jnp.float32) + 0.5) * np.float32(
        1.0 / (1 << 24))


def hash_normal(*keys) -> jax.Array:
    u1 = hash_uniform(*keys, 101)
    u2 = hash_uniform(*keys, 202)
    return jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos(2.0 * np.pi * u2)


def lognormal_factor(sigma: float, *keys) -> jax.Array:
    """Mean-one lognormal, matching faults.Jitter's parameterization."""
    if sigma <= 0:
        return jnp.ones(jnp.broadcast_shapes(
            *(jnp.shape(k) for k in keys)), jnp.float32)
    z = hash_normal(*keys)
    return jnp.exp(np.float32(-0.5 * sigma * sigma) + np.float32(sigma) * z)


# ---------------------------------------------------------------------------
# Barrier-release strategies: where the close phase's global reductions run
# ---------------------------------------------------------------------------
class LocalRelease:
    """Single-device release reductions: plain jnp reductions."""

    #: staged strategies consume reductions issued one superstep boundary
    #: earlier (see :class:`PipelinedRelease`)
    staged = False

    def all_stopped(self, x: jax.Array) -> jax.Array:
        return jnp.all(x)

    def any_waiting(self, x: jax.Array) -> jax.Array:
        return jnp.any(x)

    def max_time(self, x: jax.Array) -> jax.Array:
        return jnp.max(x)


#: the default strategy (one device holds the whole population)
LOCAL_RELEASE = LocalRelease()


class MeshRelease:
    """Cross-shard release reductions: exact psum-style pmin/pmax scalars
    over the named mesh axis, once per (super)step."""

    staged = False

    def __init__(self, axis: str):
        self.axis = axis

    def all_stopped(self, x: jax.Array) -> jax.Array:
        return jax.lax.pmin(jnp.all(x).astype(jnp.int32), self.axis) > 0

    def any_waiting(self, x: jax.Array) -> jax.Array:
        return jax.lax.pmax(jnp.any(x).astype(jnp.int32), self.axis) > 0

    def max_time(self, x: jax.Array) -> jax.Array:
        return jax.lax.pmax(jnp.max(x), self.axis)


class PipelinedRelease(MeshRelease):
    """Release strategy for the ``pipelined`` scheduler: the cross-shard
    release reductions issued at superstep boundary i are *consumed* at
    boundary i+1, so the pmin/pmax collectives never serialize against the
    boundary's own compute.

    Correctness rests on the frozen-cohort argument (DESIGN.md §12): once
    ``all_stopped`` is observed true, every live process is waiting, no
    process is active, and therefore nothing can join, leave, or advance
    the cohort before the (stale) decision is applied one boundary later.
    The release *time* — max over the frozen waiting clocks plus the
    barrier cost — is exactly what an un-staged release would compute;
    only the lockstep window it lands on moves one superstep later.
    ``close_window`` reads the carried decision from ``u["rel_ready"]`` /
    ``u["rel_t"]`` and stores fresh post-release reductions for the next
    boundary.
    """

    staged = True


class SendPhase(NamedTuple):
    """Result of one edge-major send attempt over a block of rings."""
    rings: Dict[str, jax.Array]   # q_avail / q_touch / q_size / q_pay
    accepted: jax.Array           # (rows,) bool push accepted
    sums: Optional[jax.Array]     # (n, 3) attempted/ok/dropped per process


class BucketSlab(NamedTuple):
    """Static view of one dense degree bucket's flat row slab.

    ``members is None`` marks the identity bucket: it covers every
    receiver (``nb == n_dst``, member i == receiver i), which is what
    every degree-regular topology collapses to — the per-bucket phases
    then skip all gathers/scatters and trace exactly the pre-bucketed
    receiver-major graph.  Otherwise ``members`` maps slab block index to
    receiver id; sentinel entries (value ``n_dst``) mark dead padding
    blocks whose scatters drop (the sharded engine pads shards to a
    uniform slab shape with them)."""

    start: int                       # first flat row of the slab
    nb: int                          # member blocks in the slab
    deg: int                         # padded rows per member block
    members: Optional[jax.Array]     # (nb,) receiver ids, or None


class DenseSpec(NamedTuple):
    """Static dense-layout geometry the bucketed phases iterate over."""

    n_dst: int                       # receivers covered
    n_rows: int                      # total flat rows R
    buckets: tuple                   # of BucketSlab


def make_dense_spec(plan) -> DenseSpec:
    """Build the phase-iteration spec from a ``topologies.LayoutPlan``,
    collapsing full-coverage single buckets to the identity fast path."""
    slabs = []
    for b in plan.buckets:
        nb = len(b.members)
        identity = (nb == plan.row_start.shape[0] and
                    bool((np.asarray(b.members) == np.arange(nb)).all()))
        slabs.append(BucketSlab(
            start=int(b.start), nb=nb, deg=int(b.deg),
            members=None if identity else jnp.asarray(b.members, jnp.int32)))
    return DenseSpec(n_dst=int(plan.row_start.shape[0]),
                     n_rows=int(plan.n_rows), buckets=tuple(slabs))


# ---------------------------------------------------------------------------
# The core
# ---------------------------------------------------------------------------
class WindowCore:
    """Window-phase kernels shared by every vectorized engine.

    Holds only population-invariant configuration (``cfg``, the batched
    app's payload shape, snapshot slot count, barrier cost).  Topology
    tables — edge endpoints, halo keys, latency bases, per-shard row
    tables — are *arguments* to the phase methods, so one core instance
    serves the full population and any sentinel-padded shard slice of it
    with identical traced semantics.
    """

    def __init__(self, cfg, bapp, n: int, *, max_pops: int = 16):
        self.cfg = cfg
        self.bapp = bapp
        self.n = n
        self.max_pops = max_pops
        warmup, interval = cfg.snapshot_warmup, cfg.snapshot_interval
        #: snapshot slots per process (S in DESIGN.md §7)
        self.S = max(1, int((cfg.duration - warmup) / interval) + 3)
        base_total = cfg.base_compute + cfg.work_units * cfg.work_unit_cost
        self.base_total = np.float32(base_total)
        if n <= 1:
            self.barrier_cost = 0.0
        else:
            self.barrier_cost = (cfg.barrier_base +
                                 cfg.barrier_per_log2 * math.log2(n))
        # generous lockstep-window budget: fastest plausible step is about
        # half the mean, plus slack for barrier-arrival idling
        self.default_max_windows = int(8 * cfg.duration / base_total) + 2048

    # ------------------------------------------------------------------
    # RNG phases
    # ------------------------------------------------------------------
    def step_factor(self, seed, steps, pids, cfactor) -> jax.Array:
        """Per-process compute-time factor; draws are keyed by original
        pid, so any shard slice reproduces the full-population stream."""
        cfg = self.cfg
        f = lognormal_factor(cfg.jitter_sigma, seed, STREAM_STEP,
                             pids, steps)
        if cfg.stall_prob > 0:
            u = hash_uniform(seed, STREAM_STALL, pids, steps)
            f = jnp.where(u < cfg.stall_prob,
                          f * np.float32(cfg.stall_factor), f)
        return f * cfactor

    @jax.named_scope(SEND)
    def fault_masks(self, seed, t_src, steps_src, eids, loss, flap,
                    flap_period, dead):
        """Per-edge typed-fault send masks (DESIGN.md §14).

        ``loss``/``flap`` are per-edge probabilities, ``dead`` marks edges
        whose destination process is crashed.  Returns ``(loss_kill,
        dead_kill)`` — disjoint bool masks (dead wins) to strip from the
        send activity bits and fold into the attribution counters.  The
        draws are keyed by canonical edge id + sender step count (loss) /
        sender-time bucket (flap), so they are layout-, scheduler-, and
        shard-invariant, and ``simulator.run``'s host-side twin makes the
        identical decisions bit-for-bit on every engine.
        """
        lost = (loss > np.float32(0)) & (
            hash_uniform(seed, STREAM_LOSS, eids, steps_src) < loss)
        bucket = jnp.floor(t_src / np.float32(flap_period)).astype(jnp.int32)
        flap_down = (flap > np.float32(0)) & (
            hash_uniform(seed, STREAM_FLAP, eids, bucket) < flap)
        return (lost | flap_down) & ~dead, dead

    # ------------------------------------------------------------------
    # State builders
    # ------------------------------------------------------------------
    def edge_rings(self, rows: int) -> Dict[str, jax.Array]:
        """Fresh (empty) edge-major ring state for ``rows`` rings — the
        unsharded engine's E canonical edges or a sharded engine's padded
        ``shards * ein`` local rows; all-constant either way."""
        cfg = self.cfg
        L = self.bapp.payload_len
        return dict(
            ptouch=jnp.zeros(rows, jnp.int32),
            q_avail=jnp.full((rows, cfg.buffer_capacity), jnp.inf,
                             jnp.float32),
            q_touch=jnp.zeros((rows, cfg.buffer_capacity), jnp.int32),
            q_pay=jnp.zeros((rows, cfg.buffer_capacity, L),
                            self.bapp.payload_dtype),
            q_head=jnp.zeros(rows, jnp.int32),
            q_size=jnp.zeros(rows, jnp.int32),
        )

    def dense_rings(self, rows: int) -> Dict[str, jax.Array]:
        """Fresh dense bucketed ring state: flat ``(R, C)`` rings (the
        bucketed phases slice per-bucket slabs and reshape) plus the
        staged-send buffers — the send *decision* happens eagerly at stage
        time, the ring *writes* ride into the next window's fused
        ``duct_window`` pass (DESIGN.md §10/§13)."""
        L = self.bapp.payload_len
        u = self.edge_rings(rows)
        u.update(
            stage_pos=jnp.zeros(rows, jnp.int32),
            stage_acc=jnp.zeros(rows, bool),
            stage_avail=jnp.zeros(rows, jnp.float32),
            stage_touch=jnp.zeros(rows, jnp.int32),
            stage_pay=jnp.zeros((rows, L), self.bapp.payload_dtype),
        )
        return u

    def superstep_rings(self, rows: int, w: int) -> Dict[str, jax.Array]:
        """Extra carry for the W-fused superstep scheduler (DESIGN.md §13):
        base rings stay frozen across a superstep while per-window pushes
        append to a compact ``(R, W)`` pushbuf and drains walk base-prefix
        then pushbuf; ``duct_commit`` folds the pushbuf into the rings once
        per superstep."""
        L = self.bapp.payload_len
        u = self.dense_rings(rows)
        u.update(
            size0=jnp.zeros(rows, jnp.int32),      # base size at superstep start
            dr_base=jnp.zeros(rows, jnp.int32),    # base pops this superstep
            pb_cnt=jnp.zeros(rows, jnp.int32),     # pushbuf appends
            pb_pop=jnp.zeros(rows, jnp.int32),     # pushbuf pops
            pb_avail=jnp.zeros((rows, w), jnp.float32),
            pb_touch=jnp.zeros((rows, w), jnp.int32),
            pb_pay=jnp.zeros((rows, w, L), self.bapp.payload_dtype),
            # FIFO offset of every ring slot from the frozen superstep
            # head, precomputed once per superstep (head starts at 0);
            # int8 when capacity permits — the drain re-reads this table
            # every window, so its footprint is paid W times per commit
            base_off=jnp.broadcast_to(
                jnp.arange(self.cfg.buffer_capacity,
                           dtype=self._off_dtype()),
                (rows, self.cfg.buffer_capacity)),
        )
        return u

    def _off_dtype(self):
        return jnp.int8 if self.cfg.buffer_capacity <= 127 else jnp.int32

    # ------------------------------------------------------------------
    # Phase 1: drain
    # ------------------------------------------------------------------
    @jax.named_scope(DRAIN)
    def drain(self, carry, t_rows, act_rows, *, halo_key, n_halo,
              dst, n_dst, dense_spec: Optional[DenseSpec] = None):
        """Edge-major drain over a block of rings living on their
        receiver's device: bounded FIFO pops, halo-winner select, and the
        three receiver-side QoS counter columns.

        ``halo_key`` flattens (receiver, slot); several in-edges may share
        one halo slot, and delivery ties resolve to the highest row index
        (rows are in ascending canonical-edge order on every engine), so
        the scatter is deterministic on every backend.  Sentinel-padded
        tables work unchanged: invalid rows carry key ``n_halo`` /
        segment ``n_dst``, which land in the sliced-off spare segment.
        With ``dense_spec`` the rows are bucketed receiver-major slabs
        (DESIGN.md §13) and both the halo merge and the counter sums
        become per-bucket reshape reductions — gather/scatter only on
        non-identity buckets, never per edge.

        Returns ``(carry updates, drained_r)``.
        """
        rows = jnp.arange(t_rows.shape[0], dtype=jnp.int32)
        d = duct_drain(carry["q_avail"], carry["q_touch"],
                       carry["q_head"], carry["q_size"],
                       t_rows, act_rows, max_pops=self.max_pops,
                       clear_popped=False)
        delivered = d.drained > 0
        payload = carry["q_pay"][rows, d.pop_pos]
        L = carry["halo"].shape[-1]
        new_touch = d.recv_touch + 1
        dtouch = jnp.where(delivered, new_touch - carry["ptouch"], 0)
        ptouch = jnp.where(delivered, new_touch, carry["ptouch"])
        # one multi-column reduction for all receiver-side counters
        recv_cols = jnp.stack([d.drained, delivered.astype(jnp.int32),
                               dtouch], axis=1)
        if dense_spec is not None:
            halo, recv_sums = self._merge_buckets(
                dense_spec, carry["halo"], delivered, payload, recv_cols)
        else:
            winner = jax.ops.segment_max(
                jnp.where(delivered, rows, -1), halo_key,
                num_segments=n_halo + 1)[:n_halo]
            has_win = winner >= 0
            fresh = payload[jnp.where(has_win, winner, 0)]
            halo = jnp.where(has_win[:, None], fresh,
                             carry["halo"].reshape(n_halo, L)).reshape(
                n_dst, 4, L)
            recv_sums = jax.ops.segment_sum(recv_cols, dst,
                                            num_segments=n_dst + 1)[:n_dst]
        return dict(
            halo=halo, ptouch=ptouch,
            c_msgs=carry["c_msgs"] + recv_sums[:, 0],
            c_laden=carry["c_laden"] + recv_sums[:, 1],
            c_touch=carry["c_touch"] + recv_sums[:, 2],
            q_avail=d.q_avail, q_touch=d.q_touch,
            q_head=d.head, q_size=d.size), recv_sums[:, 0]

    def _merge_buckets(self, spec: DenseSpec, halo, delivered, payload,
                       recv_cols):
        """Bucket-sliced halo merge + receiver counter reduction over flat
        dense rows.  Each receiver lives in exactly one bucket, so the
        identity fast path updates whole arrays and non-identity buckets
        scatter disjoint member sets (sentinel members drop)."""
        L = halo.shape[-1]
        cols = recv_cols.shape[-1]
        recv_sums = jnp.zeros((spec.n_dst, cols), recv_cols.dtype)
        for b in spec.buckets:
            sl = slice(b.start, b.start + b.nb * b.deg)
            hp, hw = dense_halo_select(
                delivered[sl].reshape(b.nb, b.deg),
                payload[sl].reshape(b.nb, b.deg, L))
            sums_b = recv_cols[sl].reshape(b.nb, b.deg, cols).sum(axis=1)
            if b.members is None:
                halo = jnp.where(hw[:, :, None], hp, halo)
                recv_sums = recv_sums + sums_b
            else:
                old = halo[jnp.clip(b.members, 0, spec.n_dst - 1)]
                halo = halo.at[b.members].set(
                    jnp.where(hw[:, :, None], hp, old), mode="drop")
                recv_sums = recv_sums.at[b.members].add(sums_b, mode="drop")
        return halo, recv_sums

    @jax.named_scope(DRAIN)
    def window_dense(self, carry, t, active, *, spec: DenseSpec):
        """Dense-layout drain phase: per degree bucket, one fused
        ``duct_window`` pass applies the previous window's staged sends,
        drains at this window's clocks, and merges halos (DESIGN.md
        §10/§13).  Dead padding rows never get staged into, so their empty
        rings drain as no-ops without any extra masking here.  On the
        identity bucket (every degree-regular topology) this is zero
        gathers/scatters.  Returns ``(carry updates, drained_r)``."""
        C = self.cfg.buffer_capacity
        L = carry["halo"].shape[-1]
        R = spec.n_rows
        halo = carry["halo"]
        new = {key: carry[key] for key in
               ("q_avail", "q_touch", "q_pay", "q_head", "q_size",
                "ptouch")}
        drained_r = jnp.zeros(spec.n_dst, jnp.int32)
        laden_r = jnp.zeros(spec.n_dst, jnp.int32)
        touch_r = jnp.zeros(spec.n_dst, jnp.int32)
        for b in spec.buckets:
            sl = slice(b.start, b.start + b.nb * b.deg)
            shp = (b.nb, b.deg)

            def slab(key, *tail):
                return carry[key][sl].reshape(shp + tail)

            t_b = t if b.members is None else t[
                jnp.clip(b.members, 0, spec.n_dst - 1)]
            act_b = active if b.members is None else (
                active[jnp.clip(b.members, 0, spec.n_dst - 1)] &
                (b.members < spec.n_dst))
            w = duct_window(
                slab("q_avail", C), slab("q_touch", C), slab("q_pay", C, L),
                slab("q_head"), slab("q_size"),
                slab("stage_pos"), slab("stage_acc"),
                slab("stage_avail"), slab("stage_touch"),
                slab("stage_pay", L), t_b, act_b, max_pops=self.max_pops)
            delivered = w.drained > 0
            new_touch = w.recv_touch + 1
            pt_b = slab("ptouch")
            dtouch = jnp.where(delivered, new_touch - pt_b, 0)
            pt_b = jnp.where(delivered, new_touch, pt_b)
            dr_b = w.drained.sum(axis=1)
            laden_b = delivered.astype(jnp.int32).sum(axis=1)
            tch_b = dtouch.sum(axis=1)
            if b.members is None:
                halo = jnp.where(w.halo_win[:, :, None], w.halo_pay, halo)
                drained_r = drained_r + dr_b
                laden_r = laden_r + laden_b
                touch_r = touch_r + tch_b
            else:
                old = halo[jnp.clip(b.members, 0, spec.n_dst - 1)]
                halo = halo.at[b.members].set(
                    jnp.where(w.halo_win[:, :, None], w.halo_pay, old),
                    mode="drop")
                drained_r = drained_r.at[b.members].add(dr_b, mode="drop")
                laden_r = laden_r.at[b.members].add(laden_b, mode="drop")
                touch_r = touch_r.at[b.members].add(tch_b, mode="drop")

            def put(cur, val):
                flat = val.reshape((sl.stop - sl.start,) + val.shape[2:])
                if sl.start == 0 and sl.stop == R:
                    return flat
                return cur.at[sl].set(flat)

            new["q_avail"] = put(new["q_avail"], w.q_avail)
            new["q_touch"] = put(new["q_touch"], w.q_touch)
            new["q_pay"] = put(new["q_pay"], w.q_pay)
            new["q_head"] = put(new["q_head"], w.head)
            new["q_size"] = put(new["q_size"], w.size)
            new["ptouch"] = put(new["ptouch"], pt_b)
        new.update(
            halo=halo,
            c_msgs=carry["c_msgs"] + drained_r,
            c_laden=carry["c_laden"] + laden_r,
            c_touch=carry["c_touch"] + touch_r)
        return new, drained_r

    @jax.named_scope(DRAIN)
    def window_dense_fused(self, carry, t, active, *, spec: DenseSpec,
                           dst_row):
        """One window of the W-fused superstep scheduler (DESIGN.md §13).

        The base rings are FROZEN for the whole superstep: this window's
        accepted push appends to the compact ``(R, W)`` pushbuf instead of
        writing the ring, and the drain walks the base FIFO prefix with an
        ``O(max_pops)`` strided gather, then — only once every remaining
        base message is popped (FIFO: everything in the base ring is older
        than any push of this superstep) — the pushbuf prefix.  The pop
        sequence, accept decisions, and counters are therefore *bitwise
        identical* to running ``window_dense`` every window; only the
        ``O(R*C)`` ring sweep is deferred to one ``duct_commit`` per
        superstep.  Returns ``(carry updates, drained_r)``."""
        C = self.cfg.buffer_capacity
        R = spec.n_rows
        P = self.max_pops
        W = carry["pb_avail"].shape[-1]
        # --- append the previous window's staged send to the pushbuf ------
        # masked dense writes over the narrow (R, W) buffers: XLA:CPU
        # lowers row scatters to serial loops, and this append runs every
        # window — the where-form vectorizes and is the difference between
        # the fused path winning and losing to the per-window O(R*C) sweep
        wcol_a = jnp.arange(W, dtype=jnp.int32)[None, :]
        at = carry["stage_acc"][:, None] & (wcol_a == carry["pb_cnt"][:, None])
        pb_avail = jnp.where(at, carry["stage_avail"][:, None],
                             carry["pb_avail"])
        pb_touch = jnp.where(at, carry["stage_touch"][:, None],
                             carry["pb_touch"])
        pb_pay = jnp.where(at[:, :, None], carry["stage_pay"][:, None, :],
                           carry["pb_pay"])
        pb_cnt = carry["pb_cnt"] + carry["stage_acc"]
        # --- drain: base-prefix walk, head-blocking, bounded --------------
        # dense formulation over the (R, C) ring (no take_along_axis: XLA
        # CPU lowers gathers to row loops): FIFO offsets from the FROZEN
        # superstep head are precomputed once per superstep
        # (``base_off``), so the pop count is one compare + min — the
        # offset of the first blocked not-yet-popped slot, clamped by the
        # remaining base prefix and the pop budget
        t_r = t[dst_row]
        act_r = active[dst_row]
        base_rem = carry["size0"] - carry["dr_base"]
        off = carry["base_off"]
        odt = off.dtype
        blocked = ((off >= carry["dr_base"].astype(odt)[:, None]) &
                   (off < carry["size0"].astype(odt)[:, None]) &
                   (carry["q_avail"] > t_r[:, None]))
        first_block = jnp.where(blocked, off,
                                jnp.asarray(C, odt)).min(axis=1)
        n1 = jnp.minimum(first_block.astype(jnp.int32) - carry["dr_base"],
                         jnp.minimum(base_rem, P))
        n1 = jnp.where(act_r, n1, 0)
        # --- then the pushbuf prefix, within the same max_pops budget -----
        wcol = jnp.arange(W, dtype=jnp.int32)[None, :]
        pb_ok = ((wcol < pb_cnt[:, None]) & (pb_avail <= t_r[:, None])) | (
            wcol < carry["pb_pop"][:, None])
        run = (jnp.cumprod(pb_ok.astype(jnp.int32), axis=1).sum(axis=1) -
               carry["pb_pop"])
        n2 = jnp.clip(run, 0, P - n1)
        n2 = jnp.where(act_r & (n1 == base_rem), n2, 0).astype(jnp.int32)
        drained = (n1 + n2).astype(jnp.int32)
        delivered = drained > 0
        # --- freshest popped message (touch stamp + payload) --------------
        # ONE element per row: XLA:CPU's serial gather lowering is O(R)
        # here — unlike the O(R*C) full-ring gathers banished elsewhere —
        # and avoids pulling two more full (R, C[, L]) passes through the
        # cache for a one-hot reduction
        L = carry["q_pay"].shape[-1]
        last_b = ((carry["q_head"] + carry["dr_base"] + n1 - 1) % C)[:, None]
        tch_b = jnp.take_along_axis(carry["q_touch"], last_b, axis=1)[:, 0]
        pay_b = jnp.take_along_axis(
            carry["q_pay"], jnp.broadcast_to(last_b[:, :, None], (R, 1, L)),
            axis=1)[:, 0]
        last_p = jnp.clip(carry["pb_pop"] + n2 - 1, 0, W - 1)[:, None]
        tch_p = jnp.take_along_axis(pb_touch, last_p, axis=1)[:, 0]
        pay_p = jnp.take_along_axis(
            pb_pay, jnp.broadcast_to(last_p[:, :, None], (R, 1, L)),
            axis=1)[:, 0]
        has2 = n2 > 0
        recv_touch = jnp.where(has2, tch_p, jnp.where(n1 > 0, tch_b, 0))
        fresh_pay = jnp.where(has2[:, None], pay_p, pay_b)
        # --- halo merge + receiver counters (shared bucket machinery) -----
        new_touch = recv_touch + 1
        dtouch = jnp.where(delivered, new_touch - carry["ptouch"], 0)
        ptouch = jnp.where(delivered, new_touch, carry["ptouch"])
        recv_cols = jnp.stack([drained, delivered.astype(jnp.int32),
                               dtouch], axis=1)
        halo, recv_sums = self._merge_buckets(
            spec, carry["halo"], delivered, fresh_pay, recv_cols)
        return dict(
            halo=halo, ptouch=ptouch,
            c_msgs=carry["c_msgs"] + recv_sums[:, 0],
            c_laden=carry["c_laden"] + recv_sums[:, 1],
            c_touch=carry["c_touch"] + recv_sums[:, 2],
            q_size=carry["q_size"] - drained,
            dr_base=carry["dr_base"] + n1.astype(jnp.int32),
            pb_pop=carry["pb_pop"] + n2,
            pb_cnt=pb_cnt, pb_avail=pb_avail, pb_touch=pb_touch,
            pb_pay=pb_pay), recv_sums[:, 0]

    @jax.named_scope(COMMIT)
    def commit_superstep(self, carry):
        """Superstep epilogue for the fused scheduler: ONE ``duct_commit``
        launch folds the whole superstep's accepted pushes into the base
        rings (push j of ring r lands at slot ``(head0 + size0 + j) % C``,
        independent of how pops interleaved — already-popped pushbuf
        entries land behind the advanced head, on provably dead slots) and
        re-bases the head/size counters for the next superstep."""
        C = self.cfg.buffer_capacity
        qa, qt, qp = duct_commit(
            carry["q_avail"], carry["q_touch"], carry["q_pay"],
            carry["q_head"], carry["size0"], carry["pb_cnt"],
            carry["pb_avail"], carry["pb_touch"], carry["pb_pay"])
        z = jnp.zeros_like(carry["pb_cnt"])
        # new base size counts only committed messages: the last window's
        # staged accept (already in q_size) rides into the NEXT superstep's
        # pushbuf at its first window, not into the base ring
        size0 = (carry["size0"] - carry["dr_base"] +
                 carry["pb_cnt"] - carry["pb_pop"])
        head = (carry["q_head"] + carry["dr_base"] + carry["pb_pop"]) % C
        col = jnp.arange(C, dtype=jnp.int32)[None, :]
        return dict(
            q_avail=qa, q_touch=qt, q_pay=qp, q_head=head,
            size0=size0, dr_base=z, pb_cnt=z, pb_pop=z,
            base_off=((col - head[:, None]) % C).astype(self._off_dtype()))

    # ------------------------------------------------------------------
    # Phase 2: compute
    # ------------------------------------------------------------------
    @jax.named_scope(COMPUTE)
    def compute(self, carry, active, halo, pids):
        """The application's actual batched compute, masked by activity.
        Returns ``(app_state, edges_out, steps)``."""
        n = active.shape[0]
        new_state, edges_out = self.bapp.step(carry["app"], halo,
                                              carry["steps"], carry["seed"],
                                              pids=pids)
        app_state = jax.tree_util.tree_map(
            lambda new, old: jnp.where(
                active.reshape((n,) + (1,) * (new.ndim - 1)), new, old),
            new_state, carry["app"])
        return app_state, edges_out, carry["steps"] + active

    # ------------------------------------------------------------------
    # Phase 3: send (edge-major)
    # ------------------------------------------------------------------
    @jax.named_scope(SEND)
    def send_edge(self, rings, now, act, lat, touch, payload,
                  src, n_src, *, sorted_src: bool = False,
                  want_sums: bool = True) -> SendPhase:
        """Best-effort push attempt over a block of edge-major rings (drop
        iff the post-drain ring is full) plus the sender-side counter
        columns, summed per source process.  Sentinel-padded ``src``
        tables (value ``n_src``) drop their contributions into the sliced
        spare segment; ``want_sums=False`` skips the reduction (the
        sharded superstep push passes only need the final pass's sums)."""
        rows_n = rings["q_avail"].shape[0]
        rows = jnp.arange(rows_n, dtype=jnp.int32)
        s = duct_send(rings["q_avail"], rings["q_touch"],
                      rings["q_head"], rings["q_size"],
                      now, act, lat, touch,
                      capacity=self.cfg.buffer_capacity)
        q_pay = rings["q_pay"].at[
            jnp.where(s.accepted, rows, rows_n), s.push_pos].set(
            payload, mode="drop")
        sums = None
        if want_sums:
            send_cols = jnp.stack([
                act.astype(jnp.int32),
                (act & s.accepted).astype(jnp.int32),
                (act & ~s.accepted).astype(jnp.int32)], axis=1)
            sums = jax.ops.segment_sum(send_cols, src,
                                       num_segments=n_src + 1,
                                       indices_are_sorted=sorted_src)[:n_src]
        return SendPhase(
            rings=dict(q_avail=s.q_avail, q_touch=s.q_touch,
                       q_size=s.size, q_pay=q_pay),
            accepted=s.accepted, sums=sums)

    # ------------------------------------------------------------------
    # Phase 3': stage (dense layout)
    # ------------------------------------------------------------------
    @jax.named_scope(SEND)
    def stage_dense(self, carry, u, t, active, edges_out, lat,
                    *, src, rev, out_slot, live, deg, spec: DenseSpec,
                    kill_masks=None):
        """Stage this window's sends on the dense layout: decide
        drop-iff-full NOW against the post-drain rings (exactly what the
        edge-major send attempt sees, so counters land in this window)
        and defer only the ring writes to the next fused pass.  Sender
        counters come through the out-edge table as gathers — flat row
        ``r``'s sender is its receiver by construction, so no scatters on
        the identity bucket.  ``live`` masks the dead padding rows: they
        never accept a push, so their rings stay empty forever."""
        n = t.shape[0]
        src_c = jnp.clip(src, 0, n - 1)     # sentinel n on dead rows
        s_avail = t[src_c] + lat
        s_act = live & active[src_c]
        if kill_masks is not None:
            # typed faults (DESIGN.md §14): a lost / flapped / dead-bound
            # send still counts as attempted (att_r covers every out-edge
            # of an active sender) but never reaches the ring, so it folds
            # into c_drop via att - ok exactly like a capacity drop — the
            # loss_r/dead_r sums below attribute it
            loss_kill, dead_kill = kill_masks
            s_act = s_act & ~(loss_kill | dead_kill)
        s_touch = u["ptouch"][rev]
        s_pay = edges_out[src_c, out_slot]
        s_pos, s_acc = dense_stage(u["q_head"], u["q_size"], s_act,
                                   capacity=self.cfg.buffer_capacity)
        # acceptance of receiver p's own sends lives at its out-edge rows
        # rev[rows of p]; dead rows rev to themselves and contribute 0
        acc_out = s_acc[rev].astype(jnp.int32)
        cols = [acc_out]
        if kill_masks is not None:
            sender_act = (live & active[src_c]).astype(jnp.int32)
            cols.append((loss_kill.astype(jnp.int32) * sender_act)[rev])
            cols.append((dead_kill.astype(jnp.int32) * sender_act)[rev])
        out_cols = jnp.stack(cols, axis=1)
        sums_r = jnp.zeros((spec.n_dst, out_cols.shape[1]), jnp.int32)
        for b in spec.buckets:
            sl = slice(b.start, b.start + b.nb * b.deg)
            sums_b = out_cols[sl].reshape(b.nb, b.deg, -1).sum(axis=1)
            if b.members is None:
                sums_r = sums_r + sums_b
            else:
                sums_r = sums_r.at[b.members].add(sums_b, mode="drop")
        ok_r = sums_r[:, 0]
        att_r = jnp.where(active, deg, 0)
        out = dict(q_size=u["q_size"] + s_acc,
                   c_att=carry["c_att"] + att_r,
                   c_ok=carry["c_ok"] + ok_r,
                   c_drop=carry["c_drop"] + att_r - ok_r,
                   stage_pos=s_pos, stage_acc=s_acc, stage_avail=s_avail,
                   stage_touch=s_touch, stage_pay=s_pay)
        if kill_masks is not None:
            out["c_loss"] = carry["c_loss"] + sums_r[:, 1]
            out["c_dead"] = carry["c_dead"] + sums_r[:, 2]
        return out

    # ------------------------------------------------------------------
    # Phase 4: close window
    # ------------------------------------------------------------------
    @jax.named_scope(CLOSE)
    def close_window(self, u, active, drained_r, *, pids, deg, cfactor,
                     release):
        """Shared window tail: QoS snapshot write (masked select),
        termination, barrier bookkeeping, and the virtual-time advance.

        ``release`` picks where the barrier-release reductions run:
        :data:`LOCAL_RELEASE` on one device, a :class:`MeshRelease` over
        the shard axis, or ``None`` to skip the release check entirely
        (mid-superstep windows: waiting clocks do not advance, so the
        release *time* computed at the superstep boundary is identical —
        only the lockstep window it lands on moves)."""
        cfg = self.cfg
        mode = cfg.mode
        barriered = mode in BARRIER_MODES
        t, steps = u["t"], u["steps"]
        done, waiting = u["done"], u["waiting"]
        # rolling barriers meter their quantum on the WORK clock: compute
        # plus the (degree-fixed) halo pull cost, with per-message handling
        # absorbed into barrier slack.  That makes the number of updates a
        # quantum holds — and hence every release and the horizon straddle —
        # independent of drain timing, so the superstep scheduler's boundary
        # staging (which perturbs drop/drain patterns) cannot drift the
        # update schedule: rolling-barrier runs are exactly W-invariant.
        # The free-running modes keep the drain-coupled clock.
        pull_cost = deg.astype(jnp.float32) * np.float32(cfg.per_pull_cost)
        if mode == AsyncMode.ROLLING_BARRIER:
            pending = pull_cost
        else:
            pending = (drained_r.astype(jnp.float32) * np.float32(
                cfg.per_message_cost) + pull_cost)
        with jax.named_scope(SNAPSHOT):
            snap_idx = u["snap_idx"]
            thr = (np.float32(cfg.snapshot_warmup) +
                   snap_idx.astype(jnp.float32) * np.float32(
                       cfg.snapshot_interval))
            snap_due = active & (t >= thr) & (snap_idx < self.S)
            row = jnp.stack([
                steps.astype(jnp.float32), u["c_touch"].astype(jnp.float32),
                u["c_att"].astype(jnp.float32), u["c_ok"].astype(jnp.float32),
                u["c_drop"].astype(jnp.float32),
                u["c_laden"].astype(jnp.float32),
                u["c_msgs"].astype(jnp.float32), t], axis=1)
            # a masked select over the whole buffer, not a scatter: under
            # vmap XLA lifts a scatter to the scan's loop level with
            # relayouts of the buffer around it.  ``snap_idx < S`` already
            # gates ``snap_due``, so a full buffer takes no row.
            into = ((jnp.arange(self.S, dtype=jnp.int32)[None, :]
                     == snap_idx[:, None]) & snap_due[:, None])
            snap = jnp.where(into[..., None], row[:, None, :], u["snap"])
            snap_idx = snap_idx + snap_due

        # --- termination / barriers / time advance ------------------------
        newly_done = active & (t >= np.float32(cfg.duration))
        done = done | newly_done

        # --- open-loop service arrivals (runtime/service.py) --------------
        # arrivals of time bin b queue up once b has fully elapsed on the
        # process's own clock (the cumulative table travels in the carry,
        # rows keyed by original pid); each update serves up to
        # service_chunk items whose cost rides on the work clock with the
        # compute.  The recurrence reads only (t, served), never drain
        # state, so the update schedule stays engine-, layout-, shard- and
        # W-invariant — and bit-identical to simulator.run's serve block.
        served = u.get("served")
        if served is not None:
            cont = active & ~newly_done
            arr_cum = u["arr_cum"]
            nbins = arr_cum.shape[-1] - 1
            b = jnp.minimum(
                (t / np.float32(cfg.arrival_bin)).astype(jnp.int32), nbins)
            avail = jnp.take_along_axis(arr_cum, b[:, None], axis=1)[:, 0]
            serve = jnp.clip(avail - served, 0, cfg.service_chunk)
            serve = jnp.where(cont, serve, 0)
            pending = pending + serve.astype(jnp.float32) * np.float32(
                cfg.per_item_cost)
            served = served + serve

        d_next = self.base_total * self.step_factor(u["seed"], steps,
                                                    pids, cfactor)
        barrier_seq = u["barrier_seq"]
        last_release = u["last_release"]
        pending_saved = u["pending"]

        if barriered:
            if mode == AsyncMode.BARRIER_EVERY_STEP:
                due = active & ~newly_done
            elif mode == AsyncMode.ROLLING_BARRIER:
                due = active & ~newly_done & (
                    (t - last_release) >= np.float32(cfg.rolling_quantum))
            else:
                due = active & ~newly_done & (
                    t >= (barrier_seq + 1).astype(jnp.float32) *
                    np.float32(cfg.fixed_interval))
            waiting = waiting | due
            pending_saved = jnp.where(due, pending, pending_saved)
            t = jnp.where(active & ~newly_done & ~due,
                          t + d_next + pending, t)
            quarantined = "quar" in u
            tau = np.float32(cfg.barrier_timeout)
            if release is not None:
                if release.staged:
                    # pipelined: apply the decision issued one boundary
                    # earlier (frozen cohort — see PipelinedRelease)
                    release_ready = u["rel_ready"]
                    release_t = u["rel_t"]
                    if quarantined:
                        ref = u["rel_ref"]
                elif quarantined:
                    # quarantine release (DESIGN.md §14): a non-waiting,
                    # non-done process's clock is its next barrier arrival,
                    # so "unreachable" == next arrival lags the cohort
                    # front (ref) by more than the timeout; crashed clocks
                    # sit at +inf and any finite tau excludes them
                    quar0 = u["quar"]
                    ref = self._quarantine_ref(release, t, waiting, quar0)
                    stopped = waiting | done
                    unreachable = ~stopped & (t > ref + tau)
                    release_ready = (
                        release.any_waiting(waiting) &
                        release.all_stopped(stopped | quar0 | unreachable))
                    release_t = ref + np.float32(self.barrier_cost)
                else:
                    release_ready = (release.all_stopped(waiting | done) &
                                     release.any_waiting(waiting))
                    release_t = (release.max_time(
                        jnp.where(waiting, t, -jnp.inf)) +
                        np.float32(self.barrier_cost))
                rel = release_ready & waiting
                if quarantined:
                    # hysteresis, evaluated on the pre-release state: a
                    # quarantined member that made it to the barrier within
                    # tau/2 of the front is readmitted; a straggler whose
                    # next arrival exceeds ref + tau is newly quarantined
                    quar = u["quar"]
                    readmit = waiting & quar & (
                        t >= ref - tau * np.float32(0.5))
                    newq = ~done & ~waiting & (t > ref + tau)
                    quar = jnp.where(release_ready,
                                     (quar & ~readmit) | newq, quar)
                # horizon snap: a cohort released at or past the horizon is
                # done at the horizon clock — no engine schedules (and the
                # event oracle no longer executes) a post-horizon update,
                # so straddle-sensitive float drift cannot flip the final
                # update count
                at_horizon = release_t >= np.float32(cfg.duration)
                t = jnp.where(
                    rel, jnp.where(at_horizon, np.float32(cfg.duration),
                                   release_t + d_next + pending_saved), t)
                done = done | (rel & at_horizon)
                last_release = jnp.where(rel, release_t, last_release)
                barrier_seq = barrier_seq + rel
                waiting = waiting & ~release_ready
        else:
            t = jnp.where(active & ~newly_done, t + d_next + pending, t)

        out = dict(u)
        out.update(k=u["k"] + 1, t=t, done=done, waiting=waiting,
                   barrier_seq=barrier_seq, last_release=last_release,
                   pending=pending_saved, snap=snap, snap_idx=snap_idx)
        if served is not None:
            out["served"] = served
        if barriered and release is not None and quarantined:
            out["quar"] = quar
        if release is not None and release.staged and barriered:
            # store fresh post-release reductions for the next boundary
            if quarantined:
                fref = self._quarantine_ref(release, t, waiting, quar)
                fstopped = waiting | done
                funreach = ~fstopped & (t > fref + tau)
                fresh_ready = (
                    release.any_waiting(waiting) &
                    release.all_stopped(fstopped | quar | funreach))
                fresh_t = fref + np.float32(self.barrier_cost)
                out["rel_ref"] = fref.reshape(u["rel_ref"].shape)
            else:
                fresh_ready = (release.all_stopped(waiting | done) &
                               release.any_waiting(waiting))
                fresh_t = (release.max_time(
                    jnp.where(waiting, t, -jnp.inf)) +
                    np.float32(self.barrier_cost))
            out.update(rel_ready=fresh_ready.reshape(u["rel_ready"].shape),
                       rel_t=fresh_t.reshape(u["rel_t"].shape))
        return out

    def _quarantine_ref(self, release, t, waiting, quar):
        """Cohort front for the quarantine gate: max waiting clock over the
        non-quarantined core, falling back to the full waiting set when
        every waiting member is quarantined (so an all-quarantined cohort
        still releases rather than stalling)."""
        core = release.max_time(jnp.where(waiting & ~quar, t, -jnp.inf))
        full = release.max_time(jnp.where(waiting, t, -jnp.inf))
        return jnp.where(core == -jnp.inf, full, core)

    # ------------------------------------------------------------------
    # QoS assembly
    # ------------------------------------------------------------------
    def assemble(self, carry, r: int, deg: np.ndarray,
                 quality: float, app_state=None) -> SimResult:
        """Numpy-vectorized QoS assembly: all report fields for all
        (process, window) samples come from whole-array ops over the
        snapshot deltas — the python loop only constructs the result
        objects.  The math mirrors ``core.qos.report`` exactly (same
        guards, same operation order), so values are bit-identical to the
        per-pair path it replaces."""
        cfg = self.cfg
        n = deg.shape[0]
        comm = cfg.mode != AsyncMode.NO_COMM
        snap = np.asarray(carry["snap"][r], np.float64)      # (n, S, 8)
        snap_idx = np.asarray(carry["snap_idx"][r])
        steps = np.asarray(carry["steps"][r])

        nwin = np.maximum(snap_idx - 1, 0)                   # reports/proc
        d = snap[:, 1:, :] - snap[:, :-1, :]                 # (n, S-1, 8)
        dup, dtch, datt = d[..., 0], d[..., 1], d[..., 2]
        ddrop, dladen, dmsg, dwall = (d[..., 4], d[..., 5], d[..., 6],
                                      d[..., 7])
        # zero-update windows stamp the explicit inf sentinel, mirroring
        # qos.simstep_period / qos.walltime_latency (idle != fast)
        idle = dup <= 0
        fin_period = dwall / np.maximum(dup, 1)
        period = np.where(idle, np.inf, fin_period)
        lat = dup / np.maximum(dtch, 1)
        # product over the finite period only: 0 * inf would leak nan
        # through np.where's eagerly evaluated branch
        wall_lat = np.where(idle, np.inf, lat * fin_period)
        fail = np.where(datt > 0, ddrop / np.maximum(datt, 1), 0.0)
        dpull = dup * deg[:, None] if comm else np.zeros_like(dup)
        opp = np.minimum(dmsg, dpull)
        clump = np.where(
            opp > 0, 1.0 - np.minimum(dladen / np.maximum(opp, 1), 1.0),
            0.0)
        t0, t1 = snap[:, :-1, 7], snap[:, 1:, 7]

        qos_by_proc: Dict[int, List[QosReport]] = {}
        all_qos: List[QosReport] = []
        for p in range(n):
            reps = [QosReport(
                simstep_period=float(period[p, i]),
                simstep_latency=float(lat[p, i]),
                walltime_latency=float(wall_lat[p, i]),
                delivery_failure_rate=float(fail[p, i]),
                delivery_clumpiness=float(clump[p, i]),
                t_start=float(t0[p, i]), t_end=float(t1[p, i]))
                for i in range(int(nwin[p]))]
            qos_by_proc[p] = reps
            all_qos.extend(reps)

        service = None
        if "served" in carry:
            srv = np.asarray(carry["served"][r])
            tot = np.asarray(carry["arr_cum"][r])[:, -1]
            service = {
                "arrivals": [int(x) for x in tot],
                "served": [int(x) for x in srv],
                "backlog": [int(a - s) for a, s in zip(tot, srv)],
            }

        return SimResult(
            updates=[int(x) for x in steps],
            horizon=cfg.duration,
            quality=quality,
            qos=all_qos,
            qos_by_process=qos_by_proc,
            dropped=int(np.sum(carry["c_drop"][r])),
            dropped_loss=(int(np.sum(carry["c_loss"][r]))
                          if "c_loss" in carry else 0),
            dropped_dead=(int(np.sum(carry["c_dead"][r]))
                          if "c_dead" in carry else 0),
            sent=int(np.sum(carry["c_att"][r])),
            service=service,
            app_state=app_state,
        )
