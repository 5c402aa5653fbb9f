"""Mesh-sharded vectorized engine (DESIGN.md §8).

The windowed-time engine (``runtime/engine_jax.py``) advances the whole
population per lockstep window on ONE device.  This subclass partitions the
flat population arrays into contiguous per-shard process blocks over a 1-D
device mesh (``launch/mesh.py::make_shard_mesh``) and runs each window's
drain -> batched compute -> send under ``shard_map``, so only the thin set
of cross-shard boundary edges ever crosses the device link — Conduit's
partitioning discipline (arXiv:2105.10486) applied to the simulator itself.

The window phases themselves live in ``runtime/window_core.py``
(DESIGN.md §11) and are shared with the unsharded engine verbatim: this
file keeps only what is genuinely distributed — the static shard layout
and boundary tables, the packed-ppermute boundary exchange, and the
barrier-release strategy (:class:`~repro.runtime.window_core.MeshRelease`
pmin/pmax reductions over the shard axis).

Layout.  ``topologies.contiguous_partition`` reorders pids so each shard's
processes are contiguous; every duct ring lives on its *receiver's* shard,
so drains, halo scatters, and receiver-side QoS counters are shard-local.
The duct layout itself follows ``layout=`` (DESIGN.md §10): edge-major
local rows in ascending canonical order, or — for degree-regular
topologies — dense receiver-major rows (``m * d`` per shard, no padding)
whose halo merges and receiver counters are plain per-receiver reshape
reductions; the boundary machinery below is layout-agnostic and simply
indexes whichever rows the plan laid out.
Per window, boundary traffic moves in exactly two collective hops per
distinct shard offset:

  1. payload hop: for each boundary edge the source shard packs
     (edge payload, availability stamp ``t_src + latency``, touch counter,
     active bit) into one int32 buffer and ``ppermute``s it to the
     receiver's shard, which scatters the entries into its local send rows;
  2. accept hop: after the local ``duct_send`` (drop iff the ring is full)
     the receiver ``ppermute``s the accept bits back so the source shard
     can maintain its processes' attempted/ok/dropped send counters.

Parity.  All stochastic draws stay keyed by *original* pid and *canonical*
edge id (the unsharded enumeration order), and halo-scatter ties resolve
by canonical edge id, so a run is a pure function of ``(config, seed)``
regardless of shard count: ``--shards 8`` reproduces ``--shards 1``
trajectories exactly (``tests/test_engine_conformance.py``).  The
replicate axis vmaps *inside* each shard, composing ``--replicates`` with
``--shards``.

Self-paced supersteps (DESIGN.md §9).  The per-window exchange above is a
hidden barrier: every window, every shard stops at the same ppermute.
With ``superstep_windows=W`` each shard instead advances W lockstep
windows *entirely shard-locally* per superstep — fault-injected or
jittered shards drift behind in virtual time exactly as the paper's
lac-417 node does — while boundary sends are staged sender-side.  The
superstep-end window then moves all W windows' boundary traffic in ONE
packed ppermute per shard offset (and one packed reverse hop for the
accept bits), cutting the collective count per simulated window by ~W×.
Staged messages carry their sender-window availability stamps and touch
counters, so latency/clumpiness QoS is computed from exact virtual-time
metadata; what W>1 changes is only *when* boundary messages enter the
receiver's ring (superstep boundaries instead of every window), which
perturbs drop patterns and per-message handling costs within a documented
tolerance.  Barrier modes release on superstep-granular pmin/pmax: since
waiting processes' clocks do not advance, release *times* are unchanged —
releases just land on superstep boundaries.  ``W=1`` reproduces the
per-window engine bitwise (same staged values, same operation order).

Pipelined overlap (DESIGN.md §12).  ``scheduler="pipelined"`` double-
buffers the superstep exchange: at boundary k the packed payload is
*staged* into shadow carry buffers (``fly_fwd_<off>``/``fly_acc_<off>``)
and the ppermute + receiver-side ``duct_send`` run at boundary k+1,
overlapping with superstep k+1's interior windows; the accept bits ride
the k+2 hop back to the sender's counters.  Boundary messages therefore
arrive one superstep later than under ``superstep`` — an honest,
QoS-visible latency (docs/QOS.md), not a reordering: stamps and touch
counters still carry exact sender-side virtual-time metadata, and drops
still happen at the receiver against real ring occupancy.  An epilogue
flush (att-bit gated, idempotent) empties the shadow buffers after the
last superstep so conservation closes exactly
(``tests/test_engine_sharded.py::test_pipelined_conservation_across_flush``).
Release decisions under barrier modes are consumed one boundary late
(:class:`~repro.runtime.window_core.PipelinedRelease`), which is sound
because a release cohort is frozen — all-stopped shards admit no new
sends — and the window budget doubles to ``2*W`` per superstep to cover
the drained tail.  Push passes before the superstep's last window have
no interior senders, so they gather only the static union of boundary
receiver rows into a compact sub-ring block (``rows_bnd``), run the send
phase there, and scatter back — the overlap's fixed cost scales with the
boundary cut, not the shard's full edge set.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.modes import AsyncMode
from repro.launch.mesh import SHARD_AXIS, make_shard_mesh
from repro.runtime import spans
from repro.runtime.engine_jax import JaxEngine
from repro.runtime.simulator import SimResult
from repro.runtime.topologies import contiguous_partition
from repro.runtime.window_core import (
    BARRIER_MODES,
    STREAM_LAT,
    BucketSlab,
    DenseSpec,
    MeshRelease,
    PipelinedRelease,
    lognormal_factor,
)

#: window schedulers this engine implements (registry vocabulary)
_SCHEDULERS = ("window", "superstep", "pipelined")

#: carry keys indexed by the process axis (permuted into shard layout);
#: the service keys ("arr_cum", "served"), the fault-attribution counters
#: ("c_loss", "c_dead"), and the quarantine flags ("quar") are present only
#: when the config enables them, so layout transforms guard on membership
_PROC_KEYS = ("t", "steps", "done", "waiting", "barrier_seq", "last_release",
              "pending", "c_touch", "c_att", "c_ok", "c_drop", "c_laden",
              "c_msgs", "c_loss", "c_dead", "quar", "snap", "snap_idx",
              "halo", "arr_cum", "served")
#: carry keys indexed by the edge axis (re-laid-out per shard, padded)
_EDGE_KEYS = ("ptouch", "q_avail", "q_touch", "q_pay", "q_head", "q_size")
#: per-replicate scalars (replicated across shards)
_SCALAR_KEYS = ("seed", "k")


def _bits_i32(x: jax.Array) -> jax.Array:
    """Reinterpret f32 as i32 so one ppermute buffer carries mixed fields."""
    if x.dtype == jnp.int32:
        return x
    return jax.lax.bitcast_convert_type(x, jnp.int32)


def _from_bits(x: jax.Array, dtype) -> jax.Array:
    if np.dtype(dtype) == np.dtype(np.int32):
        return x
    return jax.lax.bitcast_convert_type(x, dtype)


class ShardedJaxEngine(JaxEngine):
    """Windowed-time engine sharded over a 1-D device mesh.

    Same ``Engine`` contract and same trajectories as :class:`JaxEngine`
    (canonical RNG/tie keying — see module docstring); built by the
    registry when ``--shards S`` > 1.
    """

    def __init__(self, app, cfg, faults=None, *, shards: int,
                 superstep_windows: int = 1, scheduler: str = "auto",
                 max_pops: int = 16, chunk: int = 256, layout: str = "auto"):
        super().__init__(app, cfg, faults, max_pops=max_pops, chunk=chunk,
                         layout=layout)
        if np.dtype(self.bapp.payload_dtype) not in (np.dtype(np.int32),
                                                     np.dtype(np.float32)):
            raise ValueError(
                "sharded engine payloads must be int32/float32 (32-bit "
                f"ppermute packing), got {self.bapp.payload_dtype}")
        self.superstep = int(superstep_windows)
        if self.superstep < 1:
            raise ValueError(
                f"superstep_windows must be >= 1, got {superstep_windows}")
        if scheduler == "auto":
            scheduler = "superstep" if self.superstep > 1 else "window"
        if scheduler not in _SCHEDULERS:
            raise ValueError(
                f"unknown scheduler {scheduler!r}; choose from "
                f"{('auto',) + _SCHEDULERS}")
        if scheduler == "pipelined" and self.superstep < 2:
            raise ValueError(
                "scheduler='pipelined' overlaps boundary exchange with the "
                "next superstep's interior windows; pass "
                "superstep_windows > 1 (--superstep-windows W) to choose W")
        self.scheduler = scheduler
        if cfg.mode in BARRIER_MODES:
            # releases land only on superstep boundaries, so up to W-1 idle
            # windows precede each one — same virtual-time trajectory, more
            # lockstep windows consumed.  The pipelined scheduler defers
            # both the release reductions and the boundary delivery by one
            # more superstep, so budget 2W windows per release.
            if scheduler == "pipelined":
                self._max_windows *= 2 * self.superstep
            elif self.superstep > 1:
                self._max_windows *= self.superstep
        self._supersteps_per_dispatch = max(1, chunk // self.superstep)
        self._windows_per_dispatch = (self._supersteps_per_dispatch *
                                      self.superstep)
        self.shards = int(shards)
        self.plan = contiguous_partition(self.topo, self.shards)
        self.mesh = make_shard_mesh(self.shards)
        self._m = self.n // self.shards
        self._release = (PipelinedRelease(SHARD_AXIS)
                         if scheduler == "pipelined"
                         else MeshRelease(SHARD_AXIS))
        self._build_statics()
        self._statics_sharded = None
        self._cspecs = None
        self._flusher = None

    # ------------------------------------------------------------------
    # Static shard layout: local rows (rings on the receiver's shard) and
    # per-offset boundary exchange tables.  All numpy, hoisted out of jit.
    # ------------------------------------------------------------------
    def _build_statics(self) -> None:
        S, m, E = self.shards, self._m, self.E
        esrc = np.asarray(self._esrc)
        edst = np.asarray(self._edst)
        slot = np.asarray(self._slot)
        out_slot = np.asarray(self._out_slot)
        rev = np.asarray(self._rev)
        lat_base = np.asarray(self._lat_base)
        perm = np.asarray(self.plan.perm, np.int64)
        inv = np.asarray(self.plan.inv, np.int64)

        lsrc, ldst = inv[esrc], inv[edst]     # edge endpoints as positions
        src_sh, dst_sh = lsrc // m, ldst // m
        rows_by_shard = [np.where(dst_sh == s)[0] for s in range(S)]
        bucket_members: Dict[str, np.ndarray] = {}
        if self.lplan.kind == "dense":
            # bucketed dense receiver-major local rows (DESIGN.md §13):
            # bucket degrees are global, and every shard hosts its local
            # members of bucket b in a slab at the SAME static offset —
            # member block i (ascending local position) owns rows
            # off_b + i*deg_b .. off_b + (i+1)*deg_b - 1, with j the
            # edge's sorted-source position there, so each receiver's live
            # rows stay in canonical-edge-id order and the dense halo
            # select ties break like the unsharded engine.  Slabs pad to
            # the max member count over shards with sentinel blocks
            # (member value m: gathers clamp, scatters drop).
            lp = self.lplan
            rows_live = np.where(lp.live)[0]
            jof = np.empty(E, np.int64)
            jof[lp.eid[rows_live]] = (rows_live -
                                      lp.row_start[lp.dst[rows_live]])
            bdeg_pos = np.asarray(lp.bdeg, np.int64)[perm]  # by position
            self._bucket_geom: List[tuple] = []
            row0_pos = np.zeros(self.n, np.int64)  # first local row of the
            start = 0                              # position's member block
            for bi, b in enumerate(lp.buckets):
                counts = [int(np.sum(bdeg_pos[s * m:(s + 1) * m] == b.deg))
                          for s in range(S)]
                nb_max = max(1, max(counts))
                mem = np.full((S, nb_max), m, np.int32)
                for s in range(S):
                    loc = np.where(bdeg_pos[s * m:(s + 1) * m] == b.deg)[0]
                    mem[s, :len(loc)] = loc
                    row0_pos[s * m + loc] = start + np.arange(len(loc)) * b.deg
                identity = (len(lp.buckets) == 1 and nb_max == m and
                            min(counts) == m)
                self._bucket_geom.append((start, nb_max, b.deg, identity))
                if not identity:
                    bucket_members[str(bi)] = mem
                start += nb_max * b.deg
            ein = start
            row_of = row0_pos[ldst] + jof
        else:
            # canonical edge id -> its ring's local row index (ascending
            # canonical order per shard, so local row order == canonical
            # order and segment_max tie-breaks match the unsharded engine)
            ein = max(1, max(len(r) for r in rows_by_shard))
            row_of = np.full(E, -1, np.int64)
            for rows in rows_by_shard:
                row_of[rows] = np.arange(len(rows))
        self._ein = ein

        i32, f32 = np.int32, np.float32
        has_f = self._has_faults
        if has_f:
            # per-canonical-edge fault parameters, re-laid-out onto this
            # shard's local rows (and, below, its boundary send tables) so
            # every kill draw stays keyed by canonical edge id
            loss_e = np.asarray(self._loss, f32)
            flap_e = np.asarray(self._flap, f32)
            dead_e = np.asarray(self._dead, bool)
            row_loss = np.zeros((S, ein), f32)
            row_flap = np.zeros((S, ein), f32)
            row_dead = np.zeros((S, ein), bool)
        row_canon = np.zeros((S, ein), i32)
        row_valid = np.zeros((S, ein), bool)
        row_dst = np.full((S, ein), m, i32)
        row_src = np.full((S, ein), m, i32)       # sentinel m: not interior
        row_interior = np.zeros((S, ein), bool)
        row_out_slot = np.zeros((S, ein), i32)
        row_rev = np.full((S, ein), ein, i32)     # sentinel ein: not local
        row_halo_key = np.full((S, ein), 4 * m, i32)
        row_lat = np.zeros((S, ein), f32)
        for s in range(S):
            e = rows_by_shard[s]
            r = row_of[e]   # packed ascending (edge) or receiver-major
            interior = src_sh[e] == s
            row_canon[s, r] = e
            row_valid[s, r] = True
            row_dst[s, r] = ldst[e] - s * m
            row_src[s, r] = np.where(interior, lsrc[e] - s * m, m)
            row_interior[s, r] = interior
            row_out_slot[s, r] = out_slot[e]
            # rev edge (dst, src) drains at src — local iff this edge is
            # interior; boundary rows get their touch stamp via exchange
            row_rev[s, r] = np.where(interior, row_of[rev[e]], ein)
            row_halo_key[s, r] = (ldst[e] - s * m) * 4 + slot[e]
            row_lat[s, r] = lat_base[e]
            if has_f:
                row_loss[s, r] = loss_e[e]
                row_flap[s, r] = flap_e[e]
                row_dead[s, r] = dead_e[e]

        # boundary edges grouped by shard offset: one ppermute per offset
        bnd = np.where(src_sh != dst_sh)[0]
        offs = ((dst_sh[bnd] - src_sh[bnd]) % S).astype(np.int64)
        self._offsets = sorted(int(d) for d in set(offs.tolist()))
        self._bnd_bd: Dict[int, int] = {}
        bnd_tables: Dict[str, Dict[str, np.ndarray]] = {}
        for d in self._offsets:
            sel = bnd[offs == d]
            per_s = [sel[src_sh[sel] == s] for s in range(S)]  # canon order
            bd = max(1, max(len(p) for p in per_s))
            self._bnd_bd[d] = bd
            snd_src = np.full((S, bd), m, i32)
            snd_oslot = np.zeros((S, bd), i32)
            snd_rev = np.full((S, bd), ein, i32)
            snd_canon = np.zeros((S, bd), i32)
            snd_lat = np.zeros((S, bd), f32)
            rcv_row = np.full((S, bd), ein, i32)
            if has_f:
                snd_loss = np.zeros((S, bd), f32)
                snd_flap = np.zeros((S, bd), f32)
                snd_dead = np.zeros((S, bd), bool)
            for s in range(S):
                e = per_s[s]
                k = len(e)
                snd_src[s, :k] = lsrc[e] - s * m
                snd_oslot[s, :k] = out_slot[e]
                snd_rev[s, :k] = row_of[rev[e]]
                snd_canon[s, :k] = e
                snd_lat[s, :k] = lat_base[e]
                if has_f:
                    snd_loss[s, :k] = loss_e[e]
                    snd_flap[s, :k] = flap_e[e]
                    snd_dead[s, :k] = dead_e[e]
                # sender s's entry j lands at receiver (s+d)%S, entry j
                rcv_row[(s + d) % S, :k] = row_of[e]
            bnd_tables[str(d)] = dict(
                snd_src=snd_src, snd_oslot=snd_oslot, snd_rev=snd_rev,
                snd_canon=snd_canon, snd_lat=snd_lat, rcv_row=rcv_row)
            if has_f:
                bnd_tables[str(d)].update(
                    snd_loss=snd_loss, snd_flap=snd_flap, snd_dead=snd_dead)

        # compact boundary-row set: the union of every offset's receiver
        # rows, per shard.  Mid push passes (superstep/pipelined boundary
        # windows) touch ONLY these rows — gather the sub-rings, push, and
        # scatter back — instead of sweeping all ein rows W times.
        bnd_rows = [set() for _ in range(S)]
        for d in self._offsets:
            rr = bnd_tables[str(d)]["rcv_row"]
            for s in range(S):
                bnd_rows[s].update(int(r) for r in rr[s] if r < ein)
        eb = max(1, max((len(x) for x in bnd_rows), default=1))
        self._eb = eb
        rows_bnd = np.full((S, eb), ein, i32)  # sentinel ein: scatter-drop
        pos_of: List[Dict[int, int]] = []
        for s in range(S):
            rs = sorted(bnd_rows[s])
            rows_bnd[s, :len(rs)] = rs
            pos_of.append({r: i for i, r in enumerate(rs)})
        for d in self._offsets:
            tb = bnd_tables[str(d)]
            rcv_pos = np.full(tb["rcv_row"].shape, eb, i32)
            for s in range(S):
                for j, r in enumerate(tb["rcv_row"][s].tolist()):
                    if r < ein:
                        rcv_pos[s, j] = pos_of[s][r]
            tb["rcv_pos"] = rcv_pos

        extra = {}
        if has_f:
            extra.update(row_loss=row_loss, row_flap=row_flap,
                         row_dead=row_dead)
        if self._any_crashed:
            extra["crashed"] = (
                np.asarray(self._crashed)[perm].reshape(S, m))
        self._statics = jax.tree.map(jnp.asarray, dict(
            pids=perm.reshape(S, m).astype(i32),
            cfactor=np.asarray(self._cfactor)[perm].reshape(S, m),
            deg=np.asarray(self._deg)[perm].reshape(S, m).astype(i32),
            row_canon=row_canon, row_valid=row_valid, row_dst=row_dst,
            row_src=row_src, row_interior=row_interior,
            row_out_slot=row_out_slot, row_rev=row_rev,
            row_halo_key=row_halo_key, row_lat=row_lat,
            rows_bnd=rows_bnd, bnd=bnd_tables, bmem=bucket_members,
            **extra))
        self._crashed_pos = jnp.asarray(np.asarray(self._crashed)[perm])
        self._perm_np = perm
        self._inv_np = inv

    # ------------------------------------------------------------------
    # Layout transforms around the sharded dispatch
    # ------------------------------------------------------------------
    def _edge_state(self) -> Dict[str, jax.Array]:
        """Empty rings in padded per-shard layout: ``S * ein`` rows, row
        ``s * ein + j`` = shard s's local row j.  All-constant, so no
        canonical-order gather is needed (and the full-population edge
        arrays are never allocated)."""
        return self.core.edge_rings(self.shards * self._ein)

    def _init_carry(self, seed):
        carry = super()._init_carry(seed)
        if (self.scheduler == "pipelined" and
                self.cfg.mode != AsyncMode.NO_COMM):
            # double-buffer carry entries, already in per-shard layout
            # (axis 0 partitioned like the edge keys):
            #   fly_fwd_<off>  shadow buffers staged at the previous
            #                  boundary, in flight toward their receiver —
            #                  pushed into rings at the NEXT boundary
            #   fly_acc_<off>  packed (att << 1) | accept bits returning to
            #                  the sender — folded into counters at the
            #                  next boundary
            # all-zero init: att = 0 entries are no-ops at the first
            # boundary, so the pipeline fills naturally.
            W, S, Lp = self.superstep, self.shards, self.bapp.payload_len
            for off in self._offsets:
                bd = self._bnd_bd[off]
                carry[f"fly_fwd_{off}"] = jnp.zeros((S * W, bd, Lp + 3),
                                                    jnp.int32)
                carry[f"fly_acc_{off}"] = jnp.zeros((S * W, bd), jnp.int32)
            if self.cfg.mode in BARRIER_MODES:
                # per-shard staged release decision (PipelinedRelease):
                # reductions issued at boundary i, consumed at i+1
                carry["rel_ready"] = jnp.zeros(S, bool)
                carry["rel_t"] = jnp.full(S, -np.inf, jnp.float32)
                if self.cfg.barrier_timeout > 0:
                    # quarantine gate's cohort front rides the same
                    # one-boundary stage as the release decision
                    carry["rel_ref"] = jnp.full(S, -np.inf, jnp.float32)
        return carry

    def _to_sharded_layout(self, carry):
        """Permute process-axis leaves into shard order (edge leaves are
        already built in padded per-shard layout by ``_edge_state``)."""
        perm = self._perm_np
        out = dict(carry)
        for key in _PROC_KEYS:
            if key in carry:
                out[key] = carry[key][:, perm]
        out["app"] = jax.tree.map(lambda x: x[:, perm], carry["app"])
        return out

    def _to_canonical_layout(self, carry):
        """Undo the process permutation on everything ``_assemble`` reads."""
        inv = self._inv_np
        out = dict(carry)
        for key in _PROC_KEYS:
            if key in carry:
                out[key] = carry[key][:, inv]
        out["app"] = jax.tree.map(lambda x: x[:, inv], carry["app"])
        return out

    def _carry_specs(self, carry):
        specs = jax.tree.map(lambda _: P(None, SHARD_AXIS), carry)
        for key in _SCALAR_KEYS:
            specs[key] = P(None)
        return specs

    # ------------------------------------------------------------------
    # Shard-local window phases: thin wrappers over the shared core with
    # this shard's sentinel-padded tables
    # ------------------------------------------------------------------
    def _dense_spec_local(self, st) -> DenseSpec:
        """This shard's bucket-slab geometry: static offsets shared by all
        shards, member tables from the sharded statics (identity buckets
        skip theirs and take the zero-gather fast path)."""
        slabs = tuple(
            BucketSlab(start=start, nb=nb, deg=deg,
                       members=None if ident else st["bmem"][str(bi)])
            for bi, (start, nb, deg, ident)
            in enumerate(self._bucket_geom))
        return DenseSpec(n_dst=self._m, n_rows=self._ein, buckets=slabs)

    def _drain_phase(self, st, carry, t_pad, act_pad):
        """Drain every local ring (they live on their receiver's shard)
        through the shared core, with this shard's row tables."""
        return self.core.drain(
            carry, t_pad[st["row_dst"]], act_pad[st["row_dst"]],
            halo_key=st["row_halo_key"], n_halo=4 * self._m,
            dst=st["row_dst"], n_dst=self._m,
            dense_spec=(self._dense_spec_local(st)
                        if self.lplan.kind == "dense" else None))

    def _stage_offsets(self, st, t_pad, act_pad, eo_pad, ptouch_pad,
                       seed, steps_pad):
        """Sender-side staging of this window's boundary sends: one packed
        ``(bd, L+3)`` i32 buffer per shard offset — payload bits, then the
        availability stamp ``t_src + latency``, the reverse-edge touch
        counter, and the sender-active bit.  Stamps are drawn NOW, at the
        sender's window, so a batched exchange at the superstep boundary
        still delivers exact virtual-time metadata (latency/clumpiness QoS
        is computed from these stamps, not from arrival windows).

        Typed fault kills (lossy / flapping / dead-destination links,
        DESIGN.md §14) are decided HERE, sender-side: a killed boundary
        send is staged with a zero att bit — it never crosses the mesh as
        an attempt — and its attempted/dropped/cause counts come back as
        the second return value ``(m, 2)`` [loss, dead] for the caller to
        fold in this very window, exactly when the unsharded engine counts
        it.  Draws are keyed by canonical edge id and sender step count,
        so kill decisions are shard-count invariant."""
        cfg, m = self.cfg, self._m
        staged = {}
        bks = (jnp.zeros((m, 2), jnp.int32) if self._has_faults else None)
        for off in self._offsets:
            b = st["bnd"][str(off)]
            # latency draws keyed by (canonical edge id, sender step
            # count): identical to the unsharded engine's per-edge stream,
            # and invariant to which lockstep window the send runs under
            lat_b = b["snd_lat"] * lognormal_factor(
                cfg.latency_sigma, seed, STREAM_LAT, b["snd_canon"],
                steps_pad[b["snd_src"]])
            pay_b = eo_pad[b["snd_src"], b["snd_oslot"]]
            avail_b = t_pad[b["snd_src"]] + lat_b
            att_b = act_pad[b["snd_src"]]
            tch_b = ptouch_pad[b["snd_rev"]]
            if self._has_faults:
                l_k, d_k = self.core.fault_masks(
                    seed, t_pad[b["snd_src"]], steps_pad[b["snd_src"]],
                    b["snd_canon"], b["snd_loss"], b["snd_flap"],
                    self.faults.flap_period, b["snd_dead"])
                cols = jnp.stack([(att_b & l_k).astype(jnp.int32),
                                  (att_b & d_k).astype(jnp.int32)], axis=1)
                bks = bks + jax.ops.segment_sum(
                    cols, b["snd_src"], num_segments=m + 1)[:m]
                att_b = att_b & ~(l_k | d_k)
            staged[str(off)] = jnp.concatenate([
                _bits_i32(pay_b),
                _bits_i32(avail_b)[:, None],
                tch_b[:, None],
                att_b[:, None].astype(jnp.int32)], axis=1)
        return staged, bks

    def _interior_kills(self, st, seed, t_pad, steps_pad, x_act):
        """Kill mask + per-process ``(m, 2)`` [loss, dead] counts for this
        window's interior sends, from the same canonical-eid draws as the
        unsharded engine.  Boundary and padding rows carry the m sentinel
        in ``row_src``, so their counts fall into the spare segment (and
        their garbage draws are masked by ``x_act``)."""
        loss_kill, dead_kill = self.core.fault_masks(
            seed, t_pad[st["row_src"]], steps_pad[st["row_src"]],
            st["row_canon"], st["row_loss"], st["row_flap"],
            self.faults.flap_period, st["row_dead"])
        cols = jnp.stack([(x_act & loss_kill).astype(jnp.int32),
                          (x_act & dead_kill).astype(jnp.int32)], axis=1)
        ks = jax.ops.segment_sum(cols, st["row_src"],
                                 num_segments=self._m + 1)[:self._m]
        return loss_kill | dead_kill, ks

    def _close_window(self, st, u, active, drained_r, *, release: bool):
        """Shared window tail with mesh release reductions; mid-superstep
        windows (``release=False``) skip the cross-shard pmin/pmax check —
        waiting processes stay waiting until the superstep boundary."""
        return self.core.close_window(
            u, active, drained_r, pids=st["pids"], deg=st["deg"],
            cfactor=st["cfactor"],
            release=self._release if release else None)

    # ------------------------------------------------------------------
    # Window bodies
    # ------------------------------------------------------------------
    def _local_window(self, st, carry):
        """One mid-superstep lockstep window: entirely shard-local.

        Interior edges exchange through their (local) rings as usual;
        boundary sends are packed into per-offset staging buffers and
        returned for the superstep scan to stack.  No collectives run, so
        each shard advances at its own jittered pace — fault-injected
        shards simply fall behind in virtual time.
        """
        cfg, m = self.cfg, self._m
        comm = cfg.mode != AsyncMode.NO_COMM
        seed, t = carry["seed"], carry["t"]
        active = ~carry["done"] & ~carry["waiting"]
        if self._any_crashed:
            active = active & ~st["crashed"]
        # sentinel-padded per-process vectors: index m = inactive dummy
        t_pad = jnp.concatenate([t, jnp.zeros(1, t.dtype)])
        act_pad = jnp.concatenate([active, jnp.zeros(1, bool)])
        u = dict(carry)
        drained_r = jnp.zeros(m, jnp.int32)
        staged = {}
        if comm:
            dr, drained_r = self._drain_phase(st, carry, t_pad, act_pad)
            u.update(dr)
        app_state, edges_out, steps = self.core.compute(
            carry, active, u["halo"], st["pids"])
        u.update(app=app_state, steps=steps)
        if comm:
            eo_pad = jnp.concatenate(
                [edges_out, jnp.zeros((1,) + edges_out.shape[1:],
                                      edges_out.dtype)])
            ptouch_pad = jnp.concatenate([u["ptouch"],
                                          jnp.zeros(1, jnp.int32)])
            steps_pad = jnp.concatenate([steps, jnp.zeros(1, jnp.int32)])
            staged, bks = self._stage_offsets(st, t_pad, act_pad, eo_pad,
                                              ptouch_pad, seed, steps_pad)
            # interior-only send attempt (drop iff full)
            lat_row = st["row_lat"] * lognormal_factor(
                cfg.latency_sigma, seed, STREAM_LAT, st["row_canon"],
                steps_pad[st["row_src"]])
            x_act = act_pad[st["row_src"]] & st["row_interior"]
            send_act = x_act
            if self._has_faults:
                kill, iks = self._interior_kills(st, seed, t_pad,
                                                 steps_pad, x_act)
                send_act = x_act & ~kill
            sp = self.core.send_edge(
                u, t_pad[st["row_src"]] + lat_row, send_act,
                jnp.float32(0.0), ptouch_pad[st["row_rev"]],
                eo_pad[st["row_src"], st["row_out_slot"]],
                st["row_src"], m)
            u.update(sp.rings)
            if self._has_faults:
                ks = bks + iks
                killed = ks[:, 0] + ks[:, 1]
                u.update(c_att=carry["c_att"] + sp.sums[:, 0] + killed,
                         c_ok=carry["c_ok"] + sp.sums[:, 1],
                         c_drop=carry["c_drop"] + sp.sums[:, 2] + killed,
                         c_loss=carry["c_loss"] + ks[:, 0],
                         c_dead=carry["c_dead"] + ks[:, 1])
            else:
                u.update(c_att=carry["c_att"] + sp.sums[:, 0],
                         c_ok=carry["c_ok"] + sp.sums[:, 1],
                         c_drop=carry["c_drop"] + sp.sums[:, 2])
        return self._close_window(st, u, active, drained_r,
                                  release=False), staged

    def _final_window(self, st, carry, stage_mid):
        """The superstep-end window: the only one that talks to peers.

        All staged boundary windows (plus this window's own) move in ONE
        packed ppermute per shard offset; the receiver pushes them into its
        rings in sender-window order (drop iff full per push, FIFO
        preserved), and the accept bits return in one packed reverse
        ppermute per offset so sender-side attempted/ok/dropped counters
        stay exact.  With ``superstep_windows=1`` this is operation-for-
        operation the per-window exchange engine.
        """
        cfg, m, S = self.cfg, self._m, self.shards
        comm = cfg.mode != AsyncMode.NO_COMM
        seed, t = carry["seed"], carry["t"]
        active = ~carry["done"] & ~carry["waiting"]
        if self._any_crashed:
            active = active & ~st["crashed"]
        t_pad = jnp.concatenate([t, jnp.zeros(1, t.dtype)])
        act_pad = jnp.concatenate([active, jnp.zeros(1, bool)])
        u = dict(carry)
        drained_r = jnp.zeros(m, jnp.int32)
        if comm:
            dr, drained_r = self._drain_phase(st, carry, t_pad, act_pad)
            u.update(dr)
        app_state, edges_out, steps = self.core.compute(
            carry, active, u["halo"], st["pids"])
        u.update(app=app_state, steps=steps)
        if comm:
            Lp = self.bapp.payload_len
            eo_pad = jnp.concatenate(
                [edges_out, jnp.zeros((1,) + edges_out.shape[1:],
                                      edges_out.dtype)])
            ptouch_pad = jnp.concatenate([u["ptouch"],
                                          jnp.zeros(1, jnp.int32)])
            steps_pad = jnp.concatenate([steps, jnp.zeros(1, jnp.int32)])
            own, bks = self._stage_offsets(st, t_pad, act_pad, eo_pad,
                                           ptouch_pad, seed, steps_pad)
            # --- payload hop: ONE packed ppermute per offset for all W ----
            staged_l, staged_r = {}, {}
            for off in self._offsets:
                key = str(off)
                full = (own[key][None] if stage_mid is None else
                        jnp.concatenate([stage_mid[key], own[key][None]],
                                        axis=0))
                staged_l[key] = full     # sender-local copy: the att bits
                staged_r[key] = jax.lax.ppermute(
                    full, SHARD_AXIS,
                    [(i, (i + off) % S) for i in range(S)])

            # interior send inputs for THIS window
            lat_row = st["row_lat"] * lognormal_factor(
                cfg.latency_sigma, seed, STREAM_LAT, st["row_canon"],
                steps_pad[st["row_src"]])
            int_pay = eo_pad[st["row_src"], st["row_out_slot"]]
            int_avail = t_pad[st["row_src"]] + lat_row
            int_act = act_pad[st["row_src"]] & st["row_interior"]
            int_tch = ptouch_pad[st["row_rev"]]
            if self._has_faults:
                kill, iks = self._interior_kills(st, seed, t_pad,
                                                 steps_pad, int_act)
                int_act = int_act & ~kill

            rings = {key: u[key] for key in
                     ("q_avail", "q_touch", "q_head", "q_size", "q_pay")}
            rings, acc, send_sums = self._push_passes(
                st, rings, staged_r, int_pay, int_avail, int_act, int_tch)
            u.update(rings)

            # --- accept hop: ONE packed reverse ppermute per offset -------
            for off in self._offsets:
                b = st["bnd"][str(off)]
                acc_back = jax.lax.ppermute(
                    acc[str(off)], SHARD_AXIS,
                    [(i, (i - off) % S) for i in range(S)])
                att = staged_l[str(off)][:, :, Lp + 2].astype(bool)
                ok = acc_back.astype(bool)
                cols_b = jnp.stack([
                    att.astype(jnp.int32).sum(0),
                    (att & ok).astype(jnp.int32).sum(0),
                    (att & ~ok).astype(jnp.int32).sum(0)], axis=1)
                send_sums = send_sums + jax.ops.segment_sum(
                    cols_b, b["snd_src"], num_segments=m + 1)[:m]
            if self._has_faults:
                # killed sends (att bit zeroed at staging) fold here: they
                # count attempted + dropped + cause, never ok
                ks = bks + iks
                killed = ks[:, 0] + ks[:, 1]
                u.update(c_att=carry["c_att"] + send_sums[:, 0] + killed,
                         c_ok=carry["c_ok"] + send_sums[:, 1],
                         c_drop=carry["c_drop"] + send_sums[:, 2] + killed,
                         c_loss=carry["c_loss"] + ks[:, 0],
                         c_dead=carry["c_dead"] + ks[:, 1])
            else:
                u.update(c_att=carry["c_att"] + send_sums[:, 0],
                         c_ok=carry["c_ok"] + send_sums[:, 1],
                         c_drop=carry["c_drop"] + send_sums[:, 2])
        return self._close_window(st, u, active, drained_r, release=True)

    def _push_passes(self, st, rings, bufs, int_pay, int_avail, int_act,
                     int_tch, *, want_sums: bool = True):
        """W ordered push passes over this shard's rings (FIFO per ring).

        ``bufs`` holds one receiver-side packed ``(W, bd, L+3)`` buffer per
        shard offset.  Boundary rows push buffer window j in pass j;
        interior rows push their current message in the last pass (their
        own window).  Rings are single-writer, so the row sets are disjoint
        and pass composition is exact.

        Passes 0..W-2 have no interior senders, so they run COMPACT: the
        static union of boundary receiver rows (``rows_bnd``, eb rows —
        a small fraction of ein on low-surface shardings) is gathered into
        a sub-ring block, pushed through the shared core, and scattered
        back.  Only the final pass sweeps all ein rows, so boundary-window
        send cost is ~one full sweep + (W-1) boundary-sized sweeps instead
        of W full sweeps.  Returns ``(rings, acc, sums)``: the updated
        ring dict, per-offset ``(W, bd)`` i32 accept bits, and the final
        pass's interior counter sums (``None`` unless ``want_sums`` —
        boundary rows carry the m sentinel in ``row_src``, so their
        contributions drop into the spare segment).
        """
        m, ein, W = self._m, self._ein, self.superstep
        eb = self._eb
        Lp = self.bapp.payload_len
        pay_dtype = int_pay.dtype
        rings = dict(rings)
        ring_keys = ("q_avail", "q_touch", "q_head", "q_size", "q_pay")
        rows_bnd = st["rows_bnd"]  # pad entries carry the ein sentinel
        acc = {str(off): [] for off in self._offsets}
        sums = None
        for j in range(W):
            last = j == W - 1
            if not last and not self._offsets:
                continue
            if last:
                # full-width pass: interior rows send their own message,
                # boundary rows push buffer window W-1
                x_pay = int_pay
                x_avail = int_avail
                x_act = int_act
                x_tch = int_tch
            else:
                # compact pass: only boundary rows are live, so gather the
                # union-of-offsets row subset, push into the sub-rings, and
                # scatter the touched rows back (rows_bnd pads carry the
                # ein sentinel: the gather clamps, the scatter drops)
                x_pay = jnp.zeros((eb,) + int_pay.shape[1:], pay_dtype)
                x_avail = jnp.zeros(eb, jnp.float32)
                x_act = jnp.zeros(eb, bool)
                x_tch = jnp.zeros(eb, jnp.int32)
            for off in self._offsets:
                b = st["bnd"][str(off)]
                buf = bufs[str(off)][j]
                rr = b["rcv_row"] if last else b["rcv_pos"]
                x_pay = x_pay.at[rr].set(
                    _from_bits(buf[:, :Lp], pay_dtype), mode="drop")
                x_avail = x_avail.at[rr].set(
                    _from_bits(buf[:, Lp], jnp.float32), mode="drop")
                x_tch = x_tch.at[rr].set(buf[:, Lp + 1], mode="drop")
                x_act = x_act.at[rr].set(buf[:, Lp + 2].astype(bool),
                                         mode="drop")
            if last:
                sp = self.core.send_edge(
                    rings, x_avail, x_act, jnp.float32(0.0), x_tch, x_pay,
                    st["row_src"], m, want_sums=want_sums)
                rings.update(sp.rings)
                acc_pad = jnp.concatenate([sp.accepted,
                                           jnp.zeros(1, bool)])
                for off in self._offsets:
                    acc[str(off)].append(
                        acc_pad[st["bnd"][str(off)]["rcv_row"]])
                if want_sums:
                    sums = sp.sums
            else:
                sub = {key: rings[key][rows_bnd] for key in ring_keys}
                sp = self.core.send_edge(
                    sub, x_avail, x_act, jnp.float32(0.0), x_tch, x_pay,
                    jnp.zeros(eb, jnp.int32), 1, want_sums=False)
                for key in ring_keys:
                    if key in sp.rings:
                        rings[key] = rings[key].at[rows_bnd].set(
                            sp.rings[key], mode="drop")
                acc_pad = jnp.concatenate([sp.accepted,
                                           jnp.zeros(1, bool)])
                for off in self._offsets:
                    acc[str(off)].append(
                        acc_pad[st["bnd"][str(off)]["rcv_pos"]])
        acc = {key: jnp.stack(v).astype(jnp.int32)
               for key, v in acc.items()}
        return rings, acc, sums

    def _final_window_pipelined(self, st, carry, stage_mid):
        """Superstep-boundary window of the ``pipelined`` scheduler.

        Double-buffered exchange (DESIGN.md §12): this boundary PUSHES the
        shadow buffers that arrived during the superstep (staged at the
        previous boundary), FOLDS the accept/attempt bits that returned
        for the previous boundary's pushes, then DISPATCHES this
        superstep's own staged buffers forward and this boundary's accept
        bits backward — both consumed only at the NEXT boundary, so
        neither collective's result blocks the next superstep's interior
        windows.  Boundary messages arrive exactly one superstep later
        than under ``scheduler='superstep'``; their availability stamps
        are unchanged (drawn at the sender's window), so the shift is
        honest added latency that the QoS stream observes.
        """
        cfg, m, S = self.cfg, self._m, self.shards
        comm = cfg.mode != AsyncMode.NO_COMM
        seed, t = carry["seed"], carry["t"]
        active = ~carry["done"] & ~carry["waiting"]
        if self._any_crashed:
            active = active & ~st["crashed"]
        t_pad = jnp.concatenate([t, jnp.zeros(1, t.dtype)])
        act_pad = jnp.concatenate([active, jnp.zeros(1, bool)])
        u = dict(carry)
        drained_r = jnp.zeros(m, jnp.int32)
        if comm:
            dr, drained_r = self._drain_phase(st, carry, t_pad, act_pad)
            u.update(dr)
        app_state, edges_out, steps = self.core.compute(
            carry, active, u["halo"], st["pids"])
        u.update(app=app_state, steps=steps)
        if comm:
            Lp = self.bapp.payload_len
            eo_pad = jnp.concatenate(
                [edges_out, jnp.zeros((1,) + edges_out.shape[1:],
                                      edges_out.dtype)])
            ptouch_pad = jnp.concatenate([u["ptouch"],
                                          jnp.zeros(1, jnp.int32)])
            steps_pad = jnp.concatenate([steps, jnp.zeros(1, jnp.int32)])
            own, bks = self._stage_offsets(st, t_pad, act_pad, eo_pad,
                                           ptouch_pad, seed, steps_pad)

            # interior send inputs for THIS window
            lat_row = st["row_lat"] * lognormal_factor(
                cfg.latency_sigma, seed, STREAM_LAT, st["row_canon"],
                steps_pad[st["row_src"]])
            int_pay = eo_pad[st["row_src"], st["row_out_slot"]]
            int_avail = t_pad[st["row_src"]] + lat_row
            int_act = act_pad[st["row_src"]] & st["row_interior"]
            int_tch = ptouch_pad[st["row_rev"]]
            if self._has_faults:
                kill, iks = self._interior_kills(st, seed, t_pad,
                                                 steps_pad, int_act)
                int_act = int_act & ~kill

            # --- push the shadow buffers staged at the PREVIOUS boundary --
            bufs = {str(off): u[f"fly_fwd_{off}"] for off in self._offsets}
            rings = {key: u[key] for key in
                     ("q_avail", "q_touch", "q_head", "q_size", "q_pay")}
            rings, acc, send_sums = self._push_passes(
                st, rings, bufs, int_pay, int_avail, int_act, int_tch)
            u.update(rings)

            # --- fold the bits that returned for the previous boundary's
            # pushes: packed (att << 1) | accept, already on their sender
            for off in self._offsets:
                b = st["bnd"][str(off)]
                bits = u[f"fly_acc_{off}"]
                att = (bits >> 1) & 1
                okb = bits & 1
                cols_b = jnp.stack([
                    att.sum(0),
                    (att & okb).sum(0),
                    (att & (1 - okb)).sum(0)], axis=1)
                send_sums = send_sums + jax.ops.segment_sum(
                    cols_b, b["snd_src"], num_segments=m + 1)[:m]
            if self._has_faults:
                # kills are counted at stage time (this window), while the
                # killed sends' att bits are zero for the rest of the
                # pipeline — the deferred folds never see them
                ks = bks + iks
                killed = ks[:, 0] + ks[:, 1]
                u.update(c_att=carry["c_att"] + send_sums[:, 0] + killed,
                         c_ok=carry["c_ok"] + send_sums[:, 1],
                         c_drop=carry["c_drop"] + send_sums[:, 2] + killed,
                         c_loss=carry["c_loss"] + ks[:, 0],
                         c_dead=carry["c_dead"] + ks[:, 1])
            else:
                u.update(c_att=carry["c_att"] + send_sums[:, 0],
                         c_ok=carry["c_ok"] + send_sums[:, 1],
                         c_drop=carry["c_drop"] + send_sums[:, 2])

            # --- dispatch the next hops, consumed at the NEXT boundary ----
            for off in self._offsets:
                key = str(off)
                full = (own[key][None] if stage_mid is None else
                        jnp.concatenate([stage_mid[key], own[key][None]],
                                        axis=0))
                u[f"fly_fwd_{off}"] = jax.lax.ppermute(
                    full, SHARD_AXIS,
                    [(i, (i + off) % S) for i in range(S)])
                att_r = bufs[key][:, :, Lp + 2]
                u[f"fly_acc_{off}"] = jax.lax.ppermute(
                    (att_r << 1) | acc[key], SHARD_AXIS,
                    [(i, (i - off) % S) for i in range(S)])
        return self._close_window(st, u, active, drained_r, release=True)

    def _flush_body(self, st, u):
        """Epilogue flush of the pipeline's in-flight state (one shard,
        one replicate): fold the carried accept bits, deliver the carried
        shadow buffers, and fold the bits those pushes produce.  Every
        step is gated on att bits, so anything the natural post-done
        supersteps already processed is a no-op — the flush only
        guarantees conservation when the run ends with a live superstep
        still in flight."""
        m, S = self._m, self.shards
        ein, Lp = self._ein, self.bapp.payload_len
        u = dict(u)
        send_sums = jnp.zeros((m, 3), jnp.int32)

        def fold(bits, b, sums):
            att = (bits >> 1) & 1
            okb = bits & 1
            cols_b = jnp.stack([
                att.sum(0), (att & okb).sum(0),
                (att & (1 - okb)).sum(0)], axis=1)
            return sums + jax.ops.segment_sum(
                cols_b, b["snd_src"], num_segments=m + 1)[:m]

        for off in self._offsets:
            send_sums = fold(u[f"fly_acc_{off}"], st["bnd"][str(off)],
                             send_sums)
        bufs = {str(off): u[f"fly_fwd_{off}"] for off in self._offsets}
        rings = {key: u[key] for key in
                 ("q_avail", "q_touch", "q_head", "q_size", "q_pay")}
        rings, acc, _ = self._push_passes(
            st, rings,
            bufs,
            jnp.zeros((ein, Lp), self.bapp.payload_dtype),
            jnp.zeros(ein, jnp.float32), jnp.zeros(ein, bool),
            jnp.zeros(ein, jnp.int32), want_sums=False)
        u.update(rings)
        for off in self._offsets:
            att_r = bufs[str(off)][:, :, Lp + 2]
            bits_back = jax.lax.ppermute(
                (att_r << 1) | acc[str(off)], SHARD_AXIS,
                [(i, (i - off) % S) for i in range(S)])
            send_sums = fold(bits_back, st["bnd"][str(off)], send_sums)
            u[f"fly_fwd_{off}"] = jnp.zeros_like(u[f"fly_fwd_{off}"])
            u[f"fly_acc_{off}"] = jnp.zeros_like(u[f"fly_acc_{off}"])
        u.update(c_att=u["c_att"] + send_sums[:, 0],
                 c_ok=u["c_ok"] + send_sums[:, 1],
                 c_drop=u["c_drop"] + send_sums[:, 2])
        return u

    def _get_flusher(self):
        if self._flusher is None:
            def flush_fn(st, carry):
                st = jax.tree.map(lambda a: a[0], st)
                return jax.vmap(lambda c: self._flush_body(st, c))(carry)
            sspecs = jax.tree.map(lambda _: P(SHARD_AXIS), self._statics)
            f = jax.shard_map(flush_fn, mesh=self.mesh,
                              in_specs=(sspecs, self._cspecs),
                              out_specs=self._cspecs, check_vma=False)
            self._flusher = jax.jit(f, donate_argnums=1)
        return self._flusher

    # ------------------------------------------------------------------
    def _get_runner(self):
        if self._runner is None:
            spans.key_compiles_by_names()
            W = self.superstep
            final = (self._final_window_pipelined
                     if self.scheduler == "pipelined"
                     else self._final_window)

            def chunk_fn(st, carry):
                st = jax.tree.map(lambda a: a[0], st)  # (1, ...) -> local

                def superstep(c, _):
                    if W > 1:
                        c, stage_mid = jax.lax.scan(
                            lambda cc, __: self._local_window(st, cc),
                            c, None, length=W - 1)
                    else:
                        stage_mid = None
                    return final(st, c, stage_mid), None

                def one(c):
                    c, _ = jax.lax.scan(
                        superstep, c, None,
                        length=self._supersteps_per_dispatch)
                    return c
                # replicate (seed) axis vmaps INSIDE each shard
                return jax.vmap(one)(carry)

            sspecs = jax.tree.map(lambda _: P(SHARD_AXIS), self._statics)
            # replication checking off: the bodies mix per-shard state
            # with cross-shard collectives, which the checker over-rejects
            f = jax.shard_map(chunk_fn, mesh=self.mesh,
                              in_specs=(sspecs, self._cspecs),
                              out_specs=self._cspecs, check_vma=False)
            self._runner = jax.jit(f, donate_argnums=1)
        return self._runner

    # ------------------------------------------------------------------
    def run_replicates(self, seeds: Sequence[int]) -> List[SimResult]:
        """One replicate per seed: a single sharded, vmapped dispatch."""
        carries = [self._init_carry(int(s)) for s in seeds]
        carry = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *carries)
        carry = self._to_sharded_layout(carry)
        if self._cspecs is None:
            self._cspecs = self._carry_specs(carry)
        carry = jax.device_put(carry, jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), self._cspecs,
            is_leaf=lambda x: isinstance(x, P)))
        if self._statics_sharded is None:
            self._statics_sharded = jax.device_put(
                self._statics, jax.tree.map(
                    lambda _: NamedSharding(self.mesh, P(SHARD_AXIS)),
                    self._statics))
        runner = self._get_runner()
        windows = chunks = 0
        prev_done = None
        while windows < self._max_windows:
            with spans.span("loop.dispatch"):
                carry = runner(self._statics_sharded, carry)
                # crashed processes never reach the horizon; the probe
                # treats them as terminally stopped (position order, like
                # the carry)
                all_done = (jnp.all(carry["done"] | self._crashed_pos)
                            if self._any_crashed else jnp.all(carry["done"]))
            windows += self._windows_per_dispatch
            chunks += 1
            # pipelined early-exit probe (same pattern as JaxEngine): only
            # the *previous* dispatch's done reduction is read, so the host
            # never stalls the mesh on a fresh round-trip — at the cost of
            # one state-invariant extra dispatch after the run completes.
            if prev_done is not None:
                with spans.span("loop.probe"):
                    stop = bool(prev_done)
                if stop:
                    break
            prev_done = all_done
        spans.count("loop.chunks", chunks)
        if (self.scheduler == "pipelined" and
                self.cfg.mode != AsyncMode.NO_COMM):
            # epilogue flush: deliver/fold whatever is still in flight so
            # message conservation holds even when the loop exits with a
            # live superstep's exchange un-consumed
            carry = self._get_flusher()(self._statics_sharded, carry)
        with spans.span("loop.fetch"):
            carry = jax.device_get(carry)
        spans.count("loop.fetch_bytes",
                    sum(x.nbytes for x in jax.tree_util.tree_leaves(carry)))
        carry = self._to_canonical_layout(carry)
        if getattr(self, "debug_keep_carry", False):
            self._final_carry = carry
        with spans.span("loop.assemble"):
            return [self._assemble(carry, r) for r in range(len(seeds))]
