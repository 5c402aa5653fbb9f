"""Property tests for the duct_exchange ring ops (DESIGN.md §7).

Random op sequences over a batch of bounded FIFO rings, checked two ways
each step: slot-exact agreement between the jnp ops and the numpy oracle
(``ref.duct_exchange_ref``), and model-level invariants against a python
mirror queue per ring:

  drop-iff-full   a send is accepted iff the post-drain ring has room
  FIFO order      drains pop in push order, never jumping a
                  not-yet-available head, at most ``max_pops`` per window
  conservation    accepted == delivered + in-flight and
                  attempted == accepted + dropped, per ring, every step

Runs under hypothesis when installed (the CI test matrix installs it);
falls back to a fixed seed/shape sweep otherwise, so the invariants are
exercised in either environment.
"""

import collections

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from repro.kernels.duct_exchange.ops import duct_exchange_jnp, duct_window_jnp
from repro.kernels.duct_exchange.ref import duct_exchange_ref, duct_window_ref
from repro.runtime.simulator import SimConfig
from repro.runtime.window_core import (LOCAL_RELEASE, BucketSlab, DenseSpec,
                                       WindowCore)

try:
    from hypothesis import given, settings, strategies as hyp_st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def run_sequence(seed: int, E: int, C: int, max_pops: int, steps: int):
    """Drive both implementations through one random op sequence."""
    rng = np.random.default_rng(seed)
    q_avail = np.full((E, C), np.inf, np.float32)
    q_touch = np.zeros((E, C), np.int32)
    head = np.zeros(E, np.int32)
    size = np.zeros(E, np.int32)
    # mirror[e]: FIFO of (availability, touch) for every in-flight message
    mirror = [collections.deque() for _ in range(E)]
    accepted_tot = np.zeros(E, np.int64)
    attempted_tot = np.zeros(E, np.int64)
    dropped_tot = np.zeros(E, np.int64)
    drained_tot = np.zeros(E, np.int64)
    now = np.zeros(E, np.float32)

    for _ in range(steps):
        now = (now + rng.uniform(0.5, 1.5, E)).astype(np.float32)
        recv_active = rng.random(E) < 0.8
        send_active = rng.random(E) < 0.8
        send_lat = rng.uniform(0.0, 4.0, E).astype(np.float32)
        send_touch = rng.integers(1, 100, E).astype(np.int32)

        r = duct_exchange_ref(
            q_avail,
            q_touch,
            head,
            size,
            now,
            recv_active,
            now,
            send_active,
            send_lat,
            send_touch,
            capacity=C,
            max_pops=max_pops,
        )
        j = duct_exchange_jnp(
            jnp.asarray(q_avail),
            jnp.asarray(q_touch),
            jnp.asarray(head),
            jnp.asarray(size),
            jnp.asarray(now),
            jnp.asarray(recv_active),
            jnp.asarray(now),
            jnp.asarray(send_active),
            jnp.asarray(send_lat),
            jnp.asarray(send_touch),
            capacity=C,
            max_pops=max_pops,
        )
        for name in r._fields:
            got = np.asarray(getattr(j, name))
            np.testing.assert_array_equal(got, getattr(r, name), err_msg=name)

        for e in range(E):
            # FIFO + head-blocking: the pops the oracle reports must equal
            # a front-of-queue walk of the mirror, stopping at the first
            # not-yet-available message, bounded by max_pops
            if recv_active[e]:
                expect = 0
                for avail, _tch in list(mirror[e])[: min(size[e], max_pops)]:
                    if avail <= now[e]:
                        expect += 1
                    else:
                        break
                assert r.drained[e] == expect, (e, r.drained[e], expect)
            else:
                assert r.drained[e] == 0
            popped_touch = None
            for _ in range(int(r.drained[e])):
                _avail, popped_touch = mirror[e].popleft()
            if r.drained[e] > 0:
                # the freshest popped message is the one whose touch stamp
                # (and ring slot payload) the engine consumes
                assert r.recv_touch[e] == popped_touch
            # drop-iff-full, judged against post-drain occupancy
            room = size[e] - r.drained[e] < C
            assert bool(r.accepted[e]) == bool(send_active[e] and room)
            if r.accepted[e]:
                mirror[e].append((now[e] + send_lat[e], send_touch[e]))
            assert len(mirror[e]) == r.size[e]

        drained_tot += r.drained
        accepted_tot += r.accepted
        attempted_tot += send_active
        dropped_tot += send_active & ~r.accepted
        q_avail, q_touch, head, size = r.q_avail, r.q_touch, r.head, r.size
        # conservation: every message is delivered, dropped, or in flight
        assert np.all(accepted_tot == drained_tot + size)
        assert np.all(attempted_tot == accepted_tot + dropped_tot)


# a sweep that exercises capacity-1 rings, single-pop drains, single-ring
# batches, and a larger mixed case — always runs, hypothesis or not
FALLBACK_CASES = [
    (0, 1, 1, 1, 20),
    (1, 3, 1, 2, 20),
    (2, 1, 4, 1, 20),
    (3, 4, 2, 3, 15),
    (4, 2, 4, 4, 25),
    (5, 4, 4, 2, 15),
]


@pytest.mark.parametrize("seed,E,C,max_pops,steps", FALLBACK_CASES)
def test_duct_properties_seeded(seed, E, C, max_pops, steps):
    run_sequence(seed, E, C, max_pops, steps)


def run_window_sequence(seed: int, n: int, d: int, C: int, max_pops: int, steps: int):
    """Drive the fused dense-layout window op (DESIGN.md §10) through an
    engine-style staging cycle: the send decision (drop-iff-full, slot,
    occupancy bump) is made eagerly each step, the ring writes ride into the
    *next* step's ``duct_window`` pass.  Checks jnp-vs-ref slot-exact
    agreement plus mirror-queue invariants every step:

      drop-iff-full   a staged send is accepted iff the post-drain ring
                      has room at stage time
      FIFO order      drains pop in push order, never past a
                      not-yet-available head, at most ``max_pops``
      halo select     slot ``s`` carries the freshest payload of the
                      highest delivering row ``j`` with ``j % 4 == s``
      conservation    accepted == drained + in-flight (staged included)
                      and attempted == accepted + dropped, every step
    """
    rng = np.random.default_rng(seed)
    qa = np.full((n, d, C), np.inf, np.float32)
    qt = np.zeros((n, d, C), np.int32)
    qp = np.zeros((n, d, C, 1), np.int32)
    head = np.zeros((n, d), np.int32)
    size = np.zeros((n, d), np.int32)
    stage = dict(
        pos=np.zeros((n, d), np.int32),
        acc=np.zeros((n, d), bool),
        avail=np.zeros((n, d), np.float32),
        touch=np.zeros((n, d), np.int32),
        pay=np.zeros((n, d, 1), np.int32),
    )
    # mirror[p][j]: FIFO of (availability, touch, payload) per ring
    mirror = [[collections.deque() for _ in range(d)] for _ in range(n)]
    accepted_tot = np.zeros((n, d), np.int64)
    attempted_tot = np.zeros((n, d), np.int64)
    dropped_tot = np.zeros((n, d), np.int64)
    drained_tot = np.zeros((n, d), np.int64)
    now = np.zeros(n, np.float32)

    for _ in range(steps):
        now = (now + rng.uniform(0.5, 1.5, n)).astype(np.float32)
        ract = rng.random(n) < 0.8
        args = (
            qa,
            qt,
            qp,
            head,
            size,
            stage["pos"],
            stage["acc"],
            stage["avail"],
            stage["touch"],
            stage["pay"],
            now,
            ract,
        )
        r = duct_window_ref(*args, max_pops=max_pops)
        j = duct_window_jnp(*(jnp.asarray(a) for a in args), max_pops=max_pops)
        for name in r._fields:
            got = np.asarray(getattr(j, name))
            np.testing.assert_array_equal(got, getattr(r, name), err_msg=name)

        # the staged pushes enter the mirror queues (accepted at stage time)
        for p in range(n):
            for q in range(d):
                if stage["acc"][p, q]:
                    entry = (stage["avail"][p, q], stage["touch"][p, q], stage["pay"][p, q, 0])
                    mirror[p][q].append(entry)
        for p in range(n):
            fresh_pay = {}
            for q in range(d):
                # FIFO + head-blocking: pops must equal a front-of-queue
                # walk stopping at the first unavailable message
                if ract[p]:
                    expect = 0
                    for avail, _tch, _pay in list(mirror[p][q])[:max_pops]:
                        if avail <= now[p]:
                            expect += 1
                        else:
                            break
                    assert r.drained[p, q] == expect, (p, q, r.drained[p, q], expect)
                else:
                    assert r.drained[p, q] == 0
                last = None
                for _ in range(int(r.drained[p, q])):
                    last = mirror[p][q].popleft()
                if r.drained[p, q] > 0:
                    assert r.recv_touch[p, q] == last[1]
                    fresh_pay[q] = last[2]
                assert len(mirror[p][q]) == r.size[p, q]
            # halo select: the highest delivering row of each slot wins
            for s in range(4):
                js = [q for q in range(s, d, 4) if r.drained[p, q] > 0]
                assert bool(r.halo_win[p, s]) == bool(js)
                if js:
                    assert r.halo_pay[p, s, 0] == fresh_pay[max(js)]

        qa, qt, qp = r.q_avail, r.q_touch, r.q_pay
        head, size = r.head, r.size
        drained_tot += r.drained

        # stage the next step's sends, engine-style: decide drop-iff-full
        # against the post-drain occupancy NOW, write next step
        sact = rng.random((n, d)) < 0.8
        sacc = sact & (size < C)
        attempted_tot += sact
        accepted_tot += sacc
        dropped_tot += sact & ~sacc
        stage = dict(
            pos=((head + size) % C).astype(np.int32),
            acc=sacc,
            avail=(now[:, None] + rng.uniform(0.0, 4.0, (n, d))).astype(np.float32),
            touch=rng.integers(1, 100, (n, d)).astype(np.int32),
            pay=rng.integers(0, 99, (n, d, 1)).astype(np.int32),
        )
        size = (size + sacc).astype(np.int32)
        # conservation: every accepted message is drained, staged, or queued
        assert np.all(accepted_tot == drained_tot + size)
        assert np.all(attempted_tot == accepted_tot + dropped_tot)


# capacity-1 rings, degree 1 and 5 (slot aliasing), single-pop drains
WINDOW_FALLBACK_CASES = [
    (0, 1, 1, 1, 1, 20),
    (1, 2, 2, 1, 2, 20),
    (2, 1, 4, 4, 1, 20),
    (3, 3, 2, 3, 2, 15),
    (4, 2, 5, 4, 4, 25),
    (5, 2, 4, 2, 3, 15),
]


@pytest.mark.parametrize("seed,n,d,C,max_pops,steps", WINDOW_FALLBACK_CASES)
def test_duct_window_properties_seeded(seed, n, d, C, max_pops, steps):
    run_window_sequence(seed, n, d, C, max_pops, steps)


# ---------------------------------------------------------------------------
# WindowCore phase properties (DESIGN.md §11): the same mirror-queue oracle
# driven through the *engine-facing* phase methods — drain + send_edge on
# the edge-major layout, window_dense + stage_dense on the dense layout —
# instead of the raw ops, so the shared core's counter bookkeeping, halo
# merge, and sentinel-free paths are themselves under property test.
# ---------------------------------------------------------------------------
class _StubApp:
    """Minimal batched-app surface for a WindowCore under phase test."""

    payload_len = 1
    payload_dtype = np.int32


def _make_core(n, C, max_pops):
    cfg = SimConfig(buffer_capacity=C, duration=1.0,
                    snapshot_warmup=0.25, snapshot_interval=0.25)
    return WindowCore(cfg, _StubApp(), n, max_pops=max_pops)


def run_core_edge_sequence(seed: int, n: int, d: int, C: int,
                           max_pops: int, steps: int):
    """Drive ``WindowCore.drain`` / ``send_edge`` through a random op
    sequence over ``n*d`` edge-major rings (receiver ``r // d``), checked
    per step against the mirror queues:

      drop-iff-full   send accepted iff the post-drain ring has room
      FIFO order      drains walk the queue front, head-blocked, bounded
      halo winner     slot ``s`` carries the freshest payload of the
                      highest delivering row with ``row % d % 4 == s``
      conservation    per-ring and per-process counter identities
    """
    rng = np.random.default_rng(seed)
    core = _make_core(n, C, max_pops)
    E = n * d
    dst = (np.arange(E) // d).astype(np.int32)
    halo_key = (dst * 4 + (np.arange(E) % d) % 4).astype(np.int32)
    src = ((np.arange(E) * 7 + 3) % n).astype(np.int32)
    carry = {k: v for k, v in core.edge_rings(E).items()}
    carry.update(halo=jnp.zeros((n, 4, 1), jnp.int32),
                 c_msgs=jnp.zeros(n, jnp.int32),
                 c_laden=jnp.zeros(n, jnp.int32),
                 c_touch=jnp.zeros(n, jnp.int32))
    mirror = [collections.deque() for _ in range(E)]
    ptouch_m = np.zeros(E, np.int64)
    acc_tot = np.zeros(E, np.int64)
    att_tot = np.zeros(E, np.int64)
    drop_tot = np.zeros(E, np.int64)
    drain_tot = np.zeros(E, np.int64)
    now = np.zeros(n, np.float32)

    for _ in range(steps):
        now = (now + rng.uniform(0.5, 1.5, n)).astype(np.float32)
        ract = rng.random(n) < 0.8
        prev = {k: np.asarray(v) for k, v in carry.items()}
        upd, drained_r = core.drain(
            carry, jnp.asarray(now)[jnp.asarray(dst)],
            jnp.asarray(ract)[jnp.asarray(dst)],
            halo_key=jnp.asarray(halo_key), n_halo=n * 4,
            dst=jnp.asarray(dst), n_dst=n)
        u = dict(carry)
        u.update(upd)
        drained = np.zeros(E, np.int64)
        fresh = {}
        for e in range(E):
            p = dst[e]
            expect = 0
            if ract[p]:
                for avail, _t, _pay in list(mirror[e])[:max_pops]:
                    if avail <= now[p]:
                        expect += 1
                    else:
                        break
            drained[e] = expect
            last = None
            for _ in range(expect):
                last = mirror[e].popleft()
            if expect:
                assert int(np.asarray(u["ptouch"])[e]) == last[1] + 1, e
                ptouch_m[e] = last[1] + 1
                fresh[e] = last[2]
            assert int(np.asarray(u["q_size"])[e]) == len(mirror[e]), e
        drain_tot += drained
        # receiver-side counters sum per process
        halo = np.asarray(u["halo"])
        for p in range(n):
            rows = np.arange(p * d, (p + 1) * d)
            assert int(np.asarray(drained_r)[p]) == drained[rows].sum()
            dm = (np.asarray(u["c_msgs"]) - prev["c_msgs"])[p]
            assert dm == drained[rows].sum(), p
            dl = (np.asarray(u["c_laden"]) - prev["c_laden"])[p]
            assert dl == (drained[rows] > 0).sum(), p
            # halo winner: highest delivering row per (receiver, slot)
            for s in range(4):
                js = [e for e in rows
                      if (e % d) % 4 == s and drained[e] > 0]
                if js:
                    assert halo[p, s, 0] == fresh[max(js)], (p, s)

        # send attempt through the core, against post-drain occupancy
        sact = rng.random(E) < 0.8
        lat = rng.uniform(0.0, 4.0, E).astype(np.float32)
        touch = rng.integers(1, 100, E).astype(np.int32)
        pay = rng.integers(0, 99, (E, 1)).astype(np.int32)
        sp = core.send_edge(u, jnp.asarray(now)[jnp.asarray(src)],
                            jnp.asarray(sact), jnp.asarray(lat),
                            jnp.asarray(touch), jnp.asarray(pay),
                            jnp.asarray(src), n)
        acc = np.asarray(sp.accepted)
        sums = np.asarray(sp.sums)
        u.update(sp.rings)
        for e in range(E):
            room = len(mirror[e]) < C
            assert bool(acc[e]) == bool(sact[e] and room), e
            if acc[e]:
                mirror[e].append((now[src[e]] + lat[e], touch[e],
                                  pay[e, 0]))
            assert int(np.asarray(u["q_size"])[e]) == len(mirror[e]), e
        att_tot += sact
        acc_tot += acc
        drop_tot += sact & ~acc
        for p in range(n):
            mine = src == p
            assert sums[p, 0] == sact[mine].sum(), p
            assert sums[p, 1] == (sact & acc)[mine].sum(), p
            assert sums[p, 2] == (sact & ~acc)[mine].sum(), p
        sizes = np.array([len(q) for q in mirror])
        assert np.all(acc_tot == drain_tot + sizes)
        assert np.all(att_tot == acc_tot + drop_tot)
        carry = u


def run_core_dense_sequence(seed: int, n: int, d: int, C: int,
                            max_pops: int, steps: int):
    """Drive ``WindowCore.window_dense`` / ``stage_dense`` through a random
    op sequence on the flat bucketed dense layout (DESIGN.md §13) with
    self-loop out-edge tables (flat row ``p*d + q`` is both process p's
    in-ring q and its q-th out-edge), checking the same mirror-queue
    invariants plus the staged send-decision counters (att/ok/drop per
    process, every step) on the identity single-bucket spec."""
    rng = np.random.default_rng(seed)
    core = _make_core(n, C, max_pops)
    R = n * d
    spec = DenseSpec(n_dst=n, n_rows=R,
                     buckets=(BucketSlab(start=0, nb=n, deg=d,
                                         members=None),))
    carry = {k: v for k, v in core.dense_rings(R).items()}
    carry.update(halo=jnp.zeros((n, 4, 1), jnp.int32),
                 c_msgs=jnp.zeros(n, jnp.int32),
                 c_laden=jnp.zeros(n, jnp.int32),
                 c_touch=jnp.zeros(n, jnp.int32),
                 c_att=jnp.zeros(n, jnp.int32),
                 c_ok=jnp.zeros(n, jnp.int32),
                 c_drop=jnp.zeros(n, jnp.int32))
    src = (np.arange(R, dtype=np.int32) // d).astype(np.int32)
    rev = np.arange(R, dtype=np.int32)
    out_slot = np.zeros(R, np.int32)
    live = np.ones(R, bool)
    deg = np.full(n, d, np.int32)
    mirror = [[collections.deque() for _ in range(d)] for _ in range(n)]
    staged = None   # python twin of the carried stage_* buffers
    acc_tot = np.zeros((n, d), np.int64)
    att_tot = np.zeros((n, d), np.int64)
    drop_tot = np.zeros((n, d), np.int64)
    drain_tot = np.zeros((n, d), np.int64)
    now = np.zeros(n, np.float32)

    def by_ring(x):
        return np.asarray(x).reshape((n, d) + np.asarray(x).shape[1:])

    for _ in range(steps):
        now = (now + rng.uniform(0.5, 1.5, n)).astype(np.float32)
        ract = rng.random(n) < 0.8
        prev = {k: np.asarray(v) for k, v in carry.items()}
        upd, drained_r = core.window_dense(carry, jnp.asarray(now),
                                           jnp.asarray(ract), spec=spec)
        u = dict(carry)
        u.update(upd)
        # last window's staged pushes enter the mirror first (accepted at
        # stage time), then this window's drain walks the queue front
        if staged is not None:
            for p in range(n):
                for q in range(d):
                    if staged["acc"][p, q]:
                        mirror[p][q].append(
                            (staged["avail"][p, q], staged["touch"][p, q],
                             staged["pay"][p, q]))
        halo = np.asarray(u["halo"])
        ptouch2 = by_ring(u["ptouch"])
        qsize2 = by_ring(u["q_size"])
        for p in range(n):
            fresh = {}
            drained = np.zeros(d, np.int64)
            for q in range(d):
                expect = 0
                if ract[p]:
                    for avail, _t, _pay in list(mirror[p][q])[:max_pops]:
                        if avail <= now[p]:
                            expect += 1
                        else:
                            break
                drained[q] = expect
                last = None
                for _ in range(expect):
                    last = mirror[p][q].popleft()
                if expect:
                    assert int(ptouch2[p, q]) == last[1] + 1, (p, q)
                    fresh[q] = last[2]
                assert int(qsize2[p, q]) == len(mirror[p][q]), (p, q)
            drain_tot[p] += drained
            assert int(np.asarray(drained_r)[p]) == drained.sum()
            assert (np.asarray(u["c_msgs"]) - prev["c_msgs"])[p] == \
                drained.sum()
            assert (np.asarray(u["c_laden"]) - prev["c_laden"])[p] == \
                (drained > 0).sum()
            for s in range(4):
                js = [q for q in range(s, d, 4) if drained[q] > 0]
                if js:
                    assert halo[p, s, 0] == fresh[max(js)], (p, s)

        # stage this window's sends through the core (self-loop tables)
        sact = rng.random(n) < 0.8
        lat = rng.uniform(0.0, 4.0, (n, d)).astype(np.float32)
        pay = rng.integers(0, 99, (n, 1, 1)).astype(np.int32)
        st = core.stage_dense(
            u, u, jnp.asarray(now), jnp.asarray(sact),
            jnp.asarray(pay), jnp.asarray(lat.reshape(R)),
            src=jnp.asarray(src), rev=jnp.asarray(rev),
            out_slot=jnp.asarray(out_slot), live=jnp.asarray(live),
            deg=jnp.asarray(deg), spec=spec)
        u.update(st)
        sizes = np.array([[len(mirror[p][q]) for q in range(d)]
                          for p in range(n)])
        exp_acc = sact[:, None] & (sizes < C)
        assert np.array_equal(by_ring(u["stage_acc"]), exp_acc)
        assert np.array_equal(by_ring(u["q_size"]), sizes + exp_acc)
        att = np.where(sact, d, 0)
        assert np.array_equal(
            np.asarray(u["c_att"]) - prev["c_att"], att)
        assert np.array_equal(
            np.asarray(u["c_ok"]) - prev["c_ok"], exp_acc.sum(axis=1))
        assert np.array_equal(
            np.asarray(u["c_drop"]) - prev["c_drop"],
            att - exp_acc.sum(axis=1))
        att_tot += sact[:, None]
        acc_tot += exp_acc
        drop_tot += sact[:, None] & ~exp_acc
        staged = dict(acc=exp_acc,
                      avail=now[:, None] + lat,
                      touch=by_ring(u["stage_touch"]),
                      pay=by_ring(u["stage_pay"])[:, :, 0])
        # conservation: accepted == drained + queued + staged-not-applied
        assert np.all(acc_tot == drain_tot + sizes + exp_acc)
        assert np.all(att_tot == acc_tot + drop_tot)
        carry = u


def run_shadow_sequence(seed: int, n: int, d: int, C: int,
                        max_pops: int, steps: int):
    """The pipelined scheduler's shadow-buffer exchange at the ring-op
    level (DESIGN.md §12): sends staged in superstep i ride a shadow
    buffer and are pushed through ``send_edge`` only in superstep i+1,
    with availability stamps drawn at STAGE time.  The mirror-queue
    oracle enters each message one superstep late with its original
    stamp, pinning the double-buffer contract:

      +1 delay        a staged message is invisible to the drain of its
                      own superstep (ring sizes match a mirror that
                      excludes the current shadow buffer)
      drop-iff-full   accept is decided at PUSH time — one superstep
                      after staging — against the post-drain ring
      stamp honesty   delivery eligibility uses the stage-time stamp, so
                      the delay never rewrites virtual time
      conservation    staged == attempted + in-shadow,
                      attempted == accepted + dropped, and
                      accepted == drained + in-ring, every superstep
    """
    rng = np.random.default_rng(seed)
    core = _make_core(n, C, max_pops)
    E = n * d
    dst = (np.arange(E) // d).astype(np.int32)
    halo_key = (dst * 4 + (np.arange(E) % d) % 4).astype(np.int32)
    src = ((np.arange(E) * 7 + 3) % n).astype(np.int32)
    carry = dict(core.edge_rings(E))
    carry.update(halo=jnp.zeros((n, 4, 1), jnp.int32),
                 c_msgs=jnp.zeros(n, jnp.int32),
                 c_laden=jnp.zeros(n, jnp.int32),
                 c_touch=jnp.zeros(n, jnp.int32))
    mirror = [collections.deque() for _ in range(E)]
    shadow = None   # the in-flight buffer staged last superstep
    att_tot = np.zeros(E, np.int64)
    acc_tot = np.zeros(E, np.int64)
    drop_tot = np.zeros(E, np.int64)
    drain_tot = np.zeros(E, np.int64)
    staged_tot = np.zeros(E, np.int64)
    now = np.zeros(n, np.float32)

    for _ in range(steps):
        now = (now + rng.uniform(0.5, 1.5, n)).astype(np.float32)
        ract = rng.random(n) < 0.8
        upd, _ = core.drain(
            carry, jnp.asarray(now)[jnp.asarray(dst)],
            jnp.asarray(ract)[jnp.asarray(dst)],
            halo_key=jnp.asarray(halo_key), n_halo=n * 4,
            dst=jnp.asarray(dst), n_dst=n)
        u = dict(carry)
        u.update(upd)
        for e in range(E):
            p = dst[e]
            expect = 0
            if ract[p]:
                for avail, _tch in list(mirror[e])[:max_pops]:
                    if avail <= now[p]:
                        expect += 1
                    else:
                        break
            for _ in range(expect):
                mirror[e].popleft()
            drain_tot[e] += expect
            # +1 delay: the drain sees a ring WITHOUT the current shadow
            assert int(np.asarray(u["q_size"])[e]) == len(mirror[e]), e

        # push LAST superstep's shadow buffer: stamps were drawn against
        # the stage-time clock, so some may already be in the past —
        # honest added latency, never a rewritten stamp
        if shadow is not None:
            sp = core.send_edge(
                u, jnp.asarray(shadow["avail"]), jnp.asarray(shadow["act"]),
                jnp.float32(0.0), jnp.asarray(shadow["touch"]),
                jnp.asarray(shadow["pay"]), jnp.asarray(src), n)
            acc = np.asarray(sp.accepted)
            u.update(sp.rings)
            for e in range(E):
                room = len(mirror[e]) < C
                assert bool(acc[e]) == bool(shadow["act"][e] and room), e
                if acc[e]:
                    mirror[e].append((shadow["avail"][e],
                                      shadow["touch"][e]))
                assert int(np.asarray(u["q_size"])[e]) == len(mirror[e])
            att_tot += shadow["act"]
            acc_tot += acc
            drop_tot += shadow["act"] & ~acc

        # stage a fresh shadow buffer, pushed next superstep
        act = rng.random(E) < 0.8
        shadow = dict(
            act=act,
            avail=(now[src] + rng.uniform(0.0, 4.0, E)).astype(np.float32),
            touch=rng.integers(1, 100, E).astype(np.int32),
            pay=rng.integers(0, 99, (E, 1)).astype(np.int32))
        staged_tot += act
        sizes = np.array([len(q) for q in mirror])
        assert np.all(staged_tot == att_tot + shadow["act"])
        assert np.all(att_tot == acc_tot + drop_tot)
        assert np.all(acc_tot == drain_tot + sizes)
        carry = u


CORE_EDGE_CASES = [
    (0, 1, 1, 1, 1, 15),
    (1, 2, 3, 2, 2, 15),
    (2, 3, 2, 4, 3, 12),
    (3, 2, 5, 3, 4, 12),
]


@pytest.mark.parametrize("seed,n,d,C,max_pops,steps", CORE_EDGE_CASES)
def test_window_core_edge_phases_seeded(seed, n, d, C, max_pops, steps):
    run_core_edge_sequence(seed, n, d, C, max_pops, steps)


@pytest.mark.parametrize("seed,n,d,C,max_pops,steps", CORE_EDGE_CASES)
def test_window_core_dense_phases_seeded(seed, n, d, C, max_pops, steps):
    run_core_dense_sequence(seed, n, d, C, max_pops, steps)


@pytest.mark.parametrize("seed,n,d,C,max_pops,steps", CORE_EDGE_CASES)
def test_shadow_buffer_properties_seeded(seed, n, d, C, max_pops, steps):
    run_shadow_sequence(seed, n, d, C, max_pops, steps)


# ---------------------------------------------------------------------------
# close_window's snapshot write against the scatter it replaced: a due
# process's QoS row lands in slot snap_idx, every other slot keeps its bits,
# and a full buffer (snap_idx == S) takes nothing, as mode="drop" did.
# ---------------------------------------------------------------------------
def _snapshot_scatter_oracle(core, u, active):
    cfg, n = core.cfg, u["t"].shape[0]
    snap_idx, t = u["snap_idx"], u["t"]
    thr = (np.float32(cfg.snapshot_warmup) +
           snap_idx.astype(jnp.float32) * np.float32(cfg.snapshot_interval))
    due = active & (t >= thr) & (snap_idx < core.S)
    row = jnp.stack([u[k].astype(jnp.float32) for k in (
        "steps", "c_touch", "c_att", "c_ok", "c_drop", "c_laden",
        "c_msgs")] + [t], axis=1)
    snap = u["snap"].at[jnp.where(due, jnp.arange(n, dtype=jnp.int32), n),
                        snap_idx].set(row, mode="drop")
    return snap, snap_idx + due


def _snapshot_carry(core, rng, n, case):
    """A carry whose processes are due for a snapshot as ``case`` says,
    with random counters and a buffer already holding random rows."""
    cfg, S = core.cfg, core.S
    snap_idx = rng.integers(0, S, n).astype(np.int32)
    if case == "full":
        snap_idx[::2] = S
    thr = (np.float32(cfg.snapshot_warmup) +
           snap_idx.astype(np.float32) * np.float32(cfg.snapshot_interval))
    past = {"none": np.zeros(n, bool),
            "some": np.arange(n) % 3 == 0}.get(case, np.ones(n, bool))
    t = np.where(past, thr + np.float32(1e-3), thr - np.float32(1e-3))
    active = (np.arange(n) % 2 == 0) if case == "inactive" else np.ones(
        n, bool)
    ints = {k: rng.integers(0, 1 << 20, n).astype(np.int32) for k in (
        "steps", "c_touch", "c_att", "c_ok", "c_drop", "c_laden", "c_msgs",
        "barrier_seq")}
    u = dict(ints, t=t.astype(np.float32), snap_idx=snap_idx,
             snap=rng.standard_normal((n, S, 8)).astype(np.float32),
             done=np.zeros(n, bool), waiting=np.zeros(n, bool),
             last_release=np.zeros(n, np.float32),
             pending=np.zeros(n, np.float32), seed=np.int32(rng.integers(
                 0, 1 << 30)), k=np.int32(0))
    return {k: jnp.asarray(v) for k, v in u.items()}, jnp.asarray(active)


@pytest.mark.parametrize("replicates", [1, 2])
@pytest.mark.parametrize("case", ["none", "some", "all", "full", "inactive"])
def test_close_window_snapshot_write_matches_scatter(case, replicates):
    n = 24
    core = _make_core(n, C=4, max_pops=2)
    rng = np.random.default_rng(17)
    carries = [_snapshot_carry(core, rng, n, case) for _ in range(replicates)]
    kw = dict(pids=jnp.arange(n, dtype=jnp.int32),
              deg=jnp.full(n, 4, jnp.int32),
              cfactor=jnp.ones(n, jnp.float32), release=LOCAL_RELEASE)

    def close(u, active):
        out = core.close_window(u, active, jnp.zeros(n, jnp.int32), **kw)
        return out["snap"], out["snap_idx"]

    if replicates == 1:
        got = [close(*carries[0])]
    else:
        u, active = jax.tree.map(lambda *xs: jnp.stack(xs), *carries)
        snap, idx = jax.vmap(close)(u, active)
        got = [(snap[r], idx[r]) for r in range(replicates)]
    for (u, active), (snap, idx) in zip(carries, got):
        want_snap, want_idx = _snapshot_scatter_oracle(core, u, active)
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx))
        np.testing.assert_array_equal(
            np.asarray(snap).view(np.uint32),
            np.asarray(want_snap).view(np.uint32))
        written = int(np.sum(np.asarray(idx) - np.asarray(u["snap_idx"])))
        assert written == {"none": 0, "some": n // 3, "all": n,
                           "full": n // 2, "inactive": n // 2}[case]


def run_fault_mask_sequence(seed: int, n: int, d: int, C: int,
                            max_pops: int, steps: int):
    """Typed-fault send kills (``WindowCore.fault_masks``, DESIGN.md §14)
    composed with the edge-major phases, under the mirror-queue oracle
    with full drop-attribution books:

      determinism    the masks are pure counter hashes — the same
                     (seed, clock, step count, edge id) inputs reproduce
                     them bitwise on a second call
      disjointness   loss_kill and dead_kill never overlap (dead wins);
                     clean live edges (loss == flap == 0) are never
                     loss-killed
      totality       dead edges kill every attempt; loss == 1 edges kill
                     every attempt that isn't already dead
      conservation   attempted == delivered + in-flight +
                     capacity_dropped + loss_dropped + dead_dropped,
                     per edge, every step — killed sends never enter a
                     ring, so they can neither deliver nor occupy slots
    """
    rng = np.random.default_rng(seed)
    core = _make_core(n, C, max_pops)
    E = n * d
    dst = (np.arange(E) // d).astype(np.int32)
    halo_key = (dst * 4 + (np.arange(E) % d) % 4).astype(np.int32)
    src = ((np.arange(E) * 7 + 3) % n).astype(np.int32)
    eids = jnp.arange(E, dtype=jnp.int32)
    # per-edge fault assignment: clean / lossy / certain-loss / flapping /
    # dead edges all present (modulo tiny E) so every branch is exercised
    loss_e = rng.choice(np.float32([0.0, 0.35, 1.0]), E,
                        p=[0.5, 0.3, 0.2]).astype(np.float32)
    flap_e = np.where(rng.random(E) < 0.3, np.float32(0.5),
                      np.float32(0.0))
    dead_e = rng.random(E) < 0.25
    flap_period = 2.0
    fseed = seed ^ 0x5EED

    carry = dict(core.edge_rings(E))
    carry.update(halo=jnp.zeros((n, 4, 1), jnp.int32),
                 c_msgs=jnp.zeros(n, jnp.int32),
                 c_laden=jnp.zeros(n, jnp.int32),
                 c_touch=jnp.zeros(n, jnp.int32))
    mirror = [collections.deque() for _ in range(E)]
    att_tot = np.zeros(E, np.int64)
    acc_tot = np.zeros(E, np.int64)
    cap_tot = np.zeros(E, np.int64)
    loss_tot = np.zeros(E, np.int64)
    dead_tot = np.zeros(E, np.int64)
    drain_tot = np.zeros(E, np.int64)
    steps_n = np.zeros(n, np.int32)
    now = np.zeros(n, np.float32)

    for _ in range(steps):
        now = (now + rng.uniform(0.5, 1.5, n)).astype(np.float32)
        ract = rng.random(n) < 0.8
        upd, _ = core.drain(
            carry, jnp.asarray(now)[jnp.asarray(dst)],
            jnp.asarray(ract)[jnp.asarray(dst)],
            halo_key=jnp.asarray(halo_key), n_halo=n * 4,
            dst=jnp.asarray(dst), n_dst=n)
        u = dict(carry)
        u.update(upd)
        for e in range(E):
            p = dst[e]
            expect = 0
            if ract[p]:
                for avail, _tch in list(mirror[e])[:max_pops]:
                    if avail <= now[p]:
                        expect += 1
                    else:
                        break
            for _ in range(expect):
                mirror[e].popleft()
            drain_tot[e] += expect
            assert int(np.asarray(u["q_size"])[e]) == len(mirror[e]), e

        sact = rng.random(E) < 0.8
        t_src = jnp.asarray(now[src])
        st_src = jnp.asarray(steps_n[src])
        l_k, d_k = core.fault_masks(
            fseed, t_src, st_src, eids, jnp.asarray(loss_e),
            jnp.asarray(flap_e), flap_period, jnp.asarray(dead_e))
        l2, d2 = core.fault_masks(
            fseed, t_src, st_src, eids, jnp.asarray(loss_e),
            jnp.asarray(flap_e), flap_period, jnp.asarray(dead_e))
        l_k, d_k = np.asarray(l_k), np.asarray(d_k)
        np.testing.assert_array_equal(l_k, np.asarray(l2))
        np.testing.assert_array_equal(d_k, np.asarray(d2))
        assert not (l_k & d_k).any()
        np.testing.assert_array_equal(d_k, dead_e)
        clean = (loss_e == 0) & (flap_e == 0) & ~dead_e
        assert not l_k[clean].any()
        assert l_k[(loss_e == 1.0) & ~dead_e].all()

        kill = l_k | d_k
        send_act = sact & ~kill
        lat = rng.uniform(0.0, 4.0, E).astype(np.float32)
        touch = rng.integers(1, 100, E).astype(np.int32)
        pay = rng.integers(0, 99, (E, 1)).astype(np.int32)
        sp = core.send_edge(u, jnp.asarray(now)[jnp.asarray(src)],
                            jnp.asarray(send_act), jnp.asarray(lat),
                            jnp.asarray(touch), jnp.asarray(pay),
                            jnp.asarray(src), n)
        acc = np.asarray(sp.accepted)
        u.update(sp.rings)
        for e in range(E):
            room = len(mirror[e]) < C
            assert bool(acc[e]) == bool(send_act[e] and room), e
            if acc[e]:
                mirror[e].append((now[src[e]] + lat[e], touch[e]))
        att_tot += sact
        acc_tot += acc
        cap_tot += send_act & ~acc
        loss_tot += sact & l_k
        dead_tot += sact & d_k
        sizes = np.array([len(q) for q in mirror])
        assert np.all(acc_tot == drain_tot + sizes)
        assert np.all(
            att_tot == drain_tot + sizes + cap_tot + loss_tot + dead_tot)
        steps_n += 1
        carry = u


@pytest.mark.parametrize("seed,n,d,C,max_pops,steps", CORE_EDGE_CASES)
def test_fault_mask_properties_seeded(seed, n, d, C, max_pops, steps):
    run_fault_mask_sequence(seed, n, d, C, max_pops, steps)


if HAVE_HYPOTHESIS:
    @given(
        seed=hyp_st.integers(0, 2**31 - 1),
        E=hyp_st.integers(1, 4),
        C=hyp_st.integers(1, 4),
        max_pops=hyp_st.integers(1, 3),
        steps=hyp_st.integers(2, 15),
    )
    @settings(max_examples=12, deadline=None)
    def test_duct_properties_hypothesis(seed, E, C, max_pops, steps):
        run_sequence(seed, E, C, max_pops, steps)

    @given(
        seed=hyp_st.integers(0, 2**31 - 1),
        n=hyp_st.integers(1, 3),
        d=hyp_st.integers(1, 5),
        C=hyp_st.integers(1, 4),
        max_pops=hyp_st.integers(1, 3),
        steps=hyp_st.integers(2, 12),
    )
    @settings(max_examples=12, deadline=None)
    def test_duct_window_properties_hypothesis(seed, n, d, C, max_pops, steps):
        run_window_sequence(seed, n, d, C, max_pops, steps)

    @given(
        seed=hyp_st.integers(0, 2**31 - 1),
        n=hyp_st.integers(1, 3),
        d=hyp_st.integers(1, 4),
        C=hyp_st.integers(1, 4),
        max_pops=hyp_st.integers(1, 3),
        steps=hyp_st.integers(2, 12),
    )
    @settings(max_examples=10, deadline=None)
    def test_shadow_buffer_properties_hypothesis(seed, n, d, C, max_pops,
                                                 steps):
        run_shadow_sequence(seed, n, d, C, max_pops, steps)

    @given(
        seed=hyp_st.integers(0, 2**31 - 1),
        n=hyp_st.integers(1, 3),
        d=hyp_st.integers(1, 4),
        C=hyp_st.integers(1, 4),
        max_pops=hyp_st.integers(1, 3),
        steps=hyp_st.integers(2, 12),
    )
    @settings(max_examples=10, deadline=None)
    def test_fault_mask_properties_hypothesis(seed, n, d, C, max_pops,
                                              steps):
        run_fault_mask_sequence(seed, n, d, C, max_pops, steps)


# ---------------------------------------------------------------------------
# Bucketed layout planner properties (DESIGN.md §13)
# ---------------------------------------------------------------------------
from repro.kernels.duct_exchange import dense_stage  # noqa: E402
from repro.runtime.topologies import (  # noqa: E402
    Topology,
    canonical_edges,
    next_pow2,
    plan_layout,
)


def random_irregular_topology(seed: int, n: int) -> Topology:
    """Random connected symmetric graph: a ring spine plus random chords,
    so in-degrees vary and the planner must genuinely bucket."""
    rng = np.random.default_rng(seed)
    nbrs = [set() for _ in range(n)]
    for i in range(n):
        nbrs[i].add((i + 1) % n)
        nbrs[(i + 1) % n].add(i)
    for _ in range(int(rng.integers(1, 2 * n))):
        a, b = (int(x) for x in rng.integers(0, n, 2))
        if a != b:
            nbrs[a].add(b)
            nbrs[b].add(a)
    return Topology("randgraph", n,
                    tuple(tuple(sorted(s)) for s in nbrs),
                    tuple(0 for _ in range(n))).validate()


def check_bucketed_plan(topo: Topology):
    """Structural invariants of the degree-bucketed dense plan:

      bucket assignment   bdeg[p] = min(next_pow2(deg_p), dmax), exact
      row blocks          live prefix of deg_p rows in sorted-source
                          (= canonical-edge-id) order, dead padding after
      sentinels           dead rows carry src == n, eid == E
      rev involution      rev[rev] = id on ALL rows; dead rows are fixed
                          points; live rows map edge (s, p) to (p, s)
      dead rows           never accept a stage, even with room and every
                          sender active — the live mask gates the push
    """
    plan = plan_layout(topo, "dense")
    n = topo.n
    degs = [topo.degree(p) for p in range(n)]
    dmax = max(degs)
    _, _, eindex = canonical_edges(topo)
    E = len(eindex)
    assert plan.kind == "dense" and plan.degree == dmax
    np.testing.assert_array_equal(
        plan.bdeg, [min(next_pow2(k), dmax) for k in degs])
    assert plan.n_rows == int(plan.bdeg.sum())
    rows = np.arange(plan.n_rows)
    live, dead = plan.live, ~plan.live
    np.testing.assert_array_equal(plan.rev[plan.rev], rows)
    np.testing.assert_array_equal(plan.rev[dead], rows[dead])
    np.testing.assert_array_equal(plan.src[plan.rev][live],
                                  plan.dst[live])
    np.testing.assert_array_equal(plan.dst[plan.rev][live],
                                  plan.src[live])
    for p in range(n):
        sl = slice(int(plan.row_start[p]),
                   int(plan.row_start[p]) + int(plan.bdeg[p]))
        assert live[sl].sum() == degs[p] and live[sl][:degs[p]].all()
        assert (plan.dst[sl] == p).all()
        assert list(plan.src[sl][:degs[p]]) == sorted(topo.neighbors[p])
        assert (plan.src[sl][degs[p]:] == n).all()
        assert (plan.eid[sl][degs[p]:] == E).all()
        assert list(plan.eid[sl][:degs[p]]) == [
            eindex[(s, p)] for s in sorted(topo.neighbors[p])]
    # dead rows never receive: the stage accept mask is gated by `live`
    # (window_core.WindowCore.stage_dense), so with empty rings and every
    # sender active only live rows accept
    head = jnp.zeros(plan.n_rows, jnp.int32)
    size = jnp.zeros(plan.n_rows, jnp.int32)
    _, acc = dense_stage(head, size, jnp.asarray(plan.live), capacity=2)
    acc = np.asarray(acc)
    assert not acc[dead].any() and acc[live].all()


PLANNER_CASES = [(0, 6), (1, 9), (2, 12), (3, 16), (4, 24), (5, 7)]


@pytest.mark.parametrize("seed,n", PLANNER_CASES)
def test_bucketed_planner_properties_seeded(seed, n):
    check_bucketed_plan(random_irregular_topology(seed, n))


def test_bucketed_planner_properties_builtin_topologies():
    from repro.runtime.topologies import make_topology

    for name in ("ring", "torus", "smallworld", "cliques"):
        check_bucketed_plan(make_topology(name, 16))


@pytest.mark.parametrize("seed,n", [(0, 8), (3, 12)])
def test_bucketed_dense_matches_edge_on_random_graphs(seed, n):
    """End-to-end closure of the padding argument: on a random irregular
    graph the bucketed dense engine reproduces the edge-major engine's
    full QoS signature bitwise — dead rows contribute nothing, ever."""
    from engine_cases import jittered_cfg
    from repro.apps.graphcolor import GraphColorApp, GraphColorConfig
    from repro.core.qos import qos_signature
    from repro.runtime.engine import make_engine

    topo = random_irregular_topology(seed, n)
    cfg = jittered_cfg(0.02, seed=seed)

    def app():
        return GraphColorApp(
            GraphColorConfig(n_processes=n, nodes_per_process=1),
            topology=topo)

    res_e = make_engine("jax", app(), cfg, layout="edge").run()
    res_d = make_engine("jax", app(), cfg, layout="dense").run()
    assert qos_signature(res_d) == qos_signature(res_e)


if HAVE_HYPOTHESIS:
    @given(
        seed=hyp_st.integers(0, 2**31 - 1),
        n=hyp_st.integers(4, 24),
    )
    @settings(max_examples=15, deadline=None)
    def test_bucketed_planner_properties_hypothesis(seed, n):
        check_bucketed_plan(random_irregular_topology(seed, n))
