"""Dense duct-layout tests: the bucketed planner and the fused megakernel.

The dense receiver-major layout is a pure memory-layout change; its
bitwise parity with the edge-major path — across topologies, modes, fault
injection, and block payloads — is asserted by the registry-driven suite
(``tests/test_engine_conformance.py``, family 3).  This file keeps what is
specific to the layout machinery itself: the degree-bucketed planner's
tables, interpret-mode Pallas parity for the ``duct_window`` /
``duct_commit`` megakernel family, the W-fused superstep scheduler's
bitwise parity on every topology, and the dense path's replicate plumbing.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from engine_cases import case_seed, gc_app, jittered_cfg  # noqa: E402
from repro.core.qos import qos_signature  # noqa: E402
from repro.kernels.duct_exchange import (  # noqa: E402
    duct_commit,
    duct_commit_jnp,
    duct_commit_ref,
    duct_window,
    duct_window_jnp,
    duct_window_ref,
)
from repro.runtime.engine import make_engine  # noqa: E402
from repro.runtime.engine_jax import JaxEngine  # noqa: E402
from repro.runtime.topologies import (  # noqa: E402
    canonical_edges,
    make_topology,
    next_pow2,
    plan_layout,
    regular_degree,
)

_app = gc_app
_cfg = jittered_cfg

TOPOLOGIES = ("ring", "torus", "smallworld", "cliques")


# ---------------------------------------------------------------------------
# Layout planner
# ---------------------------------------------------------------------------
def test_plan_dense_for_regular_topologies():
    for name, n, want_d in (("ring", 16, 2), ("torus", 16, 4)):
        topo = make_topology(name, n)
        plan = plan_layout(topo, "auto")
        assert plan.kind == "dense"
        assert plan.degree == want_d
        assert regular_degree(topo) == want_d
        # degree-regular topologies collapse to ONE exact-d bucket: no
        # padding, every flat row live, receiver p's block at p*d
        assert len(plan.buckets) == 1 and plan.buckets[0].deg == want_d
        assert plan.n_rows == n * want_d
        assert plan.live.all()
        np.testing.assert_array_equal(plan.row_start,
                                      np.arange(n) * want_d)
        np.testing.assert_array_equal(plan.bdeg, np.full(n, want_d))
        # row (p, j) holds in-edge j of receiver p in sorted-source order
        for p in range(n):
            rows = slice(p * want_d, (p + 1) * want_d)
            assert list(plan.src[rows]) == sorted(topo.neighbors[p])
            assert (plan.dst[rows] == p).all()
        # rev is an involution: the reverse of the reverse is the row
        np.testing.assert_array_equal(plan.rev[plan.rev],
                                      np.arange(n * want_d))


@pytest.mark.parametrize("name", ["smallworld", "cliques"])
def test_plan_buckets_irregular_topologies(name):
    topo = make_topology(name, 16)
    n = topo.n
    degs = [len(nbs) for nbs in topo.neighbors]
    dmax = max(degs)
    plan = plan_layout(topo, "auto")
    assert plan.kind == "dense"
    assert plan.degree == dmax
    # bucket degree = next power of two, clamped to the max in-degree
    np.testing.assert_array_equal(
        plan.bdeg, [min(next_pow2(k), dmax) for k in degs])
    assert plan.n_rows == int(plan.bdeg.sum())
    # each receiver's block: live prefix of its true in-degree in
    # sorted-source (= canonical-edge-id) order, dead padding after
    _, _, eindex = canonical_edges(topo)
    E = len(eindex)
    for p in range(n):
        rows = slice(plan.row_start[p], plan.row_start[p] + plan.bdeg[p])
        live = plan.live[rows]
        assert live.sum() == degs[p] and live[:degs[p]].all()
        assert (plan.dst[rows] == p).all()
        srcs = plan.src[rows]
        assert list(srcs[:degs[p]]) == sorted(topo.neighbors[p])
        # dead rows carry sentinels: src == n, eid == E
        assert (srcs[degs[p]:] == n).all()
        assert (plan.eid[rows][degs[p]:] == E).all()
        eids = plan.eid[rows][:degs[p]]
        assert list(eids) == [eindex[(s, p)] for s in sorted(
            topo.neighbors[p])]
    # rev is a full involution; dead rows map to themselves
    np.testing.assert_array_equal(plan.rev[plan.rev],
                                  np.arange(plan.n_rows))
    dead = ~plan.live
    np.testing.assert_array_equal(plan.rev[dead],
                                  np.arange(plan.n_rows)[dead])
    # bucket slabs tile the flat row space with ascending members
    covered = 0
    for b in plan.buckets:
        assert b.start == covered
        assert (np.diff(b.members) > 0).all() or len(b.members) == 1
        covered += b.deg * len(b.members)
    assert covered == plan.n_rows


def test_plan_forced_layouts_and_unknown_layout():
    # forcing dense on an irregular topology now buckets instead of
    # raising; forcing edge still yields the fully general layout
    assert plan_layout(make_topology("smallworld", 16), "dense").kind \
        == "dense"
    assert plan_layout(make_topology("smallworld", 16), "edge").kind \
        == "edge"
    with pytest.raises(ValueError, match="unknown layout"):
        plan_layout(make_topology("ring", 8), "banana")


# ---------------------------------------------------------------------------
# Megakernel parity: jnp twin and interpret-mode Pallas vs the numpy ref
# ---------------------------------------------------------------------------
def _random_window_state(rng, n=6, d=3, C=5, L=2, cap=5):
    qa = np.full((n, d, C), np.inf, np.float32)
    qt = np.zeros((n, d, C), np.int32)
    qp = np.zeros((n, d, C, L), np.int32)
    head = rng.integers(0, C, (n, d)).astype(np.int32)
    size = np.zeros((n, d), np.int32)
    for p in range(n):
        for j in range(d):
            s = rng.integers(0, cap)
            size[p, j] = s
            for k in range(s):
                pos = (head[p, j] + k) % C
                qa[p, j, pos] = rng.random() * 2
                qt[p, j, pos] = rng.integers(0, 50)
                qp[p, j, pos] = rng.integers(0, 99, L)
    # staged push, engine-style: eager drop-iff-full against carried size
    pacc = (rng.random((n, d)) < 0.7) & (size < cap)
    ppos = ((head + size) % C).astype(np.int32)
    size = (size + pacc).astype(np.int32)
    pav = (rng.random((n, d)) * 2).astype(np.float32)
    ptch = rng.integers(0, 50, (n, d)).astype(np.int32)
    ppay = rng.integers(0, 99, (n, d, L)).astype(np.int32)
    rnow = (rng.random(n) * 2).astype(np.float32)
    ract = rng.random(n) < 0.8
    return (qa, qt, qp, head, size, ppos, pacc, pav, ptch, ppay, rnow, ract)


@pytest.mark.parametrize("impl", ["jnp", "pallas_interpret"])
def test_duct_window_matches_ref(impl):
    rng = np.random.default_rng(11)
    args = _random_window_state(rng)
    ref = duct_window_ref(*args, max_pops=3)
    if impl == "jnp":
        out = duct_window_jnp(*map(jnp.asarray, args), max_pops=3)
    else:
        out = duct_window(
            *map(jnp.asarray, args),
            max_pops=3,
            use_pallas=True,
            interpret=True,
        )
    for name, a, b in zip(ref._fields, ref, out):
        np.testing.assert_array_equal(
            np.asarray(b),
            np.asarray(a),
            err_msg=f"{impl}: field {name}",
        )


def test_duct_window_degree_one_and_empty_rings():
    rng = np.random.default_rng(5)
    args = _random_window_state(rng, n=3, d=1, C=1, L=1, cap=1)
    ref = duct_window_ref(*args, max_pops=1)
    out = duct_window_jnp(*map(jnp.asarray, args), max_pops=1)
    for name, a, b in zip(ref._fields, ref, out):
        np.testing.assert_array_equal(np.asarray(b), np.asarray(a), err_msg=name)


def _random_commit_state(rng, R=24, C=6, L=2, W=5):
    qa = (rng.random((R, C)) * 2).astype(np.float32)
    qt = rng.integers(0, 50, (R, C)).astype(np.int32)
    qp = rng.integers(0, 99, (R, C, L)).astype(np.int32)
    head = rng.integers(0, C, R).astype(np.int32)
    size0 = rng.integers(0, C, R).astype(np.int32)
    # the engine guarantees pb_cnt pushes fit behind the frozen tail
    cnt = np.minimum(rng.integers(0, W + 1, R), C - size0).astype(np.int32)
    pa = (rng.random((R, W)) * 2).astype(np.float32)
    pt = rng.integers(0, 50, (R, W)).astype(np.int32)
    pp = rng.integers(0, 99, (R, W, L)).astype(np.int32)
    return (qa, qt, qp, head, size0, cnt, pa, pt, pp)


@pytest.mark.parametrize("impl", ["jnp", "pallas_interpret"])
def test_duct_commit_matches_ref(impl):
    """The superstep commit is slot-exact across all three backends:
    push j of ring r lands at (head + size0 + j) % C, untouched slots
    keep their frozen base values bit-for-bit."""
    rng = np.random.default_rng(17)
    args = _random_commit_state(rng)
    ref = duct_commit_ref(*args)
    if impl == "jnp":
        out = duct_commit_jnp(*map(jnp.asarray, args))
    else:
        out = duct_commit(*map(jnp.asarray, args), use_pallas=True,
                          interpret=True)
    for name, a, b in zip(ref._fields, ref, out):
        np.testing.assert_array_equal(np.asarray(b), np.asarray(a),
                                      err_msg=f"{impl}: field {name}")


@pytest.mark.parametrize("op", ["window", "commit"])
def test_duct_kernels_ragged_lane_blocks(op):
    """Enough rings for several lane blocks, the last one ragged: 2800
    rings of 64 slots outgrow one block's VMEM budget, and a block short
    of the whole ring axis is a multiple of 128 lanes, which 2800 is not.
    Blocks are independent, so the kernel stays slot-exact with no
    padding."""
    rng = np.random.default_rng(23)
    if op == "window":
        args = _random_window_state(rng, n=700, d=4, C=64, L=2, cap=64)
        ref = duct_window_ref(*args, max_pops=16)
        out = duct_window(*map(jnp.asarray, args), max_pops=16,
                          use_pallas=True, interpret=True)
    else:
        args = _random_commit_state(rng, R=2800, C=64, L=2, W=8)
        ref = duct_commit_ref(*args)
        out = duct_commit(*map(jnp.asarray, args), use_pallas=True,
                          interpret=True)
    for name, a, b in zip(ref._fields, ref, out):
        np.testing.assert_array_equal(np.asarray(b), np.asarray(a),
                                      err_msg=f"{op}: field {name}")


# ---------------------------------------------------------------------------
# W-fused superstep scheduler: bitwise vs per-window dense on EVERY topology
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("topology", TOPOLOGIES)
def test_superstep_fusion_bitwise_per_topology(topology):
    """Fusing W windows into one launch (frozen rings + compact pushbuf +
    one duct_commit) is a pure execution-strategy change: the full QoS
    signature must match the per-window dense engine bit-for-bit — on the
    padded bucketed rows of the irregular topologies too."""
    cfg = _cfg(0.02, seed=case_seed(topology))
    base = make_engine("jax", _app(16, topology), cfg).run()
    for w in (2, 4):
        fused = make_engine("jax", _app(16, topology), cfg,
                            superstep_windows=w).run()
        assert qos_signature(fused) == qos_signature(base), \
            f"{topology}: W={w} fused diverged from per-window dense"


# ---------------------------------------------------------------------------
# Replicate plumbing and auto-layout resolution on the dense path
# ---------------------------------------------------------------------------
def test_dense_engine_replicates_and_registry():
    cfg = _cfg(0.01)
    eng = make_engine("jax", _app(16, "torus"), cfg, layout="dense")
    assert eng.layout == "dense"
    reps = eng.run_replicates([0, 1])
    base = make_engine("jax", _app(16, "torus"), cfg, layout="edge")
    singles = base.run_replicates([0, 1])
    for rd, re_ in zip(reps, singles):
        assert rd.updates == re_.updates
    # distinct seeds give distinct trajectories on the dense path too
    assert reps[0].updates != reps[1].updates


def test_auto_layout_resolves_dense_everywhere():
    cfg = _cfg(0.01)
    for topology in TOPOLOGIES:
        assert JaxEngine(_app(16, topology), cfg).layout == "dense", topology
