"""Vectorized-engine tests: duct-op parity, determinism, replicates.

The jax engine's conformance with the event engine — exact (dyadic
configs) and statistical (jittered configs) — lives in the registry-driven
suite ``tests/test_engine_conformance.py``; this file keeps what is
specific to the jax engine itself:

  - the duct op agrees slot-for-slot with the numpy oracle
    (``kernels/duct_exchange/ref.py``), including bounded-buffer drops;
  - runs are deterministic in the seed, and vmapped replicates are
    independent and identical to single runs.
"""
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from engine_cases import PARITY_RTOL, gc_app, jittered_cfg  # noqa: E402,F401
from repro.core.modes import AsyncMode  # noqa: E402
from repro.kernels.duct_exchange import (  # noqa: E402
    duct_exchange,
    duct_exchange_jnp,
    duct_exchange_ref,
)
from repro.runtime.engine_jax import JaxEngine  # noqa: E402

_app = gc_app
_cfg = jittered_cfg


# ---------------------------------------------------------------------------
# Duct op parity against the numpy oracle
# ---------------------------------------------------------------------------
def _random_duct_state(rng, E=41, C=8, cap=6):
    qa = np.full((E, C), np.inf, np.float32)
    qt = np.zeros((E, C), np.int32)
    head = rng.integers(0, C, E).astype(np.int32)
    size = np.zeros(E, np.int32)
    for e in range(E):
        s = rng.integers(0, cap + 1)
        size[e] = s
        for j in range(s):
            qa[e, (head[e] + j) % C] = rng.random() * 2
            qt[e, (head[e] + j) % C] = rng.integers(0, 50)
    return qa, qt, head, size


@pytest.mark.parametrize("impl", ["jnp", "pallas_interpret"])
def test_duct_exchange_matches_ref(impl):
    rng = np.random.default_rng(7)
    qa, qt, head, size = _random_duct_state(rng)
    E = qa.shape[0]
    args = (qa, qt, head, size,
            (rng.random(E) * 2).astype(np.float32), rng.random(E) < 0.8,
            (rng.random(E) * 2).astype(np.float32), rng.random(E) < 0.8,
            (rng.random(E) * 0.5).astype(np.float32),
            rng.integers(0, 50, E).astype(np.int32))
    kw = dict(capacity=6, max_pops=4)
    ref = duct_exchange_ref(*args, **kw)
    if impl == "jnp":
        out = duct_exchange_jnp(*map(jnp.asarray, args), **kw)
    else:
        out = duct_exchange(*map(jnp.asarray, args), **kw,
                            use_pallas=True, interpret=True)
    for name, a, b in zip(ref._fields, ref, out):
        np.testing.assert_allclose(
            np.asarray(b, dtype=np.float64), np.asarray(a, np.float64),
            err_msg=f"{impl}: field {name}")


def test_duct_exchange_drops_when_full():
    """Bounded-buffer drop parity: a full ring rejects the push."""
    C, cap = 8, 4
    qa = np.full((1, C), np.inf, np.float32)
    qt = np.zeros((1, C), np.int32)
    head = np.zeros(1, np.int32)
    for j in range(cap):
        qa[0, j] = 100.0  # queued but unavailable for a long time
    size = np.full(1, cap, np.int32)
    args = (qa, qt, head, size,
            np.zeros(1, np.float32), np.ones(1, bool),
            np.zeros(1, np.float32), np.ones(1, bool),
            np.full(1, 0.1, np.float32), np.zeros(1, np.int32))
    kw = dict(capacity=cap, max_pops=4)
    ref = duct_exchange_ref(*args, **kw)
    out = duct_exchange_jnp(*map(jnp.asarray, args), **kw)
    assert not bool(ref.accepted[0])
    assert not bool(out.accepted[0])
    assert int(out.size[0]) == cap
    np.testing.assert_array_equal(np.asarray(out.q_avail), ref.q_avail)


# ---------------------------------------------------------------------------
# Engine determinism / replicates
# ---------------------------------------------------------------------------
def test_same_seed_determinism():
    cfg = _cfg(0.02)
    r1 = JaxEngine(_app(16), cfg).run()
    r2 = JaxEngine(_app(16), cfg).run()
    assert r1.updates == r2.updates
    assert r1.quality == r2.quality
    assert r1.dropped == r2.dropped and r1.sent == r2.sent


def test_vmap_replicates_independent_and_match_single_runs():
    cfg = _cfg(0.02)
    eng = JaxEngine(_app(16), cfg)
    reps = eng.run_replicates([0, 1, 2, 3])
    single0 = JaxEngine(_app(16), cfg).run()
    assert reps[0].updates == single0.updates
    assert reps[0].dropped == single0.dropped
    # distinct seeds give distinct trajectories
    assert len({tuple(r.updates) for r in reps}) > 1
    # every replicate produces a full QoS distribution
    for r in reps:
        assert len(r.qos) >= 16 * 3


def test_engine_counter_consistency():
    res = JaxEngine(_app(16), _cfg(0.02)).run()
    assert res.sent > 0
    assert 0 <= res.dropped <= res.sent
    # explicit drop counter backs the failure rate
    assert res.delivery_failure_rate == res.dropped / res.sent


def test_no_comm_sends_nothing():
    res = JaxEngine(_app(16), _cfg(0.02, mode=AsyncMode.NO_COMM)).run()
    assert res.sent == 0 and res.dropped == 0
    for rep in res.qos:
        assert rep.delivery_failure_rate == 0.0


def test_best_effort_beats_barrier_rate_on_jax():
    r0 = JaxEngine(_app(16), _cfg(0.02, mode=AsyncMode.BARRIER_EVERY_STEP,
                                  base_latency=100e-6)).run()
    r3 = JaxEngine(_app(16), _cfg(0.02, mode=AsyncMode.BEST_EFFORT,
                                  base_latency=100e-6)).run()
    assert r3.update_rate_per_cpu > 2.0 * r0.update_rate_per_cpu
    # barrier-every-step stays in lockstep
    assert max(r0.updates) - min(r0.updates) <= 1


# ---------------------------------------------------------------------------
# The chunk program writes the QoS snapshot buffer without a scatter
# ---------------------------------------------------------------------------
def _scatter_result_types(stablehlo: str):
    """The result type of every ``stablehlo.scatter`` in the text."""
    return [m.group(1) for m in re.finditer(
        r'"stablehlo\.scatter".*?\}\) : \([^\n]*\) -> (tensor<[^>]*>)',
        stablehlo, re.S)]


@pytest.mark.parametrize("scheduler,W", [("window", 1), ("superstep", 8)])
def test_chunk_writes_snapshots_without_scatter(scheduler, W):
    """Under ``vmap`` a scatter into the carried ``(n, S, 8)`` buffer is
    lifted to the scan's loop level with relayouts around it; the snapshot
    write is a masked select, and a scatter of that shape must not come
    back."""
    from repro.runtime.config import RunConfig
    from repro.runtime.engine import make_engine

    eng = make_engine(
        RunConfig(engine="jax", scheduler=scheduler, superstep_windows=W),
        _app(64, "torus"), _cfg(0.02), chunk=16)
    carry = jax.tree.map(lambda x: x[None], eng._init_carry(0))
    text = eng._get_runner().lower(carry).as_text()
    snap_type = "tensor<{}xf32>".format(
        "x".join(map(str, carry["snap"].shape)))
    assert snap_type in text
    assert snap_type not in _scatter_result_types(text)
