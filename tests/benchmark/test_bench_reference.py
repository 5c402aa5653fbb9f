"""The reference's topology tables and draws agree with the conventions the
program states: duct slots by ascending neighbour, source-major canonical
edge ids, and the counter-based lognormal draws; its barrier-every-update
release equals the program's bit for bit."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_cases import harness, small_cell

import reference  # noqa: E402


@pytest.mark.parametrize("n", [9, 12, 64])
def test_torus_tables_follow_the_program_wiring(n):
    from repro.runtime.topologies import (canonical_edges, halo_slot_map,
                                          make_topology)
    tabs = reference.module("topology", "torus").tables(n)
    topo = make_topology("torus", n)
    _, _, index = canonical_edges(topo)
    for d in range(n):
        slot = halo_slot_map(topo.neighbors[d])
        for j in range(4):
            s = int(tabs["src"][d, j])
            assert slot[s] == j
            assert tabs["eid"][d, j] == index[(s, d)]
            assert tabs["src"].reshape(-1)[tabs["rev"][d, j]] == d
    flat = tabs["rev"].reshape(-1)
    assert (flat[flat] == np.arange(4 * n)).all()


@pytest.mark.parametrize("n", [9, 12, 64])
def test_torus_rolls_equal_gathers_by_the_tables(n):
    torus = reference.module("topology", "torus")
    tabs = {k: jnp.asarray(v) for k, v in torus.tables(n).items()}
    x = jnp.arange(n, dtype=jnp.int32) * 7 + 3
    y = jnp.arange(4 * n, dtype=jnp.int32).reshape(n, 4) * 5 + 1
    assert (torus.from_sender(tabs, x) == x[tabs["src"]]).all()
    assert (torus.of_reverse(tabs, y) == y.reshape(-1)[tabs["rev"]]).all()


def test_lognormal_draws_match_the_program_bit_for_bit():
    from repro.runtime.window_core import lognormal_factor
    keys = (12345, 3, jnp.arange(4096, dtype=jnp.int32),
            jnp.arange(4096, dtype=jnp.int32) % 17)
    for sigma in (0.0, 0.15, 0.5):
        a = np.asarray(reference.lognormal(sigma, *keys))
        b = np.asarray(lognormal_factor(sigma, *keys))
        assert (a.view(np.int32) == b.view(np.int32)).all()
    assert (np.asarray(reference.lognormal(0.0, *keys)) == 1.0).all()


def test_an_unknown_topology_is_refused():
    with pytest.raises(FileNotFoundError):
        reference.module("topology", "no-such-topology")


#: windows the barrier tests drive: 64 processes stall on ~60 of their steps
BARRIER_WINDOWS = 96


def _program_and_reference(cell, seed, windows):
    """The program's state and result after ``windows`` windows of ``cell``
    through the harness's own build and chunk, and the reference's."""
    import compare
    engine, carry = harness.build(cell, seed)
    step = harness.compile_chunk(engine, carry)
    assert windows % engine._windows_per_call == 0
    for _ in range(windows // engine._windows_per_call):
        carry = step(carry)
    host = jax.device_get(carry)
    res = compare.result_digest(engine._assemble(host, 0))
    sw = harness.swarm(cell)
    ref = reference.run(sw, seed, windows, chunk=cell.traffic["chunk"])
    ref_digest = compare.reference_digest(ref, reference.quality(sw, ref),
                                          sw.comm)
    compared, failed = compare.compare(compare.program_view(host), ref, res,
                                       ref_digest, windows)
    return sw, ref, compared, failed


@pytest.mark.parametrize("workload", ["gc1-barrier", "gc2048-be"])
@pytest.mark.parametrize("seed", [17, 2 ** 31 + 5])
def test_barrier_release_equals_the_program_bit_for_bit(workload, seed):
    """Barrier-every-update release in both configurations: every window
    releases every process once, at the latest clock plus the barrier's
    cost, and releases cross stalled steps."""
    cell = small_cell(workload, mode="BARRIER_EVERY_STEP",
                      **{k: harness.resolve(harness.load_benchmark(),
                                            "gc1-barrier").traffic[k]
                         for k in reference.MODES["BARRIER_EVERY_STEP"]})
    seed = harness.seed32(seed)
    sw, ref, compared, failed = _program_and_reference(cell, seed,
                                                       BARRIER_WINDOWS)
    assert all(c["value"] == 0 for c in compared.values()), compared
    assert failed == 0
    assert (ref["barrier_seq"] == BARRIER_WINDOWS).all()
    assert (ref["steps"] == BARRIER_WINDOWS).all()
    assert not ref["waiting"].any() and (ref["pending"] > 0).all()
    # one release for all: every process left the last one together
    assert (ref["last_release"] == ref["last_release"][0]).all()
    pids = np.arange(sw.n)[:, None]
    steps = np.arange(BARRIER_WINDOWS)[None, :]
    stalled = np.asarray(reference.hash_uniform(
        seed, reference.STREAM_STALL, pids, steps)) < sw.stall_prob
    assert stalled.any()


def test_barrier_release_past_the_horizon_ends_at_the_horizon():
    """At 30 mean steps of horizon the second release of seed 3 falls past
    it: every process ends there, its clock set to the horizon."""
    cell = small_cell("gc1-barrier", horizon_steps=30)
    sw, ref, compared, _ = _program_and_reference(cell, 3, BARRIER_WINDOWS)
    assert all(c["value"] == 0 for c in compared.values()), compared
    assert ref["done"].all()
    assert (ref["t"] == np.float32(sw.duration)).all()
    assert (ref["last_release"] >= np.float32(sw.duration)).all()
    assert (ref["barrier_seq"] == 2).all()


def test_barrier_cost_is_rounded_once_and_nothing_for_one_process():
    sw = harness.swarm(small_cell("gc1-barrier"))
    assert sw.barrier_cost == np.float32(2e-5 + 1.5e-5 * 6)
    one = dataclasses.replace(sw, n=1)
    assert one.barrier_cost == 0
