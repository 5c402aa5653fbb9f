"""The reference's topology tables and draws agree with the conventions the
program states: duct slots by ascending neighbour, source-major canonical
edge ids, and the counter-based lognormal draws."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench_cases import harness  # noqa: F401  (puts the benchmark on the path)

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
