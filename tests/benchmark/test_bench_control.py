"""The comparison that decides ``correct`` fails what it must: the control
(the reference with latency broken, in the program's place) and the timed
path broken underneath the harness, once for each fault a one-chip cell
can have. The exchange between chips does not exist on one chip."""
import time

import jax
import jax.numpy as jnp
import pytest

from bench_cases import harness, small_cell

import compare  # noqa: E402  (on the path once bench_cases is imported)
from control import control  # noqa: E402


@pytest.mark.parametrize("workload,fault", [
    ("gc1-be", "no_latency"), ("gc2048-be", "no_latency"),
    ("gc1-be-ss8", "no_latency"), ("gc1-nocomm", "no_stall"),
    ("gc1-barrier", "no_latency")])
@pytest.mark.parametrize("seed", [5, 2 ** 31 + 9, 4_000_000_001])
def test_control_is_not_correct(workload, fault, seed):
    compared = control(small_cell(workload), harness.seed32(seed), 96,
                       fault)
    assert not compare.is_correct(compared)
    assert compared["procs_differ"]["value"] > 0


def _unchanged(step):
    return lambda carry: carry


def _half_left_out(step):
    """Only the first half of the processes advance; the rest keep their
    state."""
    def run(carry):
        new = step(jax.tree.map(jnp.copy, carry))
        def keep(x, y):
            if x.ndim < 2 or x.shape[1] != y.shape[1] or x.shape[1] < 2:
                return x
            half = x.shape[1] // 2
            return x.at[:, half:].set(y[:, half:])
        n = carry["t"].shape[1]
        per_proc = {k for k, v in carry.items()
                    if hasattr(v, "shape") and v.ndim >= 2
                    and v.shape[1] == n}
        return {k: (keep(new[k], carry[k]) if k in per_proc else new[k])
                for k in new}
    return run


def _colour_altered(step):
    """One process's colour is changed where the step produces it."""
    def run(carry):
        new = step(carry)
        app = dict(new["app"])
        app["colors"] = app["colors"].at[0, 0].set(
            (app["colors"][0, 0] + 1) % 3)
        return dict(new, app=app)
    return run


def _run_broken(workload, fault, monkeypatch):
    real = harness.compile_chunk
    monkeypatch.setattr(harness, "compile_chunk",
                        lambda engine, carry: fault(real(engine, carry)))
    return harness.run_cell(small_cell(workload), 21, 0.1, None,
                            jax.devices(), time.perf_counter())


@pytest.mark.parametrize("fault", [_unchanged, _half_left_out,
                                   _colour_altered])
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    run = _run_broken("gc1-be", fault, monkeypatch)
    assert not run.correct, run.compared


@pytest.mark.parametrize("fault", [_unchanged, _half_left_out,
                                   _colour_altered])
def test_broken_barrier_path_is_not_correct(fault, monkeypatch):
    run = _run_broken("gc1-barrier", fault, monkeypatch)
    assert not run.correct, run.compared
