"""The harness's timed path and comparison on the CPU at a few dozen
processes: the loop counts what the program did, each cell's run is
correct against the reference, and the command refuses to run without a
TPU."""
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from bench_cases import FAULTED, ROOT, SMALL, harness, small_cell


def test_loop_counts_updates_and_assembles():
    cell = small_cell("gc1-be")
    engine, carry = harness.build(cell, 11)
    step = harness.compile_chunk(engine, carry)
    probe = jax.jit(harness.any_done)
    carry = step(carry)
    before = harness.counter_totals(carry)
    win = harness.measure(engine, step, probe, carry, 0.05)
    wpc = engine._windows_per_call
    counts = harness.window_counts(cell, before, win.carry, win.chunks * wpc)
    steps = np.asarray(win.carry["steps"][0])
    assert counts["updates"] == int(steps.sum()) - before["steps"]
    # every process is active in every window before the horizon
    assert counts["updates"] == 64 * win.chunks * wpc
    assert int(win.carry["k"][0]) == (1 + win.chunks) * wpc
    assert win.result.updates == [int(s) for s in steps]
    assert win.result.sent == int(np.asarray(win.carry["c_att"]).sum())
    assert win.end > win.start and win.fetch_s > 0


def test_horizon_inside_the_window_is_an_error():
    cell = small_cell("gc1-be", horizon_steps=64)
    engine, carry = harness.build(cell, 3)
    step = harness.compile_chunk(engine, carry)
    with pytest.raises(harness.BenchError, match="horizon"):
        harness.measure(engine, step, jax.jit(harness.any_done), carry, 5.0)


@pytest.mark.parametrize("workload,traffic", [
    pytest.param(w, {}, id=w) for w in sorted(SMALL)] + [
    pytest.param("gc1-be", dict(faults=kind, **settings), id=f"gc1-be-{kind}")
    for kind, settings in sorted(FAULTED.items())])
def test_run_is_correct_against_the_reference(workload, traffic, capsys):
    """Each cell, and the best-effort cell with each faulted host."""
    run = harness.run_cell(small_cell(workload, **traffic),
                           harness.seed32(2 ** 31 + 77), 0.2, None,
                           jax.devices(), time.perf_counter())
    line = json.loads(run.line)
    assert run.correct and line["correct"], run.compared
    assert list(line)[-1] == "compared"
    assert all(c["value"] == 0 for c in run.compared.values())
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"updates_per_s", "setup_s"}
    assert line["device"]["count"] == len(jax.devices())


def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", "gc1-be",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    last = (proc.stdout.strip().splitlines() or [""])[-1]
    return proc.returncode != 0 and not last.startswith("{")


def test_command_exits_nonzero_without_a_tpu():
    proc = _command(ROOT)
    assert _no_result(proc), proc.stdout
    assert "needs a TPU" in proc.stderr


def test_command_exits_nonzero_with_only_the_benchmark(tmp_path):
    """A checkout holding only BENCHMARK.json and the benchmark's paths has
    no program to run."""
    bench = harness.load_benchmark()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in bench["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p)
    assert _no_result(_command(tmp_path))
