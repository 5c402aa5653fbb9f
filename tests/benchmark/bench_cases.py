"""Shared helpers for the benchmark's CPU tests: the harness on the path
and its cells cut to a few dozen processes."""
import dataclasses
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CHIP = os.path.join(ROOT, "benchmarks", "chip")
for p in (CHIP, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402

#: each fault kind of the reference, at the program's default settings, on
#: host 0: the traffic keys a faulted cell's file states
FAULTED = {
    "faulty_host": dict(fault_host=0, compute_factor=30.0, link_factor=50.0),
    "lossy_host": dict(fault_host=0, loss_prob=0.05),
    "flapping_host": dict(fault_host=0, down_frac=0.5,
                          flap_period=2.0 ** -12)}

#: small shapes for each cell: (processes, simels per process)
SMALL = {"gc1-be": (64, 1), "gc1-nocomm": (64, 1), "gc1-be-ss8": (64, 1),
         "gc2048-be": (64, 8), "gc1-barrier": (64, 1)}


def small_cell(workload: str, processes=None, **traffic):
    """``workload`` as BENCHMARK.json defines it, at a CPU test's size."""
    cell = harness.resolve(harness.load_benchmark(), workload)
    n, simels = SMALL[workload]
    config = dict(cell.config, processes=processes or n,
                  simels_per_process=simels)
    return dataclasses.replace(cell, config=config,
                               traffic=dict(cell.traffic, **traffic))
