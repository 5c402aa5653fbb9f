#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 benchmarks/chip/run.py --workload gc1-be --seed 7 --seconds 10 --trace 0

Set-up builds the cell through the program's entry points from ``--seed``,
compiles (or loads from the persistent cache) the cell's one chunk program
and runs one warm chunk. The window then drives that chunk until
``--seconds`` have passed and ends with the user's result: the fetched
carry, assembled. After the window the plain reference replays as many windows
from the same seed and the comparison decides ``correct``.

``--trace 0`` reports the end-to-end metrics, ``--trace 1`` the per-layer
metrics read from a profiler trace of the window. The last line of stdout
is one JSON object; without a TPU, or with fewer chips than the cell asks
for, the run exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402
from harness import BenchError  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None):
    args = parse(argv)
    harness.sys_path()
    cell = harness.resolve(harness.load_benchmark(), args.workload)
    harness.check_supported(cell)
    devices = harness.device_summary(cell.chips)
    harness.use_compile_cache()
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    try:
        run = harness.run_cell(cell, harness.seed32(args.seed), args.seconds,
                               trace_dir, devices, T_START)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    for name, c in run.compared.items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(run.line, flush=True)

if __name__ == "__main__":
    try:
        main()
    except BenchError as e:
        sys.exit(f"run.py: {e}")
