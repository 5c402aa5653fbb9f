#!/usr/bin/env python3
"""Run a cell several times, one process after another, and print the
spread of each metric: how ``BENCHMARK.json``'s bounds are measured.

    python3 benchmarks/chip/sets.py --workload gc1-be --sets 2 \
        --seeds 11 12 13 14 15 16 --seconds 20 --log chiprun_out/logs

Each set runs ``run.py`` once per seed, the same seeds in every set, each
run in a process of its own (this one never touches JAX, so each child has
the chip to itself). For each set and metric it prints the median and the
spread: the distance between the first and third quartile, as
``statistics.quantiles(values, n=4)`` gives them, over the median. Every
run's whole output goes to ``--log``; the last line of stdout is one JSON
object with every run's result line.
"""
import argparse
import json
import re
import statistics
import subprocess
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
CHUNKS = re.compile(r"(\d+) chunks of (\d+) windows in ([\d.]+) s")


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def one(workload, seed, seconds, trace, log: Path) -> dict:
    cmd = [sys.executable, str(HERE / "run.py"), "--workload", workload,
           "--seed", str(seed), "--seconds", str(seconds),
           "--trace", str(trace)]
    t0 = time.perf_counter()
    proc = subprocess.run(cmd, capture_output=True, text=True,
                          cwd=HERE.parents[1])
    wall = time.perf_counter() - t0
    name = f"{workload}_s{seed}_t{trace}_{int(time.time() * 1e3)}.log"
    (log / name).write_text(
        f"$ {' '.join(cmd)}\nrc={proc.returncode} wall={wall:.3f}\n"
        f"--- stdout\n{proc.stdout}\n--- stderr\n{proc.stderr}")
    lines = proc.stdout.strip().splitlines()
    result = None
    if proc.returncode == 0 and lines and lines[-1].startswith("{"):
        result = json.loads(lines[-1])
    m = CHUNKS.search(proc.stdout)
    return dict(seed=seed, rc=proc.returncode, wall_s=wall, result=result,
                chunks=int(m.group(1)) if m else None,
                window_s=float(m.group(3)) if m else None,
                tail=None if result else proc.stderr[-2000:])


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--log", default="chiprun_out/logs")
    args = ap.parse_args(argv)
    log = Path(args.log)
    log.mkdir(parents=True, exist_ok=True)
    sets = []
    for k in range(args.sets):
        runs = []
        for seed in args.seeds:
            r = one(args.workload, seed, args.seconds, args.trace, log)
            res = r["result"]
            print(f"set {k} seed {seed}: rc={r['rc']} wall={r['wall_s']:.1f} "
                  f"chunks={r['chunks']} "
                  + (f"correct={res['correct']} " + " ".join(
                      f"{n}={v['value']}" for n, v in res["metrics"].items())
                     if res else f"no result: {r['tail']}"), flush=True)
            runs.append(r)
        sets.append(runs)
        good = [r["result"] for r in runs if r["result"]]
        for name in (good[0]["metrics"] if good else ()):
            vals = [g["metrics"][name]["value"] for g in good]
            if len(vals) >= 2:
                print(f"set {k} {name}: median {statistics.median(vals)!r} "
                      f"spread {spread(vals)!r} of {len(vals)} runs",
                      flush=True)
    print(json.dumps({"workload": args.workload, "seconds": args.seconds,
                      "trace": args.trace, "sets": sets}))


if __name__ == "__main__":
    main()
