#!/usr/bin/env python3
"""The control of ``correct``: the plain reference with one guarantee of the
configuration broken, put in the program's place and compared with the
reference by the benchmark's own comparison. It must come out not correct.

    python3 benchmarks/chip/control.py --workload gc1-be --windows 112 \
        --fault no_latency --seeds 1 2 3

The broken guarantee is the latency (``--fault no_latency``: a message is
available as soon as it is sent) where the cell communicates, and the
stalls of the timing model (``--fault no_stall``: no step ever stalls)
where it does not. Every number compared and its limit is printed per
seed, and a JSON line closes the output. The benchmark's runs do not run
this.
"""
import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402

FAULTS = ("no_latency", "no_stall")


def reference_view(state):
    """A reference state in the shape of ``compare.program_view``."""
    import numpy as np

    import compare
    out = {k: np.asarray(state[k]) for k in compare.PROCESS_FIELDS}
    out["app"] = {k: np.asarray(v) for k, v in state["app"].items()}
    out["in_duct"] = np.asarray(state["size"])
    out["k"] = int(state["k"])
    return out


def control(cell, seed: int, windows: int, fault: str) -> dict:
    """The numbers compared for the control against the reference."""
    import compare
    import reference
    sw = harness.swarm(cell)
    chunk = cell.traffic["chunk"]
    ref = reference.run(sw, seed, windows, chunk=chunk)
    bad = reference.run(sw, seed, windows, chunk=chunk, fault=fault)
    digest = lambda s: compare.reference_digest(
        s, reference.quality(sw, s), sw.comm)
    compared, _ = compare.compare(reference_view(bad), ref, digest(bad),
                                  digest(ref), windows)
    return compared


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--windows", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", choices=FAULTS, required=True)
    args = ap.parse_args(argv)
    harness.sys_path()
    import compare
    cell = harness.resolve(harness.load_benchmark(), args.workload)
    harness.check_supported(cell)
    devices = harness.device_summary(cell.chips)
    harness.use_compile_cache()
    rows = []
    for seed in args.seeds:
        t0 = time.perf_counter()
        c = control(cell, harness.seed32(seed), args.windows, args.fault)
        rows.append({"seed": seed, "correct": compare.is_correct(c),
                     "compared": c})
        print(f"seed {seed}: correct={compare.is_correct(c)} "
              + " ".join(f"{k}={v['value']}(limit {v['limit']})"
                         for k, v in c.items())
              + f" in {time.perf_counter() - t0:.1f} s", flush=True)
    print(json.dumps({"workload": args.workload, "fault": args.fault,
                      "windows": args.windows,
                      "device": devices[0].device_kind, "runs": rows}))


if __name__ == "__main__":
    try:
        main()
    except harness.BenchError as e:
        sys.exit(f"control.py: {e}")
