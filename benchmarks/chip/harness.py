"""The benchmark's registry and the program-facing half of a run.

``BENCHMARK.json`` names every cell; a cell is a configuration file under
``configs/`` (one deployment) and a traffic file under ``traffic/`` (one
mix), and every per-layer metric is a reader under ``metrics/``. All three
are found by name, so a new cell or metric is a new file and a new entry,
with no edit to this code.

The timed path is the program's own: ``make_engine`` builds the engine,
``JaxEngine._init_carry`` the carry on the device, and the window drives
the compiled chunk (``JaxEngine._get_runner``) the way ``run_replicates``
does: the carry is donated, and the host reads the previous chunk's
``done`` reduction while the next chunk runs. The window dispatches
chunks until ``--seconds`` have passed and ends with the user's result,
``jax.device_get`` of the carry and ``JaxEngine._assemble``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
import time
from pathlib import Path
from typing import Callable, Dict, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

#: host annotations the trace reduction attributes idle device time to
DISPATCH, PROBE, FETCH, ASSEMBLE = ("bench.dispatch", "bench.probe",
                                    "bench.fetch", "bench.assemble")


class BenchError(RuntimeError):
    """A run that must exit non-zero without a result."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: tuple
    per_layer: tuple


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def resolve(bench: dict, workload: str, root: Path = ROOT) -> Cell:
    """The cell named ``workload``, with its configuration and traffic read
    from their files and the metrics it reports."""
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise BenchError(f"unknown workload {workload!r}; choose from "
                         f"{sorted(by_name)}")
    w = by_name[workload]
    config = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(root / config["file"]) as f:
        cfg = json.load(f)
    with open(root / HERE.relative_to(ROOT) / "traffic"
              / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    return Cell(
        name=workload, config=cfg, traffic=traffic, chips=int(w["chips"]),
        end_to_end=tuple(m for m in bench["end_to_end"]
                         if _applies(m, workload)),
        per_layer=tuple(m for m in bench["per_layer"]
                        if _applies(m, workload)))


def metric_reader(name: str, root: Path = ROOT) -> Callable:
    """``read(reading)`` of the per-layer metric ``name``, from
    ``metrics/<name>.py``."""
    path = root / HERE.relative_to(ROOT) / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def seed32(seed: int) -> int:
    """The program keeps its seed in an int32: fold any whole number in."""
    return int(seed) % (1 << 31)


# ---------------------------------------------------------------------------
# the deployment as the program and the reference see it
# ---------------------------------------------------------------------------
def duration(cell: Cell) -> float:
    """Virtual horizon: ``horizon_steps`` mean compute times."""
    return cell.traffic["horizon_steps"] * cell.config["timing"]["base_compute"]


def mode_settings(cell: Cell) -> dict:
    """The traffic's settings of its release mode (the barrier's cost, for
    one), by the keys the reference declares for that mode: the program
    and the reference take the same numbers."""
    import reference
    tr = cell.traffic
    return {k: tr[k] for k in reference.MODES[tr["mode"]]}


def sim_config(cell: Cell, seed: int):
    from repro.core.modes import AsyncMode
    from repro.runtime.simulator import SimConfig
    c, tr = cell.config, cell.traffic
    dur = duration(cell)
    return SimConfig(mode=AsyncMode[tr["mode"]], duration=dur,
                     snapshot_warmup=dur / tr["snapshot_warmup_div"],
                     snapshot_interval=dur / tr["snapshot_interval_div"],
                     buffer_capacity=c["buffer_capacity"], seed=seed,
                     **c["timing"], **mode_settings(cell))


def swarm(cell: Cell):
    """The reference's view of the same deployment."""
    import reference
    c, tr, tm = cell.config, cell.traffic, cell.config["timing"]
    dur = duration(cell)
    return reference.Swarm(
        n=c["processes"], topology=c["topology"],
        app=reference.module("app", c["app"]).App.from_config(c),
        capacity=c["buffer_capacity"], max_pops=c["max_pops"],
        mode=tr["mode"],
        duration=dur, snapshot_warmup=dur / tr["snapshot_warmup_div"],
        snapshot_interval=dur / tr["snapshot_interval_div"], **tm,
        **mode_settings(cell))


def check_supported(cell: Cell):
    """The reference has the cell's topology and app modules and declares
    its mode and faults, and the traffic states what the mode takes."""
    import reference
    c, tr = cell.config, cell.traffic
    try:
        reference.module("topology", c["topology"])
        reference.module("app", c["app"])
    except FileNotFoundError as e:
        raise BenchError(str(e)) from None
    if tr["mode"] not in reference.MODES:
        raise BenchError(f"the reference has no mode {tr['mode']!r}; it "
                         f"implements {sorted(reference.MODES)}")
    if tr["faults"] not in reference.FAULTS:
        raise BenchError(f"the reference draws no faults {tr['faults']!r}; "
                         f"it implements {list(reference.FAULTS)}")
    missing = [k for k in reference.MODES[tr["mode"]] if k not in tr]
    if missing:
        raise BenchError(f"mode {tr['mode']!r} takes {missing} from the "
                         f"traffic file, which does not state them")


# ---------------------------------------------------------------------------
# the timed path
# ---------------------------------------------------------------------------
class Spans:
    """Host-clock spans of the run's phases, in seconds."""

    def __init__(self):
        self.s: Dict[str, float] = {}

    def add(self, name: str, start: float):
        self.s[name] = self.s.get(name, 0.0) + time.perf_counter() - start


def build(cell: Cell, seed: int):
    """Topology, app and engine through the program's entry points, and
    replicate ``seed``'s carry on the device. Returns (engine, carry)."""
    import jax
    from repro.apps.graphcolor import GraphColorApp, GraphColorConfig
    from repro.runtime.config import RunConfig
    from repro.runtime.engine import make_engine
    from repro.runtime.topologies import make_topology
    c, tr = cell.config, cell.traffic
    n = c["processes"]
    app = GraphColorApp(
        GraphColorConfig(n_processes=n,
                         nodes_per_process=c["simels_per_process"],
                         n_colors=c["n_colors"], b=c["b"], seed=seed),
        topology=make_topology(c["topology"], n))
    eng = make_engine(
        RunConfig(engine="jax", scheduler=tr["scheduler"],
                  superstep_windows=tr["superstep_windows"]),
        app, sim_config(cell, seed), max_pops=c["max_pops"],
        chunk=tr["chunk"])
    carry = jax.tree.map(lambda x: x[None], eng._init_carry(seed))
    return eng, jax.block_until_ready(carry)


def any_done(carry):
    import jax.numpy as jnp
    return jnp.any(carry["done"])


@dataclasses.dataclass
class Window:
    carry: object          # the fetched carry (host)
    result: object         # the assembled SimResult
    chunks: int            # chunks dispatched in the window
    start: float           # perf_counter at the first dispatch
    end: float             # perf_counter after the assemble
    fetch_s: float         # device_get + assemble


def measure(engine, step, probe, carry, seconds: float,
            annotate=None) -> Window:
    """Dispatch chunks until ``seconds`` have passed, reading each chunk's
    ``any(done)`` while the next runs; then fetch and assemble. Raises if
    any process reaches the virtual horizon: its windows would make no
    update and the rate would count idle work."""
    import jax
    if annotate is None:
        from contextlib import nullcontext
        annotate = lambda name: nullcontext()
    chunks, pending = 0, None
    start = time.perf_counter()
    while True:
        with annotate(DISPATCH):
            carry = step(carry)
            flag = probe(carry)
        chunks += 1
        if pending is not None:
            with annotate(PROBE):
                if bool(pending):
                    raise BenchError("a process reached the virtual horizon "
                                     "inside the window")
        pending = flag
        if time.perf_counter() - start >= seconds:
            break
    f0 = time.perf_counter()
    with annotate(FETCH):
        host = jax.device_get(carry)
    del carry
    with annotate(ASSEMBLE):
        result = engine._assemble(host, 0)
    end = time.perf_counter()
    if bool(host["done"].any()):
        raise BenchError("a process reached the virtual horizon inside the "
                         "window")
    return Window(host, result, chunks, start, end, end - f0)


def device_summary(chips: int):
    """JAX's devices; raises where they are not TPUs or too few."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"the benchmark needs a TPU; JAX found "
                         f"{devs[0].platform} devices only")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips; JAX found "
                         f"{len(devs)}")
    return devs


def use_compile_cache():
    """JAX's persistent compilation cache at a fixed place inside the
    checkout, or where ``JAX_COMPILATION_CACHE_DIR`` says."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT /
                                                              ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def sys_path():
    for p in (str(ROOT / "src"), str(HERE)):
        if p not in sys.path:
            sys.path.insert(0, p)


def result_line(correct: bool, attempted: int, failed: int,
                metrics: Dict[str, dict], device: dict, compared: dict,
                breakdown: Optional[dict] = None) -> str:
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = compared
    return json.dumps(out)


def window_counts(cell: Cell, before, after, windows: int) -> dict:
    """What the window did, from the program's counters: updates, messages
    drained and pushes accepted, per window and in total."""
    import numpy as np

    def total(c, k):
        return int(np.sum(np.asarray(c[k]), dtype=np.int64))
    d = {k: total(after, k) - before[k]
         for k in ("steps", "c_msgs", "c_ok", "c_att")}
    return dict(updates=d["steps"], drained=d["c_msgs"], pushed=d["c_ok"],
                attempted_sends=d["c_att"], windows=windows)


def counter_totals(carry) -> dict:
    import numpy as np
    return {k: int(np.sum(np.asarray(carry[k]), dtype=np.int64))
            for k in ("steps", "c_msgs", "c_ok", "c_att")}


@dataclasses.dataclass
class Reading:
    """What a per-layer metric's reader gets: the cell, the host spans of
    the traced run, the reduced trace (None where it held nothing), the
    window's counts and the chip's peaks."""
    cell: Cell
    spans: Dict[str, float]
    trace: object
    counts: dict
    peaks: dict


@dataclasses.dataclass
class Run:
    line: str
    compared: dict
    correct: bool


def compile_chunk(engine, carry):
    """The cell's one chunk program: compiled, or loaded from the cache."""
    return engine._get_runner().lower(carry).compile()


def shapes(cell: Cell, windows_per_call: int) -> dict:
    c = cell.config
    return dict(n=c["processes"], R=4 * c["processes"],
                C=c["buffer_capacity"], L=swarm(cell).L,
                simels=c["simels_per_process"], n_colors=c["n_colors"],
                superstep_windows=cell.traffic["superstep_windows"],
                windows_per_call=windows_per_call)


def run_cell(cell: Cell, seed: int, seconds: float, trace_dir: Optional[str],
             devices, t_start: float) -> Run:
    """One run of ``cell``: set-up, the window (traced into ``trace_dir``
    when given), the reference replay and the comparison."""
    import jax
    import compare
    import reference
    spans = Spans()
    t = time.perf_counter()
    engine, carry = build(cell, seed)
    spans.add("setup.host", t)
    t = time.perf_counter()
    step = compile_chunk(engine, carry)
    spans.add("setup.compile", t)
    probe = jax.jit(any_done)
    carry = step(carry)
    if bool(probe(carry)):
        raise BenchError("a process reached the virtual horizon in warm-up")
    before = counter_totals(carry)
    wpc = engine._windows_per_call
    annotate = None
    setup_s = time.perf_counter() - t_start
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
        annotate = jax.profiler.TraceAnnotation
    try:
        win = measure(engine, step, probe, carry, seconds, annotate)
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
    del carry, step
    spans.s["loop.fetch"] = win.fetch_s
    windows = (1 + win.chunks) * wpc
    counts = window_counts(cell, before, win.carry, win.chunks * wpc)
    used = float(win.carry["t"].max()) / duration(cell)
    print(f"{cell.name}: {win.chunks} chunks of {wpc} windows in "
          f"{win.end - win.start:.3f} s, {counts['updates']} updates; "
          f"horizon used {used:.6f}", flush=True)
    stats = [d.memory_stats() or {} for d in devices[:cell.chips]]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    prog = compare.program_view(win.carry)
    res_digest = compare.result_digest(win.result)
    del win.carry, win.result, engine
    t = time.perf_counter()
    sw = swarm(cell)
    ref = reference.run(sw, seed, windows, chunk=cell.traffic["chunk"])
    ref_digest = compare.reference_digest(ref, reference.quality(sw, ref),
                                          sw.comm)
    compared, failed = compare.compare(prog, ref, res_digest, ref_digest,
                                       windows)
    print(f"{cell.name}: reference of {windows} windows took "
          f"{time.perf_counter() - t:.3f} s", flush=True)
    correct = compare.is_correct(compared)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    breakdown = None
    if trace_dir:
        import tracing
        from roofline import peaks
        summary = tracing.reduce(tracing.find_xplane(trace_dir))
        if summary is None:
            raise BenchError("the trace holds no device operation in the "
                             "window")
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        breakdown = summary.breakdown()
        counts.update(shapes(cell, wpc))
        reading = Reading(cell, {k + "_s": v for k, v in spans.s.items()},
                          summary, counts, peaks(dev.device_kind))
        metrics = {}
        for m in cell.per_layer:
            v = metric_reader(m["name"])(reading)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = {"updates_per_s": counts["updates"] / (win.end - win.start),
                  "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    line = result_line(correct, counts["updates"], failed, metrics, device,
                       compared, breakdown)
    return Run(line, compared, correct)
