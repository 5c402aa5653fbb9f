"""Driver for the paper's four experiment families (DESIGN.md §5).

  modes          asynchronicity-mode sweep: update rate + solution quality
                 under barrier / rolling / fixed / best-effort / no-comm
                 (paper §III-A/B, claims C1 + C2)
  weak_scaling   QoS distributions while scaling the process count at fixed
                 work per process (paper §III-F, claim C3)
  intensivity    communication-intensivity sweep: simels per process from
                 maximal (1) down to the benchmark parameterization (2048)
                 (paper §III-C/E)
  faults         an apparently-faulty host: extreme degradation inside its
                 clique, stable global medians (paper §III-G, claim C4)

Every family reports per-process QoS *distributions* — median + tail
percentiles over (process, window) samples — because under best-effort
communication the distribution, not a scalar, is the result.

Every family runs on either simulation backend (``--engine event`` — the
discrete-event reference, or ``--engine jax`` — the vectorized windowed-time
engine, DESIGN.md §7); ``--replicates R`` sweeps R seeds, dispatched as one
vmapped scan on the jax engine.  ``--shards S`` partitions the population
over an S-device mesh (DESIGN.md §8) with the seed axis vmapped inside
each shard; any shard count reproduces the single-device trajectories
exactly.  ``--superstep-windows W`` fuses W windows per exchange (sharded:
one packed ppermute per superstep, DESIGN.md §9; unsharded: the W-fused
dense megakernel with one ring commit per superstep, DESIGN.md §13 —
bitwise-identical either way at W=1, and the unsharded fusion at any W),
``--scheduler pipelined`` double-buffers the sharded exchange so it
overlaps the next superstep's interior windows (boundary messages arrive
one superstep later — honest latency the QoS stream observes, DESIGN.md
§12 / docs/QOS.md), and ``--qos-interval`` pins the snapshot spacing of
the time-resolved ``qos_timeseries`` every row carries.

All of these axes travel as one frozen
:class:`~repro.runtime.config.RunConfig` (built from the CLI namespace by
``RunConfig.from_args``, stamped into every result row by ``to_dict``).

CLI::

    PYTHONPATH=src python -m repro.runtime.experiments \
        --topology torus --procs 64 256 --engine jax

runs weak scaling on a torus at 64 and 256 processes; ``--family all``
runs every family.  See EXPERIMENTS.md for the full matrix.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import pathlib
import time
from typing import Dict, List, Optional, Sequence

from repro.core.modes import AsyncMode
from repro.core.qos import METRICS, aggregate_reports, aggregate_timeseries
from repro.core.slo import SloPolicy
from repro.runtime import spans
from repro.runtime.config import RunConfig
from repro.runtime.engine import (ENGINES, make_engine, run_replicates,
                                  validate_run_config)
from repro.runtime.faults import (crashed_host, faulty_host, flapping_host,
                                  lossy_host)
from repro.runtime.service import default_timeline, run_service
from repro.runtime.simulator import SimConfig
from repro.runtime.topologies import TOPOLOGIES, Topology, make_topology

PERCENTILES = (50, 95)

_UNITS = {"simstep_period": ("us", 1e6), "simstep_latency": ("steps", 1.0),
          "walltime_latency": ("us", 1e6), "delivery_failure_rate": ("", 1.0),
          "delivery_clumpiness": ("", 1.0)}


def make_app(name: str, n: int, simels: int, topology: Optional[Topology],
             seed: int = 0, initial_state=None):
    if name == "graphcolor":
        from repro.apps.graphcolor import GraphColorApp, GraphColorConfig
        return GraphColorApp(
            GraphColorConfig(n_processes=n, nodes_per_process=simels,
                             seed=seed), topology=topology,
            initial_state=initial_state)
    if name == "evo":
        # evo carries no state across service epochs yet; it restarts fresh
        from repro.apps.evo import EvoApp, EvoConfig
        return EvoApp(EvoConfig(n_processes=n, cells_per_process=simels,
                                seed=seed), topology=topology)
    raise ValueError(f"unknown app {name!r} (graphcolor|evo)")


def _sim_config(args, n: int, mode: AsyncMode = AsyncMode.BEST_EFFORT,
                **overrides) -> SimConfig:
    # windows shrink with the horizon so every scale yields >= ~6 windows;
    # --qos-interval pins the snapshot spacing instead (time-resolved QoS)
    warmup = args.duration / 6
    interval = (args.qos_interval if args.qos_interval
                else args.duration / 12)
    base = dict(mode=mode, duration=args.duration,
                base_compute=args.base_compute,
                base_latency=args.base_latency,
                intra_node_latency=args.intra_latency,
                snapshot_warmup=warmup, snapshot_interval=interval,
                buffer_capacity=args.buffer, seed=args.seed,
                barrier_timeout=args.barrier_timeout)
    base.update(overrides)
    return SimConfig(**base)


def _distributions(res) -> Dict[str, Dict[str, float]]:
    return aggregate_reports(res.qos, percentiles=PERCENTILES)


def _print_distributions(dist, indent: str = "    "):
    for m in METRICS:
        unit, scale = _UNITS[m]
        parts = []
        for key, v in dist[m].items():
            if v is None:
                parts.append(f"{key}=n/a")
            else:
                parts.append(f"{key}={v * scale:.3f}{unit}")
        print(f"{indent}{m:<24} " + "  ".join(parts))


def _topology_for(args, n: int) -> Topology:
    kw = {}
    if args.topology == "cliques" and args.clique_size:
        kw["clique_size"] = args.clique_size
    return make_topology(args.topology, n, **kw)


def _run_config(args) -> RunConfig:
    """The frozen strategy selection every family launches with.

    One :class:`RunConfig` is built from the CLI namespace in ``main``
    (the flag names match the field names), validated once against the
    engine registry, and stamped into every result row via ``to_dict``.
    """
    return RunConfig.from_args(args)


# ---------------------------------------------------------------------------
# Families
# ---------------------------------------------------------------------------
def run_modes(args) -> List[dict]:
    n = args.procs[0]
    topo = _topology_for(args, n)
    print(f"[modes] app={args.app} topology={topo.name} n={n} "
          f"simels={args.simels} engine={args.engine}")
    rows = []
    for mode in AsyncMode:
        app = make_app(args.app, n, args.simels, topo, args.seed)
        res = make_engine(args.run, app,
                          _sim_config(args, n, mode=mode)).run()
        dist = _distributions(res)
        row = dict(family="modes", mode=int(mode), n=n,
                   topology=topo.name, engine=args.engine,
                   run=args.run.to_dict(),
                   rate_per_cpu=res.update_rate_per_cpu,
                   quality=res.quality,
                   delivery_failure_rate=res.delivery_failure_rate,
                   qos=dist)
        rows.append(row)
        print(f"  mode {int(mode)} ({mode.description}): "
              f"{res.update_rate_per_cpu:9.0f} upd/s/cpu  "
              f"quality={res.quality:.3f}  fail={res.delivery_failure_rate:.3f}")
    return rows


def run_weak_scaling(args) -> List[dict]:
    print(f"[weak_scaling] app={args.app} topology={args.topology} "
          f"simels={args.simels} duration={args.duration}s "
          f"engine={args.engine} replicates={args.replicates} "
          f"shards={args.shards} superstep={args.superstep_windows} "
          f"scheduler={args.scheduler}")
    rows = []
    for n in args.procs:
        topo = _topology_for(args, n)
        cfg = _sim_config(args, n)
        t0 = time.perf_counter()
        # seeds omitted: the RunConfig's replicates field sizes the sweep,
        # rooted at cfg.seed
        results = run_replicates(
            args.run,
            lambda s: make_app(args.app, n, args.simels, topo, s), cfg)
        wall = time.perf_counter() - t0
        # QoS distribution pools (process, window) samples over replicates
        all_qos = [q for res in results for q in res.qos]
        dist = aggregate_reports(all_qos, percentiles=PERCENTILES)
        # time-resolved stream: interval i pools every replicate's
        # processes' i-th observation window
        series = aggregate_timeseries(
            [reps for res in results for reps in res.qos_by_process.values()],
            percentiles=PERCENTILES)
        rate = sum(r.update_rate_per_cpu for r in results) / len(results)
        updates = sum(sum(r.updates) for r in results)
        rows.append(dict(family="weak_scaling", n=n, topology=topo.name,
                         simels=args.simels, engine=args.engine,
                         run=args.run.to_dict(),
                         shards=args.shards,
                         superstep_windows=args.superstep_windows,
                         scheduler=args.scheduler,
                         replicates=args.replicates, rate_per_cpu=rate,
                         wall_seconds=wall, qos=dist,
                         qos_timeseries=series))
        print(f"  n={n:<5} ({topo.name}, {updates} updates "
              f"in {wall:.1f}s wall, {len(series)} QoS intervals)")
        _print_distributions(dist)
    return rows


def run_intensivity(args) -> List[dict]:
    n = args.procs[0]
    topo = _topology_for(args, n)
    sweep = args.intensivity_simels
    print(f"[intensivity] app={args.app} topology={topo.name} n={n} "
          f"simels sweep={sweep} engine={args.engine}")
    rows = []
    for simels in sweep:
        # heavier blocks cost more virtual compute per update (2048 simels
        # ~ 200us, matching the benchmark parameterization)
        base = args.base_compute * (1 + simels / 160)
        app = make_app(args.app, n, simels, topo, args.seed)
        res = make_engine(args.run, app,
                          _sim_config(args, n, base_compute=base)).run()
        dist = _distributions(res)
        rows.append(dict(family="intensivity", n=n, simels=simels,
                         topology=topo.name, engine=args.engine,
                         run=args.run.to_dict(),
                         rate_per_cpu=res.update_rate_per_cpu, qos=dist))
        print(f"  simels/process={simels}")
        _print_distributions(dist)
    return rows


def _fault_model(args, topo, host):
    """Build the --fault-kind model for the faults family (DESIGN.md §14):
    slowdown = the paper's degraded host (compute + link factors), crash =
    the host's processes die without churn splicing (neighbors keep
    sending into dead ducts), lossy = clique links drop each message with
    probability --loss-prob, flap = clique links cycle down/up on the
    deterministic hash schedule with down fraction --loss-prob."""
    if args.fault_kind == "crash":
        return crashed_host(topo, host)
    if args.fault_kind == "lossy":
        return lossy_host(topo, host, args.loss_prob)
    if args.fault_kind == "flap":
        return flapping_host(topo, host, args.loss_prob)
    return faulty_host(topo, host, args.fault_compute, args.fault_link)


def run_faults(args) -> List[dict]:
    n = args.procs[0]
    topo = _topology_for(args, n)
    host = args.faulty_host if args.faulty_host is not None else topo.n_nodes // 2
    victims = set(topo.host_pids(host))
    clique = set()
    for p in victims:
        clique.update(topo.clique_of(p))
    print(f"[faults] app={args.app} topology={topo.name} n={n} "
          f"faulty host={host} kind={args.fault_kind} ({len(victims)} "
          f"procs, clique of {len(clique)}) engine={args.engine}")

    rows = []
    for label, faults in (("without_fault", None),
                          ("with_fault", _fault_model(args, topo, host))):
        app = make_app(args.app, n, args.simels, topo, args.seed)
        res = make_engine(args.run, app, _sim_config(args, n),
                          faults).run()
        groups = {
            "global": res.qos,
            "clique": [q for p in clique for q in res.qos_by_process[p]],
            "rest": [q for p in range(n) if p not in clique
                     for q in res.qos_by_process[p]],
        }
        by_proc = {
            "global": list(res.qos_by_process.values()),
            "clique": [res.qos_by_process[p] for p in sorted(clique)],
            "rest": [res.qos_by_process[p] for p in range(n)
                     if p not in clique],
        }
        row = dict(family="faults", label=label, n=n, topology=topo.name,
                   faulty_host=host, fault_kind=args.fault_kind,
                   engine=args.engine,
                   run=args.run.to_dict(),
                   qos={g: aggregate_reports(reps, PERCENTILES)
                        for g, reps in groups.items()},
                   qos_timeseries={
                       g: aggregate_timeseries(reps, PERCENTILES)
                       for g, reps in by_proc.items()})
        rows.append(row)
        print(f"  {label}:")
        for g in ("global", "clique", "rest"):
            print(f"   {g}:")
            _print_distributions(row["qos"][g], indent="      ")
    return rows


def run_serve(args) -> List[dict]:
    """Live-service scenario: open-loop traffic + churn + SLO verdicts.

    One long-running serve on the first ``--procs`` count: the
    ``--traffic`` arrival shape feeds every process's work queue at
    ``--arrival-rate``, ``--churn`` incidents (host fault/heal, process
    leave/join) split the run into epochs with patched topologies, and
    the per-interval QoS stream is scored against the ``--slo-*`` budgets
    (``runtime/service.py`` / ``core/slo.py``).
    """
    n = args.procs[0]
    topo = _topology_for(args, n)
    timeline = default_timeline(topo, args.churn, args.duration,
                                args.fault_compute, args.fault_link)
    policy = SloPolicy(latency_p99_budget=args.slo_latency,
                       failure_p99_budget=args.slo_failure,
                       burn_window=args.burn_window,
                       burn_threshold=args.burn_threshold)
    cfg = _sim_config(args, n, arrival_rate=args.arrival_rate,
                      arrival_shape=args.traffic)
    print(f"[serve] app={args.app} topology={topo.name} n={n} "
          f"traffic={args.traffic}@{args.arrival_rate:g}/s churn={args.churn} "
          f"engine={args.engine} slo=(lat_p99<={policy.latency_p99_budget}, "
          f"fail_p99<={policy.failure_p99_budget})")
    out = run_service(
        args.run,
        lambda topology, s, init_state=None: make_app(
            args.app, topology.n, args.simels, topology, s,
            initial_state=init_state),
        cfg, topo, timeline, policy)
    for ep in out["epochs"]:
        print(f"  epoch {ep['epoch']}: t=[{ep['t_start']:.4f}, "
              f"{ep['t_end']:.4f}) procs={ep['n_procs']} "
              f"absent={ep['absent_pids']} faulty={ep['faulty_hosts']} "
              f"({ep['intervals']} intervals)")
    s = out["slo"]["summary"]
    svc = out["service"]
    print(f"  slo: {s['intervals']} intervals, {s['breaches']} breaches, "
          f"{s['no_data']} no-data, max_burn={s['max_burn_rate']:.2f} "
          f"-> {'OK' if s['ok'] else 'BREACH'}")
    print(f"  service: {svc['arrivals']} arrivals, {svc['served']} served, "
          f"{svc['backlog']} backlogged")
    _print_distributions(out["qos"])
    row = dict(family="serve", n=n, topology=topo.name, engine=args.engine,
               run=args.run.to_dict(), traffic=args.traffic,
               arrival_rate=args.arrival_rate, churn=args.churn,
               policy=dataclasses.asdict(policy), **out)
    return [row]


FAMILIES = {
    "modes": run_modes,
    "weak_scaling": run_weak_scaling,
    "intensivity": run_intensivity,
    "faults": run_faults,
    "serve": run_serve,
}


# ---------------------------------------------------------------------------
def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m repro.runtime.experiments",
        description="Run the paper's experiment families on the "
                    "discrete-event best-effort runtime.")
    p.add_argument("--family", default="weak_scaling",
                   choices=[*FAMILIES, "all"])
    p.add_argument("--engine", default="event", choices=sorted(ENGINES),
                   help="simulation backend: event (discrete-event "
                        "reference) or jax (vectorized windowed-time)")
    p.add_argument("--replicates", type=int, default=1,
                   help="seeds per weak-scaling point (one vmapped "
                        "dispatch on --engine jax)")
    p.add_argument("--shards", type=int, default=1,
                   help="partition the population over this many mesh "
                        "devices (--engine jax; the seed axis vmaps inside "
                        "each shard).  On CPU set XLA_FLAGS="
                        "--xla_force_host_platform_device_count=S")
    p.add_argument("--superstep-windows", type=int, default=1,
                   help="windows fused per exchange (self-paced "
                        "scheduler, DESIGN.md §9/§13).  Sharded: boundary "
                        "traffic batches into one packed ppermute per "
                        "superstep, cutting the collective count ~W x.  "
                        "Unsharded: the W-fused dense megakernel commits "
                        "ring writes once per superstep.  1 = per-window "
                        "exchange (bitwise-identical trajectories)")
    p.add_argument("--scheduler", default="auto",
                   choices=["auto", "window", "superstep", "pipelined"],
                   help="exchange cadence strategy (DESIGN.md §11/§12/"
                        "§13): window = exchange every lockstep window, "
                        "superstep = batched every --superstep-windows "
                        "windows (sharded: one collective per superstep; "
                        "unsharded: the W-fused dense megakernel, "
                        "bitwise-identical), pipelined = double-buffered "
                        "— superstep k's exchange overlaps superstep "
                        "k+1's interior windows, boundary messages arrive "
                        "one superstep later (honest added latency the "
                        "QoS stream observes; see docs/QOS.md; needs "
                        "--shards > 1).  superstep/pipelined need "
                        "--superstep-windows > 1; auto follows "
                        "--superstep-windows")
    p.add_argument("--layout", default="auto",
                   choices=["auto", "dense", "edge"],
                   help="duct ring layout for --engine jax (DESIGN.md "
                        "§10/§13): dense = the degree-bucketed "
                        "receiver-major fast path (zero segment/scatter "
                        "ops per window; exact-degree buckets on ring/"
                        "torus, padded power-of-two buckets on smallworld/"
                        "cliques), edge = the general edge-major path.  "
                        "auto resolves to dense on every built-in "
                        "topology.  Trajectories are bitwise identical "
                        "either way")
    p.add_argument("--qos-interval", type=float, default=None,
                   help="QoS snapshot spacing in virtual seconds for the "
                        "time-resolved stream (default: duration/12); "
                        "rows carry a qos_timeseries with per-interval "
                        "distributions")
    p.add_argument("--topology", default="torus", choices=sorted(TOPOLOGIES))
    p.add_argument("--procs", type=int, nargs="+", default=[64, 256],
                   help="process counts (weak_scaling sweeps them; other "
                        "families use the first)")
    p.add_argument("--app", default="graphcolor",
                   choices=["graphcolor", "evo"])
    p.add_argument("--simels", type=int, default=1,
                   help="simulation elements per process (1 = maximal "
                        "communication intensivity)")
    p.add_argument("--duration", type=float, default=0.05,
                   help="virtual seconds per run")
    p.add_argument("--base-compute", type=float, default=15e-6)
    p.add_argument("--base-latency", type=float, default=550e-6)
    p.add_argument("--intra-latency", type=float, default=None,
                   help="same-host link latency (enables the hierarchical "
                        "link model; default: flat)")
    p.add_argument("--buffer", type=int, default=64)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--clique-size", type=int, default=None)
    p.add_argument("--intensivity-simels", type=int, nargs="+",
                   default=[1, 64, 2048])
    p.add_argument("--faulty-host", type=int, default=None)
    p.add_argument("--fault-compute", type=float, default=30.0)
    p.add_argument("--fault-link", type=float, default=30.0)
    p.add_argument("--fault-kind", default="slowdown",
                   choices=["slowdown", "crash", "lossy", "flap"],
                   help="faults-family fault type (DESIGN.md §14): "
                        "slowdown = the paper's degraded host "
                        "(--fault-compute/--fault-link factors), crash = "
                        "the host's processes die mid-run (no churn "
                        "splicing — neighbors keep sending into dead "
                        "ducts), lossy = clique links drop messages with "
                        "probability --loss-prob, flap = clique links "
                        "cycle down/up deterministically with down "
                        "fraction --loss-prob")
    p.add_argument("--loss-prob", type=float, default=0.05,
                   help="per-send drop probability for --fault-kind lossy "
                        "(and the down fraction for flap)")
    p.add_argument("--barrier-timeout", type=float, default=0.0,
                   help="quarantine threshold tau in virtual seconds for "
                        "barrier modes (DESIGN.md §14): a process whose "
                        "next barrier arrival lags the cohort front by "
                        "more than tau is excluded from the release (and "
                        "readmitted with hysteresis once it catches up "
                        "within tau/2).  0 = plain barriers; crashed "
                        "processes are excluded under any finite tau")
    # --- live-service family (--family serve) ---------------------------
    p.add_argument("--traffic", default="poisson",
                   choices=["poisson", "bursty", "diurnal"],
                   help="open-loop arrival shape feeding each process's "
                        "work queue (runtime/service.py)")
    p.add_argument("--arrival-rate", type=float, default=1e5,
                   help="mean arrivals per process per virtual second")
    p.add_argument("--churn", type=int, default=0,
                   help="churn incidents spread over the run: even "
                        "incidents fault+heal a host, odd ones make a "
                        "process leave+rejoin (duct rings spliced via "
                        "patch_topology)")
    p.add_argument("--slo-latency", type=float, default=50.0,
                   help="per-interval p99 simstep-latency budget (updates "
                        "per one-way delivery)")
    p.add_argument("--slo-failure", type=float, default=0.35,
                   help="per-interval p99 delivery-failure-rate budget")
    p.add_argument("--burn-window", type=int, default=5,
                   help="trailing data-bearing intervals in the burn-rate "
                        "window")
    p.add_argument("--burn-threshold", type=float, default=0.5,
                   help="burn rate above which an interval is marked "
                        "burning (sustained breach)")
    p.add_argument("--json", default=None, help="write rows to this path")
    p.add_argument("--trace-dir", default=None,
                   help="record a jax.profiler trace of the whole run "
                        "into this directory: the program's host spans "
                        "and, with --engine jax, the device ops by window "
                        "phase; print the spans' host seconds and the "
                        "counters")
    return p


#: the compile cache's place when ``JAX_COMPILATION_CACHE_DIR`` is unset:
#: one fixed directory in the checkout (git ignores it), so a later run of
#: the same program finds its compiled executables again
COMPILE_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache for an entry point.
    ``JAX_COMPILATION_CACHE_DIR``, where set, places it (JAX reads the
    variable itself); otherwise it lives at :data:`COMPILE_CACHE_DIR`."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax
        jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))


def main(argv: Optional[Sequence[str]] = None) -> List[dict]:
    parser = build_parser()
    args = parser.parse_args(argv)
    # one frozen strategy carrier for every family; domain checks happen
    # in RunConfig, cross-axis rules once against the engine registry —
    # both before any app or JAX machinery is built
    try:
        args.run = _run_config(args)
        validate_run_config(args.run)
    except ValueError as e:
        parser.error(str(e))
    use_compile_cache()
    families = list(FAMILIES) if args.family == "all" else [args.family]
    rows: List[dict] = []
    t0 = time.perf_counter()
    with contextlib.ExitStack() as stack:
        if args.trace_dir:
            import jax
            spans.reset()
            stack.enter_context(jax.profiler.trace(args.trace_dir))
        for fam in families:
            rows.extend(FAMILIES[fam](args))
    print(f"done in {time.perf_counter() - t0:.1f}s wall")
    if args.trace_dir:
        print(spans.report())
        print(f"trace written under {args.trace_dir}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=1, default=float)
        print(f"wrote {args.json}")
    return rows


if __name__ == "__main__":
    main()
