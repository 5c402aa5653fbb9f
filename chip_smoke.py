#!/usr/bin/env python3
"""Smoke check of the vectorized swarm engine on a TPU.

From the repository root, on a machine with a TPU:

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chip   # the sharded engine on a 2x2 host

One chip runs three phases, all in this one process:

  engine   a 2^18-process (512x512) torus running graphcolor in best-effort
           mode, built by ``make_engine`` and run by ``run_replicates``,
           under the per-window scheduler and the W=8 fused superstep
           scheduler.  Prints set-up and compile seconds, windows, updates
           per second on the device, peak device memory and whether the
           compiled chunk holds the Pallas kernels; checks message
           conservation and that the two schedulers' QoS signatures are
           equal (the fused superstep is bitwise identical by design).
  kernels  one window of ring state at the same widths through
           ``duct_window`` and ``duct_commit``, the Pallas kernel against
           its jnp twin, bit for bit.
  oracle   a dyadic 16-process torus scenario on the chip, whose QoS
           signature must equal the discrete-event simulator's.

``--four-chip`` runs only the sharded engine (4 shards) on a 4 x 2^16-
process torus under the window and W=8 superstep schedulers, and the same
configuration unsharded on one device.  The window scheduler must match
it bitwise (sharding is a pure layout change, DESIGN.md §8); the sharded
superstep delivers boundary messages at superstep ends, so it is held to
the documented bounds (DESIGN.md §9).  It also checks from per-device
memory that the carry was spread over all four devices.

The figures are a smoke check, not a benchmark.  The last line of stdout is
one JSON object naming the device, printed only when every check passed;
without a TPU the script exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(HERE, "src"), os.path.join(HERE, "tests")]

from engine_cases import Scenario, oracle, run_case  # noqa: E402
from repro.apps.graphcolor import GraphColorApp, GraphColorConfig  # noqa: E402
from repro.core.modes import AsyncMode  # noqa: E402
from repro.core.qos import aggregate_reports, qos_signature  # noqa: E402
from repro.kernels.duct_exchange import duct_commit, duct_window  # noqa: E402
from repro.runtime.config import RunConfig  # noqa: E402
from repro.runtime.engine import make_engine  # noqa: E402
from repro.runtime.experiments import use_compile_cache  # noqa: E402
from repro.runtime.simulator import SimConfig  # noqa: E402
from repro.runtime.topologies import make_topology  # noqa: E402

SEED = 0
#: torus side: 512 x 512 = 2^18 processes
SIDE = 512
#: superstep width of the fused and sharded superstep schedulers
W = 8
#: virtual seconds per engine run: a few 256-window chunks
DURATION = 0.002
#: DESIGN.md §9 bounds on a sharded W>1 superstep run against W=1
SUPERSTEP_UPDATES_RTOL = 0.01
SUPERSTEP_QOS_RTOL = 0.15


class Checks:
    """Prints every comparison and remembers the ones that failed."""

    def __init__(self):
        self.failed = []

    def __call__(self, name, ok, detail=""):
        print(f"check {name}: {'pass' if ok else 'FAIL'}"
              + (f" ({detail})" if detail else ""), flush=True)
        if not ok:
            self.failed.append(name)


def sim_config(duration=DURATION):
    return SimConfig(duration=duration, mode=AsyncMode.BEST_EFFORT,
                     snapshot_warmup=duration / 6,
                     snapshot_interval=duration / 12, seed=SEED)


def torus_app(n):
    return GraphColorApp(
        GraphColorConfig(n_processes=n, nodes_per_process=1, seed=SEED),
        topology=make_topology("torus", n))


def peak_bytes(dev):
    return dev.memory_stats()["peak_bytes_in_use"]


def check_conservation(check, label, eng, res):
    """Every attempted send was accepted or dropped, and every accepted
    one was delivered or is still in a ring."""
    c = eng._final_carry
    att, ok, drop = (int(np.sum(c[k])) for k in ("c_att", "c_ok", "c_drop"))
    msgs, inring = int(np.sum(c["c_msgs"])), int(np.sum(c["q_size"]))
    check(f"{label} conservation",
          att == ok + drop and ok == msgs + inring
          and (res.sent, res.dropped) == (att, drop),
          f"attempted={att} accepted={ok} dropped={drop} delivered={msgs} "
          f"in_ring={inring}")


def engine_phase(check, dev, app, scheduler, superstep_windows):
    """Build, compile, time and run one engine configuration."""
    label = f"engine[{scheduler}]"
    cfg = sim_config()
    t0 = time.perf_counter()
    eng = make_engine(RunConfig(engine="jax", scheduler=scheduler,
                                superstep_windows=superstep_windows),
                      app, cfg)
    carry = jax.tree.map(lambda x: x[None], eng._init_carry(SEED))
    jax.block_until_ready(carry)
    setup = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = eng._get_runner().lower(carry).compile()
    compile_s = time.perf_counter() - t0
    has_kernel = "tpu_custom_call" in compiled.as_text()
    print(f"{label}: setup_s={setup} compile_s={compile_s} "
          f"tpu_custom_call={has_kernel}", flush=True)
    check(f"{label} compiled chunk holds the Pallas kernels", has_kernel)

    # one chunk from the initial carry, timed to the device's last write
    t0 = time.perf_counter()
    carry = jax.block_until_ready(compiled(carry))
    dt = time.perf_counter() - t0
    updates = int(jnp.sum(carry["steps"]))
    windows = eng._windows_per_call
    del carry
    print(f"{label}: windows={windows} updates={updates} seconds={dt} "
          f"updates_per_s_on_{dev.platform}_{dev.device_kind.replace(' ', '_')}"
          f"={updates / dt}", flush=True)

    # the user's entry point, run to the horizon
    eng.debug_keep_carry = True
    t0 = time.perf_counter()
    res = eng.run_replicates([SEED])[0]
    run_s = time.perf_counter() - t0
    print(f"{label}: run_replicates_s={run_s} windows={int(eng._final_carry['k'][0])} "
          f"updates={sum(res.updates)} sent={res.sent} dropped={res.dropped} "
          f"peak_bytes_in_use={peak_bytes(dev)}",
          flush=True)
    check(f"{label} ran", sum(res.updates) > 0 and len(res.qos) > 0)
    check_conservation(check, label, eng, res)
    return res


def _bits(x):
    x = jnp.asarray(x)
    if jnp.issubdtype(x.dtype, jnp.floating):
        return jax.lax.bitcast_convert_type(x, jnp.int32)
    return x


def _same_bits(a, b):
    return all(bool(jnp.array_equal(_bits(x), _bits(y)))
               for x, y in zip(a, b))


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _window_state(key, n, d, C, L):
    """Random ring state in the engine's flat carry shapes: rings hold a
    FIFO prefix of messages, a staged push lands behind it, clocks fall
    across the availability stamps."""
    R = n * d
    k = jax.random.split(key, 11)
    head = jax.random.randint(k[0], (R,), 0, C)
    size = jax.random.randint(k[1], (R,), 0, C)
    live = (jnp.arange(C)[None, :] - head[:, None]) % C < size[:, None]
    qa = jnp.where(live, jax.random.uniform(k[2], (R, C), maxval=2.0),
                   jnp.inf)
    qt = jax.random.randint(k[3], (R, C), 0, 1 << 20)
    qp = jax.random.randint(k[4], (R, C, L), -1000, 1000)
    pacc = jax.random.bernoulli(k[5], 0.7, (R,))
    ppos = (head + size) % C
    pav = jax.random.uniform(k[6], (R,), maxval=2.0)
    ptch = jax.random.randint(k[7], (R,), 0, 1 << 20)
    ppay = jax.random.randint(k[8], (R, L), -1000, 1000)
    rnow = jax.random.uniform(k[9], (n,), maxval=2.0)
    ract = jax.random.bernoulli(k[10], 0.8, (n,))
    return (qa, qt, qp, head, size + pacc, ppos, pacc, pav, ptch, ppay,
            rnow, ract)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _commit_state(key, R, C, L, W):
    """Random base rings and a superstep pushbuf that fits behind them."""
    k = jax.random.split(key, 9)
    size0 = jax.random.randint(k[0], (R,), 0, C)
    cnt = jnp.minimum(jax.random.randint(k[1], (R,), 0, W + 1), C - size0)
    return (jax.random.uniform(k[2], (R, C), maxval=2.0),
            jax.random.randint(k[3], (R, C), 0, 1 << 20),
            jax.random.randint(k[4], (R, C, L), -1000, 1000),
            jax.random.randint(k[5], (R,), 0, C), size0, cnt,
            jax.random.uniform(k[6], (R, W), maxval=2.0),
            jax.random.randint(k[7], (R, W), 0, 1 << 20),
            jax.random.randint(k[8], (R, W, L), -1000, 1000))


def kernel_phase(check, n, d=4, C=64, L=1, max_pops=16):
    """``duct_window`` and ``duct_commit`` on one window of ring state at
    the engine's widths: Pallas kernel against jnp twin, bit for bit."""
    R = n * d
    key = jax.random.PRNGKey(SEED)
    state = _window_state(key, n, d, C, L)

    def window(use_pallas, qa, qt, qp, head, size, ppos, pacc, pav, ptch,
               ppay, rnow, ract):
        def slab(x, *tail):
            return x.reshape((n, d) + tail)

        w = duct_window(slab(qa, C), slab(qt, C), slab(qp, C, L),
                        slab(head), slab(size), slab(ppos), slab(pacc),
                        slab(pav), slab(ptch), slab(ppay, L), rnow, ract,
                        max_pops=max_pops, use_pallas=use_pallas)
        return tuple(x.reshape((R,) + x.shape[2:]) for x in w[:7]) + w[7:]

    kern = jax.jit(functools.partial(window, True))(*state)
    twin = jax.jit(functools.partial(window, False))(*state)
    drained = int(jnp.sum(twin[5]))
    check("kernels duct_window pallas == jnp twin (bitwise)",
          _same_bits(kern, twin),
          f"n={n} d={d} C={C} L={L}: {R} rings, {drained} messages drained, "
          f"{int(jnp.sum(state[6]))} pushes applied")
    check("kernels duct_window drained something", drained > 0)

    cstate = _commit_state(jax.random.fold_in(key, 1), R, C, L, W)
    kern = jax.jit(functools.partial(duct_commit, use_pallas=True))(*cstate)
    twin = jax.jit(functools.partial(duct_commit, use_pallas=False))(*cstate)
    check("kernels duct_commit pallas == jnp twin (bitwise)",
          _same_bits(kern, twin),
          f"{R} rings, W={W}: {int(jnp.sum(cstate[5]))} pushes committed")


def oracle_phase(check):
    """A dyadic scenario (every time constant a power of two) on the chip
    against the discrete-event simulator."""
    sc = Scenario("torus-best-effort", "torus")
    t0 = time.perf_counter()
    jx = run_case("jax", sc)
    ev = oracle(sc)
    print(f"oracle: scenario={sc.name} n={sc.n} updates={sum(jx.updates)} "
          f"seconds={time.perf_counter() - t0}", flush=True)
    check("oracle dyadic qos_signature == event simulator",
          qos_signature(jx) == qos_signature(ev))


def one_chip(check, dev):
    n = SIDE * SIDE
    t0 = time.perf_counter()
    app = torus_app(n)
    print(f"engine: torus {SIDE}x{SIDE} ({n} processes) app_setup_s="
          f"{time.perf_counter() - t0}", flush=True)
    res_w = engine_phase(check, dev, app, "window", 1)
    res_s = engine_phase(check, dev, app, "superstep", W)
    check(f"engine superstep W={W} qos_signature == window",
          qos_signature(res_s) == qos_signature(res_w))
    del res_w, res_s
    kernel_phase(check, n)
    oracle_phase(check)


def median_gaps(ra, rb):
    """Relative gaps of the median QoS metrics of two runs."""
    ma, mb = aggregate_reports(ra.qos), aggregate_reports(rb.qos)
    gaps = {}
    for metric, stats in ma.items():
        a, b = stats["median"], mb[metric]["median"]
        if (a is None) != (b is None):
            gaps[metric] = float("inf")
        elif a is not None:
            gaps[metric] = abs(b - a) / max(abs(a), 1e-9)
    return gaps


def four_chip(check, devices):
    n, C = SIDE * SIDE, 64
    cfg = sim_config()
    app = torus_app(n)
    print(f"four-chip: torus {SIDE}x{SIDE} ({n} processes) over 4 shards",
          flush=True)
    sharded = {}
    # sharded runs first: devices 1-3 then hold nothing but their shards
    for scheduler, w in (("window", 1), ("superstep", W)):
        label = f"sharded[{scheduler}]"
        eng = make_engine(RunConfig(engine="jax", shards=4,
                                    scheduler=scheduler,
                                    superstep_windows=w), app, cfg)
        eng.debug_keep_carry = True
        t0 = time.perf_counter()
        res = eng.run_replicates([SEED])[0]
        print(f"{label}: run_replicates_s={time.perf_counter() - t0} "
              f"updates={sum(res.updates)} sent={res.sent} "
              f"dropped={res.dropped}", flush=True)
        check_conservation(check, label, eng, res)
        sharded[scheduler] = res
    peaks = [peak_bytes(d) for d in devices[:4]]
    shard_rings = n * 4 * C * 12 // 4   # q_avail, q_touch, q_pay per shard
    print(f"four-chip: peak_bytes_in_use per device={peaks} "
          f"ring_bytes_per_shard={shard_rings}", flush=True)
    check("four-chip carry spread over all four devices",
          min(peaks[1:4]) >= shard_rings, f"peaks={peaks}")

    with jax.default_device(devices[0]):
        eng = make_engine(RunConfig(engine="jax"), app, cfg)
        t0 = time.perf_counter()
        ref = eng.run_replicates([SEED])[0]
    print(f"unsharded[window]: run_replicates_s={time.perf_counter() - t0} "
          f"updates={sum(ref.updates)} sent={ref.sent} "
          f"dropped={ref.dropped}", flush=True)
    win = sharded["window"]
    check("four-chip sharded window updates == unsharded",
          win.updates == ref.updates)
    check("four-chip sharded window qos_signature == unsharded",
          qos_signature(win) == qos_signature(ref))
    sup = sharded["superstep"]
    du = abs(sum(sup.updates) - sum(ref.updates)) / max(sum(ref.updates), 1)
    check(f"four-chip sharded superstep W={W} total updates within "
          f"{SUPERSTEP_UPDATES_RTOL} of unsharded", du < SUPERSTEP_UPDATES_RTOL,
          f"relative gap {du}")
    gaps = median_gaps(ref, sup)
    check(f"four-chip sharded superstep W={W} median QoS within "
          f"{SUPERSTEP_QOS_RTOL} of unsharded",
          max(gaps.values()) <= SUPERSTEP_QOS_RTOL, f"gaps={gaps}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the sharded engine on four chips and "
                         "its unsharded comparison")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        sys.exit("chip_smoke.py needs a TPU; JAX found "
                 f"{dev.platform} devices only")
    need = 4 if args.four_chip else 1
    if len(devices) < need:
        sys.exit(f"chip_smoke.py needs {need} TPU devices; JAX found "
                 f"{len(devices)}")

    use_compile_cache()
    check = Checks()
    t0 = time.perf_counter()
    if args.four_chip:
        four_chip(check, devices)
    else:
        one_chip(check, dev)
    print(f"total_s={time.perf_counter() - t0}", flush=True)
    if check.failed:
        sys.exit(f"chip_smoke.py: {len(check.failed)} check(s) failed: "
                 f"{check.failed}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
