"""Plain reference of the windowed best-effort swarm, one lockstep window at
a time.

It is written from the semantics alone and imports nothing of the
program. One window, for every process at once:

  drain    each in-duct of an active receiver pops its FIFO prefix of
           messages whose availability time has passed on the receiver's
           clock, at most ``max_pops`` of them, stopping at the first one
           not yet available; the freshest popped message refreshes the
           halo slot the duct feeds
  compute  the app's step of every active process against its halos
  send     each active process pushes one message into each out-duct; a
           send that a lossy or flapping link kills is attempted and
           dropped, and counted lost; a full duct drops it; an accepted
           one becomes available after its duct's latency, times a factor
           drawn for (edge, sender step), on the sender's clock
  close    snapshot, horizon, and the clock advance: the compute time,
           drawn for (process, step), times ``stall_factor`` on a hashed
           1% of steps and times the process's compute factor, plus
           per-message and per-pull costs
  release  under barrier-every-update release only: every process that
           stepped and is not done waits, keeping its per-message and
           pull costs; once every process waits or is done, all waiting
           processes are released together at the latest waiting clock
           plus the barrier's cost, and each takes its next step from there

A process is active in a window when it is neither done nor waiting.
``MODES`` and ``FAULTS`` declare what this reference implements; a cell
whose traffic asks for anything else has no reference and is refused.

Faults strike one host, the processes ``p // procs_per_node == fault_host``,
and its clique, every duct with either end on it (the paper's apparently
faulty node and its degraded links):

  faulty_host    the host's compute times ``compute_factor``, its clique's
                 latency base ``base_latency * link_factor``, taken in
                 float64 and rounded once to float32
  lossy_host     a clique send is killed where the draw for (edge, sender
                 step) falls below ``loss_prob``
  flapping_host  a clique send is killed where the draw for (edge,
                 ``floor(t_sender / flap_period)``) falls below
                 ``down_frac``: the link is down for that time bucket

The topology and the app are modules of their own, found by the names in
the configuration: ``reference_topology/<name>.py`` gives each duct's
canonical edge id, its sender's value of any per-process array
(``from_sender``) and its reverse duct's value of any per-duct array
(``of_reverse``); ``reference_app/<name>.py`` the app's state, step,
outgoing rows and quality. A new topology or app is a new file.

Ducts are kept by receiver: duct ``(d, j)`` carries messages from the
``j``-th smallest neighbour of ``d`` into halo slot ``j``. Every draw is
a counter-based hash of integer keys followed by the same float32
arithmetic, in the same order, as the semantics state; the program's
trajectory must equal this one bit for bit.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import math
from functools import lru_cache, partial
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

HERE = Path(__file__).resolve().parent
#: the release modes this reference implements, each with the traffic keys
#: it takes: the free-running modes take none, barrier-every-update release
#: takes the barrier's cost, ``barrier_base + barrier_per_log2 * log2 n``
MODES = {"BEST_EFFORT": (), "NO_COMM": (),
         "BARRIER_EVERY_STEP": ("barrier_base", "barrier_per_log2")}
#: the faults it draws, each with the traffic keys it takes; a kind is
#: named after the program's constructor of its fault model
FAULTS = {"none": (),
          "faulty_host": ("fault_host", "compute_factor", "link_factor"),
          "lossy_host": ("fault_host", "loss_prob"),
          "flapping_host": ("fault_host", "down_frac", "flap_period")}
#: outgoing row a sender offers to a receiver's halo slot j (n<->s, w<->e)
OPPOSITE = (1, 0, 3, 2)
#: hash stream tags: step time, stall, latency, the app's, loss and flap
STREAM_STEP, STREAM_STALL, STREAM_LAT, STREAM_APP = 1, 2, 3, 4
STREAM_LOSS, STREAM_FLAP = 6, 7
_GOLDEN = np.uint32(0x9E3779B9)


# ---------------------------------------------------------------------------
# counter-based hash: a pure function of its integer keys
# ---------------------------------------------------------------------------
def _mix32(x):
    x = (x ^ (x >> np.uint32(16))) * np.uint32(0x7FEB352D)
    x = (x ^ (x >> np.uint32(15))) * np.uint32(0x846CA68B)
    return x ^ (x >> np.uint32(16))


def hash_uniform(*keys):
    """Uniform in (0, 1) from integer keys (broadcast), float32."""
    h = _GOLDEN
    for k in keys:
        k = jnp.asarray(k).astype(jnp.uint32)
        h = _mix32(h ^ (k + _GOLDEN + (h << np.uint32(6)) + (h >> np.uint32(2))))
    return ((h >> np.uint32(8)).astype(jnp.float32) + 0.5) * np.float32(1.0 / (1 << 24))


def hash_normal(*keys):
    """Standard normal by Box-Muller from two uniforms of the same keys."""
    u1 = hash_uniform(*keys, 101)
    u2 = hash_uniform(*keys, 202)
    return jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos(2.0 * np.pi * u2)


def lognormal(sigma: float, *keys):
    """Mean-one lognormal factor ``exp(sigma z - sigma^2 / 2)``; exactly 1
    where ``sigma`` is 0."""
    if sigma <= 0:
        return jnp.ones(jnp.broadcast_shapes(*(jnp.shape(k) for k in keys)),
                        jnp.float32)
    z = hash_normal(*keys)
    return jnp.exp(np.float32(-0.5 * sigma * sigma) + np.float32(sigma) * z)


# ---------------------------------------------------------------------------
# the deployment
# ---------------------------------------------------------------------------
@lru_cache(maxsize=None)
def module(kind: str, name: str):
    """``reference_<kind>/<name>.py``: a topology or an app."""
    path = HERE / f"reference_{kind}" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reference {kind} {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"ref_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass(frozen=True)
class Swarm:
    """Everything the reference needs, as plain numbers; ``app`` is the
    app module's frozen parameters."""
    n: int
    topology: str
    app: object
    capacity: int
    max_pops: int
    mode: str
    base_compute: float
    per_message_cost: float
    per_pull_cost: float
    base_latency: float
    jitter_sigma: float
    latency_sigma: float
    stall_prob: float
    stall_factor: float
    duration: float
    snapshot_warmup: float
    snapshot_interval: float
    barrier_base: float = 0.0
    barrier_per_log2: float = 0.0
    #: the fault and its settings; the neutral values leave every process
    #: and duct as the timing states it
    faults: str = "none"
    procs_per_node: Optional[int] = None
    fault_host: int = 0
    compute_factor: float = 1.0
    link_factor: float = 1.0
    loss_prob: float = 0.0
    down_frac: float = 0.0
    flap_period: float = 1.0

    @property
    def comm(self):
        return self.mode != "NO_COMM"

    @property
    def barriered(self):
        return self.mode == "BARRIER_EVERY_STEP"

    @property
    def barrier_cost(self):
        """The release's cost in float64, rounded once to float32; nothing
        to wait for where there is one process."""
        if self.n <= 1:
            return np.float32(0.0)
        return np.float32(self.barrier_base
                          + self.barrier_per_log2 * math.log2(self.n))

    @property
    def L(self):
        return self.app.L

    @property
    def slots(self):
        """Snapshot slots per process."""
        return max(1, int((self.duration - self.snapshot_warmup)
                          / self.snapshot_interval) + 3)


def step_factor(sw: Swarm, seed, pids, steps, cf):
    """Compute-time factor of each process's step ``steps``: the lognormal
    jitter, times ``stall_factor`` where the stall draw hits, times the
    process's compute factor ``cf``."""
    f = lognormal(sw.jitter_sigma, seed, STREAM_STEP, pids, steps)
    if sw.stall_prob > 0:
        u = hash_uniform(seed, STREAM_STALL, pids, steps)
        f = jnp.where(u < np.float32(sw.stall_prob),
                      f * np.float32(sw.stall_factor), f)
    return f * cf


def init_state(sw: Swarm, tabs, seed: int):
    """The state before the first window, on the default device."""
    n, C, L, D = sw.n, sw.capacity, sw.L, tabs["eid"].shape[1]
    app, halo = sw.app.init(seed, n)
    pids = jnp.arange(n, dtype=jnp.int32)
    t0 = np.float32(sw.base_compute) * step_factor(
        sw, seed, pids, jnp.zeros(n, jnp.int32), tabs["cf"])
    zi = lambda: jnp.zeros(n, jnp.int32)
    zf = lambda: jnp.zeros(n, jnp.float32)
    return dict(
        k=jnp.zeros((), jnp.int32), t=t0, steps=zi(), done=jnp.zeros(n, bool),
        waiting=jnp.zeros(n, bool), barrier_seq=zi(), last_release=zf(),
        pending=zf(),
        c_att=zi(), c_ok=zi(), c_drop=zi(), c_loss=zi(), c_msgs=zi(),
        c_laden=zi(), c_touch=zi(), app=app, halo=halo,
        snap=jnp.zeros((n, sw.slots, 8), jnp.float32), snap_idx=zi(),
        q_avail=jnp.full((n, D, C), jnp.inf, jnp.float32),
        q_touch=jnp.zeros((n, D, C), jnp.int32),
        q_pay=jnp.zeros((n, D, C, L), jnp.int32),
        head=jnp.zeros((n, D), jnp.int32), size=jnp.zeros((n, D), jnp.int32),
        ptouch=jnp.zeros((n, D), jnp.int32))


def window(sw: Swarm, tabs, seed, s, fault=None):
    """One lockstep window. ``fault`` names a broken guarantee for the
    control run: ``"no_latency"`` (messages are available when sent),
    ``"no_stall"`` (no step ever stalls) or ``"no_link_fault"`` (the
    clique's links keep the timing's latency and lose nothing).

    ``tabs`` are the tables of ``tables``; ``tabs["eid"][d, j]`` is the
    canonical edge id of duct ``(d, j)``."""
    topo = module("topology", sw.topology)
    from_sender = partial(topo.from_sender, tabs)
    of_reverse = partial(topo.of_reverse, tabs)
    n, C, L = sw.n, sw.capacity, sw.L
    D = tabs["eid"].shape[1]
    slots = jnp.arange(C, dtype=jnp.int32)
    pids = jnp.arange(n, dtype=jnp.int32)
    t, active = s["t"], ~s["done"] & ~s["waiting"]
    s = dict(s)
    drained_r = jnp.zeros(n, jnp.int32)

    if sw.comm:
        # FIFO position of every slot from the head: pop the available
        # prefix, stopping at the first live message not yet available
        off = (slots - s["head"][..., None]) % C
        live = off < s["size"][..., None]
        later = live & ~(s["q_avail"] <= t[:, None, None])
        first_later = jnp.min(jnp.where(later, off, C), axis=2)
        drained = jnp.minimum(jnp.minimum(first_later, s["size"]),
                              sw.max_pops)
        drained = jnp.where(active[:, None], drained, 0)
        got = drained > 0
        freshest = off == (drained - 1)[..., None]
        new_touch = jnp.sum(jnp.where(freshest, s["q_touch"], 0), axis=2) + 1
        payload = jnp.sum(jnp.where(freshest[..., None], s["q_pay"], 0),
                          axis=2)
        dtouch = jnp.where(got, new_touch - s["ptouch"], 0)
        s.update(
            halo=jnp.where(got[..., None], payload, s["halo"]),
            ptouch=jnp.where(got, new_touch, s["ptouch"]),
            head=(s["head"] + drained) % C, size=s["size"] - drained,
            c_msgs=s["c_msgs"] + drained.sum(1),
            c_laden=s["c_laden"] + got.sum(1),
            c_touch=s["c_touch"] + dtouch.sum(1))
        drained_r = drained.sum(1)

    app = sw.app.step(s["app"], s["halo"], s["steps"], seed, pids)
    s["app"] = jax.tree.map(
        lambda new, old: jnp.where(
            active.reshape((n,) + (1,) * (new.ndim - 1)), new, old),
        app, s["app"])
    steps = s["steps"] + active
    s["steps"] = steps

    if sw.comm:
        rows = sw.app.rows(s["app"])
        # duct (d, j) carries its sender's outgoing row opposite to slot j
        payload = jnp.stack([from_sender(rows[:, OPPOSITE[j]])[:, j]
                             for j in range(D)], axis=1)
        sending = from_sender(active)
        killed = jnp.zeros((n, D), bool)
        if fault != "no_link_fault":
            if sw.loss_prob > 0:
                killed |= hash_uniform(seed, STREAM_LOSS, tabs["eid"],
                                       from_sender(steps)) < tabs["loss"]
            if sw.down_frac > 0:
                bucket = jnp.floor(from_sender(t) / np.float32(
                    sw.flap_period)).astype(jnp.int32)
                killed |= hash_uniform(seed, STREAM_FLAP, tabs["eid"],
                                       bucket) < tabs["flap"]
        killed &= sending
        accept = sending & ~killed & (s["size"] < C)
        tail = (slots == ((s["head"] + s["size"]) % C)[..., None]) \
            & accept[..., None]
        if fault == "no_latency":
            lat = jnp.zeros((n, D), jnp.float32)
        else:
            base = (np.float32(sw.base_latency) if fault == "no_link_fault"
                    else tabs["lat"])
            lat = base * lognormal(sw.latency_sigma, seed, STREAM_LAT,
                                   tabs["eid"], from_sender(steps))
        s.update(
            q_avail=jnp.where(tail, (from_sender(t) + lat)[..., None],
                              s["q_avail"]),
            q_touch=jnp.where(tail, of_reverse(s["ptouch"])[..., None],
                              s["q_touch"]),
            q_pay=jnp.where(tail[..., None], payload[:, :, None, :],
                            s["q_pay"]),
            size=s["size"] + accept)
        # a sender's accepted pushes are the accept bits of its out-ducts,
        # which are the reverse ducts of its in-ducts
        ok = of_reverse(accept.astype(jnp.int32)).sum(1)
        att = jnp.where(active, D, 0)
        s.update(c_att=s["c_att"] + att, c_ok=s["c_ok"] + ok,
                 c_drop=s["c_drop"] + att - ok,
                 c_loss=s["c_loss"] + of_reverse(
                     killed.astype(jnp.int32)).sum(1))

    pending = (drained_r.astype(jnp.float32) * np.float32(sw.per_message_cost)
               + np.float32(D) * np.float32(sw.per_pull_cost))
    idx = s["snap_idx"]
    due = (active & (t >= np.float32(sw.snapshot_warmup)
                     + idx.astype(jnp.float32)
                     * np.float32(sw.snapshot_interval))
           & (idx < sw.slots))
    row = jnp.stack([steps, s["c_touch"], s["c_att"], s["c_ok"],
                     s["c_drop"], s["c_laden"], s["c_msgs"]],
                    axis=1).astype(jnp.float32)
    row = jnp.concatenate([row, t[:, None]], axis=1)
    into = (jnp.arange(sw.slots) == idx[:, None]) & due[:, None]
    s["snap"] = jnp.where(into[..., None], row[:, None, :], s["snap"])
    s["snap_idx"] = idx + due
    ends = active & (t >= np.float32(sw.duration))
    done = s["done"] | ends
    if fault == "no_stall":
        f = lognormal(sw.jitter_sigma, seed, STREAM_STEP, pids,
                      steps) * tabs["cf"]
    else:
        f = step_factor(sw, seed, pids, steps, tabs["cf"])
    d_next = np.float32(sw.base_compute) * f
    if sw.barriered:
        s.update(release(sw, s, t, done, active & ~ends, pending, d_next))
    else:
        s.update(done=done, t=jnp.where(active & ~ends, t + d_next + pending,
                                        t))
    s["k"] = s["k"] + 1
    return s


def release(sw: Swarm, s, t, done, stepped, pending, d_next):
    """Barrier-every-update release after a window's step: the waiting,
    clock, done and barrier fields of the state."""
    waiting = s["waiting"] | stepped
    saved = jnp.where(stepped, pending, s["pending"])
    ready = jnp.all(waiting | done) & jnp.any(waiting)
    at = jnp.max(jnp.where(waiting, t, -jnp.inf)) + sw.barrier_cost
    out = ready & waiting
    horizon = np.float32(sw.duration)
    past = at >= horizon
    return dict(
        waiting=waiting & ~ready, pending=saved,
        t=jnp.where(out, jnp.where(past, horizon, at + d_next + saved), t),
        done=done | (out & past),
        last_release=jnp.where(out, at, s["last_release"]),
        barrier_seq=s["barrier_seq"] + out)


def tables(sw: Swarm):
    """The topology's duct tables and the fault's, on the default device:
    ``cf`` each process's compute factor, ``lat`` each duct's latency
    base, ``loss`` and ``flap`` its loss and down fractions."""
    topo = module("topology", sw.topology)
    tabs = topo.tables(sw.n)
    on = np.zeros(sw.n, bool)
    if sw.faults != "none":
        on = topo.hosts(sw.n, sw.procs_per_node) == sw.fault_host
    hit = topo.clique(tabs, on)
    faulted = lambda x, normal: np.where(hit, x, normal).astype(np.float32)
    out = {k: jnp.asarray(v, jnp.int32) for k, v in tabs.items()}
    out.update(
        cf=jnp.asarray(np.where(on, sw.compute_factor, 1.0)
                       .astype(np.float32)),
        lat=jnp.asarray(faulted(sw.base_latency * sw.link_factor,
                                sw.base_latency)),
        loss=jnp.asarray(faulted(sw.loss_prob, 0.0)),
        flap=jnp.asarray(faulted(sw.down_frac, 0.0)))
    return out


@partial(jax.jit, static_argnums=(0, 3, 4), donate_argnums=(2,))
def _chunk(sw: Swarm, tabs, s, windows: int, fault):
    seed = s["seed"]
    body = lambda c, _: (window(sw, tabs, seed, c, fault), None)
    s, _ = jax.lax.scan(body, s, None, length=windows)
    return s


def run(sw: Swarm, seed: int, windows: int, *, chunk: int = 16, fault=None):
    """The state after ``windows`` windows from ``seed``, on the host."""
    tabs = tables(sw)
    s = init_state(sw, tabs, seed)
    s["seed"] = jnp.asarray(seed, jnp.int32)
    done = 0
    while done < windows:
        step = min(chunk, windows - done)
        s = _chunk(sw, tabs, s, step, fault)
        done += step
    return jax.device_get(s)


def quality(sw: Swarm, state) -> float:
    """The app's solution quality of a host state."""
    return sw.app.quality(state["app"])
