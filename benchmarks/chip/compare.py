"""The comparison that decides ``correct``: the program's state and result
after the timed run against the plain reference's, after as many windows.

Every number compared is exact, so every limit is 0:

  procs_differ          processes whose clock, step count, done flag,
                        message counters, halo, snapshots, colouring
                        state or barrier state (waiting flag, releases
                        seen, last release time, saved costs; all zero
                        where the mode has no barrier) differ in any bit
                        from the reference's
  digest_fields_differ  fields of the assembled result's digest (updates
                        per process, sent, dropped, quality, every QoS
                        report) that differ from the reference's; a
                        report needs two snapshots inside the run, which
                        the snapshot rule of today's traffic never gives
  conservation_gap      |attempted - accepted - dropped| +
                        |accepted - delivered - still in a duct|, summed
                        over the program's whole population
  windows_gap           windows the program counted against the windows
                        the harness dispatched
"""
from __future__ import annotations

import numpy as np

LIMITS = {"procs_differ": 0, "digest_fields_differ": 0,
          "conservation_gap": 0, "windows_gap": 0}
PROCESS_FIELDS = ("t", "steps", "done", "c_att", "c_ok", "c_drop", "c_msgs",
                  "c_laden", "c_touch", "snap_idx", "snap", "halo",
                  "waiting", "barrier_seq", "last_release", "pending")
METRICS = ("simstep_period", "simstep_latency", "walltime_latency",
           "delivery_failure_rate", "delivery_clumpiness")


def _bits(x):
    x = np.asarray(x)
    if x.dtype == np.float32:
        return x.view(np.int32)
    return x


def _rows_differ(a, b):
    a, b = _bits(a), _bits(b)
    return (a != b).reshape(a.shape[0], -1).any(axis=1)


def program_view(carry):
    """The program's per-process fields of replicate 0 of a fetched carry."""
    out = {k: np.asarray(carry[k][0]) for k in PROCESS_FIELDS}
    out["app"] = {k: np.asarray(v[0]) for k, v in carry["app"].items()}
    out["in_duct"] = np.asarray(carry["q_size"][0])
    out["k"] = int(np.asarray(carry["k"][0]))
    return out


def reports(snap, snap_idx, deg, comm):
    """Per-process QoS reports between consecutive snapshots: the paper's
    five metrics plus the window's start and end clock."""
    snap = np.asarray(snap, np.float64)
    d = snap[:, 1:, :] - snap[:, :-1, :]
    dup, dtch, datt = d[..., 0], d[..., 1], d[..., 2]
    ddrop, dladen, dmsg, dwall = d[..., 4], d[..., 5], d[..., 6], d[..., 7]
    idle = dup <= 0
    period_f = dwall / np.maximum(dup, 1)
    lat = dup / np.maximum(dtch, 1)
    cols = {
        "simstep_period": np.where(idle, np.inf, period_f),
        "simstep_latency": lat,
        "walltime_latency": np.where(idle, np.inf, lat * period_f),
        "delivery_failure_rate": np.where(
            datt > 0, ddrop / np.maximum(datt, 1), 0.0),
        "t_start": snap[:, :-1, 7], "t_end": snap[:, 1:, 7],
    }
    opp = np.minimum(dmsg, dup * deg if comm else np.zeros_like(dup))
    cols["delivery_clumpiness"] = np.where(
        opp > 0, 1.0 - np.minimum(dladen / np.maximum(opp, 1), 1.0), 0.0)
    nwin = np.maximum(np.asarray(snap_idx) - 1, 0)
    return {f: {p: [float(x) for x in cols[f][p, :nwin[p]]]
                for p in range(snap.shape[0])}
            for f in METRICS + ("t_start", "t_end")}


def reference_digest(ref, quality, comm):
    return {
        "updates": [int(u) for u in ref["steps"]],
        "sent": int(np.sum(ref["c_att"], dtype=np.int64)),
        "dropped": int(np.sum(ref["c_drop"], dtype=np.int64)),
        "dropped_loss": 0, "dropped_dead": 0,
        "quality": float(quality),
        "qos": reports(ref["snap"], ref["snap_idx"], 4, comm),
    }


def result_digest(res):
    """The same digest of the program's assembled result."""
    return {
        "updates": [int(u) for u in res.updates],
        "sent": int(res.sent), "dropped": int(res.dropped),
        "dropped_loss": int(res.dropped_loss),
        "dropped_dead": int(res.dropped_dead),
        "quality": float(res.quality),
        "qos": {f: {int(p): [float(getattr(r, f)) for r in reps]
                    for p, reps in sorted(res.qos_by_process.items())}
                for f in METRICS + ("t_start", "t_end")},
    }


def _digest_fields_differ(a, b):
    bad = sum(a[k] != b[k] for k in a if k != "qos")
    for f, per in a["qos"].items():
        other = b["qos"].get(f, {})
        bad += sum(per[p] != other.get(p) for p in per)
    return int(bad)


def compare(prog, ref, res_digest, ref_digest, windows):
    """The numbers compared, each with its limit, and the count of updates
    or messages the run cannot account for."""
    differ = np.zeros(prog["t"].shape[0], bool)
    for k in PROCESS_FIELDS:
        differ |= _rows_differ(prog[k], ref[k])
    for k, v in ref["app"].items():
        differ |= _rows_differ(prog["app"][k], v)
    att, ok, drop, msgs = (int(np.sum(prog[k], dtype=np.int64))
                           for k in ("c_att", "c_ok", "c_drop", "c_msgs"))
    in_duct = int(np.sum(prog["in_duct"], dtype=np.int64))
    gap = abs(att - ok - drop) + abs(ok - msgs - in_duct)
    values = {
        "procs_differ": int(differ.sum()),
        "digest_fields_differ": _digest_fields_differ(res_digest, ref_digest),
        "conservation_gap": gap,
        "windows_gap": abs(prog["k"] - windows),
    }
    failed = gap + int((prog["steps"] != ref["steps"]).sum())
    return {k: {"value": v, "limit": LIMITS[k]} for k, v in values.items()}, failed


def is_correct(compared) -> bool:
    return all(c["value"] <= c["limit"] for c in compared.values())
