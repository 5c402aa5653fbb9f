"""Reduce a profiler trace of the window to the numbers the per-layer
metrics read.

The trace is JAX's ``.xplane.pb``, read with ``jax.profiler.ProfileData``.
Device planes (``/device:TPU:<i>``) carry one event per executed XLA
operation on their ``XLA Ops`` line and one per program run on their
``XLA Modules`` line; the host plane carries the harness's annotations
(``bench.dispatch``, ``bench.probe``, ``bench.fetch``, ``bench.assemble``).
Both are on one clock.

  window   from the start of the first annotation to the end of the last
  busy     the union of the device's operation intervals inside it
  gaps     the window less busy, split among the host annotations over
           it (``host.other`` where none is)
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

from harness import ASSEMBLE, DISPATCH, FETCH, PROBE

ANNOTATIONS = (DISPATCH, PROBE, FETCH, ASSEMBLE)
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
OTHER = "host.other"

Interval = Tuple[float, float]


@dataclasses.dataclass
class Summary:
    window_s: float                    # traced window, host clock
    busy_s: float                      # device busy, mean over chips
    ops: Dict[str, float]              # operation -> device self seconds
    modules: Dict[str, Tuple[int, float]]   # program -> (runs, seconds)
    gaps: Dict[str, float]             # host activity -> idle seconds

    def time_of(self, marker: str) -> float:
        """Device seconds of every operation whose name holds ``marker``."""
        return sum(s for name, s in self.ops.items() if marker in name)

    def module(self, marker: str) -> Tuple[int, float]:
        runs = secs = 0
        for name, (r, s) in self.modules.items():
            if marker in name:
                runs, secs = runs + r, secs + s
        return runs, secs

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def complement(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    gaps, at = [], lo
    for a, b in busy:
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def _overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def attribute(gaps: List[Interval], spans: List[Tuple[str, Interval]]):
    """Seconds of idle time per host activity: the part of each gap under
    an annotation goes to it, the rest to ``host.other``."""
    out: Dict[str, float] = {}
    for g in gaps:
        rest = g[1] - g[0]
        for n, s in spans:
            o = _overlap(g, s)
            if o > 0:
                out[n] = out.get(n, 0.0) + o * 1e-9
                rest -= o
        if rest > 0:
            out[OTHER] = out.get(OTHER, 0.0) + rest * 1e-9
    return out


def short_name(name: str) -> str:
    """``%fusion.51 = f32[3407872,8]{0,1:T(8,128)} fusion(...)`` ->
    ``fusion.51: fusion f32[3407872,8]``: the trace names an operation by
    its whole HLO text."""
    head, _, rest = name.partition(" = ")
    head = head.lstrip("%")
    kind = re.search(r"(?:^|[\s)}])([a-z][\w-]*)\(", rest)
    if not kind:
        return head
    shape = "" if rest.startswith("(") else " " + rest.split("{", 1)[0].split(" ", 1)[0]
    return f"{head}: {kind.group(1)}{shape}"


def self_times(events) -> Dict[str, float]:
    """Device seconds per operation, less the operations nested in it (a
    ``while`` holds the scan's whole body)."""
    out: Dict[str, float] = {}
    stack: List[list] = []          # [end, name, seconds covered]

    def close(item):
        end, name, covered, dur = item
        out[name] = out.get(name, 0.0) + (dur - covered) * 1e-9
        if stack:
            stack[-1][2] += dur

    for start, end, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= start:
            close(stack.pop())
        stack.append([end, name, 0.0, end - start])
    while stack:
        close(stack.pop())
    return out


def reduce(path: str) -> Optional[Summary]:
    """The summary of the ``.xplane.pb`` at ``path``."""
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path))


def reduce_profile(pd, annotations=ANNOTATIONS) -> Optional[Summary]:
    """The window's summary, or None where the trace holds no annotated
    window or no device operation in it."""
    spans: List[Tuple[str, Interval]] = []
    devices = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in annotations:
                    spans.append((ev.name, (ev.start_ns, ev.end_ns)))
    if not spans or not devices:
        return None
    lo = min(s[0] for _, s in spans)
    hi = max(s[1] for _, s in spans)
    ops: Dict[str, float] = {}
    modules: Dict[str, Tuple[int, float]] = {}
    gaps: Dict[str, float] = {}
    busy_total = 0.0
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        events = [(ev.start_ns, ev.end_ns, short_name(ev.name))
                  for ev in (lines[OPS_LINE].events
                             if OPS_LINE in lines else ())
                  if lo < ev.end_ns and ev.start_ns < hi]
        intervals = [(a, b) for a, b, _ in events]
        for k, v in self_times(events).items():
            ops[k] = ops.get(k, 0.0) + v
        for ev in (lines[MODULES_LINE].events
                   if MODULES_LINE in lines else ()):
            if ev.end_ns <= lo or ev.start_ns >= hi:
                continue
            r, s = modules.get(ev.name, (0, 0.0))
            modules[ev.name] = (r + 1, s + ev.duration_ns * 1e-9)
        busy = union(clip(intervals, lo, hi))
        busy_total += sum(b - a for a, b in busy) * 1e-9
        for k, v in attribute(complement(busy, lo, hi), spans).items():
            gaps[k] = gaps.get(k, 0.0) + v / len(devices)
    if busy_total <= 0:
        return None
    n = len(devices)
    return Summary(window_s=(hi - lo) * 1e-9, busy_s=busy_total / n,
                   ops={k: v / n for k, v in ops.items()},
                   modules={k: (r // n, s / n)
                            for k, (r, s) in modules.items()},
                   gaps=gaps)
