"""What the program's own names say about a run: the chunk program's
device time by window phase, the device's idle time by the innermost host
span over it, and the host-clock seconds of the program's spans.

The window core puts each phase's ops under a ``jax.named_scope``
(``window.drain``, ``.compute``, ``.send``, ``.close``, ``.snapshot``
inside ``.close``, ``.commit``); the trace keeps an op's scope path as the
``tf_op`` stat of its event metadata (read with the schema in
``xplane.py``). ``repro.runtime.spans`` writes the program's host spans
(``setup.*``, ``assemble.*``, ``loop.*``, named by their path) beside the
harness's ``bench.*`` annotations, on the device's clock, and keeps their
host seconds in a table of the process.

  phases        device self seconds of the chunk program's ops in the
                window, by the innermost ``window.*`` component of the
                op's ``tf_op`` path
  unscoped      chunk ops with no ``tf_op`` stat (compiler copies, the
                scan's ``while``)
  outside       chunk ops whose ``tf_op`` path is under no phase
  program_gaps  idle device seconds in the window by the innermost span
                over them: a program span, else a harness annotation,
                else ``host.other``

Window, ops, busy time, gaps and self time follow ``tracing.py``, so
``phases`` + ``unscoped`` + ``outside`` is the chunk program's op self
time and ``program_gaps`` sums to ``tracing``'s ``gaps``.

    python3 benchmarks/chip/program_spans.py <file.xplane.pb[.gz]>

prints the split of a trace's window: the ``bench.*`` annotations', or
where there are none (a trace of the experiments CLI's ``--trace-dir``),
the chunk loop's spans'.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import json
import os
import re
import sys
import tempfile
from typing import Dict, List, Optional, Tuple

from google.protobuf.message import DecodeError

import tracing
import xplane

PHASE = re.compile(r"(?:^|[/;(])(window\.[a-z]+)(?=$|[/;):])")
PROGRAM_SPAN = re.compile(r"^(?:setup|assemble|loop)\.")
UNSCOPED, OUTSIDE, OTHER_PROGRAMS = "unscoped", "outside", "other programs"
#: the chunk loop's spans in ``run_replicates``: the window of a trace
#: the experiments CLI recorded (``--trace-dir``), which has no ``bench.*``
LOOP_SPANS = ("loop.dispatch", "loop.probe", "loop.fetch", "loop.assemble")
#: where ``run.py`` has the profiler write a traced run
TRACE_GLOB = os.path.join("bench_trace_*", "**", "*.xplane.pb")

Interval = Tuple[float, float]


@dataclasses.dataclass
class ProgramTrace:
    window_s: float
    busy_s: float
    phases: Dict[str, float]         # window.* scope -> device self s
    unscoped: float
    outside: float
    other_s: float                   # ops of other programs, self s
    program_gaps: Dict[str, float]   # innermost span -> idle device s

    @property
    def chunk_op_s(self) -> float:
        return sum(self.phases.values()) + self.unscoped + self.outside

    def breakdown(self) -> dict:
        def ranked(d):
            return [[k, v] for k, v in sorted(d.items(),
                                              key=lambda kv: -kv[1])]
        return {"device_phases": ranked(self.phases),
                "unscoped": self.unscoped, "outside": self.outside,
                "idle_gaps_by_span": ranked(self.program_gaps)}


def phase_of(path: str) -> Optional[str]:
    """The innermost ``window.*`` component of an ``op_name`` path."""
    found = PHASE.findall(path)
    return found[-1] if found else None


def _segments(spans: List[Tuple[str, Interval]]):
    """Disjoint, sorted ``(start, end, innermost span)`` pieces of the
    time the spans cover; of nested spans the later-starting one, of two
    that start together the shorter, is the inner."""
    points = sorted({x for _, s in spans for x in s})
    out = []
    for p, q in zip(points, points[1:]):
        cover = [(s[0], -s[1], n) for n, s in spans if s[0] <= p and s[1] >= q]
        if cover:
            out.append((p, q, max(cover)[2]))
    return out


def innermost(gaps: List[Interval], spans: List[Tuple[str, Interval]]):
    """Seconds of each gap per innermost span over it (``host.other``
    where none is)."""
    segs = _segments(spans)
    starts = [s[0] for s in segs]
    out: Dict[str, float] = {}
    for a, b in gaps:
        rest = b - a
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(segs) and segs[i][0] < b:
            o = min(b, segs[i][1]) - max(a, segs[i][0])
            if o > 0:
                out[segs[i][2]] = out.get(segs[i][2], 0.0) + o * 1e-9
                rest -= o
            i += 1
        if rest > 0:
            out[tracing.OTHER] = out.get(tracing.OTHER, 0.0) + rest * 1e-9
    return out


def _events(line):
    """``(start ns, end ns, metadata id)`` of a line's events, in whole
    nanoseconds as ``jax.profiler.ProfileData`` gives them to
    ``tracing.py``."""
    t0 = line.timestamp_ns
    for ev in line.events:
        start = t0 + ev.offset_ps // 1000
        yield start, start + ev.duration_ps // 1000, ev.metadata_id


def reduce_space(space, marker: str = "chunk",
                 annotations=tracing.ANNOTATIONS) -> Optional[ProgramTrace]:
    """The reduction of an ``XSpace``; None where it holds no annotated
    window or no device operation in it."""
    host: List[Tuple[str, Interval]] = []
    devices = []
    for plane in space.planes:
        if tracing.DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        names = {e.key: e.value.name for e in plane.event_metadata}
        for line in plane.lines:
            for a, b, mid in _events(line):
                name = names.get(mid, "")
                if name in annotations or PROGRAM_SPAN.match(name):
                    host.append((name, (a, b)))
    window = [s for n, s in host if n in annotations]
    if not window or not devices:
        return None
    lo, hi = min(s[0] for s in window), max(s[1] for s in window)
    spans = [(n, s) for n, s in host if s[1] > lo and s[0] < hi]
    phases: Dict[str, float] = {}
    gaps: Dict[str, float] = {}
    busy_total = 0.0
    for plane in devices:
        stat_ids = {e.value.name: e.key for e in plane.stat_metadata}
        tf_op = stat_ids.get("tf_op")
        meta = {}
        for e in plane.event_metadata:
            path = next((s.str_value for s in e.value.stats
                         if s.metadata_id == tf_op), None)
            meta[e.key] = (e.value.name, path)
        lines = {ln.name: ln for ln in plane.lines}
        runs = sorted((a, b) for a, b, mid in (
            _events(lines[tracing.MODULES_LINE])
            if tracing.MODULES_LINE in lines else ())
            if marker in meta.get(mid, ("", None))[0])
        run_starts = [a for a, _ in runs]
        events = []
        for a, b, mid in (_events(lines[tracing.OPS_LINE])
                          if tracing.OPS_LINE in lines else ()):
            if not (lo < b and a < hi):
                continue
            i = bisect.bisect_right(run_starts, a) - 1
            in_chunk = i >= 0 and a < runs[i][1]
            path = meta.get(mid, ("", None))[1]
            if not in_chunk:
                key = OTHER_PROGRAMS
            elif path is None:
                key = UNSCOPED
            else:
                key = phase_of(path) or OUTSIDE
            events.append((a, b, key))
        for k, v in tracing.self_times(events).items():
            phases[k] = phases.get(k, 0.0) + v
        busy = tracing.union(tracing.clip([(a, b) for a, b, _ in events],
                                          lo, hi))
        busy_total += sum(b - a for a, b in busy) * 1e-9
        for k, v in innermost(tracing.complement(busy, lo, hi),
                              spans).items():
            gaps[k] = gaps.get(k, 0.0) + v
    if busy_total <= 0:
        return None
    n = len(devices)
    phases = {k: v / n for k, v in phases.items()}
    return ProgramTrace(
        window_s=(hi - lo) * 1e-9, busy_s=busy_total / n,
        phases={k: v for k, v in phases.items() if k.startswith("window.")},
        unscoped=phases.get(UNSCOPED, 0.0), outside=phases.get(OUTSIDE, 0.0),
        other_s=phases.get(OTHER_PROGRAMS, 0.0),
        program_gaps={k: v / n for k, v in gaps.items()})


def reduce_file(path: str, annotations=tracing.ANNOTATIONS
                ) -> Optional[ProgramTrace]:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        return reduce_space(xplane.parse(f.read()), annotations=annotations)


#: the last reduction, [summary, its ProgramTrace]: the phase readers of one
#: run share it and parse the run's trace once
_last: list = [None, None]


def for_reading(r) -> Optional[ProgramTrace]:
    """The reduction of the trace that ``r.trace`` (``tracing.Summary``)
    was read from: the newest trace ``run.py`` left in this process's
    temporary directory whose window and busy time are the summary's.
    None where there is none."""
    s = r.trace
    if s is None:
        return None
    if _last[0] is s:
        return _last[1]
    found = None
    paths = glob.glob(os.path.join(tempfile.gettempdir(), TRACE_GLOB),
                      recursive=True)
    for path in sorted(paths, key=os.path.getmtime, reverse=True):
        try:
            p = reduce_file(path)
        except (OSError, DecodeError):     # a trace cut short, or gone
            continue
        if p is not None and _same(p.window_s, s.window_s) and _same(
                p.busy_s, s.busy_s):
            found = p
            break
    _last[:] = [s, found]
    return found


def _same(a: float, b: float) -> bool:
    return abs(a - b) <= 1e-9 * max(abs(a), abs(b), 1e-12)


def _ms_per_window(r, seconds) -> Optional[float]:
    """``seconds(ProgramTrace)`` in milliseconds per window: over the chunk
    runs times the windows a run holds; None where it gives None."""
    p = for_reading(r)
    runs, _ = r.trace.module("chunk") if r.trace is not None else (0, 0.0)
    secs = seconds(p) if p is not None and runs else None
    if secs is None:
        return None
    return 1e3 * secs / (runs * r.counts["windows_per_call"])


def phase_ms_per_window(r, scope: str) -> Optional[float]:
    """Device self milliseconds of phase ``scope`` per window."""
    return _ms_per_window(r, lambda p: p.phases.get(scope))


def outside_ms_per_window(r) -> Optional[float]:
    """Device self milliseconds per window of the chunk ops whose scope
    path holds no phase (``outside``); None on a trace of a program that
    names no phase."""
    return _ms_per_window(r, lambda p: p.outside if p.phases else None)


def span_s(name: str) -> Optional[float]:
    """Host seconds of the program's spans named ``name``, at any depth,
    from ``repro.runtime.spans`` in this process; None where the program
    has no such span."""
    try:
        from repro.runtime import spans
    except ImportError:
        return None
    secs = [s for path, (_, s) in spans.totals().items()
            if path.rsplit("/", 1)[-1] == name]
    return sum(secs) if secs else None


if __name__ == "__main__":
    result = (reduce_file(sys.argv[1])
              or reduce_file(sys.argv[1], annotations=LOOP_SPANS))
    if result is None:
        sys.exit("no annotated window with device operations in the trace")
    print(json.dumps(dict(window_s=result.window_s, busy_s=result.busy_s,
                          chunk_op_s=result.chunk_op_s,
                          **result.breakdown()), indent=1))
