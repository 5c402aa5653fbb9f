"""The split of a traced window by the program's own names
(``program_spans.py``): device time by window phase and idle time by the
innermost span, on a trace written by hand and on traces recorded on the
chip; and the per-layer metrics that read them."""
import gzip
import os
import sys

import pytest
from google.protobuf import text_format

from bench_cases import harness, small_cell

import program_spans  # noqa: E402
import tracing  # noqa: E402
import xplane  # noqa: E402

DATA = os.path.join(os.path.dirname(__file__), "data")
CLOSED = "jit(chunk)/vmap()/while/body/closed_call"

# One chunk run on the chip and one probe run; four harness annotations and
# three program spans inside ``bench.assemble``. Times in ns:
#   host   bench.dispatch [1000, 4000)  bench.probe [4000, 6000)
#          bench.fetch [6000, 9000)     bench.assemble [9000, 15000)
#          loop.assemble [9200, 14500) holding
#            loop.assemble/assemble.quality [9500, 11000)
#            loop.assemble/assemble.qos [11000, 14000)
#   device jit_chunk [1000, 5000): while.1 [1000, 5000), no tf_op, holding
#            drain 500, snapshot (inside close) 500, close 300, the scan's
#            own copy 100, send 600, compute 500 (under a scope whose name
#            only contains "window.drain"); jit_any_done [5200, 5300)
OPS = [  # (name, tf_op path or None, start, end)
    ("while.1", None, 1000, 5000),
    ("fusion.1", f"{CLOSED}/window.drain/jit(duct_window_kernel)/gather:",
     1000, 1500),
    ("fusion.2", f"{CLOSED}/window.close/window.snapshot/scatter:",
     1500, 2000),
    ("fusion.3", f"{CLOSED}/window.close/add:", 2000, 2300),
    ("copy.1", "jit(chunk)/vmap()/while:", 2300, 2400),
    ("fusion.4", f"{CLOSED}/window.send/mul:", 2400, 3000),
    ("fusion.5", f"{CLOSED}/window.compute/mywindow.drain_x/select_n:",
     3000, 3500),
    ("reduce.1", "jit(any_done)/reduce_or:", 5200, 5300),
]
MODULES = [("jit_chunk(1)", 1000, 5000), ("jit_any_done(2)", 5200, 5300)]
BENCH_SPANS = [("bench.dispatch", 1000, 4000), ("bench.probe", 4000, 6000),
               ("bench.fetch", 6000, 9000), ("bench.assemble", 9000, 15000)]
PROGRAM_SPANS = [("loop.assemble", 9200, 14500),
                 ("loop.assemble/assemble.quality", 9500, 11000),
                 ("loop.assemble/assemble.qos", 11000, 14000)]


def _plane(pid, name, lines, metadata, stat_names=()):
    out = [f'planes {{ id: {pid} name: "{name}"']
    for lid, (lname, events) in enumerate(lines, 1):
        out.append(f'  lines {{ id: {lid} name: "{lname}" timestamp_ns: 0')
        for mid, start, end in events:
            out.append(f"    events {{ metadata_id: {mid} "
                       f"offset_ps: {start * 1000} "
                       f"duration_ps: {(end - start) * 1000} }}")
        out.append("  }")
    for mid, mname, stats in metadata:
        st = "".join(f' stats {{ metadata_id: {sid} str_value: "{v}" }}'
                     for sid, v in stats)
        out.append(f'  event_metadata {{ key: {mid} value {{ id: {mid} '
                   f'name: "{mname}"{st} }} }}')
    for sid, sname in stat_names:
        out.append(f'  stat_metadata {{ key: {sid} value {{ id: {sid} '
                   f'name: "{sname}" }} }}')
    out.append("}")
    return "\n".join(out)


def synthetic(program=True) -> bytes:
    """The trace above, serialized; without the program's names (no
    ``tf_op`` stats, no program spans) where ``program`` is false."""
    ops_meta = [(i, name, [(7, path)] if program and path else [])
                for i, (name, path, _, _) in enumerate(OPS, 1)]
    mod_meta = [(len(OPS) + i, name, []) for i, (name, _, _)
                in enumerate(MODULES, 1)]
    device = _plane(
        1, "/device:TPU:0",
        [("XLA Ops", [(i, a, b) for i, (_, _, a, b) in enumerate(OPS, 1)]),
         ("XLA Modules", [(len(OPS) + i, a, b)
                          for i, (_, a, b) in enumerate(MODULES, 1)])],
        ops_meta + mod_meta, stat_names=[(7, "tf_op")])
    spans = BENCH_SPANS + (PROGRAM_SPANS if program else [])
    host = _plane(2, "/host:CPU",
                  [("python", [(i, a, b) for i, (_, a, b)
                               in enumerate(spans, 1)])],
                  [(i, name, []) for i, (name, _, _) in enumerate(spans, 1)])
    space = text_format.Parse(device + "\n" + host, xplane.XSpace())
    return space.SerializeToString()


def _summary(data: bytes):
    from jax.profiler import ProfileData
    return tracing.reduce_profile(ProfileData.from_serialized_xspace(data))


def test_synthetic_phases_and_program_gaps_by_hand():
    p = program_spans.reduce_space(xplane.parse(synthetic()))
    ns = 1e-9
    assert p.phases == pytest.approx({
        "window.drain": 500 * ns, "window.snapshot": 500 * ns,
        "window.close": 300 * ns, "window.send": 600 * ns,
        "window.compute": 500 * ns}, rel=1e-12)
    assert p.unscoped == pytest.approx(1500 * ns, rel=1e-12)
    assert p.outside == pytest.approx(100 * ns, rel=1e-12)
    assert p.other_s == pytest.approx(100 * ns, rel=1e-12)
    assert p.chunk_op_s == pytest.approx(4000 * ns, rel=1e-12)
    assert p.program_gaps == pytest.approx({
        "bench.probe": 900 * ns, "bench.fetch": 3000 * ns,
        "bench.assemble": 700 * ns, "loop.assemble": 800 * ns,
        "loop.assemble/assemble.quality": 1500 * ns,
        "loop.assemble/assemble.qos": 3000 * ns}, rel=1e-12)
    b = p.breakdown()
    assert b["device_phases"][0][0] == "window.send"
    assert b["idle_gaps_by_span"][0][0] == "bench.fetch"


@pytest.mark.parametrize("program", [True, False])
def test_program_names_leave_the_window_reduction_unmoved(program):
    """``tracing``'s window, busy time, ops and gaps read the same with and
    without the program's names, and agree with ``program_spans``."""
    data = synthetic(program)
    s, bare = _summary(data), _summary(synthetic(program=False))
    assert (s.window_s, s.busy_s, s.gaps, s.ops, s.modules) == (
        bare.window_s, bare.busy_s, bare.gaps, bare.ops, bare.modules)
    assert s.window_s == pytest.approx(14000e-9, rel=1e-12)
    assert s.busy_s == pytest.approx(4100e-9, rel=1e-12)
    assert s.gaps == pytest.approx({"bench.probe": 900e-9,
                                    "bench.fetch": 3000e-9,
                                    "bench.assemble": 6000e-9}, rel=1e-12)
    p = program_spans.reduce_space(xplane.parse(data))
    assert (p.window_s, p.busy_s) == (s.window_s, s.busy_s)
    assert sum(p.program_gaps.values()) == pytest.approx(
        sum(s.gaps.values()), rel=1e-12)
    assert p.chunk_op_s + p.other_s == pytest.approx(sum(s.ops.values()),
                                                     rel=1e-12)
    if not program:
        assert p.phases == {} and p.outside == 0
        assert p.program_gaps == pytest.approx(s.gaps, rel=1e-12)


@pytest.mark.parametrize("path,phase", [
    ("jit(chunk)/vmap()/while/body/closed_call/window.send/mul:",
     "window.send"),
    ("a/window.close/window.snapshot/scatter:", "window.snapshot"),
    ("a/vmap(window.drain)/gather:", "window.drain"),
    ("a/mywindow.drain/gather:", None),
    ("a/window.drain_x/gather:", None),
    ("jit(chunk)/vmap()/while:", None),
])
def test_phase_is_the_innermost_whole_component(path, phase):
    assert program_spans.phase_of(path) == phase


def _reading(tmp_path, monkeypatch, data, windows_per_call=2):
    trace = tmp_path / "bench_trace_x" / "plugins" / "profile" / "t"
    trace.mkdir(parents=True)
    (trace / "host.xplane.pb").write_bytes(data)
    monkeypatch.setattr("tempfile.tempdir", str(tmp_path))
    return harness.Reading(small_cell("gc1-be"), {}, _summary(data),
                           {"windows_per_call": windows_per_call}, {})


@pytest.mark.parametrize("metric,ms", [
    ("window_core.drain_ms", 500e-6 / 2), ("window_core.compute_ms",
                                           500e-6 / 2),
    ("window_core.send_ms", 600e-6 / 2), ("window_core.close_ms",
                                          300e-6 / 2),
    ("window_core.snapshot_ms", 500e-6 / 2), ("window_core.commit_ms", None),
    ("window_core.loop_ms", 100e-6 / 2),
])
def test_phase_metrics_read_the_runs_trace(metric, ms, tmp_path,
                                           monkeypatch):
    """A phase metric finds the trace ``run.py`` left in the temporary
    directory by its window, and reads device ms per window (one chunk
    run of two windows); a phase the program never ran reads nothing."""
    r = _reading(tmp_path, monkeypatch, synthetic())
    got = harness.metric_reader(metric)(r)
    assert got == (None if ms is None else pytest.approx(ms, rel=1e-12))


def test_phase_metrics_read_nothing_without_the_programs_scopes(
        tmp_path, monkeypatch):
    r = _reading(tmp_path, monkeypatch, synthetic(program=False))
    for scope in ("drain", "compute", "send", "close", "snapshot", "loop"):
        assert harness.metric_reader(f"window_core.{scope}_ms")(r) is None


def test_phase_metrics_ignore_a_trace_of_another_window(tmp_path,
                                                        monkeypatch):
    r = _reading(tmp_path, monkeypatch, synthetic())
    other = _summary(synthetic())
    other.window_s *= 2
    r = harness.Reading(r.cell, {}, other, r.counts, {})
    assert harness.metric_reader("window_core.drain_ms")(r) is None


SPAN_METRICS = {"setup.topology_s": "setup.topology",
                "setup.import_s": "setup.import",
                "setup.engine_s": "setup.engine",
                "setup.carry_s": "setup.carry",
                "assemble.quality_s": "assemble.quality",
                "assemble.qos_s": "assemble.qos"}


def test_span_metrics_read_the_programs_table(monkeypatch):
    from repro.runtime import spans
    spans.reset()
    r = harness.Reading(small_cell("gc1-be"), {}, None, {}, {})
    for metric, name in SPAN_METRICS.items():
        assert harness.metric_reader(metric)(r) is None
        with spans.span(name):
            pass
    with spans.span("loop.assemble"):
        with spans.span("assemble.qos"):
            pass
    t = spans.totals()
    for metric, name in SPAN_METRICS.items():
        want = t[name][1] + (t["loop.assemble/assemble.qos"][1]
                             if name == "assemble.qos" else 0.0)
        assert harness.metric_reader(metric)(r) == want
    spans.reset()
    # a program without the span table (the parent of this change) reads
    # nothing
    import repro.runtime
    monkeypatch.delattr(repro.runtime, "spans")
    monkeypatch.setitem(sys.modules, "repro.runtime.spans", None)
    assert harness.metric_reader("setup.engine_s")(r) is None


def test_recorded_chip_trace_without_scopes():
    """The trace ``test_bench_trace.py`` reads, recorded before the program
    named its phases: no phase, every chunk op outside or unscoped, and
    the idle time split as ``tracing`` splits it."""
    with gzip.open(os.path.join(DATA, "gc1-be-1024.xplane.pb.gz")) as f:
        data = f.read()
    p, s = program_spans.reduce_space(xplane.parse(data)), _summary(data)
    assert p.phases == {}
    assert (p.window_s, p.busy_s) == (s.window_s, s.busy_s)
    assert p.program_gaps == pytest.approx(s.gaps, rel=1e-12)
    assert p.chunk_op_s + p.other_s == pytest.approx(sum(s.ops.values()),
                                                     rel=1e-12)
    assert 0 < p.chunk_op_s <= s.module("chunk")[1]


def test_recorded_chip_trace_with_scopes():
    """One traced run of ``gc1-be`` cut to 1024 processes and chunks of 2
    windows on a TPU v5 lite, recorded with the phase scopes and program
    spans: two chunks dispatched, then the fetch and the assemble. Every
    phase of the window scheduler appears; phases, ``unscoped`` and
    ``outside`` make up the chunk program's op self time, which
    ``ProfileData``'s own events give too (one ``jit_chunk`` run overlaps
    the window); the assemble's idle time lies under the program's two
    spans (``assemble.quality`` 54,468,819–55,906,919 ns and
    ``assemble.qos`` 55,929,489–58,557,959 ns inside ``bench.assemble``
    54,416,839–58,581,759 ns)."""
    from jax.profiler import ProfileData
    with gzip.open(os.path.join(DATA, "gc1-be-1024-phases.xplane.pb.gz")) as f:
        data = f.read()
    p, s = program_spans.reduce_space(xplane.parse(data)), _summary(data)
    assert set(p.phases) == {"window.drain", "window.compute",
                             "window.send", "window.close",
                             "window.snapshot"}
    assert p.phases == pytest.approx({
        "window.send": 351_255e-9, "window.drain": 138_062e-9,
        "window.snapshot": 13_417e-9, "window.compute": 1_762e-9,
        "window.close": 1_021e-9}, rel=1e-9)
    assert p.unscoped == pytest.approx(14_068e-9, rel=1e-9)
    assert p.outside == pytest.approx(38_428e-9, rel=1e-9)
    assert (p.window_s, p.busy_s) == (s.window_s, s.busy_s)
    assert s.module("chunk")[0] == 1

    pd = ProfileData.from_serialized_xspace(data)
    (device,) = [pl for pl in pd.planes if pl.name == "/device:TPU:0"]
    lines = {ln.name: ln for ln in device.lines}
    runs = [(e.start_ns, e.end_ns) for e in lines["XLA Modules"].events
            if "chunk" in e.name]
    hosts = [e for pl in pd.planes if pl.name.startswith("/host")
             for ln in pl.lines for e in ln.events
             if e.name in tracing.ANNOTATIONS]
    lo = min(e.start_ns for e in hosts)
    hi = max(e.end_ns for e in hosts)
    ops = [(e.start_ns, e.end_ns,
            any(a <= e.start_ns < b for a, b in runs))
           for e in lines["XLA Ops"].events
           if lo < e.end_ns and e.start_ns < hi]
    chunk = tracing.self_times(ops)[True]
    assert p.chunk_op_s == pytest.approx(chunk, rel=1e-12)
    assert p.chunk_op_s + p.other_s == pytest.approx(sum(s.ops.values()),
                                                     rel=1e-12)

    assert p.program_gaps["assemble.quality"] == pytest.approx(1_438_100e-9,
                                                               rel=1e-9)
    assert p.program_gaps["assemble.qos"] == pytest.approx(2_628_470e-9,
                                                           rel=1e-9)
    assert (p.program_gaps["assemble.quality"] + p.program_gaps[
        "assemble.qos"] + p.program_gaps["bench.assemble"]) == pytest.approx(
        s.gaps["bench.assemble"], rel=1e-12)
    assert sum(p.program_gaps.values()) == pytest.approx(
        sum(s.gaps.values()), rel=1e-12)
