"""The program's spans: the host span table (nesting paths, self time,
counters, reset), the spans that set-up, the chunk loop and the assemble
record, and the ``jax.named_scope`` of every window phase in the lowered
chunk program."""
import re
import time

import jax
import pytest

from engine_cases import gc_app, jittered_cfg
from repro.runtime import spans
from repro.runtime.config import RunConfig
from repro.runtime.engine import make_engine
from repro.runtime.window_core import (CLOSE, COMMIT, COMPUTE, DRAIN, SEND,
                                       SNAPSHOT)


def test_nesting_paths_self_time_counters_and_reset():
    spans.reset()
    with spans.span("outer"):
        with spans.span("inner"):
            time.sleep(0.02)
        with spans.span("inner"):
            pass
        time.sleep(0.01)
    spans.count("things", 3)
    spans.count("things", 4)

    @spans.span("deco")
    def plus_one(x):
        return x + 1

    assert plus_one(1) == 2 and plus_one(2) == 3
    t = spans.totals()
    assert set(t) == {"outer", "outer/inner", "deco"}
    assert (t["outer"][0], t["outer/inner"][0], t["deco"][0]) == (1, 2, 2)
    # self time: a span's seconds less its direct children's
    assert t["outer/inner"][1] >= 0.02
    assert t["outer"][1] - t["outer/inner"][1] >= 0.01
    assert spans.counters() == {"things": 7}
    spans.reset()
    assert spans.totals() == {} and spans.counters() == {}


def test_report_lists_spans_with_self_time_and_counters():
    spans.reset()
    with spans.span("outer"):
        with spans.span("inner"):
            time.sleep(0.01)
    spans.count("things", 5)
    t = spans.totals()
    rows = {line.split()[0]: line.split()[1:]
            for line in spans.report().splitlines()}
    assert rows["outer"][0] == "1" and rows["outer/inner"][0] == "1"
    assert float(rows["outer"][1]) == pytest.approx(t["outer"][1], abs=1e-4)
    assert float(rows["outer"][2]) == pytest.approx(
        t["outer"][1] - t["outer/inner"][1], abs=2e-4)
    assert rows["things"] == ["5"]
    spans.reset()


def test_span_that_raises_is_recorded_and_closed():
    spans.reset()
    with pytest.raises(ValueError):
        with spans.span("boom"):
            raise ValueError("inside")
    with spans.span("after"):
        pass
    assert spans.totals().keys() == {"boom", "after"}


SETUP = {"setup.topology", "setup.import", "setup.engine",
         "setup.engine/app", "setup.engine/edges", "setup.engine/layout",
         "setup.engine/tables", "setup.carry", "setup.carry/app"}
LOOP = {"loop.dispatch", "loop.probe", "loop.fetch", "loop.assemble",
        "loop.assemble/assemble.quality", "loop.assemble/assemble.qos"}


def _run_table(n: int):
    spans.reset()
    eng = make_engine(RunConfig(engine="jax"), gc_app(n, "torus"),
                      jittered_cfg(duration=0.002), chunk=256)
    eng.debug_keep_carry = True
    (res,) = eng.run_replicates([3])
    eng._assemble(eng._final_carry, 0)
    return res, spans.totals(), spans.counters()


def test_set_up_loop_and_assemble_spans_do_not_scale_with_processes():
    """Set-up, ``run_replicates`` and ``_assemble`` record the same spans,
    as often, at 16 and at 64 processes: no span sits in a per-process or
    per-edge loop; the loops' sizes are counters."""
    small, large = _run_table(16), _run_table(64)
    for n, (res, totals, counters) in ((16, small), (64, large)):
        assert set(totals) == SETUP | LOOP | {"assemble.quality",
                                              "assemble.qos"}
        assert counters["setup.processes"] == n
        assert counters["setup.ducts"] == 4 * n
        # once under run_replicates, once called directly
        assert counters["assemble.processes"] == 2 * n
        assert counters["assemble.reports"] == 2 * len(res.qos)
        assert counters["loop.chunks"] == totals["loop.dispatch"][0]
        assert counters["loop.fetch_bytes"] > 0
    calls = {p: c for p, (c, _) in small[1].items()}
    assert calls == {p: c for p, (c, _) in large[1].items()}
    assert all(c == 1 for p, c in calls.items()
               if p not in ("loop.dispatch", "loop.probe"))


@pytest.mark.parametrize("scheduler,windows,scopes", [
    ("window", 1, {DRAIN, COMPUTE, SEND, CLOSE, SNAPSHOT}),
    ("superstep", 8, {DRAIN, COMPUTE, SEND, CLOSE, SNAPSHOT, COMMIT}),
])
def test_chunk_program_names_every_phase(scheduler, windows, scopes):
    """The lowered chunk program of a 64-process torus carries the named
    scope of every phase its scheduler runs in its ops' metadata, and no
    other; building it keys the compilation cache by that metadata."""
    eng = make_engine(
        RunConfig(engine="jax", scheduler=scheduler,
                  superstep_windows=windows),
        gc_app(64, "torus"), jittered_cfg(duration=0.002), chunk=16)
    carry = jax.tree.map(lambda x: x[None], eng._init_carry(1))
    text = eng._get_runner().lower(carry).as_text(debug_info=True)
    assert set(re.findall(r"window\.[a-z]+", text)) == scopes
    # the scopes are metadata: the persistent cache must key by them
    assert jax.config.jax_compilation_cache_include_metadata_in_key


def test_experiments_trace_dir_records_the_programs_spans(tmp_path, capsys):
    """``--trace-dir`` records a profiler trace of the run whose host plane
    holds the program's spans, named by their path, and prints the span
    table and the counters."""
    import glob

    from jax.profiler import ProfileData

    from repro.runtime import experiments
    experiments.main(["--engine", "jax", "--procs", "16", "--duration",
                      "0.002", "--trace-dir", str(tmp_path)])
    out = capsys.readouterr().out
    assert f"trace written under {tmp_path}" in out
    printed = {line.split()[0] for line in out.splitlines() if line.strip()}
    assert {"setup.engine/edges", "loop.assemble/assemble.qos",
            "setup.processes", "loop.fetch_bytes"} <= printed
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    names = {ev.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events}
    assert {"setup.topology", "setup.engine", "setup.engine/edges",
            "setup.carry", "loop.dispatch", "loop.fetch",
            "loop.assemble/assemble.qos"} <= names
