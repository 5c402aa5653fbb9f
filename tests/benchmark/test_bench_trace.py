"""The trace reduction, on a trace written by hand and on one recorded on
the chip."""
import pytest

from bench_cases import harness  # noqa: F401  (puts the harness on the path)

import tracing  # noqa: E402

# Two device operations and one program run on the chip, three host
# annotations. Times in ns (offsets in ps, as the format keeps them):
#   host   bench.dispatch [1000, 4000)  bench.probe [4000, 6000)
#          bench.fetch    [6000, 11000)
#   device fusion.1 [1000, 3000)  _window_kernel [2500, 5000)
#          jit_chunk (module) [1000, 5000)
# window [1000, 11000) = 10 us; busy = [1000, 5000) = 4 us; idle gaps:
# [5000, 6000) under bench.probe (1 us) and [6000, 11000) under
# bench.fetch (5 us).
SYNTHETIC = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1500000 duration_ps: 2500000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 4000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "_window_kernel" } }
  event_metadata { key: 3 value { id: 3 name: "jit_chunk" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 3000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 5000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.dispatch" } }
  event_metadata { key: 2 value { id: 2 name: "bench.probe" } }
  event_metadata { key: 3 value { id: 3 name: "bench.fetch" } }
}
'''


def test_synthetic_trace_by_hand():
    from jax.profiler import ProfileData
    s = tracing.reduce_profile(ProfileData.from_text_proto(SYNTHETIC))
    assert s.window_s == pytest.approx(10e-6, rel=1e-12)
    assert s.busy_s == pytest.approx(4e-6, rel=1e-12)
    assert s.gaps == pytest.approx({"bench.probe": 1e-6, "bench.fetch": 5e-6},
                                   rel=1e-12)
    assert s.time_of("_window_kernel") == pytest.approx(2.5e-6, rel=1e-12)
    assert s.module("chunk") == (1, pytest.approx(4e-6, rel=1e-12))
    b = s.breakdown()
    assert [k for k, _ in b["device_ops"]] == ["_window_kernel", "fusion.1"]
    assert [k for k, _ in b["idle_gaps"]] == ["bench.fetch", "bench.probe"]


def test_interval_helpers():
    assert tracing.union([(5, 7), (1, 3), (2, 4)]) == [(1, 4), (5, 7)]
    assert tracing.clip([(0, 2), (3, 9)], 1, 5) == [(1, 2), (3, 5)]
    assert tracing.complement([(1, 4), (5, 7)], 0, 10) == [
        (0, 1), (4, 5), (7, 10)]
    assert tracing.attribute([(0, 10)], []) == {tracing.OTHER: 10e-9}


def test_no_device_plane_gives_nothing():
    from jax.profiler import ProfileData
    host_only = SYNTHETIC[SYNTHETIC.index("planes {\n  id: 2"):]
    assert tracing.reduce_profile(ProfileData.from_text_proto(host_only)) \
        is None


def test_recorded_chip_trace():
    """One traced run of ``gc1-be`` cut to 1024 processes and chunks of 2
    windows on a TPU v5 lite: three chunks dispatched, then the fetch and
    the assemble. The numbers below were read off the raw events: the
    first ``bench.dispatch`` starts at 45,297,404 ns and ``bench.assemble``
    ends at 56,150,235 ns; 353 ``XLA Ops`` events fall in that window and
    their union covers 767,899 ns; ``duct_window_kernel`` events in it
    last 16,178 ns; two ``jit_chunk`` runs overlap it (511,937 +
    512,358 ns; the third ran before the first annotation's start, the
    device clock running about 0.8 ms ahead of the host's); the device is
    idle through all of ``bench.fetch`` (3,793,050 ns) and
    ``bench.assemble`` (3,415,720 ns)."""
    import gzip
    import os

    from jax.profiler import ProfileData
    path = os.path.join(os.path.dirname(__file__), "data",
                        "gc1-be-1024.xplane.pb.gz")
    with gzip.open(path) as f:
        s = tracing.reduce_profile(ProfileData.from_serialized_xspace(
            f.read()))
    assert s.window_s == pytest.approx(10_852_831e-9, rel=1e-12)
    assert s.busy_s == pytest.approx(767_899e-9, rel=1e-12)
    assert s.time_of("_window_kernel") == pytest.approx(16_178e-9, rel=1e-12)
    assert s.module("chunk") == (2, pytest.approx(1_024_295e-9, rel=1e-12))
    assert s.gaps["bench.fetch"] == pytest.approx(3_793_050e-9, rel=1e-12)
    assert s.gaps["bench.assemble"] == pytest.approx(3_415_720e-9,
                                                     rel=1e-12)
    assert sum(s.gaps.values()) == pytest.approx(10_084_932e-9, rel=1e-12)
    b = s.breakdown()
    assert len(b["device_ops"]) == 10 and b["device_ops"][0][0] == (
        "while.2: while")
