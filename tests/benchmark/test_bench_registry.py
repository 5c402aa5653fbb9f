"""BENCHMARK.json against the benchmark's contract, and every cell and
metric found by name from files of its own."""
import dataclasses
import json
import os
import re
import shutil

import pytest

from bench_cases import CHIP, ROOT, harness

import reference  # noqa: E402  (on the path once bench_cases is imported)

BENCH = harness.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys_and_paths():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    script = BENCH["command"][1]
    assert any(script.startswith(p + "/") for p in BENCH["paths"])
    assert os.path.isfile(os.path.join(ROOT, script))
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_units_and_bounds():
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += [w["name"] for w in BENCH["workloads"]]
    names += [c["name"] for c in BENCH["configs"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    assert all(m["source"] in ("host_clock", "device_trace")
               for m in e2e.values())
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", ())) <= {w["name"]
                                               for w in BENCH["workloads"]}


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves_to_its_files(workload):
    cell = harness.resolve(BENCH, workload)
    entry = {c["name"]: c for c in BENCH["configs"]}[
        {w["name"]: w for w in BENCH["workloads"]}[workload]["config"]]
    assert cell.config["name"] == entry["name"]
    assert sorted(cell.config["reduced"]) == sorted(entry["reduced"])
    assert cell.traffic["mode"] in reference.MODES
    harness.check_supported(cell)
    assert [m["name"] for m in cell.end_to_end] == ["updates_per_s",
                                                    "setup_s"]
    assert cell.per_layer


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_every_metric_has_a_reader(metric):
    assert callable(harness.metric_reader(metric))


def test_an_added_cell_and_metric_need_no_edit(tmp_path):
    """A new configuration, traffic mix and metric are new files and new
    entries; the harness finds them by name."""
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip")
    bench = json.loads(json.dumps(BENCH))
    chip = tmp_path / "benchmarks" / "chip"
    cfg = json.loads((chip / "configs/graphcolor-torus-1simel.json")
                     .read_text())
    cfg.update(name="graphcolor-torus-1simel-c32", buffer_capacity=32)
    (chip / "configs/graphcolor-torus-1simel-c32.json").write_text(
        json.dumps(cfg))
    tr = json.loads((chip / "traffic/best-effort.json").read_text())
    (chip / "traffic/best-effort-chunk8.json").write_text(
        json.dumps(dict(tr, chunk=8)))
    (chip / "metrics/fetch_ms.py").write_text(
        "def read(r):\n    return 1e3 * r.spans['loop.fetch_s']\n")
    bench["configs"].append(
        {"name": cfg["name"], "source": "https://arxiv.org/abs/2211.10897",
         "file": "benchmarks/chip/configs/graphcolor-torus-1simel-c32.json",
         "reduced": [], "why": "smaller ducts"})
    bench["workloads"].append(
        {"name": "gc1-c32", "config": cfg["name"],
         "traffic": "best-effort-chunk8", "chips": 1, "why": "test"})
    bench["per_layer"].append(
        {"name": "fetch_ms", "unit": "ms", "better": "lower",
         "source": "host_clock", "layer": "engine loop",
         "moves": "updates_per_s", "workloads": ["gc1-c32"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.resolve(harness.load_benchmark(tmp_path), "gc1-c32",
                           root=tmp_path)
    assert cell.config["buffer_capacity"] == 32
    assert cell.traffic["chunk"] == 8
    assert [m["name"] for m in cell.per_layer][-1] == "fetch_ms"
    read = harness.metric_reader("fetch_ms", root=tmp_path)
    reading = harness.Reading(cell, {"loop.fetch_s": 0.25}, None, {}, {})
    assert read(reading) == 250.0
    # the cells already there resolve as before
    assert harness.resolve(harness.load_benchmark(tmp_path), "gc1-be",
                           root=tmp_path).traffic == harness.resolve(
        BENCH, "gc1-be").traffic


def test_unknown_workload_is_refused():
    with pytest.raises(harness.BenchError):
        harness.resolve(BENCH, "no-such-cell")


def test_seed_folds_into_int32():
    assert harness.seed32(2 ** 31 + 5) == 5
    assert 0 <= harness.seed32(4_000_000_007) < 2 ** 31


@pytest.mark.parametrize("traffic,named", [
    ({"mode": "ROLLING_BARRIER"}, "ROLLING_BARRIER"),
    ({"mode": "FIXED_BARRIER"}, "FIXED_BARRIER"),
    ({"faults": "lossy"}, "lossy"),
    ({"faults": "crash"}, "crash")])
def test_check_supported_refuses_what_the_reference_lacks(traffic, named):
    """A mode or fault the reference does not declare is refused, in the
    reference's words: the error names it and what the reference has."""
    cell = harness.resolve(BENCH, "gc1-be")
    cell = dataclasses.replace(cell, traffic=dict(cell.traffic, **traffic))
    with pytest.raises(harness.BenchError, match=named) as e:
        harness.check_supported(cell)
    assert "BEST_EFFORT" in str(e.value) or "none" in str(e.value)


def test_check_supported_refuses_a_barrier_without_its_cost():
    cell = harness.resolve(BENCH, "gc1-barrier")
    traffic = {k: v for k, v in cell.traffic.items() if k != "barrier_base"}
    with pytest.raises(harness.BenchError, match="barrier_base"):
        harness.check_supported(dataclasses.replace(cell, traffic=traffic))


def test_check_supported_refuses_a_missing_reference_module():
    cell = harness.resolve(BENCH, "gc1-be")
    cell = dataclasses.replace(cell, config=dict(cell.config,
                                                 topology="kronecker"))
    with pytest.raises(harness.BenchError, match="kronecker"):
        harness.check_supported(cell)


def test_program_and_reference_take_the_traffic_barrier_cost():
    cell = harness.resolve(BENCH, "gc1-barrier")
    cfg, sw = harness.sim_config(cell, 1), harness.swarm(cell)
    for k in ("barrier_base", "barrier_per_log2"):
        assert getattr(cfg, k) == getattr(sw, k) == cell.traffic[k]
