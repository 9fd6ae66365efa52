"""Names resolve to files, and what does not resolve fails before a run."""
import json
import re

import pytest

import bench_tiny
from benchlib import harness, traffic
from benchlib.model import Model

BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves(workload):
    cell = harness.load_cell(workload)
    assert cell.chips == 1
    assert set(cell.metrics) == {m["name"] for m in BENCH["end_to_end"]}
    assert cell.per_layer  # every cell reports a per-layer metric
    for name in cell.per_layer:
        entry = {m["name"]: m for m in BENCH["per_layer"]}[name]
        assert entry["moves"] in cell.metrics


@pytest.mark.parametrize("kind,what", [("workload", "nope.b1"),
                                        ("config", "nope-224"),
                                        ("traffic", "nope"),
                                        ("metric", "nope_ms")])
def test_an_unknown_name_fails(kind, what):
    bench = json.loads(json.dumps(BENCH))
    w = bench["workloads"][0]
    if kind == "config":
        bench["configs"].append(dict(bench["configs"][0], name=what))
        w["config"] = what
    elif kind == "traffic":
        w["traffic"] = what
    elif kind == "metric":
        bench["per_layer"].append(dict(bench["per_layer"][0], name=what,
                                       workloads=[w["name"]]))
    name = what if kind == "workload" else w["name"]
    with pytest.raises(harness.BenchError):
        harness.load_cell(name, bench)


def test_unknown_config_and_traffic_files_fail():
    with pytest.raises(KeyError):
        Model("nope-224")
    with pytest.raises(KeyError):
        traffic.load("nope")


def test_an_unknown_device_kind_fails():
    with pytest.raises(harness.BenchError):
        harness.peaks_for("TPU v0 imaginary")
    assert harness.peaks_for("TPU v5 lite")["peak_flops"] == 197e12


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_file_keeps_to_its_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert Model(c["name"]).cfg["reduced"] == c["reduced"]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert (harness.METRIC_DIR / f"{m['name']}.py").is_file()
    for m in BENCH["per_layer"]:
        if "_roofline" in m["name"]:
            assert any("mfu" in o["name"] and o["moves"] == m["moves"]
                       and set(m["workloads"]) <= set(o["workloads"])
                       for o in BENCH["per_layer"])
    assert bench_tiny.ROOT
