"""BENCHMARK.json against the contract's limits, and against the files
it names: every cell, configuration, mix and per-layer metric is found
by its name."""
import json
import os
import re

import pytest

from benchmark.lib import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
FILE = re.compile(r"^[A-Za-z0-9_.\-/]+$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


@pytest.fixture(scope="module")
def bench():
    path = os.path.join(harness.ROOT, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    with open(path) as f:
        return json.load(f)


def _line(s, n=200):
    return 1 <= len(s) <= n and "\n" not in s and "\t" not in s


def test_top_level(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "benchmark/run.py"]
    assert bench["paths"] == ["benchmark"]
    rs = bench["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check with the full 24 cells must fit 43200 s
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200


def test_configs(bench):
    used = {w["config"] for w in bench["workloads"]}
    names = [c["name"] for c in bench["configs"]]
    assert len(set(names)) == len(names) and set(names) == used
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith("benchmark/") and FILE.match(c["file"])
        assert len(c["reduced"]) <= 16
        with open(os.path.join(harness.ROOT, c["file"])) as f:
            cfg = json.load(f)
        for key in c["reduced"]:
            assert NAME.match(key) and key in cfg, key
            assert not re.search(r"(_dim|_rank|hidden_size|head)", key)
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        for group, name in (("models", cfg["builder"]),
                            ("reference", cfg["reference"])):
            assert os.path.exists(os.path.join(
                harness.BENCH_DIR, group, name + ".py"))


def test_workloads(bench):
    cells = bench["workloads"]
    assert 1 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    four = [w for w in cells if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
        _, cell, cfg, mix = harness.load_cell(harness.ROOT, w["name"])
        assert mix["kind"] in ("train", "serve_closed", "serve_open")
        assert cfg["chips"] == w["chips"]
        assert os.path.exists(os.path.join(
            harness.BENCH_DIR, "lib",
            "run_" + mix["kind"].split("_")[0] + ".py"))


def test_metrics(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(names)) == len(names)
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    layers = {}
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and _line(m["layer"])
        assert m["moves"] in e2e
        mod = harness.load_layer_metric(m["name"])
        assert (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE) == (
            m["layer"], m["unit"], m["moves"], m["source"])
        layers.setdefault(m["layer"], []).append(m["name"])
        # each listed cell reports the end-to-end metric it should move
        moved = e2e[m["moves"]]
        for c in m.get("workloads", cells):
            assert c in moved.get("workloads", cells)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for c in cells:
        mine = harness.metrics_of(bench, "end_to_end", c)
        assert {"setup_s"} < {m["name"] for m in mine}
        assert harness.metrics_of(bench, "per_layer", c)


def test_files_under_paths_are_named_from_allowed_characters():
    for d, _, files in os.walk(harness.BENCH_DIR):
        if "__pycache__" in d:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(d, f), harness.ROOT)
            assert FILE.match(rel), rel


def test_traffic_files_are_data():
    for f in os.listdir(os.path.join(harness.BENCH_DIR, "traffic")):
        assert f.rsplit(".", 1)[-1] in ("json", "jsonl", "toml", "txt", "csv")
