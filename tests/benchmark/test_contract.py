"""BENCHMARK.json and the files it names, against the contract's limits."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    assert 1 <= len(bench["paths"]) <= 16
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in bench["paths"])
    assert len(bench["command"]) <= 32 and all(map(_line, bench["command"]))
    for word in bench["command"]:
        if os.path.exists(os.path.join(ROOT, word)):
            assert any(word.startswith(p + "/") for p in bench["paths"])


def test_names_units_and_keys(bench):
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert _line(m["layer"])
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    assert "setup_s" in [m["name"] for m in bench["end_to_end"]]
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert len(c["reduced"]) <= 16 and all(map(NAME.match, c["reduced"]))
    cells = bench["workloads"]
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4) and _line(w["why"])
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    four = sum(w["chips"] == 4 for w in cells)
    assert four <= max(1, len(cells) // 4)
    assert {w["config"] for w in cells} == {c["name"] for c in bench["configs"]}


def test_files_under_paths_are_named_from_allowed_characters(bench):
    for path in bench["paths"]:
        for base, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d not in ("__pycache__", "cache")]
            for name in files:
                if name.endswith(".pyc"):
                    continue
                rel = os.path.relpath(os.path.join(base, name), ROOT)
                assert PATH.match(rel), rel


def _cells_of(metric, bench):
    return metric.get("workloads") or [w["name"] for w in bench["workloads"]]


def test_every_cell_and_config_has_its_files(bench):
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    for c in bench["configs"]:
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        with open(os.path.join(ROOT, c["file"])) as f:
            config = json.load(f)
        assert sorted(config.get("reduced", {})) == sorted(c["reduced"])
        assert os.path.exists(os.path.join(
            BENCH, "builders", config["builder"] + ".py"))
    for w in bench["workloads"]:
        with open(os.path.join(BENCH, "cells", w["name"] + ".json")) as f:
            cell = json.load(f)
        assert cell["config"] == w["config"] and cell["chips"] == w["chips"]
        assert os.path.exists(os.path.join(
            BENCH, "runners", cell["runner"] + ".py"))
        assert "setup_s" in cell["end_to_end"]
        assert len(cell["end_to_end"]) >= 2
        for name in cell["end_to_end"]:
            entry = next(m for m in bench["end_to_end"] if m["name"] == name)
            assert w["name"] in _cells_of(entry, bench)
        assert any(w["name"] in _cells_of(m, bench)
                   for m in bench["per_layer"])
        for key, value in cell["correct"].items():
            if key.endswith(("_tolerance", "_err", "_atol")):
                assert any(k.endswith("_reason") for k in cell["correct"])
        for comparison in cell["correct"].get("comparisons", []):
            assert set(comparison) == {"precision", "max_rel_err", "reason"}
            assert "PLACEHOLDER" not in comparison["reason"]
    # and the other way round: a metric names only cells that report it
    for m in bench["end_to_end"]:
        for name in _cells_of(m, bench):
            with open(os.path.join(BENCH, "cells", name + ".json")) as f:
                assert m["name"] in json.load(f)["end_to_end"], (m, name)


def test_every_metric_has_a_reader_and_moves_a_reported_metric(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for kind, metrics in (("end_to_end", bench["end_to_end"]),
                          ("layers", bench["per_layer"])):
        for m in metrics:
            with open(os.path.join(BENCH, kind, m["name"] + ".json")) as f:
                spec = json.load(f)
            assert os.path.exists(os.path.join(
                BENCH, "readers", spec["reader"] + ".py")), m["name"]
    layers = {}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e, m
        moved = set(_cells_of(e2e[m["moves"]], bench))
        assert set(_cells_of(m, bench)) <= moved, m
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(spellings) == 1 for spellings in layers.values())
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for m in bench["per_layer"]:
        assert m["layer"] in perf, f"PERF.md lacks the layer {m['layer']!r}"
