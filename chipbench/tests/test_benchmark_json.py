"""BENCHMARK.json against the contract's limits, and against the files
the harness finds by its names."""

import os
import re

import pytest

from chipbench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return harness.load_json(harness.ROOT, "BENCHMARK.json")


def line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(harness.ROOT,
                                        "BENCHMARK.json")) <= 64 * 1024
    assert bench["paths"] == ["chipbench"]
    assert len(bench["command"]) <= 32 and all(map(line, bench["command"]))
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    # a full check with all 24 cells fits into 43200 s
    cells = 24
    assert (2 + 14 * cells) * (bench["run_seconds"] + 60) \
        + cells * 2 * 90 + 1200 <= 43200


def test_configs(bench):
    assert 1 <= len(bench["configs"]) <= 24
    names = [c["name"] for c in bench["configs"]]
    assert len(set(names)) == len(names)
    used = {w["config"] for w in bench["workloads"]}
    files = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert line(c["source"]) and line(c["why"])
        assert c["file"].startswith("chipbench/") and c["file"] not in files
        files.add(c["file"])
        held = harness.load_json(harness.ROOT, c["file"])
        assert len(c["reduced"]) <= 16
        assert sorted(c["reduced"]) == sorted(held["reduced"])
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not re.search(r"(_dim|_rank|hidden_size|intermediate_size"
                                 r"|head_dim|experts_per_tok)$", key)
        assert held["source"] == c["source"]
        for part in ("assumed", "deployment", "runner", "reference"):
            assert part in held


def test_workloads(bench):
    assert 1 <= len(bench["workloads"]) <= 24
    names = [w["name"] for w in bench["workloads"]]
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(names)) == len(names) and len(set(pairs)) == len(pairs)
    configs = {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert line(w["why"])
        traffic = harness.load_json(harness.HERE, "traffic",
                                    w["traffic"] + ".json")
        assert os.path.exists(os.path.join(
            harness.HERE, "generators", traffic["generator"] + ".py"))
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)


def test_metrics(bench):
    cells = {w["name"] for w in bench["workloads"]}
    end = {m["name"]: m for m in bench["end_to_end"]}
    assert 1 <= len(end) <= 16 and "setup_s" in end
    assert len(end) == len(bench["end_to_end"])
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        assert set(m.get("workloads", cells)) <= cells
    reported = {c: {n for n, m in end.items()
                    if c in m.get("workloads", cells)} for c in cells}
    assert 1 <= len(bench["per_layer"]) <= 128
    names = [m["name"] for m in bench["per_layer"]]
    assert len(set(names)) == len(names) and not set(names) & set(end)
    covered = set()
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert line(m["layer"]) and m["moves"] in end
        for c in m.get("workloads", cells):
            assert c in cells and m["moves"] in reported[c]
            covered.add(c)
        # the metric's own file says the same, and names a reader
        spec = harness.load_json(harness.HERE, "metrics", m["name"] + ".json")
        for key in ("name", "unit", "better", "source", "layer", "moves",
                    "workloads"):
            assert spec[key] == m[key], (m["name"], key)
        assert os.path.exists(os.path.join(harness.HERE, "readers",
                                           spec["reader"] + ".py"))
    for c in cells:        # setup_s, one more end-to-end, one per-layer
        assert "setup_s" in reported[c] and len(reported[c]) >= 2
        assert c in covered


def test_every_file_under_paths_is_named_from_a_names_characters():
    for folder, _, files in os.walk(harness.HERE):
        if "__pycache__" in folder:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(folder, f), harness.ROOT)
            assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel
