"""The manifest's form, before any chip time is spent on it."""

import glob
import json
import os
import re

import pytest

from benchmark import manifest
from benchmark.selfcheck.conftest import ROOT, tiny_cell, tiny_file

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
BENCH = manifest.benchmark()
CELLS = {w["name"]: w for w in BENCH["workloads"]}
END_TO_END = {m["name"]: m for m in BENCH["end_to_end"]}


def _files(kind):
    return sorted(glob.glob(os.path.join(manifest.HERE, kind, "*.json")))


def _one_line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmark"]
    assert len(BENCH["command"]) <= 32
    assert all(_one_line(word) for word in BENCH["command"])
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(
        manifest.ROOT, "BENCHMARK.json")) <= 64 * 1024
    # the full 24 cells at this length fit a check
    runs = 2 + 14 * 24
    assert (runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200) <= 43200


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end",
                                  "per_layer"])
def test_names_are_slugs_and_unique(kind):
    names = [e["name"] for e in BENCH[kind]]
    assert all(NAME.match(n) for n in names), names
    assert len(set(names)) == len(names)


def test_metric_names_do_not_collide():
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(names)) == len(names)


def test_configs():
    assert 1 <= len(BENCH["configs"]) <= 24
    used = {w["config"] for w in BENCH["workloads"]}
    files = set()
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used
        assert _one_line(c["source"]) and _one_line(c["why"])
        assert c["file"].startswith("benchmark/") and c["file"] not in files
        files.add(c["file"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        with open(os.path.join(manifest.ROOT, c["file"])) as f:
            held = json.load(f)
        assert held["name"] == c["name"]
        assert held["reduced"] == c["reduced"]
        assert NAME.match(held["family"])


def test_workloads():
    assert 1 <= len(BENCH["workloads"]) <= 24
    configs = {c["name"] for c in BENCH["configs"]}
    pairs = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        assert _one_line(w["why"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        manifest.cell(w["name"])  # its files exist and agree
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_every_cell_has_its_selfcheck_sizes(name):
    """``selfcheck/tiny/<cell>.json``: keys of the cell's workload file, at
    sizes a test run can hold, with a limit for every number compared."""
    assert os.path.exists(tiny_file(name)), os.path.relpath(
        tiny_file(name), ROOT)
    _, whole, _ = manifest.cell(name)
    _, tiny, _ = tiny_cell(name)
    with open(tiny_file(name)) as f:
        assert set(json.load(f)) <= set(whole)
    assert set(tiny["limits"]) == set(whole["limits"])


def test_a_cell_without_its_selfcheck_sizes_fails_with_the_files_name():
    entry = dict(BENCH["workloads"][0], name="a_config.a_cell_to_come")
    with pytest.raises(FileNotFoundError) as e:
        tiny_cell(entry["name"], whole=lambda name: (entry, {}, {}))
    assert "benchmark/selfcheck/tiny/a_config.a_cell_to_come.json" \
        in str(e.value)


def _cells_of(metric):
    return metric.get("workloads", list(CELLS))


def test_end_to_end():
    assert 1 <= len(BENCH["end_to_end"]) <= 16
    assert "setup_s" in END_TO_END
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        assert all(c in CELLS for c in _cells_of(m))
    for cell in CELLS:
        reported = [m["name"] for m in BENCH["end_to_end"]
                    if cell in _cells_of(m)]
        assert "setup_s" in reported and len(reported) >= 2


def test_per_layer():
    assert 1 <= len(BENCH["per_layer"]) <= 128
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert NAME.match(m["layer"]), m["layer"]
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        moved = END_TO_END[m["moves"]]
        for cell in _cells_of(m):
            assert cell in CELLS and cell in _cells_of(moved)
        if m["name"].endswith("_roofline") or "mfu" in re.split(
                r"[_.-]", m["name"]):
            assert m["unit"] == "%"
    for cell in CELLS:
        assert any(cell in _cells_of(m) for m in BENCH["per_layer"])


def test_metric_files_agree_with_the_manifest():
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for path in _files("metrics"):
        with open(path) as f:
            held = json.load(f)
        assert os.path.basename(path) == held["name"] + ".json"
        assert NAME.match(held["name"]) and NAME.match(held["layer"])
        assert UNIT.match(held["unit"])
        manifest.reader(held["reader"])  # the reader exists
        if held["name"] in by_name:
            for key in ("unit", "better", "source", "layer", "moves"):
                assert held[key] == by_name[held["name"]][key], (path, key)
    assert set(by_name) <= {os.path.basename(p)[:-5]
                            for p in _files("metrics")}


@pytest.mark.parametrize("kind", ["configs", "workloads"])
def test_data_files_are_named_by_slugs(kind):
    for path in _files(kind):
        with open(path) as f:
            held = json.load(f)
        assert os.path.basename(path) == held["name"] + ".json"
        assert NAME.match(held["name"])
        if kind == "workloads":
            assert NAME.match(held["config"]) and NAME.match(held["traffic"])
            assert _one_line(held["why"])


def test_files_under_paths_are_named_from_name_characters():
    ok = re.compile(r"^[A-Za-z0-9_./-]+$")
    for base, dirs, files in os.walk(manifest.HERE):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            rel = os.path.relpath(os.path.join(base, f), manifest.ROOT)
            assert ok.match(rel), rel


def test_peaks_name_their_source():
    with open(os.path.join(manifest.HERE, "peaks.json")) as f:
        for kind, row in json.load(f).items():
            assert row["flops_per_s"] > 0 and row["hbm_bytes_per_s"] > 0
            assert row["source"]
    with pytest.raises(KeyError):
        manifest.peaks("a device nobody has")
