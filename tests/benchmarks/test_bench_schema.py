"""``BENCHMARK.json`` keeps to the contract's shapes, and every name in it
leads to a file."""
import json
import os
import re

import pytest

from conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
CELLS = {w["name"]: w for w in BENCH["workloads"]}
E2E = {m["name"]: m for m in BENCH["end_to_end"]}


def _cells_of(metric):
    if "workloads" in metric:
        return metric["workloads"]
    if "moves" in metric:
        return _cells_of(E2E[metric["moves"]])
    return list(CELLS)


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert 1 <= len(BENCH["paths"]) <= 16 and len(BENCH["command"]) <= 32
    for p in BENCH["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p) and ".." not in p and not p.startswith("/")
        assert os.path.isdir(os.path.join(ROOT, p))
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher") and metric["source"] in SOURCES
    end_to_end = metric["name"] in E2E
    allowed = {"name", "unit", "better", "source", "workloads"}
    allowed |= {"bound"} if end_to_end else {"layer", "moves"}
    assert set(metric) <= allowed
    if end_to_end:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert metric["moves"] in E2E and 1 <= len(metric["layer"]) <= 200
        assert os.path.isfile(os.path.join(ROOT, "benchmarks", "metrics", metric["name"] + ".py"))
        if re.search(r"roofline|mfu", metric["name"]):
            assert metric["unit"] == "%"
    cells = _cells_of(metric)
    assert cells and set(cells) <= set(CELLS)
    if not end_to_end:  # each of its cells reports the metric it moves
        assert set(cells) <= set(_cells_of(E2E[metric["moves"]]))


def test_metric_names_unique_and_setup_present():
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names)) and "setup_s" in E2E
    assert E2E["setup_s"]["bound"] <= 0.1 and "workloads" not in E2E["setup_s"]


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_entry_and_files(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"]) and cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"] and "\t" not in cell["why"]
    assert cell["config"] in {c["name"] for c in BENCH["configs"]}
    mix_path = os.path.join(ROOT, "benchmarks", "traffic", "mixes", cell["traffic"] + ".json")
    with open(mix_path) as f:
        mix = json.load(f)
    assert os.path.isfile(os.path.join(ROOT, "benchmarks", "drivers", mix["driver"] + ".py"))
    assert mix["limits"] and all(v >= 0 for v in mix["limits"].values())
    reported = [m for m in BENCH["end_to_end"] if cell["name"] in _cells_of(m)]
    assert {"setup_s"} < {m["name"] for m in reported}
    assert any(cell["name"] in _cells_of(m) for m in BENCH["per_layer"])


def test_cells_pair_config_and_traffic_once():
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs)) and len(CELLS) == len(pairs)


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entry_and_file(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"]) and len(entry["reduced"]) <= 16
    assert any(entry["file"].startswith(p + "/") for p in BENCH["paths"])
    assert any(w["config"] == entry["name"] for w in BENCH["workloads"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    assert config["source"] == entry["source"] and config["reduced"] == entry["reduced"]
    for key in entry["reduced"]:
        assert NAME.match(key) and key in config
        assert not re.search(r"(_dim|_rank|hidden|intermediate|latent|head_|d_model|channels)", key)
    for module in ("reference", "rooflines"):
        assert os.path.isfile(os.path.join(ROOT, "benchmarks", module, config["reference"] + ".py"))
    assert os.path.isfile(os.path.join(ROOT, "benchmarks", "adapters", config["program"] + ".py"))


def test_files_under_paths_keep_to_the_allowed_characters():
    for p in BENCH["paths"]:
        for folder, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                rel = os.path.relpath(os.path.join(folder, name), ROOT)
                assert re.fullmatch(r"[A-Za-z0-9_.\-/]+", rel), rel
