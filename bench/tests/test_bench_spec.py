"""BENCHMARK.json against the benchmark's contract, and every cell
resolving to the files it names."""
import json
import os
import re

import pytest

from bench import benchspec

BENCH = benchspec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert BENCH["command"][1] == "bench/run.py"
    assert 1 <= BENCH["run_seconds"] <= 51
    assert 1 <= len(BENCH["configs"]) <= 24
    assert 1 <= len(BENCH["workloads"]) <= 24
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)
    assert os.path.getsize(os.path.join(benchspec.ROOT,
                                        "BENCHMARK.json")) <= 64 * 1024


def test_names_units_and_keys():
    names = []
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/")
        names.append(c["name"])
        assert all(NAME.match(k) for k in c["reduced"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        names.append(w["name"])
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        names.append(m["name"])
    assert all(NAME.match(n) for n in names)
    assert len({m["name"] for m in metrics}) == len(metrics)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_check_fits_the_time_limit():
    """2 + 14 runs per cell, each run_seconds + 60, 2 x 90 s of compiling
    per cell and 1200 s spare must fit 43200 s with 24 cells."""
    per_cell = 14 * (BENCH["run_seconds"] + 60) + 2 * 90
    assert 2 * (BENCH["run_seconds"] + 60) + 24 * per_cell + 1200 <= 43200


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves(name):
    cell = benchspec.resolve(name)
    assert cell.traffic["driver"] in ("step", "open_loop")
    assert cell.config["chips"] == cell.chips
    assert 0 < cell.limits["rel_err"] < 1
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(benchspec.reader(m["name"]))


CONFIG_FILES = sorted(os.listdir(os.path.join(benchspec.BENCH_DIR,
                                              "configs")))


@pytest.mark.parametrize("fname", CONFIG_FILES)
def test_config_file_states_its_cuts(fname):
    with open(os.path.join(benchspec.BENCH_DIR, "configs", fname)) as f:
        cfg = json.load(f)
    assert fname == cfg["name"] + ".json"
    assert cfg["source"] and isinstance(cfg["assumed"], dict)
    assert all(k in cfg["assumed"] for k in cfg["reduced"])
    assert cfg["dtype"] == "float32" and cfg["chips"] in (1, 4)
    entry = {c["name"]: c for c in BENCH["configs"]}.get(cfg["name"])
    if entry is not None:
        assert entry["reduced"] == cfg["reduced"]
        assert entry["file"] == "bench/configs/" + fname


def test_every_config_is_used_and_every_metric_has_a_reader():
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    readers = {f[:-3] for f in os.listdir(os.path.join(
        benchspec.BENCH_DIR, "metrics")) if f.endswith(".py")}
    assert {m["name"] for m in BENCH["per_layer"]} <= readers
    cells = set(CELLS)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells


def test_missing_files_raise(tmp_path):
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"][0]["traffic"] = "no-such-mix"
    with pytest.raises(FileNotFoundError):
        benchspec.resolve(bench["workloads"][0]["name"], bench=bench)
    with pytest.raises(KeyError):
        benchspec.resolve("no-such-cell")
    with pytest.raises(FileNotFoundError):
        benchspec.reader_path("no.such.metric")
