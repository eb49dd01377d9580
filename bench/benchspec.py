"""What ``BENCHMARK.json`` says about one cell, found by name.

A cell names a configuration (``bench/configs/<config>.json`` through
the ``configs`` entry's ``file``), a traffic mix
(``bench/traffic/<mix>.json``) and, through the metric lists, the
per-layer readers (``bench/metrics/<metric>.py``) and the limits of its
correctness check (``bench/limits/<cell>.json``).  Adding a cell adds
files and entries; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass
class Cell:
    """One workload entry with everything it names, loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_json(root: str, rel: str) -> dict:
    with open(os.path.join(root, rel)) as f:
        return json.load(f)


def _applies(metric: dict, cell: str, e2e_names) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return e2e_names is None or metric["moves"] in e2e_names


def resolve(name: str, root: str = ROOT, bench: dict = None) -> Cell:
    """Load the cell called ``name``; a missing entry or file raises."""
    bench = bench if bench is not None else load_benchmark(root)
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r}; known: {sorted(entries)}")
    entry = entries[name]
    configs = {c["name"]: c for c in bench["configs"]}
    return load_cell(entry, configs[entry["config"]]["file"], bench, root)


def load_cell(entry: dict, config_file: str, bench: dict,
              root: str = ROOT) -> Cell:
    """The cell of one ``workloads`` entry, its configuration file, and
    the metrics of ``bench`` that apply to it."""
    name = entry["name"]
    config = _load_json(root, config_file)
    traffic = _load_json(root, os.path.join(
        "bench", "traffic", entry["traffic"] + ".json"))
    limits = _load_json(root, os.path.join("bench", "limits",
                                           name + ".json"))
    e2e = [m for m in bench["end_to_end"] if _applies(m, name, None)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _applies(m, name, names)]
    for m in per_layer:
        reader_path(m["name"], root)        # every reader must exist
    return Cell(name=name, chips=int(entry["chips"]), config=config,
                traffic=traffic, limits=limits, end_to_end=e2e,
                per_layer=per_layer)


def reader_path(metric: str, root: str = ROOT) -> str:
    path = os.path.join(root, "bench", "metrics", metric + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no reader for per-layer metric "
                                f"{metric!r} at {path}")
    return path


def reader(metric: str, root: str = ROOT) -> Callable:
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``: it
    returns the metric's value, or None where the run holds nothing for
    it to read."""
    path = reader_path(metric, root)
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
