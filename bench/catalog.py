"""Find a cell's parts by the names ``BENCHMARK.json`` gives them.

A configuration is the JSON file its entry names; a traffic mix is
``traffic/<name>.json``, whose ``algorithm`` names a module
``algorithms/<algorithm>.py``; a per-layer metric is
``layer_metrics/<name>.py`` with a ``read(window)`` function.  A later
cell, mix, algorithm or metric is added by adding its file and its entry,
with no edit here.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH_DIR)
BENCHMARK_JSON = os.path.join(CHECKOUT, "BENCHMARK.json")


class CatalogError(ValueError):
    """A cell, configuration, traffic mix or metric that cannot be found."""


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: list        # [Metric] this cell reports with --trace 0
    per_layer: list         # [Metric] this cell reports with --trace 1


def _read_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except OSError as exc:
        raise CatalogError(f"cannot read {path}: {exc}") from exc


def load_benchmark(path: str = BENCHMARK_JSON) -> dict:
    return _read_json(path)


def _metrics(entries, cell: str) -> list:
    return [Metric(m["name"], m["unit"], m["better"], m["source"])
            for m in entries if cell in m.get("workloads", [cell])]


def traffic_file(name: str) -> str:
    return os.path.join(BENCH_DIR, "traffic", f"{name}.json")


def find_cell(name: str, bench: dict | None = None) -> Cell:
    bench = load_benchmark() if bench is None else bench
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise CatalogError(f"no workload {name!r} in BENCHMARK.json "
                           f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise CatalogError(f"workload {name!r} names configuration "
                           f"{w['config']!r}, which BENCHMARK.json lacks")
    config = _read_json(os.path.join(CHECKOUT, configs[w["config"]]["file"]))
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, traffic_name=w["traffic"],
                traffic=_read_json(traffic_file(w["traffic"])),
                end_to_end=_metrics(bench["end_to_end"], name),
                per_layer=_metrics(bench["per_layer"], name))


def layer_reader(name: str):
    """The ``read`` function of ``layer_metrics/<name>.py``."""
    path = os.path.join(BENCH_DIR, "layer_metrics", f"{name}.py")
    if not os.path.exists(path):
        raise CatalogError(f"per-layer metric {name!r} has no reader at "
                           f"{path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_layer_metric_{name.replace('.', '_').replace('-', '_')}",
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def algorithm(name: str):
    """The module ``algorithms/<name>.py`` (its interface is set out in
    ``algorithms/__init__.py``)."""
    path = os.path.join(BENCH_DIR, "algorithms", f"{name}.py")
    if not re.fullmatch(r"[A-Za-z_][A-Za-z0-9_]*", name) or \
            not os.path.exists(path):
        raise CatalogError(f"traffic algorithm {name!r} has no module at "
                           f"{path}")
    return importlib.import_module(f"bench.algorithms.{name}")


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind`` (``peaks.json``);
    a kind that is not in the table is an error, not a default."""
    table = _read_json(os.path.join(BENCH_DIR, "peaks.json"))["devices"]
    if device_kind not in table:
        raise CatalogError(f"no peaks for device kind {device_kind!r} "
                           f"(have {sorted(table)})")
    return table[device_kind]
