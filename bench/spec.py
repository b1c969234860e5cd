"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; each lives in a file of its
own, found by name:

  bench/configs/<config>.json     model configuration as it is run (the
                                  file ``BENCHMARK.json`` gives it)
  bench/models/<model_type>.py    the configuration's family, by the file's
                                  ``model_type``: the program's config,
                                  the weights' layout, the plain reference
                                  forward and the work a step requires
  bench/traffic/<traffic>.json    traffic mix and the engine geometry
  bench/limits/<cell>.json        limits of the numbers that decide correct
  bench/metrics/<metric>.py       reader of one per-layer metric

A new cell, metric or model is new files plus new entries in
``BENCHMARK.json``; no file here needs an edit.
"""
from __future__ import annotations

import importlib.util
import json
import os
from types import ModuleType
from typing import Callable, Dict, List, NamedTuple

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH_DIR)


class SpecError(RuntimeError):
    """The benchmark's files do not define the asked-for cell."""


class Cell(NamedTuple):
    name: str
    chips: int
    config: Dict           # the configuration file's contents
    model: ModuleType      # its family, bench/models/<model_type>.py
    traffic: Dict          # the traffic file's contents, plus its "name"
    limits: Dict           # {number name: {"limit": x, ...}}
    end_to_end: List[Dict]  # BENCHMARK.json entries this cell reports
    per_layer: List[Dict]


def _load_json(path: str) -> Dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError as e:
        raise SpecError(f"missing file {path}") from e


def _load_module(path: str, kind: str, name: str) -> ModuleType:
    modname = f"bench_{kind}_" + "".join(c if c.isalnum() else "_"
                                         for c in name)
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_model(config: Dict, root: str = CHECKOUT) -> ModuleType:
    """The family of a configuration: ``<root>/bench/models/<model_type>.py``
    for the ``model_type`` its file states."""
    if "model_type" not in config:
        raise SpecError(f"configuration {config.get('name')!r} states no "
                        f"model_type")
    kind = config["model_type"]
    path = os.path.join(root, "bench", "models", f"{kind}.py")
    if not os.path.exists(path):
        raise SpecError(f"missing file {path} for model_type {kind!r}")
    return _load_module(path, "model", kind)


def _reports(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = CHECKOUT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files (read
    from ``<root>/bench``)."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                        f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {name!r} names config {w['config']!r}, "
                        f"which BENCHMARK.json does not list")
    config = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _load_json(os.path.join(root, "bench", "traffic",
                                      f"{w['traffic']}.json"))
    traffic["name"] = w["traffic"]
    limits = _load_json(os.path.join(root, "bench", "limits",
                                     f"{name}.json"))
    return Cell(name=name, chips=int(w["chips"]), config=config,
                model=load_model(config, root), traffic=traffic,
                limits=limits,
                end_to_end=[m for m in bench["end_to_end"]
                            if _reports(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _reports(m, name)])


def metric_reader(name: str, root: str = CHECKOUT) -> Callable:
    """``read(run) -> float | None`` from ``bench/metrics/<name>.py``."""
    path = os.path.join(root, "bench", "metrics", f"{name}.py")
    if not os.path.exists(path):
        raise SpecError(f"no reader {path} for metric {name!r}")
    return _load_module(path, "metric", name).read


def peaks_for(kind: str, root: str = CHECKOUT) -> Dict:
    """The chip's published peaks, keyed by ``device_kind``.  An unknown
    kind is an error, never a default."""
    table = _load_json(os.path.join(root, "bench", "peaks.json"))
    if kind not in table["chips"]:
        raise SpecError(f"no peaks for device kind {kind!r} in "
                        f"bench/peaks.json (have {sorted(table['chips'])})")
    return table["chips"][kind]
