"""Finding a cell's pieces by name.

``BENCHMARK.json`` at the checkout's root lists the cells.  A cell names a
configuration and a traffic mix; each is a file of its own, found by name:

- ``configs/<config>.json``: the model's sizes, its source and its plan;
- ``traffic/<traffic>.json``: the parameters the one generator
  (``cellbench.client``) reads;
- ``metrics/<metric>.py``: the reader of one metric, a module with
  ``read(run) -> float | dict | None``;
- ``models/<model>.py``: a configuration's model family (its ``model``
  key): the reference forward, weights, work counts and the program's
  engine for it.

A later cell or metric is a new file beside these, and no existing file
changes.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import pathlib
from types import ModuleType
from typing import Dict

HERE = pathlib.Path(__file__).resolve().parents[1]      # benchmarks/chip
ROOT = HERE.parents[1]                                  # the checkout


def load_benchmark(root: pathlib.Path = ROOT) -> Dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def workload(bench: Dict, name: str) -> Dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def _json(kind: str, name: str) -> Dict:
    path = HERE / kind / f"{name}.json"
    if not path.is_file():
        raise KeyError(f"no {kind[:-1]} file {path.relative_to(HERE)}")
    return json.loads(path.read_text())


def config(name: str) -> Dict:
    return _json("configs", name)


def traffic(name: str) -> Dict:
    return _json("traffic", name)


def _module(kind: str, name: str) -> ModuleType:
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no {kind[:-1]} file {kind}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"cellbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str) -> ModuleType:
    return _module("metrics", name)


@functools.lru_cache(maxsize=None)
def model_family(name: str) -> ModuleType:
    return _module("models", name)


def names(kind: str) -> list:
    """Every config, traffic mix, metric reader or model family present,
    by name."""
    suffix = ".py" if kind in ("metrics", "models") else ".json"
    return sorted(p.name[:-len(suffix)] for p in (HERE / kind).iterdir()
                  if p.name.endswith(suffix) and not p.name.startswith("_"))


def cell_metrics(bench: Dict, workload_name: str, trace: bool) -> list:
    """The metrics a run of this cell reports: its end-to-end metrics with
    ``trace`` off, its per-layer metrics with it on."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload_name in m["workloads"]]
