"""Finds the benchmark's pieces by name.

``BENCHMARK.json`` at the root names each cell's configuration and traffic
mix. A configuration is ``bench/configs/<config>.json`` (its file is named in
``BENCHMARK.json``), a traffic mix ``bench/traffic/<traffic>.json``, a cell's
limits for ``correct`` ``bench/limits/<cell>.json``, and a per-layer metric
the function ``read`` in ``bench/metrics/<metric>.py``, and a tier-model
family (a model's ``family`` in its configuration) ``bench/families/<family>.py``.
Adding a cell, a metric or a model family means adding such files and an
entry; no code changes.
"""
from __future__ import annotations

import functools
import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, benchmark: dict | None = None, root: Path = REPO) -> Cell:
    bench = benchmark if benchmark is not None else load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    return Cell(
        name=name, chips=int(w["chips"]), config=config,
        traffic=load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(BENCH / "limits" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
    )


def _load(kind: str, name: str):
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """The ``read(record)`` function of ``bench/metrics/<name>.py``."""
    return _load("metrics", name).read


@functools.cache
def family(name: str):
    """The module ``bench/families/<name>.py`` of a tier-model family, with
    ``register(name, spec, registry)``, ``reference(spec, num_classes,
    image)``, ``forward_flops(spec, image, num_classes)`` and
    ``param_count(spec, image, num_classes)``. Loaded once:
    the operation count is looked up for every pair of every round."""
    if not (BENCH / "families" / f"{name}.py").is_file():
        known = sorted(p.stem for p in (BENCH / "families").glob("*.py"))
        raise KeyError(f"unknown model family {name!r}; known: {known}")
    return _load("families", name)


def peaks(device_kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"bench/peaks.json has {sorted(table)}")
    return table[device_kind]
