"""Resolve a cell of ``BENCHMARK.json`` to the files that define it.

Everything is found by name: configuration ``file`` as listed, traffic
``traffic/<traffic>.json``, one reader ``metrics/<metric>.py`` per
metric, and a learning payload's kind by ``payloads.of`` (its builder
``payloads/<kind>.py`` and reference ``reference/<kind>.py``); a metric
split by the cells it moves (``<metric>.<split>``) without a reader of
its own takes ``metrics/<metric>.py``. Adding a cell, a configuration, a
payload kind, a traffic mix or a metric is adding files and entries;
nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

from chipbench import payloads

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    read: object  # callable(Record) -> float | None


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple  # Metric
    per_layer: tuple  # Metric
    kind: object = None  # payloads.Kind of the config's payload; None: walks alone


def protocol(config: dict, name: str) -> dict:
    """The full protocol settings of one named protocol of a config: the
    shared ``protocol`` entry updated with ``protocols[name]``."""
    return {**config["protocol"], **config["protocols"][name]}


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def reader(name: str):
    """The ``read`` function of ``metrics/<name>.py``, or of the unsplit
    metric's reader."""
    path = HERE / "metrics" / f"{name}.py"
    if not path.exists():
        path = HERE / "metrics" / f"{name.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}", path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(name: str, bench: dict | None = None, root: Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json") if bench is None else bench
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r}; cells are {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(HERE / "traffic" / f"{w['traffic']}.json")

    def metrics(kind):
        return tuple(
            Metric(m["name"], m["unit"], reader(m["name"]))
            for m in bench[kind]
            if _applies(m, name)
        )

    return Cell(
        name=name,
        chips=w["chips"],
        config=config,
        traffic=traffic,
        end_to_end=metrics("end_to_end"),
        per_layer=metrics("per_layer"),
        kind=payloads.of(config),
    )
