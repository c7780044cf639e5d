"""Find a cell's parts by name: ``BENCHMARK.json`` and the files beside it.

A cell names a configuration (``configs/<name>.json``) and a traffic mix
(``traffic/<name>.json``); every metric is a reader ``metrics/<name>.py``
with a ``read(ctx)`` function; a device's peaks are one file under
``peaks/`` that names its ``device_kind``.  A later cell, mix, metric or
device is a new file and a new entry, with no edit to what is here.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    end_to_end: bool
    read: Callable
    workloads: tuple[str, ...] | None

    def applies(self, cell: str) -> bool:
        return self.workloads is None or cell in self.workloads


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    metrics: tuple[Metric, ...]


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return _json(root / "BENCHMARK.json")


def _reader(name: str) -> Callable:
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('-', '_').replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def metrics(bench: dict, cell: str) -> tuple[Metric, ...]:
    out = []
    for group, e2e in (("end_to_end", True), ("per_layer", False)):
        for m in bench[group]:
            wl = m.get("workloads")
            metric = Metric(m["name"], m["unit"], m["better"], m["source"],
                            e2e, _reader(m["name"]),
                            tuple(wl) if wl is not None else None)
            if metric.applies(cell):
                out.append(metric)
    return tuple(out)


def cell(name: str, bench: dict | None = None) -> Cell:
    bench = bench or benchmark()
    found = [w for w in bench["workloads"] if w["name"] == name]
    if not found:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json; cells: "
                       f"{[w['name'] for w in bench['workloads']]}")
    w = found[0]
    cfg = [c for c in bench["configs"] if c["name"] == w["config"]][0]
    return Cell(name=name, chips=int(w["chips"]),
                config=_json(ROOT / cfg["file"]),
                traffic=_json(HERE / "traffic" / f"{w['traffic']}.json"),
                metrics=metrics(bench, name))


def peaks(device_kind: str) -> dict:
    """The peaks file whose ``device_kind`` is this one; an error if none."""
    for path in sorted((HERE / "peaks").glob("*.json")):
        p = _json(path)
        if p["device_kind"] == device_kind:
            return p
    raise KeyError(f"no peaks for device kind {device_kind!r} under "
                   f"{HERE / 'peaks'}")
