"""A cell's unit of work: the schedule's layers, cut into exactly-once rounds.

The program's front door, ``repro.api.Session``, schedules the
configuration's tenants on the simulated array.  Its trace gives each layer
a compute interval ``[a, b)`` on a slice of the array's columns.  The round
boundaries are every interval's ends, sorted; in round ``[s, s')`` each
layer live at ``s`` streams its rows ``[row(s), row(s'))`` with
``row(s) = floor(T * (s - a) / (b - a))``, so a layer's weights stay while
its activations stream through the rounds it spans (the paper's
weight-stationary dataflow, in time).  The rows of consecutive rounds meet,
so over a pass every layer's output rows are computed once and only once.
A round's tenants are ordered by the first array column they own.  What a
layer's rows are, and what a slice of them computes, its kind says
(``chipbench.kinds``).
"""

from __future__ import annotations

import dataclasses
import math
import time
from types import ModuleType
from typing import Any, Sequence

from chipbench import kinds


@dataclasses.dataclass(frozen=True)
class Layer:
    """One layer of one tenant: ``rows`` streamed rows, and the rest as its
    kind (a module of ``chipbench.kinds``) reads it from ``spec``."""

    tenant: str
    name: str
    kind: ModuleType
    rows: int
    spec: Any


@dataclasses.dataclass(frozen=True)
class Slice:
    """Rows ``[row0, row1)`` of layer ``layer`` (an index into the plan)."""

    layer: int
    row0: int
    row1: int

    @property
    def rows(self) -> int:
        return self.row1 - self.row0


@dataclasses.dataclass(frozen=True)
class Plan:
    layers: tuple[Layer, ...]
    rounds: tuple[tuple[Slice, ...], ...]


def layer(tenant: str, row) -> Layer:
    """A configuration row of ``tenant`` as a layer of the kind it names."""
    kind = kinds.of_row(row)
    name, rows, spec = kind.parse(row)
    return Layer(tenant, name, kind, rows, spec)


def config_layers(config: dict) -> tuple[Layer, ...]:
    """Every layer the configuration file lists, tenant by tenant."""
    return tuple(layer(t["model"], row)
                 for t in config["tenants"] for row in t["layers"])


def row_at(s: float, a: float, b: float, t: int) -> int:
    """Rows of a layer computing over ``[a, b)`` streamed by time ``s``."""
    if s <= a:
        return 0
    if s >= b:
        return t
    return min(t, math.floor(t * (s - a) / (b - a)))


def rounds_from_trace(trace, index: dict[tuple[str, int], int],
                      layers: Sequence[Layer]
                      ) -> tuple[tuple[Slice, ...], ...]:
    """Cut a schedule trace into exactly-once row-sliced rounds.

    ``trace`` holds events with ``tenant``, ``layer_index``,
    ``compute_start``, ``compute_end`` and ``partition.col_start``;
    ``index`` maps ``(tenant, layer_index)`` to the plan's layer number.
    """
    seen = set()
    for e in trace:
        key = (e.tenant, e.layer_index)
        if key in seen:
            raise ValueError(f"layer {key} runs in more than one segment; "
                             "the replay needs one compute interval a layer")
        seen.add(key)
    if seen != set(index):
        raise ValueError(f"the schedule ran {len(seen)} layers, the "
                         f"configuration has {len(index)}")
    bounds = sorted({e.compute_start for e in trace}
                    | {e.compute_end for e in trace})
    rounds = []
    for s, s2 in zip(bounds, bounds[1:]):
        live = sorted((e for e in trace
                       if e.compute_start <= s < e.compute_end),
                      key=lambda e: e.partition.col_start)
        rnd = []
        for e in live:
            li = index[e.tenant, e.layer_index]
            t = layers[li].rows
            a, b = e.compute_start, e.compute_end
            r0, r1 = row_at(s, a, b, t), row_at(s2, a, b, t)
            if r1 > r0:
                rnd.append(Slice(li, r0, r1))
        if rnd:
            rounds.append(tuple(rnd))
    return tuple(rounds)


def _dnngs(config: dict, stagger_s: float):
    """The configuration's tenants as the program's DNNGs, staggered.

    Raises when the program's layers differ from the configuration file's
    rows, as each row's kind reads them, so a change to
    ``repro.sim.workloads`` cannot change the work.
    """
    from repro.sim.workloads import MODELS

    dnngs = []
    for i, t in enumerate(config["tenants"]):
        g = MODELS[t["model"]]()
        rows = t["layers"]
        if len(g.layers) != len(rows) or not all(
                kinds.of_row(row).matches(row, la)
                for row, la in zip(rows, g.layers)):
            raise ValueError(f"{t['model']}: the program's layers "
                             f"differ from configuration {config['name']}")
        dnngs.append(dataclasses.replace(g, arrival_time=i * stagger_s))
    return dnngs


def schedule(config: dict, traffic: dict):
    """The program's schedule trace for this cell; ``(trace, seconds)``.

    The seconds are the host time of the ``Session`` call alone.
    """
    from repro.api import Session

    dnngs = _dnngs(config, traffic["stagger_s"])
    kind = traffic["schedule"]
    t0 = time.perf_counter()
    if kind == "coresident":
        res = Session(policy=traffic["policy"], backend="sim").run(
            dnngs, compare_baseline=False)
        trace = res.partitioned.trace
    elif kind == "single_tenancy":
        trace = Session(backend="sim").run_baseline(dnngs).schedule.trace
    else:
        raise ValueError(f"traffic {traffic['name']}: unknown schedule "
                         f"{kind!r} (coresident, single_tenancy)")
    return trace, time.perf_counter() - t0


def build(config: dict, traffic: dict) -> tuple[Plan, float]:
    """The cell's plan and the host seconds its scheduling took."""
    layers = config_layers(config)
    index, i = {}, 0
    for t in config["tenants"]:
        for j in range(len(t["layers"])):
            index[t["model"], j] = i
            i += 1
    trace, seconds = schedule(config, traffic)
    return Plan(layers, rounds_from_trace(trace, index, layers)), seconds
