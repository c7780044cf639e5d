"""Analytic weight-stationary systolic-array timing model (Scale-Sim analogue).

The paper evaluates with Scale-Sim [16] in analytical mode; this module is the
equivalent closed-form model, extended to be **partition-aware** (col offsets,
per-partition folds) via :func:`repro.core.dataflow.ws_cost`.

Array config follows the paper §4.2: a TPU-v3-like 128×128 PE array.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import numpy as np

from repro.core.dataflow import (
    GEMM,
    BatchCost,
    DataflowCost,
    ws_cost,
    ws_cost_batch,
)
from repro.core.dnng import LayerShape
from repro.core.partition import ArrayShape, Partition
from repro.core.scheduler import TimeFn


@dataclasses.dataclass(frozen=True)
class SystolicConfig:
    """Hardware parameters of the simulated accelerator (paper §4.2)."""

    rows: int = 128
    cols: int = 128
    clock_hz: float = 940e6          # TPU v3 core clock
    dram_bw_bytes: float = 64e9      # off-chip staging bandwidth (shared bus)

    @property
    def array(self) -> ArrayShape:
        return ArrayShape(rows=self.rows, cols=self.cols)

    @property
    def macs_per_cycle(self) -> int:
        return self.rows * self.cols


@functools.lru_cache(maxsize=1 << 16)
def layer_cost(layer: LayerShape, part: Partition) -> DataflowCost:
    """Cycle/access breakdown of one layer on one partition: the sum over
    the GEMMs it lowers to (``layer.gemms``; one for a plain layer), run one
    after another on the partition.

    Memoized on top of the (also memoized) :func:`ws_cost`: the extra LRU
    level skips even the layer→GEMM lowering for the exact repeats the
    scheduler's rebalance loop generates.
    """
    costs = [ws_cost(GEMM(*g), part) for g in layer.gemms]
    return DataflowCost(*(sum(getattr(c, f.name) for c in costs)
                          for f in dataclasses.fields(DataflowCost)))


def layer_cycles(layer: LayerShape, part: Partition) -> int:
    return layer_cost(layer, part).cycles


def layer_cost_batch(layers: Sequence[LayerShape],
                     parts: Sequence[Partition],
                     bw_shares: "Sequence[float] | None" = None
                     ) -> BatchCost:
    """Vectorized :func:`layer_cost` over paired (layer, partition)
    candidates — one :func:`repro.core.dataflow.ws_cost_batch` NumPy pass
    over every layer's GEMMs, each priced on its layer's partition, then
    each layer's rows summed.  Bit-identical to the scalar path.

    ``bw_shares`` (optional per-pair memory-bandwidth shares) fills the
    table's ``dram_stall_elems`` column — zeros at share 1.0, and the
    int64 columns are untouched by it (see
    :func:`repro.core.dataflow.ws_cost_batch`)."""
    counts = [len(layer.gemms) for layer in layers]
    owner = np.repeat(np.arange(len(layers)), counts)
    flat = ws_cost_batch(
        [GEMM(*g) for layer in layers for g in layer.gemms],
        [parts[i] for i in owner],
        bw_shares=None if bw_shares is None
        else [bw_shares[i] for i in owner])
    starts = np.cumsum(counts) - counts
    return BatchCost(**{
        f.name: None if getattr(flat, f.name) is None
        else np.add.reduceat(getattr(flat, f.name), starts)
        for f in dataclasses.fields(BatchCost)})


class _BatchTimeOracle:
    """Memoized vectorized seconds oracle — ``time_fn.batch``.

    ``pairs`` → seconds for each (layer, partition), serving exact repeats
    from a dict memo (the batch analogue of the ``layer_cost`` LRU: the
    rebalance loop re-prices the same pairings round after round).  Misses
    are evaluated in ONE :func:`layer_cost_batch` NumPy pass when the
    batch is large enough to amortize array packing; small miss sets go
    through the (globally warm) ``layer_cost`` LRU instead — the NumPy
    fixed cost loses below a few dozen pairs.  Seconds always come from
    Python-int cycles divided by ``clock_hz`` — the very float op of the
    scalar path, so values are bit-identical either way.

    The memo is shared per ``clock_hz`` across all oracle instances (one
    serving fleet spawns one backend per node/cell), mirroring the global
    scalar LRUs.
    """

    __slots__ = ("clock_hz", "_memo", "hits", "misses")

    #: below this many missing pairs the scalar LRU path is used
    VECTOR_THRESHOLD = 32
    #: memo reset bound — mirrors the scalar LRUs' maxsize so the shared
    #: dict cannot grow without bound over long geometry sweeps (a full
    #: reset is the cheap bound: entries are pure and re-derivable)
    MAX_ENTRIES = 1 << 16

    _shared_memos: dict = {}

    @classmethod
    def clear_all(cls) -> None:
        """Drop every shared memo (tests, memory) — the batch analogue of
        :func:`repro.core.dataflow.ws_cost_cache_clear`."""
        cls._shared_memos.clear()

    def __init__(self, clock_hz: float):
        self.clock_hz = clock_hz
        self._memo = self._shared_memos.setdefault(clock_hz, {})
        self.hits = 0
        self.misses = 0

    def __call__(self, pairs: Sequence[tuple[LayerShape, Partition]]
                 ) -> list[float]:
        memo = self._memo
        missing = [pair for pair in pairs if pair not in memo]
        if missing:
            missing = list(dict.fromkeys(missing))  # dedupe, order kept
            self.misses += len(missing)
            if len(memo) + len(missing) > self.MAX_ENTRIES:
                # reset, but keep the entries this very call still serves
                keep = {p: memo[p] for p in pairs if p in memo}
                memo.clear()
                memo.update(keep)
            if len(missing) < self.VECTOR_THRESHOLD:
                clock = self.clock_hz
                for pair in missing:
                    memo[pair] = layer_cost(*pair).cycles / clock
            else:
                table = layer_cost_batch([la for la, _ in missing],
                                         [p for _, p in missing])
                for pair, cyc in zip(missing, table.cycles.tolist()):
                    memo[pair] = cyc / self.clock_hz
        self.hits += len(pairs) - len(missing)
        return [memo[pair] for pair in pairs]

    def stats(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "currsize": len(self._memo)}


def layer_time_fn(cfg: SystolicConfig) -> TimeFn:
    """Scheduler oracle: seconds for ``layer`` on ``part`` at ``cfg.clock_hz``.

    The returned callable carries a ``batch`` attribute (a
    :class:`_BatchTimeOracle`): consumers holding many candidates price
    them in one vectorized pass via ``time_fn.batch(pairs)`` —
    :meth:`repro.api.policy.AssignContext.time_batch` discovers it by
    ``getattr`` and falls back to the scalar loop for oracles without one.
    """

    clock = cfg.clock_hz

    def fn(layer: LayerShape, part: Partition) -> float:
        return layer_cost(layer, part).cycles / clock

    fn.batch = _BatchTimeOracle(clock)
    return fn
