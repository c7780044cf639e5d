"""The benchmark's own count of a round's operations and bytes.

Each slice's kind counts its operations and bytes from the unpadded shapes
(``chipbench.kinds``), so the count reads the same work whatever implements
the call.
"""

from __future__ import annotations

from typing import Sequence

from chipbench.plan import Plan, Slice


def _work(plan: Plan, rnd: Sequence[Slice]) -> list[tuple[int, int]]:
    return [plan.layers[s.layer].kind.work(plan.layers[s.layer], s.row0,
                                           s.row1) for s in rnd]


def flops(plan: Plan, rnd: Sequence[Slice]) -> int:
    return sum(f for f, _ in _work(plan, rnd))


def bytes_moved(plan: Plan, rnd: Sequence[Slice]) -> int:
    return sum(b for _, b in _work(plan, rnd))


def least_seconds(plan: Plan, rnd: Sequence[Slice], peak_flops: float,
                  peak_bytes: float) -> float:
    """The roofline: the larger of compute time and memory time at peak."""
    return max(flops(plan, rnd) / peak_flops,
               bytes_moved(plan, rnd) / peak_bytes)
