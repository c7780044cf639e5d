"""A layer kind for the tests alone: routed experts as one layer.

Row ``{"kind": "kind_experts", "name": ..., "experts": [r0, r1, ...],
"k": K, "n": N}``: expert ``e`` multiplies its ``r_e`` routed rows by its own
bf16 ``(K, N)`` weight.  The layer streams the experts' rows one expert
after another, so a slice of rows ``[row0, row1)`` passes one piece, with
its own ragged row count, for each expert it touches, all into
``fused_tenant_gemm`` beside the round's other tenants.  Its products are
GEMMs on bf16 operands, so it shares the GEMM kind's check and limit.
"""

from __future__ import annotations

import numpy as np

from chipbench.kinds import gemm

ENTRY = gemm.ENTRY
CHECK = gemm.CHECK
REL_ERR_LIMIT = gemm.REL_ERR_LIMIT
program = gemm.program
control = gemm.control


def parse(row):
    return row["name"], sum(row["experts"]), (tuple(row["experts"]),
                                              row["k"], row["n"])


def matches(row, program_layer) -> bool:
    return False


def _spans(layer, row0: int, row1: int) -> list[tuple[int, int, int]]:
    """``(expert, first row, end row)`` of each expert within the rows."""
    out, start = [], 0
    for e, rows in enumerate(layer.spec[0]):
        lo, hi = max(start, row0), min(start + rows, row1)
        if hi > lo:
            out.append((e, lo, hi))
        start += rows
    return out


def operands(layer):
    experts, k, n = layer.spec
    return [(layer.rows, k)], [(k, n)] * len(experts)


def cut(layer, row0: int, row1: int, xs):
    return [xs[0][lo:hi] for _, lo, hi in _spans(layer, row0, row1)]


def pieces(layer, row0: int, row1: int, cut, ws):
    return [(x, ws[e]) for x, (e, _, _) in
            zip(cut, _spans(layer, row0, row1))]


def out_shape(layer):
    return layer.rows, layer.spec[2]


def reference(layer, xs, ws) -> np.ndarray:
    x = np.asarray(xs[0], np.float32)
    return np.concatenate([x[lo:hi] @ np.asarray(ws[e], np.float32)
                           for e, lo, hi in _spans(layer, 0, layer.rows)])


def work(layer, row0: int, row1: int) -> tuple[int, int]:
    _, k, n = layer.spec
    spans = _spans(layer, row0, row1)
    rows = sum(hi - lo for _, lo, hi in spans)
    return (2 * rows * k * n,
            rows * k * gemm.X_BYTES + len(spans) * k * n * gemm.W_BYTES
            + rows * n * gemm.OUT_BYTES)
