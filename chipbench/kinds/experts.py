"""Routed experts: the experts a chip holds of one sparse MLP layer, each a
SiLU-gated MLP on the token rows the router sent it.

Row ``{"kind": "experts", "name": ..., "hidden": H, "width": F, "rows":
[r_0, r_1, ...]}``, the program's ``ExpertLayer``: expert ``e`` holds a bf16
gate|up weight ``(H, 2F)`` (gate then up) and a down weight ``(F, H)`` and
takes ``r_e`` rows.  The layer streams its experts' rows one expert after
another; a slice of rows ``[row0, row1)`` passes one piece ``(rows, gate|up,
down)`` for each expert with rows in it to ``repro.kernels.routed_experts``,
which runs them in one fused call per projection.  The rows come already
dispatched: routing, dispatch and combine are not timed (as im2col is not
for a convolution).

Each expert's rows are drawn whole, among the operands the harness keeps
whole, rather than as activations that set-up's one jitted draw cuts per
slice: a slice that covers an expert passes its rows as they were drawn,
where a cut would be a second copy of every routed row (300 MB a layer at
Mellum2's widths) on the device.  A slice that covers part of an expert's
rows passes them sliced in set-up.

The reference is NumPy on the host, expert by expert: ``x @ gate|up`` in
float32, ``silu(gate) * up`` rounded to bf16 where the program feeds it to
its bf16 down projection, then ``h @ down`` in float32.  The program and
the reference accumulate their float32 sums in different orders, so an
activation lying within that difference of a bf16 rounding boundary rounds
the other way, one bf16 step; that row's outputs then differ by the step
times the down weights.  With random N(0, 1) operands the activations are
products of two sums of 2,304 terms, heavy-tailed, and the largest such
step sets ``worst_rel_err.experts``, an order of magnitude above a plain
GEMM's.  The limit lies between the largest reading of sound runs and the
smallest reading of the control, the same layer with int8 operands in both
projections (``int8_experts``); the readings it was set from are in
``PERF.md``.

The work is counted from the unpadded shapes, per expert with rows in the
slice: ``2 r (H 2F + F H)`` operations, and the bytes of its bf16 rows,
gate|up and down weights and bf16 activation, and its two float32 outputs,
each moved once.
"""

from __future__ import annotations

import jax
import ml_dtypes
import numpy as np

from chipbench.kinds import gemm

ENTRY = "routed_experts"
CHECK = "worst_rel_err.experts"
REL_ERR_LIMIT = 5e-3


def program():
    from repro.kernels import routed_experts
    return routed_experts


def control():
    return int8_experts


def parse(row):
    rows = tuple(row["rows"])
    return row["name"], sum(rows), (rows, row["hidden"], row["width"])


def matches(row, program_layer) -> bool:
    rows = tuple(row["rows"])
    return (getattr(program_layer, "rows", None) == rows
            and program_layer.name == row["name"]
            and program_layer.hidden == row["hidden"]
            and program_layer.width == row["width"]
            and program_layer.held == tuple(range(len(rows))))


def _spans(layer, row0: int, row1: int) -> list[tuple[int, int, int]]:
    """``(expert, first, end)`` of each expert's rows within layer rows
    ``[row0, row1)``, counted from the expert's own first row."""
    out, start = [], 0
    for e, r in enumerate(layer.spec[0]):
        lo, hi = max(start, row0), min(start + r, row1)
        if hi > lo:
            out.append((e, lo - start, hi - start))
        start += r
    return out


def operands(layer):
    rows, h, f = layer.spec
    n = len(rows)
    return [], [(r, h) for r in rows] + [(h, 2 * f)] * n + [(f, h)] * n


def cut(layer, row0: int, row1: int, xs):
    return []


def pieces(layer, row0: int, row1: int, cut, ws):
    rows = layer.spec[0]
    n = len(rows)
    return [(ws[e] if (lo, hi) == (0, rows[e]) else ws[e][lo:hi],
             ws[n + e], ws[2 * n + e])
            for e, lo, hi in _spans(layer, row0, row1)]


def out_shape(layer):
    return layer.rows, layer.spec[1]


def _silu(g: np.ndarray) -> np.ndarray:
    return g * (0.5 * (1.0 + np.tanh(0.5 * g)))


def reference(layer, xs, ws) -> np.ndarray:
    n = len(layer.spec[0])
    out = []
    for e in range(n):
        x = np.asarray(ws[e], np.float32)
        g, u = np.split(x @ np.asarray(ws[n + e], np.float32), 2, axis=1)
        h = (_silu(g) * u).astype(ml_dtypes.bfloat16).astype(np.float32)
        out.append(h @ np.asarray(ws[2 * n + e], np.float32))
    return np.concatenate(out, axis=0)


def work(layer, row0: int, row1: int) -> tuple[int, int]:
    _, h, f = layer.spec
    flops = nbytes = 0
    for _, lo, hi in _spans(layer, row0, row1):
        r = hi - lo
        flops += 2 * r * (h * 2 * f + f * h)
        nbytes += (r * h * gemm.X_BYTES + 3 * h * f * gemm.W_BYTES
                   + r * 2 * f * gemm.OUT_BYTES + r * f * gemm.X_BYTES
                   + r * h * gemm.OUT_BYTES)
    return flops, nbytes


# ---------------------------------------------------------------------------
# the control: the layer with int8 operands in both projections
# ---------------------------------------------------------------------------

@jax.jit
def _int8_expert(x, w_gate_up, w_down):
    g, u = jax.numpy.split(gemm._int8_dot(x, w_gate_up), 2, axis=1)
    return gemm._int8_dot(jax.nn.silu(g) * u, w_down)


def int8_experts(xs, w_gate_ups, w_downs, **_):
    """The control, with ``routed_experts``' call shape."""
    return [_int8_expert(x, gu, d) for x, gu, d in zip(xs, w_gate_ups,
                                                       w_downs)]
