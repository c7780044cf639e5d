"""What decides ``correct``: each layer's assembled output against its
kind's plain float32 reference on the same bf16 operands.

The reference is NumPy on the host (``chipbench.kinds``) and imports nothing
of the program.  A layer's output is assembled by stacking the outputs of
its row slices in the order the pass computed them, so a row computed twice
or never shows as a wrong row count before any value is compared.

The numbers compared, each with its limit:

* ``rows_off``: over the layers compared, the sum of ``|assembled rows -
  rows|`` plus the rows of any slice with a wrong shape; an exact
  comparison, limit 0, for every kind.
* per kind's ``CHECK`` name (``worst_rel_err`` for a GEMM): the largest
  ``max|out - ref| / max|ref|`` over the kind's layers compared, against
  the kind's ``REL_ERR_LIMIT``; the kind's docstring gives the reason for
  the limit.
"""

from __future__ import annotations

import math

import jax
import numpy as np

from chipbench.plan import Plan

ROWS_OFF_LIMIT = 0


def limits(plan: Plan) -> dict:
    """Each check name of the plan's kinds and its limit, in the order the
    kinds first appear; an error where two kinds give one name two
    limits."""
    out: dict[str, float] = {}
    for layer in plan.layers:
        name, limit = layer.kind.CHECK, layer.kind.REL_ERR_LIMIT
        if out.setdefault(name, limit) != limit:
            raise ValueError(f"check {name!r} has two limits, {out[name]} "
                             f"and {limit}")
    return out


def assemble(parts: list, ndim: int) -> tuple[np.ndarray | None, int]:
    """Stack a layer's slice outputs; ``(output, rows of bad slices)``.

    ``parts`` holds per slice its rows and its pieces' outputs.  A missing
    slice output, or one whose pieces are not ``ndim``-D float32 arrays
    stacking to the slice's rows, counts its rows as off; the output is then
    None.
    """
    good, bad = [], 0
    for rows, pieces in parts:
        arrays = [] if pieces is None else [np.asarray(p) for p in pieces]
        if not arrays or any(a.ndim != ndim or a.dtype != np.float32
                             or a.shape[1:] != arrays[0].shape[1:]
                             for a in arrays):
            bad += rows
            continue
        a = np.concatenate(arrays, axis=0)
        if a.shape[0] != rows:
            bad += rows
        else:
            good.append(a)
    if bad or not good:
        return None, bad
    return np.concatenate(good, axis=0), 0


def to_host(outputs: dict, xs, ws) -> tuple[dict, dict, dict]:
    """The window's slice outputs (layer -> [(rows, pieces' outputs)]) and
    those layers' operands, copied to the host."""
    outs = {li: [(rows, None if o is None else jax.device_get(o))
                 for rows, o in parts] for li, parts in outputs.items()}
    return (outs, {li: jax.device_get(xs[li]) for li in outs},
            {li: jax.device_get(ws[li]) for li in outs})


def compare(plan: Plan, outputs: dict, xs_host: dict, ws_host: dict) -> dict:
    """Compare every layer in ``outputs`` (layer -> [(rows, pieces'
    outputs), ...]).

    ``xs_host``/``ws_host`` hold those layers' operands on the host.
    Returns the readings: layers compared and failed, ``rows_off``, and per
    check name its largest reading and its limit.
    """
    errors = {name: [0.0, limit] for name, limit in limits(plan).items()}
    rows_off, failed = 0, 0
    for li, parts in outputs.items():
        layer = plan.layers[li]
        shape = tuple(layer.kind.out_shape(layer))
        out, bad = assemble(parts, len(shape))
        off = bad
        if out is not None:
            off += abs(out.shape[0] - shape[0])
            if out.shape[1:] != shape[1:]:
                off += shape[0]
        err = math.inf
        if out is not None and out.shape == shape:
            ref = layer.kind.reference(layer, xs_host[li], ws_host[li])
            scale = float(np.max(np.abs(ref)))
            err = float(np.max(np.abs(out - ref))) / scale if scale else \
                float(np.max(np.abs(out)))
            if not np.isfinite(err):
                err = math.inf
        rows_off += off
        worst = errors[layer.kind.CHECK]
        worst[0] = max(worst[0], err)
        if off > ROWS_OFF_LIMIT or not err <= worst[1]:
            failed += 1
    return {"layers": len(outputs), "failed": failed, "rows_off": rows_off,
            "errors": errors}


def checks(readings: dict) -> dict:
    """The numbers compared, each beside its limit, for the result line."""
    return {**{name: {"value": value, "limit": limit}
               for name, (value, limit) in readings["errors"].items()},
            "rows_off": {"value": readings["rows_off"],
                         "limit": ROWS_OFF_LIMIT},
            "layers_compared": {"value": readings["layers"],
                                "limit": "at least 1"}}


def passed(readings: dict) -> bool:
    """Every layer compared is within both limits, and there is one."""
    return readings["layers"] > 0 and readings["failed"] == 0
