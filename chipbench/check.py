"""What decides ``correct``: each layer's assembled output against a plain
float32 reference on the same bf16 operands.

The reference is NumPy on the host, ``x.astype(float32) @ w.astype(float32)``,
and imports nothing of the program.  A layer's output is assembled by
stacking the outputs of its row slices in the order the pass computed them,
so a row computed twice or never shows as a wrong row count before any
value is compared.

Two numbers are compared, each with its limit:

* ``rows_off``: over the layers compared, the sum of ``|assembled rows - T|``
  plus the rows of any slice with a wrong shape; an exact comparison, limit 0.
* ``worst_rel_err``: the largest ``max|out - ref| / max|ref|`` over the
  layers compared.  bf16 products are exact in f32, so a sound run differs
  from the reference only in the order of f32 accumulation.  The limit lies
  between the largest reading of sound runs and the smallest reading of the
  control, the reference computed with int8 operands (``int8_gemm``); the
  readings it was set from are in ``PERF.md``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.plan import Plan

REL_ERR_LIMIT = 2e-4
ROWS_OFF_LIMIT = 0


def reference(x: np.ndarray, w: np.ndarray) -> np.ndarray:
    return np.asarray(x, np.float32) @ np.asarray(w, np.float32)


def assemble(parts: list) -> tuple[np.ndarray | None, int]:
    """Stack a layer's slice outputs; ``(output, rows of bad slices)``.

    A missing slice output, or one that is not a 2-D float32 array, counts
    its rows as off; the output is then None.
    """
    good, bad = [], 0
    for rows, out in parts:
        a = None if out is None else np.asarray(out)
        if a is None or a.ndim != 2 or a.dtype != np.float32 \
                or a.shape[0] != rows:
            bad += rows
        else:
            good.append(a)
    if bad or not good:
        return None, bad
    return np.concatenate(good, axis=0), 0


def to_host(outputs: dict, xs, ws) -> tuple[dict, dict, dict]:
    """The window's slice outputs (layer -> [(rows, output)]) and those
    layers' operands, copied to the host."""
    outs = {li: [(rows, None if o is None else jax.device_get(o))
                 for rows, o in parts] for li, parts in outputs.items()}
    return (outs, {li: jax.device_get(xs[li]) for li in outs},
            {li: jax.device_get(ws[li]) for li in outs})


def compare(plan: Plan, outputs: dict, xs_host: dict, ws_host: dict) -> dict:
    """Compare every layer in ``outputs`` (layer -> [(rows, output), ...]).

    ``xs_host``/``ws_host`` hold those layers' operands on the host.
    Returns the readings: layers compared and failed, ``rows_off`` and
    ``worst_rel_err``.
    """
    rows_off, worst, failed = 0, 0.0, 0
    for li, parts in outputs.items():
        layer = plan.layers[li]
        out, bad = assemble(parts)
        off = bad
        if out is not None:
            off += abs(out.shape[0] - layer.t)
            if out.shape[1] != layer.n:
                off += layer.t
        err = math.inf
        if out is not None and out.shape == (layer.t, layer.n):
            ref = reference(xs_host[li], ws_host[li])
            scale = float(np.max(np.abs(ref)))
            err = float(np.max(np.abs(out - ref))) / scale if scale else \
                float(np.max(np.abs(out)))
            if not np.isfinite(err):
                err = math.inf
        rows_off += off
        worst = max(worst, err)
        if off > ROWS_OFF_LIMIT or not err <= REL_ERR_LIMIT:
            failed += 1
    return {"layers": len(outputs), "failed": failed, "rows_off": rows_off,
            "worst_rel_err": worst}


def checks(readings: dict) -> dict:
    """The numbers compared, each beside its limit, for the result line."""
    return {"worst_rel_err": {"value": readings["worst_rel_err"],
                              "limit": REL_ERR_LIMIT},
            "rows_off": {"value": readings["rows_off"],
                         "limit": ROWS_OFF_LIMIT},
            "layers_compared": {"value": readings["layers"],
                                "limit": "at least 1"}}


def passed(readings: dict) -> bool:
    """Every layer compared is within both limits, and there is one."""
    return readings["layers"] > 0 and readings["failed"] == 0


# ---------------------------------------------------------------------------
# the control: the reference with int8 operands, in the program's place
# ---------------------------------------------------------------------------

def _quantize(a: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-tensor int8: ``a ~ q * scale``."""
    a = a.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 127.0
    return jnp.clip(jnp.round(a / scale), -127, 127).astype(jnp.int8), scale


@jax.jit
def _int8_dot(x: jax.Array, w: jax.Array) -> jax.Array:
    xq, sx = _quantize(x)
    wq, sw = _quantize(w)
    acc = jax.lax.dot(xq, wq, preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * (sx * sw)


def int8_gemm(xs, ws, **_):
    """The control, with ``fused_tenant_gemm``'s call shape."""
    return [_int8_dot(x, w) for x, w in zip(xs, ws)]
