"""A plain GEMM layer: one bf16 activation ``(t, k)`` times one bf16 weight
``(k, n)``, its ``t`` rows streamed through the rounds.

Row format ``[name, t, k, n]``, the program's ``[name, gemm_m, gemm_k,
gemm_n]``.  A slice of rows ``[row0, row1)`` passes one piece, its
activation rows and the whole weight, to ``fused_tenant_gemm``.

The reference is NumPy on the host, ``x.astype(float32) @
w.astype(float32)``.  bf16 products are exact in f32, so a sound run differs
from the reference only in the order of f32 accumulation: the limit on
``worst_rel_err``, 2e-4, lies between the largest reading of sound runs and
the smallest reading of the control, the reference computed with int8
operands (``int8_gemm``); the readings it was set from are in ``PERF.md``.

The work is counted from the unpadded shapes, so it reads the same whatever
implements the call: ``2 * rows * k * n`` operations, and the bytes of a
bf16 ``(rows, k)`` activation slice, a bf16 ``(k, n)`` weight and an f32
``(rows, n)`` output, each moved once.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

ENTRY = "fused_tenant_gemm"
CHECK = "worst_rel_err"
REL_ERR_LIMIT = 2e-4

X_BYTES = 2    # bf16 activations
W_BYTES = 2    # bf16 weights
OUT_BYTES = 4  # f32 outputs


def program():
    from repro.kernels import fused_tenant_gemm
    return fused_tenant_gemm


def control():
    return int8_gemm


def parse(row) -> tuple[str, int, tuple[int, int]]:
    name, t, k, n = row
    return name, t, (k, n)


def matches(row, program_layer) -> bool:
    return row == [program_layer.name, program_layer.gemm_m,
                   program_layer.gemm_k, program_layer.gemm_n]


def shape(layer, row0: int, row1: int) -> tuple[int, int, int]:
    """Rows ``[row0, row1)`` as the GEMM ``(rows, k, n)``."""
    k, n = layer.spec
    return row1 - row0, k, n


def operands(layer):
    k, n = layer.spec
    return [(layer.rows, k)], [(k, n)]


def cut(layer, row0: int, row1: int, xs):
    return [xs[0][row0:row1]]


def pieces(layer, row0: int, row1: int, cut, ws):
    return [(cut[0], ws[0])]


def out_shape(layer) -> tuple[int, int]:
    return layer.rows, layer.spec[1]


def reference(layer, xs, ws) -> np.ndarray:
    return np.asarray(xs[0], np.float32) @ np.asarray(ws[0], np.float32)


def work(layer, row0: int, row1: int) -> tuple[int, int]:
    t, k, n = shape(layer, row0, row1)
    return (2 * t * k * n,
            t * k * X_BYTES + k * n * W_BYTES + t * n * OUT_BYTES)


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
