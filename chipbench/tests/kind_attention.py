"""A layer kind for the tests alone, with a program entry of its own.

Row ``{"kind": "kind_attention", "name": ..., "t": T, "s": S, "d": D}``: one
head's softmax attention of ``T`` bf16 query rows over ``S`` bf16 keys and
values of width ``D``, the queries streamed.  Its entry, a jitted
``jax.numpy`` function, runs apart from ``fused_tenant_gemm`` in the same
rounds.  The entry computes in float32 at full precision from bf16
operands, so it differs from the float32 reference only by the order of
accumulation and by ``exp``: the limit 1e-4 on ``worst_rel_err.attention``
lies well above that and well below the control, which computes in bf16.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

ENTRY = "attention_test"
CHECK = "worst_rel_err.attention"
REL_ERR_LIMIT = 1e-4


def _attend(q, k, v, dtype):
    hi = jax.lax.Precision.HIGHEST
    s = jnp.dot(q.astype(dtype), k.astype(dtype).T, precision=hi,
                preferred_element_type=dtype) / np.sqrt(q.shape[1])
    p = jax.nn.softmax(s, axis=-1)
    return jnp.dot(p, v.astype(dtype), precision=hi,
                   preferred_element_type=dtype).astype(jnp.float32)


@jax.jit
def attention(qs, ks, vs):
    return [_attend(q, k, v, jnp.float32) for q, k, v in zip(qs, ks, vs)]


@jax.jit
def attention_bf16(qs, ks, vs):
    return [_attend(q, k, v, jnp.bfloat16) for q, k, v in zip(qs, ks, vs)]


def program():
    return attention


def control():
    return attention_bf16


def parse(row):
    return row["name"], row["t"], (row["s"], row["d"])


def matches(row, program_layer) -> bool:
    return False


def operands(layer):
    s, d = layer.spec
    return [(layer.rows, d)], [(s, d), (s, d)]


def cut(layer, row0: int, row1: int, xs):
    return [xs[0][row0:row1]]


def pieces(layer, row0: int, row1: int, cut, ws):
    return [(cut[0], ws[0], ws[1])]


def out_shape(layer):
    return layer.rows, layer.spec[1]


def reference(layer, xs, ws) -> np.ndarray:
    q, k, v = (np.asarray(a, np.float32) for a in (xs[0], *ws))
    s = q @ k.T / np.sqrt(q.shape[1])
    p = np.exp(s - s.max(axis=1, keepdims=True))
    return (p / p.sum(axis=1, keepdims=True)) @ v


def work(layer, row0: int, row1: int) -> tuple[int, int]:
    s, d = layer.spec
    rows = row1 - row0
    return 4 * rows * s * d, rows * d * 2 + 2 * s * d * 2 + rows * d * 4
