"""A prefill attention core: grouped-query heads over packed prompts with
rotary embedding, causal within each prompt, windowed or full.

Row ``{"kind": "attention", "name": ..., "heads": Hq, "kv_heads": Hkv,
"head_dim": D, "seq_lens": [...], "window": W, "rope": {...}}``, the
program's ``AttentionLayer`` (``window`` 0: full; ``rope`` its ``Rope``
fields).  The layer streams its ``sum(seq_lens)`` query rows ``(rows, Hq
D)``; its keys and values ``(rows, Hkv D)`` are the stationary operands,
whole.  A slice of query rows passes one piece to
``repro.kernels.prefill_attention``: its queries, the keys and values, each
row's position and prompt id, the rotary inverse frequencies and scale, the
window and the live-block table of its rows, every one an array, so that
one compiled program serves each layer of the same shapes and table length
(the table's length tells a windowed layer from a full one).

The reference is NumPy on the host, prompt by prompt and head by head:
rotary embedding in float32 (its inverse frequencies from the row by
transformers' formulas), rounded to bf16 where the program rounds it, every
visible score in float32, softmax, and the probabilities times the values
in float32.  The program computes the scores exactly from the bf16
operands, keeps the softmax in float32 and multiplies the probabilities in
two bf16 parts, so it differs from the reference by the order of float32
sums, by ``exp`` and ``cos``, by the remainder of the probabilities' second
part, and where a rotated value lies within those differences of a bf16
rounding boundary.  The limit on ``worst_rel_err.attention`` lies between
the largest reading of sound runs and the smallest reading of the control,
the same attention computed in bf16 (``bf16_attention``); the readings it
was set from are in ``PERF.md``.

The work is counted from the unpadded shapes and the visible pairs only:
``4 Hq D`` operations per visible (query, key) pair (scores and
probabilities times values), and the bytes of the bf16 queries, keys and
values and the float32 output, each moved once.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

ENTRY = "prefill_attention"
CHECK = "worst_rel_err.attention"
REL_ERR_LIMIT = 2e-3

BF16 = 2
F32 = 4


def _run(*columns):
    """The program's entry with the harness's call shape: one call of
    ``prefill_attention`` per piece."""
    from repro.kernels import prefill_attention
    return [prefill_attention(*piece) for piece in zip(*columns)]


def program():
    return _run


def control():
    return bf16_attention


def parse(row):
    rope = dict(sorted(row["rope"].items()))
    return row["name"], sum(row["seq_lens"]), dict(
        heads=row["heads"], kv_heads=row["kv_heads"],
        head_dim=row["head_dim"], seq_lens=tuple(row["seq_lens"]),
        window=row["window"], rope=rope)


def matches(row, program_layer) -> bool:
    name, _, spec = parse(row)
    la = program_layer
    rope = getattr(la, "rope", None)
    return (name == la.name and getattr(la, "seq_lens", None)
            == spec["seq_lens"]
            and (la.heads, la.kv_heads, la.head_dim, la.window)
            == (spec["heads"], spec["kv_heads"], spec["head_dim"],
                spec["window"])
            and all(getattr(rope, k, None) == v
                    for k, v in spec["rope"].items()))


def _positions(spec) -> tuple[np.ndarray, np.ndarray]:
    """Each packed row's prompt id and position in its prompt."""
    lens = spec["seq_lens"]
    seg = np.repeat(np.arange(len(lens)), lens)
    starts = np.repeat(np.cumsum([0, *lens[:-1]]), lens)
    return seg, np.arange(len(seg)) - starts


def operands(layer):
    s = layer.spec
    kv = (layer.rows, s["kv_heads"] * s["head_dim"])
    return [(layer.rows, s["heads"] * s["head_dim"])], [kv, kv]


def cut(layer, row0: int, row1: int, xs):
    return [xs[0][row0:row1]]


def pieces(layer, row0: int, row1: int, cut, ws):
    from repro.core.dnng import Rope
    from repro.kernels import (attention_table, packed_positions,
                               rope_inv_freq)

    s = layer.spec
    seg, pos = packed_positions(s["seq_lens"])
    inv_freq, scale = rope_inv_freq(Rope(**s["rope"]), s["head_dim"])
    return [(cut[0], ws[0], ws[1],
             jnp.asarray(pos[row0:row1]), jnp.asarray(seg[row0:row1]),
             jnp.asarray(pos), jnp.asarray(seg), jnp.asarray(inv_freq),
             jnp.asarray(scale, jnp.float32),
             np.asarray(s["window"], np.int32),
             attention_table(s["seq_lens"], s["window"], (row0, row1)))]


def out_shape(layer):
    return layer.rows, layer.spec["heads"] * layer.spec["head_dim"]


# ---------------------------------------------------------------------------
# the reference: NumPy, float32, prompt by prompt and head by head
# ---------------------------------------------------------------------------

def inv_freq(rope: dict, head_dim: int) -> tuple[np.ndarray, float]:
    """Rotary inverse frequencies (float32) and the scale of cos and sin:
    transformers' default, or its yarn (``_compute_yarn_parameters``)."""
    d = head_dim
    base = rope["theta"]
    inv = 1.0 / base ** (np.arange(0, d, 2, dtype=np.float64) / d)
    if rope["kind"] == "default":
        return inv.astype(np.float32), 1.0
    orig = rope["original_max_position_embeddings"]

    def dim_at(rotations):
        return d * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(dim_at(rope["beta_fast"])), 0)
    high = min(math.ceil(dim_at(rope["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    inv = inv / rope["factor"] * ramp + inv * (1.0 - ramp)
    return inv.astype(np.float32), float(rope["attention_factor"])


def _rotate(x: np.ndarray, pos: np.ndarray, inv: np.ndarray,
            scale: float) -> np.ndarray:
    """Rotate-half rotary embedding of ``(rows, heads, d)``, float32,
    rounded to bf16."""
    ang = pos.astype(np.float32)[:, None] * inv[None, :]
    ang = np.concatenate([ang, ang], axis=1)[:, None, :]
    cos = np.cos(ang).astype(np.float32) * np.float32(scale)
    sin = np.sin(ang).astype(np.float32) * np.float32(scale)
    x1, x2 = np.split(x, 2, axis=2)
    out = x * cos + np.concatenate([-x2, x1], axis=2) * sin
    return out.astype(ml_dtypes.bfloat16).astype(np.float32)


def reference(layer, xs, ws) -> np.ndarray:
    s = layer.spec
    d, hq, hkv = s["head_dim"], s["heads"], s["kv_heads"]
    inv, scale = inv_freq(s["rope"], d)
    seg, pos = _positions(s)
    q = np.asarray(xs[0], np.float32).reshape(-1, hq, d)
    k = np.asarray(ws[0], np.float32).reshape(-1, hkv, d)
    v = np.asarray(ws[1], np.float32).reshape(-1, hkv, d)
    out = np.zeros(q.shape, np.float32)
    start = 0
    for n in s["seq_lens"]:
        rows = slice(start, start + n)
        qr = _rotate(q[rows], pos[rows], inv, scale)
        kr = _rotate(k[rows], pos[rows], inv, scale)
        i, j = np.arange(n)[:, None], np.arange(n)[None, :]
        visible = (j <= i) & ((i - j < s["window"]) if s["window"] else True)
        for h in range(hq):
            g = h // (hq // hkv)
            sc = (qr[:, h] @ kr[:, g].T) / np.float32(math.sqrt(d))
            sc = np.where(visible, sc, -np.inf)
            p = np.exp(sc - sc.max(axis=1, keepdims=True))
            out[rows, h] = (p / p.sum(axis=1, keepdims=True)) @ v[rows, g]
        start += n
    return out.reshape(len(seg), hq * d)


def _pairs(layer, row0: int, row1: int) -> int:
    """Visible (query, key) pairs of query rows ``[row0, row1)``."""
    _, pos = _positions(layer.spec)
    seen = pos[row0:row1] + 1
    w = layer.spec["window"]
    return int(np.minimum(seen, w).sum() if w else seen.sum())


def work(layer, row0: int, row1: int) -> tuple[int, int]:
    s = layer.spec
    rows, d = row1 - row0, s["head_dim"]
    return (4 * s["heads"] * d * _pairs(layer, row0, row1),
            rows * s["heads"] * d * (BF16 + F32)
            + 2 * layer.rows * s["kv_heads"] * d * BF16)


# ---------------------------------------------------------------------------
# the control: the same attention computed in bf16
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("group",))
def _bf16_prompt(q, k, v, q_pos, kv_pos, inv, scale, window, group: int):
    """One prompt's attention in bf16: queries ``(nq, Hq, d)`` at
    ``q_pos`` over keys and values ``(nk, Hkv, d)`` at ``kv_pos``, rotary
    embedding as the reference's."""
    bf = jnp.bfloat16

    def rotate(x, pos):
        ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
        ang = jnp.concatenate([ang, ang], axis=1)[:, None, :]
        cos = (jnp.cos(ang) * scale).astype(bf)
        sin = (jnp.sin(ang) * scale).astype(bf)
        x1, x2 = jnp.split(x, 2, axis=2)
        return x * cos + jnp.concatenate([-x2, x1], axis=2) * sin

    qr = rotate(q, q_pos)
    kr = jnp.repeat(rotate(k, kv_pos), group, axis=1)
    vr = jnp.repeat(v, group, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", qr, kr, preferred_element_type=bf
                    ) / jnp.sqrt(jnp.asarray(q.shape[2], bf))
    i, j = q_pos[:, None], kv_pos[None, :]
    visible = (j <= i) & ((window <= 0) | (i - j < window))
    p = jax.nn.softmax(jnp.where(visible[None], sc, -jnp.inf), axis=2)
    return jnp.einsum("hqk,khd->qhd", p.astype(bf), vr,
                      preferred_element_type=bf).astype(jnp.float32)


def bf16_attention(qs, ks, vs, q_pos, q_seg, kv_pos, kv_seg, inv_freqs,
                   scales, windows, tables):
    """The control, with the program entry's call shape."""
    outs = []
    for q, k, v, qp, qs_, kp, ks_, inv, sc, w in zip(
            qs, ks, vs, q_pos, q_seg, kv_pos, kv_seg, inv_freqs, scales,
            windows):
        d = 2 * inv.shape[0]
        qs_, ks_ = np.asarray(qs_), np.asarray(ks_)
        parts = []
        for p in np.unique(qs_):       # prompts in row order
            qi, ki = np.nonzero(qs_ == p)[0], np.nonzero(ks_ == p)[0]
            parts.append(_bf16_prompt(
                q[qi].reshape(len(qi), -1, d), k[ki].reshape(len(ki), -1, d),
                v[ki].reshape(len(ki), -1, d), qp[qi], kp[ki], inv, sc,
                jnp.asarray(w), group=q.shape[1] // k.shape[1]
            ).reshape(len(qi), -1))
        outs.append(jnp.concatenate(parts, axis=0))
    return outs
