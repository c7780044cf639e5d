"""Prefill attention over packed prompts: grouped-query heads, rotary
embedding, causal within each prompt, windowed or full, as one Pallas grid
over the live (query block, key block) pairs.

A prefill step packs several prompts into one row axis; a token attends to
the tokens before it in its own prompt, and on a windowed layer only to the
``window - 1`` nearest of them.  Most (query block, key block) pairs of the
packed square are then invisible.  Like the compact GEMM grid, the kernel
walks a table of the live pairs only, built on the host by
:func:`attention_table` and scalar-prefetched, so an invisible block is
neither fetched nor computed; the table's length keys the compile cache, its
contents are run-time data.  Every per-layer choice arrives as an array
(positions, prompt ids, the rotary inverse frequencies and scale, the
window, the table), so one compiled program serves every layer of the same
shapes and table length.

Per grid step the kernel takes one key/value head's block of keys and
values and the block of queries of the ``heads / kv_heads`` query heads
that share it, keeps a running softmax per query row and head in float32,
and multiplies the probabilities by the values in two bf16 passes (the
probabilities' bf16 part and their remainder), so the result is close to a
float32 computation from the bf16 operands.  Rotary embedding (rotate-half
convention, cos and sin times the rope scale) is applied to queries and
keys in float32 before the kernel and rounded to bf16, as a bf16 model
applies it.  The kernel is named ``attention_prefill`` on the device.

Host phases are marked for the profiler: ``attention.plan`` (checks; stats
``live_blocks`` and ``window``) and ``attention.kernel`` (the dispatch of
the jitted rotary embedding and kernel).
"""

from __future__ import annotations

import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.dnng import Rope

BLOCK_Q = 256
BLOCK_K = 256
# the running max of a query row before any visible key; finite, so that
# no exp of a difference of two of them is a NaN
_NEG = -1e30
_LANES = 128


# ---------------------------------------------------------------------------
# per-layer data: rotary frequencies, packed positions, the live-pair table
# ---------------------------------------------------------------------------

def rope_inv_freq(rope: Rope, head_dim: int) -> tuple[np.ndarray, float]:
    """``(inverse frequencies (head_dim / 2,) float32, scale of cos and
    sin)`` of a rotary embedding, as transformers'
    ``_compute_default_rope_parameters`` and ``_compute_yarn_parameters``
    give them (computed in float64, rounded once to float32)."""
    dim = head_dim
    pos_freqs = rope.theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inv = 1.0 / pos_freqs
    if rope.kind == "default":
        return inv.astype(np.float32), 1.0

    def correction_dim(rotations: float) -> float:
        return (dim * math.log(rope.original_max_position_embeddings
                               / (rotations * 2 * math.pi))
                / (2 * math.log(rope.theta)))

    low = max(math.floor(correction_dim(rope.beta_fast)), 0)
    high = min(math.ceil(correction_dim(rope.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    extrapolation = 1.0 - ramp
    inv = inv / rope.factor * (1.0 - extrapolation) + inv * extrapolation
    return inv.astype(np.float32), float(rope.attention_factor)


def packed_positions(seq_lens: Sequence[int]) -> tuple[np.ndarray,
                                                       np.ndarray]:
    """``(prompt ids, positions)``, int32 per packed token: positions
    restart at 0 in each prompt."""
    seg = np.repeat(np.arange(len(seq_lens), dtype=np.int32), seq_lens)
    starts = np.repeat(np.cumsum([0, *seq_lens[:-1]]), seq_lens)
    pos = (np.arange(len(seg)) - starts).astype(np.int32)
    return seg, pos


def _block_ranges(seg: np.ndarray, pos: np.ndarray, block: int
                  ) -> list[dict[int, tuple[int, int]]]:
    """Per block of rows, each prompt's ``(lowest, highest)`` position in
    it."""
    out = []
    for b in range(-(-len(seg) // block)):
        s, p = seg[b * block:(b + 1) * block], pos[b * block:(b + 1) * block]
        out.append({int(k): (int(p[s == k].min()), int(p[s == k].max()))
                    for k in np.unique(s)})
    return out


def live_blocks(q_seg, q_pos, kv_seg, kv_pos, window: int, *,
                block_q: int = BLOCK_Q, block_k: int = BLOCK_K
                ) -> np.ndarray:
    """The live-pair table, int32 ``(4, pairs)``: rows query block, key
    block, first and last flag of each query block's run of key blocks.

    A pair is live where some query of the query block may see some key of
    the key block: same prompt, the key's position at most the query's and,
    with a ``window`` (0: none), within ``window - 1`` of it.  Decided on
    each prompt's position range within each block, so a pair is never
    missed.  Query blocks come in order, each with its key blocks in order.
    """
    qr = _block_ranges(np.asarray(q_seg), np.asarray(q_pos), block_q)
    kr = _block_ranges(np.asarray(kv_seg), np.asarray(kv_pos), block_k)
    qb, kb, first, last = [], [], [], []
    for i, qs in enumerate(qr):
        run = [j for j, ks in enumerate(kr)
               if any(k in ks and ks[k][0] <= hi
                      and (not window or lo - ks[k][1] < window)
                      for k, (lo, hi) in qs.items())]
        for n, j in enumerate(run):
            qb.append(i)
            kb.append(j)
            first.append(n == 0)
            last.append(n == len(run) - 1)
    return np.asarray([qb, kb, first, last], np.int32).reshape(4, -1)


def attention_table(seq_lens: Sequence[int], window: int,
                    rows: tuple[int, int] | None = None, *,
                    block_q: int = BLOCK_Q, block_k: int = BLOCK_K
                    ) -> np.ndarray:
    """:func:`live_blocks` of the query rows ``rows`` (default: all) of the
    packed prompts ``seq_lens`` against all their keys."""
    seg, pos = packed_positions(seq_lens)
    r0, r1 = rows or (0, len(seg))
    return live_blocks(seg[r0:r1], pos[r0:r1], seg, pos, window,
                       block_q=block_q, block_k=block_k)


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------

def _attention_kernel(qb_ref, kb_ref, first_ref, last_ref, win_ref,
                      q_ref, k_ref, v_ref, qpos_ref, qseg_ref, kpos_ref,
                      kseg_ref, o_ref, m_ref, l_ref, acc_ref, *,
                      group: int, head_dim: int, scale: float):
    """One live (query block, key block) pair of one key/value head, for
    the ``group`` query heads that share it."""
    i = pl.program_id(1)
    bk = k_ref.shape[0]

    @pl.when(first_ref[i] == 1)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qp, kp = qpos_ref[...], kpos_ref[...]          # (bq, 1), (1, bk)
    w = win_ref[0]
    visible = ((qseg_ref[...] == kseg_ref[...]) & (kp <= qp)
               & ((w <= 0) | (qp - kp < w)))       # (bq, bk)
    k, v = k_ref[...], v_ref[...]
    for g in range(group):
        cols = slice(g * head_dim, (g + 1) * head_dim)
        s = jax.lax.dot_general(q_ref[:, cols], k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(visible, s, _NEG)
        m_prev = m_ref[g]                                     # (bq, 128)
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.where(visible,
                      jnp.exp(s - pltpu.repeat(m_next, bk // _LANES, 1)),
                      0.0)
        l_ref[g] = alpha * l_ref[g] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[g] = m_next
        p_hi = p.astype(jnp.bfloat16)
        p_lo = (p - p_hi.astype(jnp.float32)).astype(jnp.bfloat16)
        pv = (jnp.dot(p_hi, v, preferred_element_type=jnp.float32)
              + jnp.dot(p_lo, v, preferred_element_type=jnp.float32))
        acc_ref[:, cols] = (acc_ref[:, cols]
                            * pltpu.repeat(alpha, head_dim // _LANES, 1)
                            + pv)

    @pl.when(last_ref[i] == 1)
    def _drain():
        for g in range(group):
            cols = slice(g * head_dim, (g + 1) * head_dim)
            l = l_ref[g]
            inv = 1.0 / jnp.where(l == 0.0, 1.0, l)
            o_ref[:, cols] = acc_ref[:, cols] * pltpu.repeat(
                inv, head_dim // _LANES, 1)


def _rope(x: jax.Array, pos: jax.Array, inv_freq: jax.Array,
          scale: jax.Array) -> jax.Array:
    """Rotate-half rotary embedding of ``x`` ``(rows, heads * head_dim)``
    at positions ``pos``, in float32, rounded to bf16.

    The rotated half comes from a product with a signed permutation, exact
    in bf16 (one nonzero term a column), so no half-lane slice or
    concatenation relays the rows out."""
    d = 2 * inv_freq.shape[0]
    xh = x.reshape(x.shape[0], -1, d)
    eye = jnp.eye(d // 2, dtype=x.dtype)
    zero = jnp.zeros_like(eye)
    rotated = jnp.dot(xh, jnp.block([[zero, eye], [-eye, zero]]),
                      preferred_element_type=jnp.float32)
    ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=1)[:, None, :]
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    out = xh.astype(jnp.float32) * cos + rotated * sin
    return out.reshape(x.shape).astype(jnp.bfloat16)


def _pad_rows(a: jax.Array, rows: int, value) -> jax.Array:
    return jnp.pad(a, ((0, rows - a.shape[0]),) + ((0, 0),) * (a.ndim - 1),
                   constant_values=value)


@functools.partial(jax.jit,
                   static_argnames=("block_q", "block_k", "interpret"))
def _attention_call(table, window, q, k, v, q_pos, q_seg, kv_pos, kv_seg,
                    inv_freq, rope_scale, *, block_q: int, block_k: int,
                    interpret: bool) -> jax.Array:
    head_dim = 2 * inv_freq.shape[0]
    kv_heads = k.shape[1] // head_dim
    group = q.shape[1] // (kv_heads * head_dim)
    lq, lk = q.shape[0], k.shape[0]
    tq, tk = -(-lq // block_q) * block_q, -(-lk // block_k) * block_k
    q = _pad_rows(_rope(q, q_pos, inv_freq, rope_scale), tq, 0)
    k = _pad_rows(_rope(k, kv_pos, inv_freq, rope_scale), tk, 0)
    v = _pad_rows(v, tk, 0)
    # padded rows belong to no prompt, and to different ones on each side
    q_pos = _pad_rows(q_pos.astype(jnp.int32), tq, 0)[:, None]
    q_seg = _pad_rows(q_seg.astype(jnp.int32), tq, -1)[:, None]
    kv_pos = _pad_rows(kv_pos.astype(jnp.int32), tk, 0)[None, :]
    kv_seg = _pad_rows(kv_seg.astype(jnp.int32), tk, -2)[None, :]
    qw = group * head_dim
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(kv_heads, table.shape[1]),
        in_specs=[
            pl.BlockSpec((block_q, qw),
                         lambda h, i, qb, kb, f, la, w: (qb[i], h)),
            pl.BlockSpec((block_k, head_dim),
                         lambda h, i, qb, kb, f, la, w: (kb[i], h)),
            pl.BlockSpec((block_k, head_dim),
                         lambda h, i, qb, kb, f, la, w: (kb[i], h)),
            pl.BlockSpec((block_q, 1),
                         lambda h, i, qb, kb, f, la, w: (qb[i], 0)),
            pl.BlockSpec((block_q, 1),
                         lambda h, i, qb, kb, f, la, w: (qb[i], 0)),
            pl.BlockSpec((1, block_k),
                         lambda h, i, qb, kb, f, la, w: (0, kb[i])),
            pl.BlockSpec((1, block_k),
                         lambda h, i, qb, kb, f, la, w: (0, kb[i])),
        ],
        out_specs=pl.BlockSpec((block_q, qw),
                               lambda h, i, qb, kb, f, la, w: (qb[i], h)),
        scratch_shapes=[pltpu.VMEM((group, block_q, _LANES), jnp.float32),
                        pltpu.VMEM((group, block_q, _LANES), jnp.float32),
                        pltpu.VMEM((block_q, qw), jnp.float32)],
    )
    kernel = functools.partial(_attention_kernel, group=group,
                               head_dim=head_dim,
                               scale=1.0 / math.sqrt(head_dim))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((tq, q.shape[1]), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="attention_prefill",
    )(table[0], table[1], table[2], table[3],
      jnp.reshape(window, (1,)).astype(jnp.int32),
      q, k, v, q_pos, q_seg, kv_pos, kv_seg)
    return out[:lq]


def prefill_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      q_pos, q_seg, kv_pos, kv_seg, inv_freq, rope_scale,
                      window, table=None, *, block_q: int = BLOCK_Q,
                      block_k: int = BLOCK_K, interpret: bool = False
                      ) -> jax.Array:
    """Causal grouped-query attention of packed prompts with rotary
    embedding.

    ``q`` ``(rows, heads * head_dim)``, ``k`` and ``v`` ``(keys, kv_heads *
    head_dim)``, bf16, before rotary embedding; query head ``j`` reads
    key/value head ``j // (heads / kv_heads)``.  ``q_pos``/``q_seg`` and
    ``kv_pos``/``kv_seg`` are each row's position and prompt id; a query
    sees the keys of its prompt at its position or before, within
    ``window`` positions (0: no window).  ``inv_freq`` ``(head_dim / 2,)``
    and ``rope_scale`` are :func:`rope_inv_freq`'s.  ``table`` is the
    live-pair table of these rows and blocks (:func:`live_blocks`); built
    here from the positions when None, which reads them back to the host.
    Returns float32 ``(rows, heads * head_dim)``.  ``head_dim`` is a
    multiple of 128, ``block_k`` of 128 and ``block_q`` of 8.
    """
    if table is None:
        table = live_blocks(np.asarray(q_seg), np.asarray(q_pos),
                            np.asarray(kv_seg), np.asarray(kv_pos),
                            int(np.asarray(window)), block_q=block_q,
                            block_k=block_k)
    with jax.profiler.TraceAnnotation(
            "attention.plan", live_blocks=int(table.shape[1]),
            window=int(np.asarray(window))):
        head_dim = 2 * int(inv_freq.shape[0])
        if head_dim % _LANES or block_k % _LANES or block_q % 8:
            raise ValueError(f"head_dim {head_dim} and block_k {block_k} "
                             f"must be multiples of {_LANES}, block_q "
                             f"{block_q} of 8")
        if k.shape != v.shape or k.shape[1] % head_dim \
                or q.shape[1] % k.shape[1]:
            raise ValueError(f"q {q.shape}, k {k.shape} and v {v.shape} are "
                             "not grouped heads of width " f"{head_dim}")
        if table.ndim != 2 or table.shape[0] != 4 or not table.shape[1]:
            raise ValueError(f"table {table.shape} is not a (4, pairs) "
                             "live-pair table with a pair")
    with jax.profiler.TraceAnnotation("attention.kernel"):
        return _attention_call(table, window, q, k, v, q_pos, q_seg, kv_pos,
                               kv_seg, inv_freq, rope_scale,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret)
