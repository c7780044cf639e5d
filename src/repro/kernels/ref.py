"""Pure-jnp oracles for the Pallas kernels (the allclose references).

Semantics contract shared with ``partitioned_matmul.py``:

* ``xs``      — (E, T, K): one (padded) activation matrix per tenant.  Rows
  at/after ``valid_t[e]`` and K-columns beyond the tenant's true K MUST be
  zero-padded by the caller (zeros contribute nothing to any dot product —
  this is how per-tenant ragged shapes stay exact inside one fused grid).
* ``w``       — (K, N): all tenants' weight matrices concatenated along N —
  the *column/partition* dimension of the paper's systolic array.
* ``owner``   — (N // block_n,) int32: which tenant owns each column block
  (the partition map of Algorithm 1; contiguous runs = vertical partitions).
* ``valid_t`` — (E,) int32: number of valid streamed rows per tenant.  Blocks
  entirely past ``valid_t[owner]`` are skipped by the kernel (the ``Mul_En``
  tri-state analogue); the oracle zeroes them explicitly.

Output — (T, N) f32: column block j equals ``xs[owner[j]] @ w[:, block j]``
with rows >= valid_t[owner[j]] equal to zero.
"""

from __future__ import annotations

import jax.numpy as jnp


def partitioned_matmul_ref(xs: jnp.ndarray, w: jnp.ndarray,
                           owner: jnp.ndarray, valid_t: jnp.ndarray,
                           block_n: int) -> jnp.ndarray:
    """O(E·T·K·N) reference for the multi-tenant partitioned GEMM."""
    E, T, K = xs.shape
    K2, N = w.shape
    assert K2 == K, (K2, K)
    assert N % block_n == 0
    n_blocks = N // block_n
    assert owner.shape == (n_blocks,)

    # out[:, j] = xs[owner[j]] @ w[:, j] — computed densely then masked.
    # (E, T, N) full cross-product, then select the owner's plane per block.
    full = jnp.einsum("etk,kn->etn", xs.astype(jnp.float32),
                      w.astype(jnp.float32))
    owner_per_col = jnp.repeat(owner, block_n)              # (N,)
    out = jnp.take_along_axis(
        full, owner_per_col[None, None, :].repeat(T, axis=1), axis=0)[0]
    # Mul_En masking: rows past the owning tenant's valid_t are zero.
    rows = jnp.arange(T)[:, None]
    live = rows < valid_t[owner_per_col][None, :]
    return jnp.where(live, out, 0.0)


def matmul_ref(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """Plain GEMM oracle (single-tenant baseline)."""
    return x.astype(jnp.float32) @ w.astype(jnp.float32)


# ---------------------------------------------------------------------------
# a sparse MLP layer and prefill attention, in plain float32
# (callers set ``jax.default_matmul_precision("highest")`` on a TPU)
# ---------------------------------------------------------------------------

def route_ref(logits: jnp.ndarray, top_k: int
              ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Softmax over every expert, the ``top_k`` largest logits (lower index
    first on ties), their probabilities renormalised: ``(weights,
    experts)``."""
    logits = logits.astype(jnp.float32)
    probs = jnp.exp(logits - logits.max(axis=-1, keepdims=True))
    probs = probs / probs.sum(axis=-1, keepdims=True)
    # a stable sort of the negated logits keeps the lower index first
    experts = jnp.argsort(-logits, axis=-1, stable=True)[:, :top_k]
    weights = jnp.take_along_axis(probs, experts, axis=-1)
    return weights / weights.sum(axis=-1, keepdims=True), experts


def expert_ref(x: jnp.ndarray, w_gate_up: jnp.ndarray,
               w_down: jnp.ndarray) -> jnp.ndarray:
    """One SiLU-gated expert, float32, the activation rounded to bf16 where
    the program feeds it to its bf16 down projection."""
    gu = x.astype(jnp.float32) @ w_gate_up.astype(jnp.float32)
    g, u = jnp.split(gu, 2, axis=1)
    h = (g / (1.0 + jnp.exp(-g)) * u).astype(jnp.bfloat16)
    return h.astype(jnp.float32) @ w_down.astype(jnp.float32)


def moe_block_ref(x, w_router, w_gate_ups, w_downs, *, top_k: int,
                  held=None) -> jnp.ndarray:
    """The sparse MLP layer as a dense sum: every held expert on every
    token, weighted by its gate where the token routed to it (else 0)."""
    logits = x.astype(jnp.float32) @ w_router.astype(jnp.float32)
    weights, experts = route_ref(logits, top_k)
    held = range(w_router.shape[1]) if held is None else held
    y = jnp.zeros(x.shape, jnp.float32)
    for e, wgu, wd in zip(held, w_gate_ups, w_downs):
        gate = jnp.sum(jnp.where(experts == e, weights, 0.0), axis=1)
        y = y + gate[:, None] * expert_ref(x, wgu, wd)
    return y


def rope_ref(x: jnp.ndarray, pos: jnp.ndarray, inv_freq: jnp.ndarray,
             scale: float) -> jnp.ndarray:
    """Rotate-half rotary embedding of ``(rows, heads * d)`` in float32,
    rounded to bf16 as the program rounds it."""
    d = 2 * inv_freq.shape[0]
    xh = x.astype(jnp.float32).reshape(x.shape[0], -1, d)
    ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=1)[:, None, :]
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    x1, x2 = xh[..., :d // 2], xh[..., d // 2:]
    out = xh * cos + jnp.concatenate([-x2, x1], axis=2) * sin
    return out.reshape(x.shape).astype(jnp.bfloat16).astype(jnp.float32)


def attention_ref(q, k, v, q_pos, q_seg, kv_pos, kv_seg, inv_freq, scale,
                  window: int) -> jnp.ndarray:
    """Dense masked grouped-query attention with rotary embedding, float32:
    every (query, key) score computed, the invisible ones masked."""
    d = 2 * inv_freq.shape[0]
    qr = rope_ref(q, q_pos, inv_freq, scale)
    kr = rope_ref(k, kv_pos, inv_freq, scale)
    hq, hkv = q.shape[1] // d, k.shape[1] // d
    qh = qr.reshape(-1, hq, d)
    kh = jnp.repeat(kr.reshape(-1, hkv, d), hq // hkv, axis=1)
    vh = jnp.repeat(v.astype(jnp.float32).reshape(-1, hkv, d), hq // hkv,
                    axis=1)
    s = jnp.einsum("qhd,khd->hqk", qh, kh) / jnp.sqrt(jnp.float32(d))
    qp, kp = q_pos[:, None], kv_pos[None, :]
    vis = (q_seg[:, None] == kv_seg[None, :]) & (kp <= qp)
    if window:
        vis = vis & (qp - kp < window)
    s = jnp.where(vis[None], s, -jnp.inf)
    p = jnp.exp(s - s.max(axis=2, keepdims=True))
    p = p / p.sum(axis=2, keepdims=True)
    return jnp.einsum("hqk,khd->qhd", p, vh).reshape(q.shape[0], hq * d)
