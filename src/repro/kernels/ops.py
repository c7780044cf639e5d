"""Public jit'd wrappers around the Pallas kernels.

``fused_tenant_gemm`` is the host-facing API (``chip_smoke.py``,
``benchmarks/kernel_bench.py`` and ``examples/quickstart.py`` call it): it
takes one (x, w) GEMM per tenant — arbitrary ragged shapes — pads them to a
shared grid geometry, builds the column-block ``owner`` map with the SAME
column-splitting rule as Algorithm 1 (``partition_calculation`` over N
blocks), invokes the fused kernel once, and splits the outputs back out.

The padding contract (zeros in the padded region of xs/w) is what makes the
ragged fusion exact — see ``ref.py``.

On top of the raw kernel this layer makes the performance decisions:

* **grid mode** — ``"auto"`` (default) schedules the compact live-block
  grid whenever the ragged mix leaves dead blocks in the dense iteration
  space, and falls back to the dense grid when every block is live (no
  index-table overhead to pay for nothing);
* **block sizes** — when not pinned by the caller, a dtype-aware autotuner
  searches MXU-aligned ``(block_t, block_k, block_n)`` candidates that fit
  the VMEM budget, ranks them by predicted HBM-fetch bytes per useful MAC
  (:func:`repro.kernels.partitioned_matmul.grid_accounting` is the cost
  model) and caches the winner per problem geometry.

Each call marks its host phases for the profiler with
``jax.profiler.TraceAnnotation``, on the clock of the device trace:
``tenant_gemm.plan`` (checks, autotune, grid choice),
``tenant_gemm.pack`` (pads, stack, concatenate; stat ``packed_bytes``, the
bytes of the packed operands), ``tenant_gemm.plan`` again (the partition
state sent to the device), then, in
:mod:`repro.kernels.partitioned_matmul`, ``tenant_gemm.tables`` (the compact
grid's index tables), ``tenant_gemm.kernel`` (stat ``grid_mode``) and
``tenant_gemm.unpack`` (the compact mask, then the per-tenant output
slices).  The spans never nest, so each one's duration is its own time;
with no profiler running each costs about a microsecond.  On the device
the kernels are named ``tenant_gemm_dense`` and ``tenant_gemm_compact``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.partitioned_matmul import (
    VMEM_BUDGET_BYTES,
    BlockAccounting,
    block_vmem_bytes,
    grid_accounting,
    partitioned_matmul,
)

# MXU-aligned candidate edge lengths the autotuner searches per dimension.
BLOCK_CANDIDATES = (128, 256, 512)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def build_owner_map(n_cols: Sequence[int], block_n: int) -> jnp.ndarray:
    """Column-block owner map for tenants with ``n_cols[i]`` output columns.

    Each tenant's columns are padded up to a whole number of blocks, so
    partitions are contiguous block runs — the kernel-level mirror of the
    paper's vertical slices.
    """
    owners = []
    for i, n in enumerate(n_cols):
        owners += [i] * (_round_up(n, block_n) // block_n)
    return jnp.asarray(owners, jnp.int32)


# ---------------------------------------------------------------------------
# geometry accounting + block-size autotuner
# ---------------------------------------------------------------------------

def _geometry_accounting(shapes: tuple[tuple[int, int, int], ...],
                         block_t: int, block_k: int, block_n: int,
                         x_dtype: str, w_dtype: str,
                         grid_mode: str) -> BlockAccounting:
    """Accounting for a fused call over per-tenant ``(T, K, N)`` shapes,
    after the shared-grid padding ``fused_tenant_gemm`` applies."""
    T = _round_up(max(t for t, _, _ in shapes), block_t)
    K = _round_up(max(k for _, k, _ in shapes), block_k)
    owner = np.asarray(build_owner_map([n for _, _, n in shapes], block_n))
    valid_t = np.asarray([t for t, _, _ in shapes], np.int64)
    valid_k = np.asarray([k for _, k, _ in shapes], np.int64)
    return grid_accounting(
        T=T, K=K, N=int(owner.size) * block_n, owner=owner,
        valid_t=valid_t, valid_k=valid_k, block_t=block_t, block_k=block_k,
        block_n=block_n, x_dtype=x_dtype, w_dtype=w_dtype,
        grid_mode=grid_mode)


@functools.lru_cache(maxsize=1024)
def autotune_blocks(shapes: tuple[tuple[int, int, int], ...],
                    x_dtype: str = "float32", w_dtype: str = "float32",
                    grid_mode: str = "compact",
                    vmem_budget_bytes: int = VMEM_BUDGET_BYTES,
                    candidates: tuple[int, ...] = BLOCK_CANDIDATES
                    ) -> tuple[int, int, int]:
    """Pick ``(block_t, block_k, block_n)`` for a fused-GEMM geometry.

    Exhaustive search over ``candidates³`` MXU-aligned blockings: candidates
    whose working set busts the dtype-aware VMEM budget are discarded, the
    rest are ranked by predicted fetched bytes per useful MAC (padding
    inflates fetches, so the model self-penalises oversized blocks), ties
    broken toward fewer grid steps, then smaller tiles.  Results are cached
    per geometry (``autotune_blocks.cache_info()`` exposes the hit rate) —
    serving re-tunes a layer mix once, not per batch.
    """
    useful_macs = sum(t * k * n for t, k, n in shapes) or 1
    best, best_key = None, None
    for bt in candidates:
        for bk in candidates:
            for bn in candidates:
                if block_vmem_bytes(bt, bk, bn, x_dtype,
                                    w_dtype) > vmem_budget_bytes:
                    continue
                acc = _geometry_accounting(shapes, bt, bk, bn,
                                           x_dtype, w_dtype, grid_mode)
                key = (acc.bytes_fetched / useful_macs,
                       acc.blocks_scheduled, bt * bk * bn)
                if best_key is None or key < best_key:
                    best, best_key = (bt, bk, bn), key
    if best is None:
        raise ValueError(
            f"no block candidate from {candidates} fits the VMEM budget "
            f"{vmem_budget_bytes} B for dtypes ({x_dtype}, {w_dtype})")
    return best


@dataclasses.dataclass(frozen=True)
class FusedGemmStats:
    """What one :func:`fused_tenant_gemm` call actually scheduled."""

    grid_mode: str
    block_t: int
    block_k: int
    block_n: int
    accounting: BlockAccounting

    def as_dict(self) -> dict:
        return {"grid_mode": self.grid_mode, "block_t": self.block_t,
                "block_k": self.block_k, "block_n": self.block_n,
                **self.accounting.as_dict()}


# ---------------------------------------------------------------------------
# fused multi-tenant GEMM
# ---------------------------------------------------------------------------

def fused_tenant_gemm(xs: Sequence[jax.Array], ws: Sequence[jax.Array], *,
                      block_t: Optional[int] = None,
                      block_k: Optional[int] = None,
                      block_n: Optional[int] = None,
                      grid_mode: str = "auto",
                      vmem_budget_bytes: int = VMEM_BUDGET_BYTES,
                      interpret: bool = False,
                      return_stats: bool = False):
    """Run every tenant's GEMM ``xs[i] @ ws[i]`` in ONE fused kernel call.

    xs[i]: (T_i, K_i);  ws[i]: (K_i, N_i).  Returns [(T_i, N_i) f32, ...]
    — or ``(outs, FusedGemmStats)`` with ``return_stats=True``.

    Block sizes left as ``None`` are autotuned per geometry (see
    :func:`autotune_blocks`); ``grid_mode`` is ``"dense"``, ``"compact"``
    or ``"auto"`` (compact exactly when the ragged mix leaves dead blocks).
    """
    with jax.profiler.TraceAnnotation("tenant_gemm.plan"):
        if len(xs) != len(ws) or not xs:
            raise ValueError("need one (x, w) pair per tenant")
        for i, (x, w) in enumerate(zip(xs, ws)):
            if x.ndim != 2 or w.ndim != 2 or x.shape[1] != w.shape[0]:
                raise ValueError(
                    f"tenant {i}: bad shapes {x.shape} @ {w.shape}")
        if grid_mode not in ("auto", "dense", "compact"):
            raise ValueError(f"grid_mode must be 'auto', 'dense' or "
                             f"'compact', got {grid_mode!r}")

        shapes = tuple((int(x.shape[0]), int(x.shape[1]), int(w.shape[1]))
                       for x, w in zip(xs, ws))
        # a tenant with no rows is a dead column slice, but a call in which
        # every tenant has none has no grid to run
        if not any(t for t, _, _ in shapes):
            raise ValueError("every tenant has 0 rows: nothing to compute; "
                             "pass only tenants that have rows")
        # the packed operands keep these types; the kernel promotes a mixed
        # x/w pair to a common type, so the autotuner budgets for that type
        # BEFORE the VMEM filter and byte accounting, and never approves
        # blocks the promoted call would reject
        x_dt = jnp.result_type(*(x.dtype for x in xs))
        w_dt = jnp.result_type(*(w.dtype for w in ws))
        x_dtype = w_dtype = str(jnp.promote_types(x_dt, w_dt))
        if block_t is None or block_k is None or block_n is None:
            tuned = autotune_blocks(
                shapes, x_dtype, w_dtype,
                grid_mode="compact" if grid_mode == "auto" else grid_mode,
                vmem_budget_bytes=vmem_budget_bytes)
            block_t = block_t if block_t is not None else tuned[0]
            block_k = block_k if block_k is not None else tuned[1]
            block_n = block_n if block_n is not None else tuned[2]

        probe = None
        if grid_mode == "auto":
            probe = _geometry_accounting(shapes, block_t, block_k, block_n,
                                         x_dtype, w_dtype, "dense")
            grid_mode = ("compact" if probe.blocks_live < probe.blocks_total
                         else "dense")

        T = _round_up(max(t for t, _, _ in shapes), block_t)
        K = _round_up(max(k for _, k, _ in shapes), block_k)
        n_pad = [_round_up(n, block_n) for _, _, n in shapes]
        packed_bytes = (len(xs) * T * K * x_dt.itemsize
                        + K * sum(n_pad) * w_dt.itemsize)

    with jax.profiler.TraceAnnotation("tenant_gemm.pack",
                                      packed_bytes=packed_bytes):
        xs_pad = jnp.stack([
            jnp.pad(x, ((0, T - x.shape[0]), (0, K - x.shape[1])))
            for x in xs])                                 # (E, T, K)
        w_pad = jnp.concatenate([
            jnp.pad(w, ((0, K - w.shape[0]), (0, n - w.shape[1])))
            for w, n in zip(ws, n_pad)], axis=1)          # (K, N_total)

    # the partition state goes to the device after the pads are dispatched
    with jax.profiler.TraceAnnotation("tenant_gemm.plan"):
        owner = build_owner_map([n for _, _, n in shapes], block_n)
        valid_t = jnp.asarray([t for t, _, _ in shapes], jnp.int32)
        valid_k = jnp.asarray([k for _, k, _ in shapes], jnp.int32)

    out = partitioned_matmul(xs_pad, w_pad, owner, valid_t, valid_k,
                             block_t=block_t, block_k=block_k,
                             block_n=block_n, grid_mode=grid_mode,
                             vmem_budget_bytes=vmem_budget_bytes,
                             interpret=interpret)

    with jax.profiler.TraceAnnotation("tenant_gemm.unpack"):
        outs = []
        col = 0
        for (t, _, n), n_cols in zip(shapes, n_pad):
            outs.append(out[:t, col:col + n])
            col += n_cols
    if not return_stats:
        return outs
    acc = (probe if probe is not None and grid_mode == "dense"
           else _geometry_accounting(shapes, block_t, block_k, block_n,
                                     x_dtype, w_dtype, grid_mode))
    return outs, FusedGemmStats(grid_mode=grid_mode, block_t=block_t,
                                block_k=block_k, block_n=block_n,
                                accounting=acc)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sequential_tenant_gemm(xs: Sequence[jax.Array],
                           ws: Sequence[jax.Array],
                           interpret: bool = False) -> list[jax.Array]:
    """Single-tenancy baseline: one dense GEMM per tenant, run back-to-back
    (what a non-partitioned accelerator does — the Fig. 9 baseline)."""
    return [x.astype(jnp.float32) @ w.astype(jnp.float32)
            for x, w in zip(xs, ws)]
