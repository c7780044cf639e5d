"""Multi-tenant partitioned weight-stationary GEMM — the paper's kernel on TPU.

The paper partitions a 128×128 weight-stationary systolic array *vertically*:
every tenant owns all PE rows and a contiguous range of PE **columns**, and a
one-gate PE change (``Mul_En``) keeps foreign data flowing through without
firing the multiplier.  The TPU has no per-PE enable, so the insight is
re-expressed structurally (DESIGN.md §2):

* PE columns        →  the GEMM **N dimension** (output channels / lanes);
* vertical slices   →  disjoint contiguous **N-block ranges**, one per tenant
  (``owner`` map — the partition table of Algorithm 1);
* ``Mul_En`` gating →  a three-rung ladder, each rung cheaper than the last:
  (a) the grid's index map never routes tenant A's activations against
  tenant B's weight columns; (b) in ``grid_mode="dense"`` a ``pl.when``
  keeps dead blocks (past a tenant's valid streamed rows / reduction depth)
  from firing the MXU — compute is *gated*, but the block still costs a
  grid step and its HBM→VMEM fetches; (c) in ``grid_mode="compact"``
  host-built scalar-prefetch index tables enumerate **only the live
  blocks**, so dead work is *not scheduled* and its operands are *not
  fetched* — the true zero-cost ``Mul_En``: gated → not-scheduled →
  not-fetched;
* load/feed/drain SRAM buffers → the HBM→VMEM BlockSpec pipeline (weights
  double-buffered into VMEM = ① load; activation stream = ② feed; the f32
  accumulator flushed at the last K step = ③ drain).

All tenants execute inside ONE fused ``pallas_call`` grid, so a single TPU
core is time/space-shared among tenants exactly like the paper's single
systolic array — no per-tenant kernel launches, no dead lanes between
partitions (ragged edges are zero-padded, not recomputed).

Dense grid layout: ``(n_blocks, t_blocks, k_blocks)`` with K innermost — the
f32 accumulator tile stays resident in VMEM across the K reduction (the TPU
analogue of partial sums flowing down the array's columns) and is drained
once per (n, t) tile.  The compact grid flattens the same iteration space to
a 1-D walk over live ``(n, t, k)`` triples with every K-run kept contiguous,
so the accumulator discipline is unchanged — only the dead steps between
runs disappear.

Scalar-prefetch operands (``owner``, ``valid_t``, ``valid_k`` — and in
compact mode the live-block index tables) are the dynamic partition state:
Algorithm 1 re-computes them per scheduling round on the host, and the SAME
compiled kernel serves any partition layout of the same geometry — that is
what makes the partitioning *dynamic* at zero recompile cost.  (The compact
grid's *length* is the live-block count: each distinct count, with the
operands' shapes and the blocks, is compiled once and then reused from the
jit cache with any tables of that length; a layout with a new count
compiles a grid of its own.  :func:`repro.kernels.ops.fused_tenant_gemm`
weighs that trade when ``grid_mode="auto"``.)
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# MXU/VREG-aligned defaults: 128-multiples on the matmul dims.
DEFAULT_BLOCK_T = 128
DEFAULT_BLOCK_K = 128
DEFAULT_BLOCK_N = 128

# Scoped VMEM a kernel may allocate: Mosaic's default limit, 16 MiB on TPU
# v5e (an ahead-of-time v5e compile refuses a 1024³ f32 block with "limit
# 16.00M").  :func:`block_vmem_bytes` counts every tile double-buffered,
# at or above Mosaic's own allocation, so a working set within this budget
# stays within that limit; ``partitioned_matmul`` enforces it explicitly.
VMEM_BUDGET_BYTES = 16 * 2 ** 20

_ALLOWED_DTYPES = (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32))

GRID_MODES = ("dense", "compact")


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def block_vmem_bytes(block_t: int, block_k: int, block_n: int,
                     x_dtype, w_dtype) -> int:
    """VMEM working set of one grid step: double-buffered x/w/out tiles
    (Pallas overlaps the next fetch with the current compute) plus the
    grid-resident f32 accumulator tile."""
    x_tile = block_t * block_k * jnp.dtype(x_dtype).itemsize
    w_tile = block_k * block_n * jnp.dtype(w_dtype).itemsize
    out_tile = block_t * block_n * 4  # f32 output
    acc_tile = block_t * block_n * 4  # f32 scratch accumulator
    return 2 * (x_tile + w_tile + out_tile) + acc_tile


def _validate_promote(xs: jax.Array, w: jax.Array) -> tuple[jax.Array,
                                                            jax.Array]:
    """Enforce the bf16/f32 operand contract; promote mixed pairs to f32."""
    for name, arr in (("xs", xs), ("w", w)):
        if jnp.dtype(arr.dtype) not in _ALLOWED_DTYPES:
            raise TypeError(
                f"{name} dtype {arr.dtype} unsupported: the partitioned-WS "
                "kernel accepts bfloat16 or float32 operands (cast ints / "
                "f16 / f64 on the host first)")
    if xs.dtype != w.dtype:  # bf16 × f32 → promote both to f32
        common = jnp.promote_types(xs.dtype, w.dtype)
        xs, w = xs.astype(common), w.astype(common)
    return xs, w


# ---------------------------------------------------------------------------
# live-block enumeration + accounting (host side, concrete partition state)
# ---------------------------------------------------------------------------

def _live_extents(owner: np.ndarray, valid_t: np.ndarray,
                  valid_k: np.ndarray, *, T: int, K: int, block_t: int,
                  block_k: int) -> tuple[np.ndarray, np.ndarray]:
    """Per N-block live extents: (t_blocks_live, k_blocks_live) arrays.

    A block column owned by tenant ``e`` has ``ceil(valid_t[e]/block_t)``
    live T-blocks and ``ceil(valid_k[e]/block_k)`` live K-blocks — live
    blocks always form a contiguous prefix, which is what keeps compact
    K-runs contiguous for the VMEM accumulator.
    """
    vt = np.clip(valid_t[owner], 0, T)
    vk = np.clip(valid_k[owner], 0, K)
    tl = -(-vt // block_t)
    kl = -(-vk // block_k)
    tl = np.where(kl > 0, tl, 0)  # a zero-depth reduction has no live tiles
    return tl.astype(np.int64), kl.astype(np.int64)


def _tables_from_extents(tl: np.ndarray, kl: np.ndarray
                         ) -> tuple[np.ndarray, np.ndarray, np.ndarray,
                                    np.ndarray]:
    nidx, tidx, kidx, last = [], [], [], []
    for n in range(tl.shape[0]):
        kn = int(kl[n])
        for t in range(int(tl[n])):
            for k in range(kn):
                nidx.append(n)
                tidx.append(t)
                kidx.append(k)
                last.append(1 if k == kn - 1 else 0)
    return (np.asarray(nidx, np.int32), np.asarray(tidx, np.int32),
            np.asarray(kidx, np.int32), np.asarray(last, np.int32))


def live_block_tables(owner, valid_t, valid_k, *, T: int, K: int,
                      block_t: int = DEFAULT_BLOCK_T,
                      block_k: int = DEFAULT_BLOCK_K
                      ) -> tuple[np.ndarray, np.ndarray, np.ndarray,
                                 np.ndarray]:
    """Flattened compact-grid index tables ``(nidx, tidx, kidx, last_k)``.

    Entry ``i`` names the ``(n, t, k)`` block the ``i``-th grid step should
    execute; ``last_k[i]`` flags the final step of its K-run (the drain
    point).  K is innermost and every K-run is contiguous, so the resident
    accumulator works exactly as in the dense grid.
    """
    tl, kl = _live_extents(np.asarray(owner, np.int64),
                           np.asarray(valid_t, np.int64),
                           np.asarray(valid_k, np.int64),
                           T=T, K=K, block_t=block_t, block_k=block_k)
    return _tables_from_extents(tl, kl)


@dataclasses.dataclass(frozen=True)
class BlockAccounting:
    """Per-call grid/traffic accounting of one ``partitioned_matmul``.

    ``blocks_total`` is the dense iteration space ``n·t·k``;
    ``blocks_scheduled`` is what the chosen grid mode actually walks
    (dense: all of it; compact: live blocks only); ``blocks_live`` is the
    MXU-firing subset; ``blocks_skipped`` are scheduled-but-gated steps —
    each one still pays its grid step and HBM→VMEM block fetches, which is
    precisely the waste the compact grid deletes.  Byte counts follow the
    one-fetch-per-scheduled-step pipeline model (x and w tiles in, one
    f32 out tile per drained (n, t) run).
    """

    grid_mode: str
    block_t: int
    block_k: int
    block_n: int
    blocks_total: int
    blocks_scheduled: int
    blocks_live: int
    blocks_skipped: int
    x_bytes_fetched: int
    w_bytes_fetched: int
    out_bytes_written: int

    @property
    def bytes_fetched(self) -> int:
        return self.x_bytes_fetched + self.w_bytes_fetched

    @property
    def schedule_efficiency(self) -> float:
        """Live fraction of scheduled steps (1.0 = zero dead work)."""
        return (self.blocks_live / self.blocks_scheduled
                if self.blocks_scheduled else 1.0)

    def as_dict(self) -> dict:
        return {f.name: getattr(self, f.name)
                for f in dataclasses.fields(self)} | {
                    "bytes_fetched": self.bytes_fetched,
                    "schedule_efficiency": self.schedule_efficiency}


def grid_accounting(*, T: int, K: int, N: int, owner, valid_t, valid_k=None,
                    block_t: int = DEFAULT_BLOCK_T,
                    block_k: int = DEFAULT_BLOCK_K,
                    block_n: int = DEFAULT_BLOCK_N,
                    x_dtype=jnp.float32, w_dtype=jnp.float32,
                    grid_mode: str = "dense") -> BlockAccounting:
    """Predict the grid/traffic accounting of a ``partitioned_matmul`` call.

    Pure host arithmetic over the concrete partition state — the same
    numbers the compact path realises, usable as a pre-flight cost model
    (the block-size autotuner ranks candidates with it).
    """
    if grid_mode not in GRID_MODES:
        raise ValueError(f"grid_mode must be one of {GRID_MODES}, "
                         f"got {grid_mode!r}")
    owner = np.asarray(owner, np.int64)
    valid_t = np.asarray(valid_t, np.int64)
    valid_k = (np.full(valid_t.shape, K, np.int64) if valid_k is None
               else np.asarray(valid_k, np.int64))
    n_blocks = _ceil_div(N, block_n)
    t_blocks = _ceil_div(T, block_t)
    k_blocks = _ceil_div(K, block_k)
    tl, kl = _live_extents(owner, valid_t, valid_k, T=T, K=K,
                           block_t=block_t, block_k=block_k)
    live = int((tl * kl).sum())
    live_runs = int(tl.sum())          # drained (n, t) tiles
    total = n_blocks * t_blocks * k_blocks
    if grid_mode == "dense":
        scheduled, runs = total, n_blocks * t_blocks
    else:
        scheduled, runs = live, live_runs
    x_item = jnp.dtype(x_dtype).itemsize
    w_item = jnp.dtype(w_dtype).itemsize
    return BlockAccounting(
        grid_mode=grid_mode, block_t=block_t, block_k=block_k,
        block_n=block_n, blocks_total=total, blocks_scheduled=scheduled,
        blocks_live=live, blocks_skipped=scheduled - live,
        x_bytes_fetched=scheduled * block_t * block_k * x_item,
        w_bytes_fetched=scheduled * block_k * block_n * w_item,
        out_bytes_written=runs * block_t * block_n * 4)


def _block_dot(x: jax.Array, w: jax.Array) -> jax.Array:
    """One block's MXU product, accumulated in f32.

    f32 operands ask for a full-f32 contraction: at Mosaic's default
    precision a TPU v5e rounds them to bf16 (2.4e-3 relative error against
    an f32 reference at K=256).  bf16 products are exact in f32 either way.
    """
    precision = (jax.lax.Precision.HIGHEST if x.dtype == jnp.float32
                 else None)
    return jax.lax.dot_general(x, w,
                               dimension_numbers=(((1,), (0,)), ((), ())),
                               precision=precision,
                               preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# dense grid (every (n, t, k) scheduled; dead blocks gated by pl.when)
# ---------------------------------------------------------------------------

def _dense_kernel(owner_ref, valid_t_ref, valid_k_ref, x_ref, w_ref, o_ref,
                  acc_ref, *, n_k_blocks: int, block_t: int, block_k: int):
    """One (n, t, k) grid step: acc += x_blk @ w_blk for the owning tenant."""
    t = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Mul_En rung (b): blocks entirely past the owning tenant's valid rows
    # (T) or valid reduction depth (K) never fire the MXU — but they are
    # still scheduled and fetched; the compact grid deletes even that.
    n = pl.program_id(0)
    tenant = owner_ref[n]
    live = (t * block_t < valid_t_ref[tenant]) \
        & (k * block_k < valid_k_ref[tenant])

    @pl.when(live)
    def _mac():
        acc_ref[...] += _block_dot(x_ref[0], w_ref[...])

    @pl.when(k == n_k_blocks - 1)
    def _drain():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_t", "block_k", "block_n", "interpret"))
def _dense_call(xs: jax.Array, w: jax.Array, owner: jax.Array,
                valid_t: jax.Array, valid_k: jax.Array, *,
                block_t: int, block_k: int, block_n: int,
                interpret: bool) -> jax.Array:
    E, T, K = xs.shape
    _, N = w.shape
    n_blocks, t_blocks, k_blocks = N // block_n, T // block_t, K // block_k
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_blocks, t_blocks, k_blocks),
        in_specs=[
            # ② feed: the OWNING tenant's activation block — the index map
            # is the partition routing (never crosses a partition edge).
            pl.BlockSpec((1, block_t, block_k),
                         lambda n, t, k, owner, vt, vk: (owner[n], t, k)),
            # ① load: stationary weight column-block of this partition.
            pl.BlockSpec((block_k, block_n),
                         lambda n, t, k, owner, vt, vk: (k, n)),
        ],
        # ③ drain: one output tile per (t, n), revisited across k.
        out_specs=pl.BlockSpec((block_t, block_n),
                               lambda n, t, k, owner, vt, vk: (t, n)),
        scratch_shapes=[pltpu.VMEM((block_t, block_n), jnp.float32)],
    )
    kernel = functools.partial(_dense_kernel, n_k_blocks=k_blocks,
                               block_t=block_t, block_k=block_k)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="tenant_gemm_dense",
    )(owner.astype(jnp.int32), valid_t.astype(jnp.int32),
      valid_k.astype(jnp.int32), xs, w)


# ---------------------------------------------------------------------------
# compact grid (live blocks only, via scalar-prefetch index tables)
# ---------------------------------------------------------------------------

def _compact_kernel(xidx_ref, nidx_ref, tidx_ref, kidx_ref, last_ref,
                    x_ref, w_ref, o_ref, acc_ref):
    """One live block.  Every scheduled step fires the MXU — no gating."""
    i = pl.program_id(0)

    @pl.when(kidx_ref[i] == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _block_dot(x_ref[0], w_ref[...])

    @pl.when(last_ref[i] == 1)
    def _drain():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_t", "block_k", "block_n", "interpret"))
def _compact_grid(xidx: jax.Array, nidx: jax.Array, tidx: jax.Array,
                  kidx: jax.Array, last: jax.Array, xs: jax.Array,
                  w: jax.Array, *, block_t: int, block_k: int, block_n: int,
                  interpret: bool) -> jax.Array:
    """The compact grid over its live-step tables.

    The grid's length is the tables' length, a shape, so it keys the jit
    cache with the operands' shapes and the blocks; the tables' contents
    are run-time operands.  A layout of a known length dispatches the
    cached program with its own tables.
    """
    T, N = xs.shape[1], w.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(nidx.shape[0],),
        in_specs=[
            pl.BlockSpec((1, block_t, block_k),
                         lambda i, xi, ni, ti, ki, la:
                         (xi[i], ti[i], ki[i])),
            pl.BlockSpec((block_k, block_n),
                         lambda i, xi, ni, ti, ki, la: (ki[i], ni[i])),
        ],
        out_specs=pl.BlockSpec((block_t, block_n),
                               lambda i, xi, ni, ti, ki, la:
                               (ti[i], ni[i])),
        scratch_shapes=[pltpu.VMEM((block_t, block_n), jnp.float32)],
    )
    return pl.pallas_call(
        _compact_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="tenant_gemm_compact",
    )(xidx, nidx, tidx, kidx, last, xs, w)


def _compact_call(xs: jax.Array, w: jax.Array, owner, valid_t, valid_k, *,
                  block_t: int, block_k: int, block_n: int,
                  interpret: bool) -> jax.Array:
    E, T, K = xs.shape
    _, N = w.shape
    with jax.profiler.TraceAnnotation("tenant_gemm.tables"):
        owner = np.asarray(owner, np.int64)
        tl, kl = _live_extents(owner, np.asarray(valid_t, np.int64),
                               np.asarray(valid_k, np.int64),
                               T=T, K=K, block_t=block_t, block_k=block_k)
        nidx, tidx, kidx, last = _tables_from_extents(tl, kl)
        if nidx.size == 0:  # nothing live: the contract output is all zeros
            return jnp.zeros((T, N), jnp.float32)
        tables = [jnp.asarray(a) for a in
                  (owner[nidx].astype(np.int32), nidx, tidx, kidx, last)]
    with jax.profiler.TraceAnnotation("tenant_gemm.kernel",
                                      grid_mode="compact"):
        out = _compact_grid(*tables, xs, w, block_t=block_t,
                            block_k=block_k, block_n=block_n,
                            interpret=interpret)
    # Tiles with no live block are never visited (never drained), so their
    # VMEM-backed output is unspecified; the contract says they are zero.
    # One host-side mask restores it — still no grid steps, no fetches.
    with jax.profiler.TraceAnnotation("tenant_gemm.unpack"):
        live_rows = np.repeat(tl * block_t, block_n)           # (N,)
        if (live_rows >= T).all():
            return out
        mask = np.arange(T)[:, None] < live_rows[None, :]
        return jnp.where(jnp.asarray(mask), out, 0.0)


# ---------------------------------------------------------------------------
# public entry point
# ---------------------------------------------------------------------------

def partitioned_matmul(xs: jax.Array, w: jax.Array, owner: jax.Array,
                       valid_t: jax.Array, valid_k: jax.Array | None = None,
                       *,
                       block_t: int = DEFAULT_BLOCK_T,
                       block_k: int = DEFAULT_BLOCK_K,
                       block_n: int = DEFAULT_BLOCK_N,
                       grid_mode: str = "dense",
                       vmem_budget_bytes: int = VMEM_BUDGET_BYTES,
                       interpret: bool = False) -> jax.Array:
    """Fused multi-tenant GEMM.  See ``ref.partitioned_matmul_ref``.

    xs:      (E, T, K) — per-tenant activations, zero-padded to shared T/K.
    w:       (K, N)    — tenant weights concatenated along N.
    owner:   (N // block_n,) int32 — column-block → tenant (partition map).
    valid_t: (E,) int32 — valid streamed rows per tenant.
    valid_k: (E,) int32 — valid reduction depth per tenant (default: K).
    Returns  (T, N) f32.

    ``grid_mode="dense"`` schedules the full (n, t, k) grid and gates dead
    blocks; ``"compact"`` schedules only the live blocks via host-built
    scalar-prefetch index tables — identical results (same per-block f32
    accumulation, same K order), fewer grid steps and fetches.  Compact
    mode derives the tables from the *values* of ``owner``/``valid_t``/
    ``valid_k``, so those must be concrete (not jit tracers).

    Operands must be bfloat16 or float32 (mixed pairs promote to float32),
    and the block working set must fit ``vmem_budget_bytes`` (see
    :func:`block_vmem_bytes`).
    """
    xs, w = _validate_promote(xs, w)
    E, T, K = xs.shape
    if valid_k is None:
        valid_k = jnp.full((E,), K, jnp.int32)
    K2, N = w.shape
    if K2 != K:
        raise ValueError(f"K mismatch: xs {K} vs w {K2}")
    for name, dim, blk in (("T", T, block_t), ("K", K, block_k),
                           ("N", N, block_n)):
        if dim % blk:
            raise ValueError(f"{name}={dim} not divisible by block {blk}; "
                             "pad in ops.fused_tenant_gemm")
    need = block_vmem_bytes(block_t, block_k, block_n, xs.dtype, w.dtype)
    if need > vmem_budget_bytes:
        raise ValueError(
            f"blocks ({block_t}, {block_k}, {block_n}) need {need} B of "
            f"VMEM (double-buffered tiles + accumulator) but the budget is "
            f"{vmem_budget_bytes} B — shrink the blocks or raise "
            "vmem_budget_bytes")
    n_blocks = N // block_n
    if owner.shape != (n_blocks,):
        raise ValueError(f"owner must be ({n_blocks},), got {owner.shape}")
    if grid_mode not in GRID_MODES:
        raise ValueError(f"grid_mode must be one of {GRID_MODES}, "
                         f"got {grid_mode!r}")
    if grid_mode == "dense":
        with jax.profiler.TraceAnnotation("tenant_gemm.kernel",
                                          grid_mode="dense"):
            return _dense_call(xs, w, owner, valid_t, valid_k,
                               block_t=block_t, block_k=block_k,
                               block_n=block_n, interpret=interpret)
    if any(isinstance(a, jax.core.Tracer) for a in (owner, valid_t, valid_k)):
        raise ValueError(
            "grid_mode='compact' builds host-side index tables from the "
            "partition state, so owner/valid_t/valid_k must be concrete "
            "arrays — call it outside jit (or use grid_mode='dense')")
    return _compact_call(xs, w, owner, valid_t, valid_k, block_t=block_t,
                         block_k=block_k, block_n=block_n,
                         interpret=interpret)
