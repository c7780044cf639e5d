"""Kernel benchmark — dense vs compact grids on ragged tenant mixes.

    PYTHONPATH=src python benchmarks/kernel_bench.py   # -> BENCH_kernel.json

On the CPU the kernels run in the Pallas interpreter, so wall-clock
numbers there are interpret-mode figures (a grid-step proxy, not silicon
truth); on a TPU they run compiled.  The record names the platform.  The
*accounting* is exact and hardware-independent — grid steps scheduled, MXU-live blocks,
``Mul_En``-gated dead steps, and the HBM→VMEM bytes each mode fetches:

* ``dense``   schedules the full (n, t, k) iteration space and gates dead
  blocks with ``pl.when`` — every dead block still pays a grid step and
  its block fetches;
* ``compact`` schedules exactly the live blocks via scalar-prefetch index
  tables — the true zero-cost ``Mul_En`` (gated → not-scheduled →
  not-fetched).

Each mix is checked against the per-tenant oracle in both modes, and the
bench **asserts** that compact mode schedules exactly the live-block count
(CI fails on any regression).  Results land in ``BENCH_kernel.json`` at
the repo root — the kernel-level perf trajectory across PRs, next to
``BENCH_fig9.json`` and ``BENCH_traffic.json``.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp

from repro.core.dataflow import GEMM
from repro.kernels.ops import (
    _round_up,
    autotune_blocks,
    build_owner_map,
    fused_tenant_gemm,
)
from repro.kernels.partitioned_matmul import live_block_tables
from repro.launch.cache import use_compile_cache
from repro.sim.workloads import heavy_workload

BENCH_JSON = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_kernel.json")


def _heavy_gemms(n_tenants: int, cap: int = 512) -> list[GEMM]:
    """First-layer GEMMs of the heavy workload's first ``n_tenants``."""
    out = []
    for g in heavy_workload()[:n_tenants]:
        layer = g.layers[0]
        out.append(GEMM(T=min(layer.gemm_m, cap), K=min(layer.gemm_k, cap),
                        N=min(layer.gemm_n, cap)))
    return out


def _mixes() -> dict[str, list[GEMM]]:
    return {
        # no raggedness: every tenant fills the shared grid exactly —
        # compact has nothing to delete (sanity anchor, auto picks dense)
        "uniform": [GEMM(T=256, K=256, N=256) for _ in range(4)],
        # the seed bench's 4-tenant heavy mix
        "ragged": _heavy_gemms(4),
        # all 8 heavy tenants — the arrival-driven serving norm: widely
        # ragged T and K, most of the dense grid is padding
        "ragged_heavy": _heavy_gemms(8),
    }


def _operands(gemms: list[GEMM]) -> tuple[list, list]:
    key = jax.random.key(0)
    xs, ws = [], []
    for i, g in enumerate(gemms):
        k1, k2 = jax.random.split(jax.random.fold_in(key, i))
        xs.append(jax.random.normal(k1, (g.T, g.K), jnp.float32))
        ws.append(jax.random.normal(k2, (g.K, g.N), jnp.float32))
    return xs, ws


def _run_mode(xs, ws, mode: str, block: int,
              interpret: bool) -> tuple[dict, float, float]:
    """One fused call: (accounting dict, max rel err vs oracle, wall s)."""
    t0 = time.perf_counter()
    outs, stats = fused_tenant_gemm(
        xs, ws, block_t=block, block_k=block, block_n=block,
        grid_mode=mode, interpret=interpret, return_stats=True)
    jax.block_until_ready(outs)
    wall = time.perf_counter() - t0
    max_rel = 0.0
    for x, w, o in zip(xs, ws, outs):
        ref = jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)
        max_rel = max(max_rel, float(
            jnp.max(jnp.abs(o - ref)) / (jnp.max(jnp.abs(ref)) + 1e-9)))
    assert max_rel < 1e-4, (mode, max_rel)
    return stats.accounting.as_dict(), max_rel, wall


def run(block: int = 128, path: str = BENCH_JSON) -> dict:
    platform = jax.devices()[0].platform
    interpret = platform == "cpu"
    print(f"== kernel_bench: dense vs compact partitioned-WS grids "
          f"({platform}) ==")
    rows = []
    for mix, gemms in _mixes().items():
        xs, ws = _operands(gemms)
        dense, err_d, wall_d = _run_mode(xs, ws, "dense", block, interpret)
        compact, err_c, wall_c = _run_mode(xs, ws, "compact", block,
                                           interpret)

        # the tentpole invariant: the compact grid IS the live-block set.
        # `realized` is the ACTUAL pallas grid length (the same table
        # _compact_call schedules); `brute` re-counts liveness with a
        # naive triple loop sharing no code with the kernel's helpers —
        # a regression that schedules dead triples fails here, not just
        # in the cost model's own books.
        T_pad = _round_up(max(g.T for g in gemms), block)
        K_pad = _round_up(max(g.K for g in gemms), block)
        owner = build_owner_map([g.N for g in gemms], block)
        realized = live_block_tables(
            owner, [g.T for g in gemms], [g.K for g in gemms],
            T=T_pad, K=K_pad, block_t=block, block_k=block)[0].size
        brute = sum(
            1
            for e in (int(o) for o in owner)
            for tb in range(T_pad // block)
            for kb in range(K_pad // block)
            if tb * block < gemms[e].T and kb * block < gemms[e].K)
        assert realized == brute == compact["blocks_scheduled"] \
            == compact["blocks_live"] == dense["blocks_live"], \
            (mix, realized, brute, compact, dense)
        assert compact["blocks_skipped"] == 0, (mix, compact)

        step_saving = 1.0 - (compact["blocks_scheduled"]
                             / dense["blocks_scheduled"])
        fetch_saving = 1.0 - (compact["bytes_fetched"]
                              / dense["bytes_fetched"])
        shapes = tuple((g.T, g.K, g.N) for g in gemms)
        tuned = autotune_blocks(shapes)
        rows.append({
            "mix": mix,
            "tenants": [f"{g.T}x{g.K}x{g.N}" for g in gemms],
            "block": block,
            "dense": dense,
            "compact": compact,
            "grid_step_saving": step_saving,
            "fetch_byte_saving": fetch_saving,
            "wall_s_dense": wall_d,
            "wall_s_compact": wall_c,
            "max_rel_err": max(err_d, err_c),
            "autotuned_blocks": list(tuned),
        })
        print(f"{mix:>14}: dense {dense['blocks_scheduled']:>4} steps "
              f"({dense['blocks_skipped']} gated dead) -> compact "
              f"{compact['blocks_scheduled']:>4} steps "
              f"({step_saving * 100:.1f}% fewer, "
              f"{fetch_saving * 100:.1f}% fewer fetched bytes); "
              f"wall {wall_d:.2f}s -> {wall_c:.2f}s; "
              f"autotune {tuned}")

    heavy = next(r for r in rows if r["mix"] == "ragged_heavy")
    assert heavy["grid_step_saving"] >= 0.25, heavy["grid_step_saving"]

    blob = {"benchmark": "kernel", "block": block, "platform": platform,
            "results": rows}
    with open(path, "w") as f:
        json.dump(blob, f, indent=1)
        f.write("\n")
    print(f"wrote {path}")
    return blob


if __name__ == "__main__":
    use_compile_cache()
    run()
    sys.exit(0)
