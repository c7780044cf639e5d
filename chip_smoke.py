#!/usr/bin/env python3
"""Smoke run of the fused multi-tenant GEMM path on one TPU chip.

Schedules the paper's Table-1 light and heavy mixes through the normal
front door, ``Session(policy="equal", backend="sim")``, cuts the schedule's
trace into rounds (the layers computing on the array at one instant), and
replays those rounds through ``fused_tenant_gemm`` at every layer's real
GEMM shape in bfloat16, beside the single-tenancy baseline
``sequential_tenant_gemm``.  Every tenant's output of both is checked
against a float32 NumPy reference on the host.

    python chip_smoke.py

Exits non-zero, printing no result, unless JAX's first device is a TPU.
Per-round lines (shapes, grid, blocks, error, first- and second-call host
times) are informational.  The last line of a passing run is one JSON
object naming the device.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import Session  # noqa: E402
from repro.kernels import fused_tenant_gemm  # noqa: E402
from repro.kernels.ops import sequential_tenant_gemm  # noqa: E402
from repro.launch.cache import use_compile_cache  # noqa: E402
from repro.sim.workloads import WORKLOADS  # noqa: E402

# bf16 products are exact in f32, so kernel, XLA and NumPy differ only in
# f32 accumulation order (~1e-6 of the largest output); a misrouted or
# dropped block is off by order 1.
REL_TOL = 1e-3
SEED = 0
HEAD_ROUNDS = 8      # heavy rounds replayed from the start of the schedule
CROWDED_ROUNDS = 8   # plus the heavy rounds with the most co-resident tenants
FC_GEMM = (1, 9216, 4096)  # AlexNet's first FC layer, (gemm_m, gemm_k, gemm_n)

Gemm = tuple[int, int, int]  # (gemm_m, gemm_k, gemm_n) = (T, K, N)


def schedule_rounds(workload: str) -> list[list[Gemm]]:
    """The schedule's rounds, each the GEMMs of its co-resident layers.

    A round starts at each distinct ``compute_start`` of the trace and holds
    every layer computing then, ordered by the array columns it owns.
    """
    res = Session(policy="equal", backend="sim").run(
        workload, compare_baseline=False)
    layers = {(g.name, i): layer for g in WORKLOADS[workload]()
              for i, layer in enumerate(g.layers)}
    trace = res.partitioned.trace
    rounds = []
    for s in sorted({e.compute_start for e in trace}):
        live = sorted((e for e in trace
                       if e.compute_start <= s < e.compute_end),
                      key=lambda e: e.partition.col_start)
        rounds.append([(layers[e.tenant, e.layer_index].gemm_m,
                        layers[e.tenant, e.layer_index].gemm_k,
                        layers[e.tenant, e.layer_index].gemm_n)
                       for e in live])
    return rounds


def _footprint(gemms: list[Gemm]) -> int:
    """Elements of the shared padded activation stack and weight matrix."""
    t = max(m for m, _, _ in gemms)
    k = max(k for _, k, _ in gemms)
    return len(gemms) * t * k + k * sum(n for _, _, n in gemms)


def select_heavy_rounds(rounds: list[list[Gemm]]) -> list[int]:
    """The first rounds, the most crowded others, and the first FC round."""
    head = set(range(min(HEAD_ROUNDS, len(rounds))))
    rest = sorted((i for i in range(len(rounds)) if i not in head),
                  key=lambda i: (-len(rounds[i]), -_footprint(rounds[i]), i))
    fc = next(i for i, r in enumerate(rounds) if FC_GEMM in r)
    return sorted(head | set(rest[:CROWDED_ROUNDS]) | {fc})


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


def _rel_err(out: np.ndarray, ref: np.ndarray) -> float:
    return float(np.max(np.abs(out - ref)) / np.max(np.abs(ref)))


def replay_round(gemms: list[Gemm], seed: int, *, grid_mode: str = "auto",
                 interpret: bool = False) -> dict:
    """Run one round fused and sequentially; raise on a wrong output.

    Operands are bf16 draws from ``seed``; the reference is float32 NumPy
    on the same bf16-rounded values.  Each path is called twice: the first
    call's time includes compilation, the second's does not.
    """
    rng = np.random.default_rng(seed)
    xs_h = [rng.standard_normal((m, k), np.float32).astype(jnp.bfloat16)
            for m, k, _ in gemms]
    ws_h = [rng.standard_normal((k, n), np.float32).astype(jnp.bfloat16)
            for _, k, n in gemms]
    xs = [jax.device_put(x) for x in xs_h]
    ws = [jax.device_put(w) for w in ws_h]

    def fused():
        return fused_tenant_gemm(xs, ws, grid_mode=grid_mode,
                                 interpret=interpret, return_stats=True)

    def sequential():
        return sequential_tenant_gemm(xs, ws)

    (outs, stats), fused_cold = _timed(fused)
    _, fused_warm = _timed(fused)
    seq_outs, seq_cold = _timed(sequential)
    _, seq_warm = _timed(sequential)

    err = 0.0
    for i, (x, w) in enumerate(zip(xs_h, ws_h)):
        ref = x.astype(np.float32) @ w.astype(np.float32)
        for path, out in (("fused", outs[i]), ("sequential", seq_outs[i])):
            out = np.asarray(out)
            if out.shape != ref.shape or out.dtype != np.float32:
                raise AssertionError(
                    f"{path} tenant {i}: got {out.shape} {out.dtype}, "
                    f"want {ref.shape} float32")
            e = _rel_err(out, ref)
            if not e <= REL_TOL:
                raise AssertionError(
                    f"{path} tenant {i} {gemms[i]}: relative error {e:.3e} "
                    f"exceeds {REL_TOL:.0e}")
            err = max(err, e)
    return {"gemms": gemms, "grid_mode": stats.grid_mode,
            "blocks": (stats.block_t, stats.block_k, stats.block_n),
            "grid_steps": stats.accounting.blocks_scheduled,
            "max_rel_err": err,
            "fused_s": (fused_cold, fused_warm),
            "sequential_s": (seq_cold, seq_warm)}


def _print_round(tag: str, r: dict) -> None:
    shapes = " ".join(f"{m}x{k}x{n}" for m, k, n in r["gemms"])
    print(f"{tag}: {len(r['gemms'])} tenants [{shapes}] "
          f"grid={r['grid_mode']} blocks={r['blocks']} "
          f"steps={r['grid_steps']} rel_err={r['max_rel_err']:.3e} "
          f"fused first/second {r['fused_s'][0]:.3f}/{r['fused_s'][1]:.4f} s "
          f"sequential first/second "
          f"{r['sequential_s'][0]:.3f}/{r['sequential_s'][1]:.4f} s",
          flush=True)


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    cache = use_compile_cache()
    print(f"device: {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache: {cache}", flush=True)

    t0 = time.perf_counter()
    light = schedule_rounds("light")
    heavy = schedule_rounds("heavy")
    picked = select_heavy_rounds(heavy)
    print(f"schedule: {len(light)} light rounds, {len(heavy)} heavy rounds, "
          f"replaying all light and heavy {picked} "
          f"({time.perf_counter() - t0:.2f} s)", flush=True)

    grids, worst = set(), 0.0
    plan = ([(f"light[{i}]", g) for i, g in enumerate(light)]
            + [(f"heavy[{i}]", heavy[i]) for i in picked])
    for n, (tag, gemms) in enumerate(plan):
        r = replay_round(gemms, SEED + n)
        _print_round(tag, r)
        grids.add(r["grid_mode"])
        worst = max(worst, r["max_rel_err"])
    for mode in ("dense", "compact"):
        if mode not in grids:  # auto never chose it: force it once
            r = replay_round(light[0], SEED, grid_mode=mode)
            _print_round(f"light[0] forced {mode}", r)
            grids.add(r["grid_mode"])
            worst = max(worst, r["max_rel_err"])
    print(f"replayed {len(plan)} rounds; grids {sorted(grids)}; "
          f"max relative error {worst:.3e} (tolerance {REL_TOL:.0e}); "
          f"total {time.perf_counter() - t0:.1f} s", flush=True)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
