#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 chipbench/run.py --workload heavy-closed --seed 7 --seconds 30 \
        --trace 0
    python3 chipbench/run.py --list      # the cells found, no device needed

From the root of a checkout.  Set-up schedules the cell's tenants through
``repro.api.Session``, makes the operands on the device from the seed and
warms every round shape; the window then replays the schedule's
exactly-once rounds through the program entries the layers' kinds name
(``repro.kernels.fused_tenant_gemm`` for a GEMM) for ``--seconds``;
afterwards each layer computed in the window is compared with its kind's
float32 reference.  ``--trace 1`` records a profiler trace of the window
and reports the per-layer metrics instead of the end-to-end ones.

Exits non-zero, printing no result, unless JAX's devices are TPUs and as
many as the cell asks for.  The last line on stdout is one JSON object; the
numbers compared, with their limits, are the last lines on stderr.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[1]
if sys.path and Path(sys.path[0]).resolve() == ROOT / "chipbench":
    sys.path[0] = str(ROOT)
else:
    sys.path.insert(0, str(ROOT))
sys.path.insert(1, str(ROOT / "src"))

from chipbench import catalog  # noqa: E402


class NoChip(RuntimeError):
    pass


def require_chips(n: int):
    """The first device, if JAX has at least ``n`` TPUs; else ``NoChip``."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devs)}")
    return devs[0]


def use_cache() -> str:
    """The program's fixed compile cache, writing every compile to it."""
    import jax
    from repro.launch.cache import use_compile_cache

    where = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def _trace_window(fn, enabled: bool):
    """Run ``fn()``; with ``enabled``, under the profiler.  Returns
    ``(result, trace reduction or None)``."""
    if not enabled:
        return fn(), None
    import jax
    from chipbench import devtrace

    tmp = tempfile.mkdtemp(prefix="chipbench-trace-")
    try:
        jax.profiler.start_trace(tmp)
        try:
            result = fn()
        finally:
            jax.profiler.stop_trace()
        files = sorted(Path(tmp).rglob("*.xplane.pb"))
        red = devtrace.reduce(devtrace.load(files[-1])) if files else None
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return result, red


def run_cell(cell: catalog.Cell, seed: int, seconds: float, trace: bool, *,
             given=None, plan=None, device=None, t_setup0=None) -> dict:
    """Set up, measure and check one run; return the result object.

    ``plan`` defaults to the cell's schedule, and each entry to the
    program's own; tests pass another plan, and callables in ``given`` (a
    dict by entry name) in the program's place.  ``device`` is the chip
    checked by ``require_chips`` (None runs on JAX's first device).
    """
    import jax
    from chipbench import check, operands, replay
    from chipbench import plan as plan_mod

    t_setup0 = time.perf_counter() if t_setup0 is None else t_setup0
    device = device or jax.devices()[0]
    peaks = catalog.peaks(device.device_kind) if device.platform == "tpu" \
        else None
    if plan is None:
        plan, schedule_s = plan_mod.build(cell.config, cell.traffic)
    else:
        schedule_s = 0.0
    xs, ws, cut = operands.make(plan, seed)
    calls = replay.calls(plan, replay.entries(plan, given), cut, ws)
    counter = replay.CompileCounter()
    try:
        with counter.counting():
            warmed = replay.warm(calls)
        warm_counts = counter.counts()
        setup_s = time.perf_counter() - t_setup0
        win, red = _trace_window(
            lambda: replay.window(plan, calls, seconds, counter), trace)
    finally:
        counter.close()
    stats = device.memory_stats() or {}
    peak_bytes = int(stats.get("peak_bytes_in_use", 0))

    # the check: the window's outputs and their operands to the host, the
    # device's arrays freed, then the reference layer by layer
    host = check.to_host(win.outputs, xs, ws)
    win.outputs = None
    del xs, ws, cut, calls
    readings = check.compare(plan, *host)
    del host

    # what the metric readers read
    ctx = SimpleNamespace(plan=plan, seed=seed, schedule_s=schedule_s,
                          setup_s=setup_s, window=win, trace=red,
                          peaks=peaks, warmed=warmed)
    metrics = {}
    for m in cell.metrics:
        if m.end_to_end != (not trace):
            continue
        value = m.read(ctx)
        if value is not None:
            metrics[m.name] = {"value": value, "unit": m.unit}

    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(jax.devices()), "memory_peak_bytes": peak_bytes}
    result = {"correct": check.passed(readings),
              "attempted": readings["layers"], "failed": readings["failed"],
              "metrics": metrics, "device": dev}
    if trace:
        dev["busy_s"] = red["busy_s"] if red else 0.0
        dev["window_s"] = red["window_s"] if red else win.seconds
        if red:
            result["breakdown"] = {"device_ops": red["device_ops"],
                                   "idle_gaps": red["idle_gaps"]}
    info = {"rounds": len(win.round_ids), "passes": win.passes,
            "window_s": win.seconds, "warmed_shapes": warmed,
            "lowerings_in_window": win.lowerings,
            "compile_requests_in_window": win.compile_requests,
            "cache_misses_in_window": win.cache_misses,
            "warm_up_lowerings_requests_misses": warm_counts,
            "setup_s": setup_s,
            "schedule_s": schedule_s}
    if red:
        info["idle_by_span"] = red["idle_by_span"]
    result["info"] = info
    result["checks"] = check.checks(readings)
    return result


def list_cells(bench: dict) -> None:
    """Print every cell and the files it resolves to."""
    for w in bench["workloads"]:
        cell = catalog.cell(w["name"], bench)
        print(f"{cell.name}: config {cell.config['name']} "
              f"({sum(len(t['layers']) for t in cell.config['tenants'])} "
              f"layers, {len(cell.config['tenants'])} tenants), traffic "
              f"{cell.traffic['name']} ({cell.traffic['schedule']}), "
              f"{cell.chips} chip(s); metrics: "
              + ", ".join(m.name for m in cell.metrics))


def main(argv=None) -> int:
    t0 = time.perf_counter()
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--list", action="store_true",
                   help="list the cells found and exit")
    args = p.parse_args(argv)
    bench = catalog.benchmark()
    if args.list:
        list_cells(bench)
        return 0
    if not args.workload:
        p.error("--workload is required")
    cell = catalog.cell(args.workload, bench)
    seconds = args.seconds if args.seconds is not None \
        else bench["run_seconds"]
    try:
        device = require_chips(cell.chips)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    use_cache()
    result = run_cell(cell, args.seed, seconds, bool(args.trace),
                      device=device, t_setup0=t0)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
