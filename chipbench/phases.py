#!/usr/bin/env python3
"""The program's own spans in a traced window: where each round's host
launch goes, and how much of the device's work is the fused kernels.

``repro.kernels.fused_tenant_gemm`` marks its host phases for the profiler:
``tenant_gemm.plan``, ``tenant_gemm.pack`` (stat ``packed_bytes``),
``tenant_gemm.tables``, ``tenant_gemm.kernel`` (stat ``grid_mode``) and
``tenant_gemm.unpack``.  They never nest, and the benchmark calls the
program only inside its ``launch`` spans.  On the device the kernels are
named ``tenant_gemm_dense`` and ``tenant_gemm_compact``.  ``load`` reads the
spans from the trace ``chipbench.devtrace`` reads; ``reduce`` splits the
window's spans, its idle time and its idle gaps by phase.  A program
without these spans gives empty sums, and the readers of
``PHASE_METRICS`` then give None.

    python3 chipbench/phases.py --workload light-closed --seed 7 \\
        --seconds 10

runs one traced window of a cell, as ``run.py --trace 1`` does, and prints
one JSON line: those metrics beside ``launch_host_ms``, the split by phase,
the idle gaps named by phase, and ``correct``.  It needs a TPU, as
``run.py`` does.
"""

from __future__ import annotations

import argparse
import bisect
import collections
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

if __name__ == "__main__":
    ROOT = Path(__file__).resolve().parents[1]
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))

from chipbench import catalog, devtrace  # noqa: E402

PREFIX = "tenant_gemm."
KERNELS = ("tenant_gemm_dense", "tenant_gemm_compact")
PHASE_METRICS = ("plan_host_ms", "pack_host_ms", "kernel_host_ms",
                 "packed_mb_per_round", "kernel_device_share")


def load(path: str | Path) -> dict:
    """The program's spans ``(start_ns, end_ns, name, stats)`` in a trace,
    and whether the trace has a device plane (a CPU trace has none)."""
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    spans, device_plane = [], False
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            device_plane |= any(line.name == "XLA Ops" for line in plane.lines)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    name = devtrace._base(e.name)
                    if name.startswith(PREFIX):
                        spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                      name, dict(e.stats)))
    spans.sort(key=lambda sp: sp[:2])
    return {"program": spans, "device_plane": device_plane}


def _phase(name: str, stats: dict) -> str:
    """A span's phase: its name, the kernel's split by grid mode."""
    mode = stats.get("grid_mode")
    return f"{name}.{mode}" if mode else name


def _overlaps(gaps, spans) -> list[list[tuple[int, float]]]:
    """For each gap, ``(index into spans, ns of overlap)`` of the spans it
    overlaps.  Both lists are sorted by start; the sweep is linear when the
    spans do not overlap one another, and correct when they do."""
    out, j = [], 0
    for g0, g1 in gaps:
        while j < len(spans) and spans[j][1] <= g0:
            j += 1
        hits, k = [], j
        while k < len(spans) and spans[k][0] < g1:
            ov = min(spans[k][1], g1) - max(spans[k][0], g0)
            if ov > 0:
                hits.append((k, ov))
            k += 1
        out.append(hits)
    return out


def reduce(trace: dict, program: dict, top: int = 10) -> dict | None:
    """The window's program spans and idle time by phase.

    ``trace`` is what ``devtrace.load`` gives and ``program`` what ``load``
    gives, for one trace.  Returns seconds per phase in the window
    (``span_s``), the sum of ``packed_bytes``, the device's busy time in
    ops named for a kernel (``kernel_busy_s``, with the names and seconds
    of the busiest in ``kernel_ops``), each idle gap's time split by phase
    (``idle_by_phase``: the part under ``launch`` outside every program
    span is ``launch.glue``, the part under ``wait`` is ``wait``, the rest
    ``other``; the split sums to the idle time) and the longest gaps, each
    named for the program span it overlaps most, else for the host span as
    ``devtrace.reduce`` names it.  None if the trace has no window.
    """
    windows = [(s, e) for s, e, n, _ in trace["host"] if n == "window"]
    if not windows:
        return None
    lo, hi = windows[0]
    ops = devtrace._clip(trace["device"], lo, hi)
    busy = devtrace.union(ops)
    kernel = [op for op in ops if any(k in op[2] for k in KERNELS)]
    by_kernel = collections.Counter()
    for s, e, n in kernel:
        by_kernel[n] += (e - s) / 1e9
    host = sorted((sp for sp in devtrace._clip(trace["host"], lo, hi)
                   if sp[2] != "window"), key=lambda sp: sp[:2])
    spans = [(s, e, n, _phase(n, st), st) for s, e, n, st in
             devtrace._clip(program["program"], lo, hi)]

    span_s, packed = collections.Counter(), 0
    for s, e, _, ph, st in spans:
        span_s[ph] += (e - s) / 1e9
        packed += int(st.get("packed_bytes", 0))

    # each program span's round: that of the launch it runs in
    launches = [(s, e, r) for s, e, n, r in host if n == "launch"]
    starts = [s for s, _, _ in launches]

    def round_of(start):
        i = bisect.bisect_right(starts, start) - 1
        return launches[i][2] if i >= 0 and launches[i][1] >= start else None

    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    idle = collections.Counter()
    named = []
    for (g0, g1), on_prog, on_host in zip(gaps, _overlaps(gaps, spans),
                                          _overlaps(gaps, host)):
        under = collections.Counter()
        for k, ov in on_prog:
            under[spans[k][3]] += ov
        prog_ns = sum(under.values())
        launch_ns = sum(ov for k, ov in on_host if host[k][2] == "launch")
        wait_ns = sum(ov for k, ov in on_host if host[k][2] == "wait")
        under["launch.glue"] += max(0, launch_ns - prog_ns)
        under["wait"] += wait_ns
        under["other"] += (g1 - g0) - sum(under.values())
        for ph, ns in under.items():
            idle[ph] += ns / 1e9
        if on_prog:
            k, _ = max(on_prog, key=lambda h: h[1])
            name, rnd = spans[k][2], round_of(spans[k][0])
        elif on_host:
            k, _ = max(on_host, key=lambda h: h[1])
            name, rnd = host[k][2], host[k][3]
        else:
            name, rnd = "other", None
        named.append((name if rnd is None else f"{name} round {rnd}",
                      (g1 - g0) / 1e9))
    named.sort(key=lambda x: -x[1])
    return {"span_s": dict(span_s), "packed_bytes": packed,
            "kernel_busy_s": sum(e - s for s, e in devtrace.union(kernel))
            / 1e9,
            "kernel_ops": [[n, s] for n, s in by_kernel.most_common(top)],
            "idle_by_phase": {k: v for k, v in idle.items() if v > 0},
            "idle_gaps": [[n, s] for n, s in named[:top]],
            "device_plane": program["device_plane"]}


def per_round_ms(ctx, *names: str) -> float | None:
    """Milliseconds per round of the window in the named spans (a name
    covers its phases, the kernel's grid modes); None if none ran."""
    span_s = (ctx.trace or {}).get("span_s") or {}
    hit = [s for ph, s in span_s.items()
           if any(ph == n or ph.startswith(n + ".") for n in names)]
    rounds = len(ctx.window.round_ids)
    return sum(hit) / rounds * 1e3 if hit and rounds else None


def measure(cell: catalog.Cell, seed: int, seconds: float, *, given=None,
            plan=None, device=None) -> dict:
    """Set up and trace one window of the cell, then check it, as
    ``run.run_cell`` does with a trace; ``given``, ``plan`` and ``device``
    as there.  The result object of ``main``."""
    import jax
    from chipbench import check, operands, replay
    from chipbench import plan as plan_mod

    t0 = time.perf_counter()
    device = device or jax.devices()[0]
    if plan is None:
        plan, _ = plan_mod.build(cell.config, cell.traffic)
    xs, ws, cut = operands.make(plan, seed)
    calls = replay.calls(plan, replay.entries(plan, given), cut, ws)
    counter = replay.CompileCounter()
    tmp = tempfile.mkdtemp(prefix="chipbench-phases-")
    try:
        with counter.counting():
            replay.warm(calls)
        setup_s = time.perf_counter() - t0
        jax.profiler.start_trace(tmp)
        try:
            win = replay.window(plan, calls, seconds, counter)
        finally:
            jax.profiler.stop_trace()
        t1 = time.perf_counter()
        path = sorted(Path(tmp).rglob("*.xplane.pb"))[-1]
        trace = devtrace.load(path)
        red = devtrace.reduce(trace) or {}
        ph = reduce(trace, load(path)) or {}
        reduce_s = time.perf_counter() - t1
    finally:
        counter.close()
        shutil.rmtree(tmp, ignore_errors=True)

    host = check.to_host(win.outputs, xs, ws)
    win.outputs = None
    del xs, ws, cut, calls
    readings = check.compare(plan, *host)

    peaks = catalog.peaks(device.device_kind) if device.platform == "tpu" \
        else None
    ctx = SimpleNamespace(plan=plan, seed=seed, window=win, peaks=peaks,
                          trace={**red, **ph} if red else None)
    metrics = {}
    for name in ("launch_host_ms", "lowerings_in_window", "idle_share",
                 "device_roofline") + PHASE_METRICS:
        value = catalog._reader(name)(ctx)
        if value is not None:
            metrics[name] = value
    return {"correct": check.passed(readings), "metrics": metrics,
            "phases": {k: ph.get(k) for k in ("span_s", "idle_by_phase",
                                              "idle_gaps", "kernel_ops")},
            "device_ops": red.get("device_ops"),
            "device": {"platform": device.platform,
                       "kind": device.device_kind,
                       "busy_s": red.get("busy_s"),
                       "window_s": red.get("window_s")},
            "info": {"rounds": len(win.round_ids), "setup_s": setup_s,
                     "reduce_s": reduce_s, "idle_by_span":
                     red.get("idle_by_span")}}


def main(argv=None) -> int:
    from chipbench import run

    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=10.0)
    args = p.parse_args(argv)
    cell = catalog.cell(args.workload)
    try:
        device = run.require_chips(cell.chips)
    except run.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    run.use_cache()
    print(json.dumps(measure(cell, args.seed, args.seconds, device=device)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
