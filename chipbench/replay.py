"""Warm-up and the measured window: the plan's rounds, back to back.

Each round is one call of the program's ``fused_tenant_gemm`` on its
tenants' activation row slices and whole weights, timed from the call to
all its outputs ready.  Passes follow one another until the window's
seconds are up; the round under way then finishes, and the window ends
with it.
"""

from __future__ import annotations

import contextlib
import dataclasses
import sys
import time

import jax

from chipbench import work
from chipbench.plan import Plan

LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_REQUEST = "/jax/core/compile/backend_compile_duration"
CACHE_MISS = "/jax/compilation_cache/cache_misses"


class CompileCounter:
    """Counts, while ``on``, JAX's lowerings to MLIR, its requests to
    compile (each answered by the persistent cache or by a compile) and the
    persistent cache's misses."""

    def __init__(self):
        self.on = False
        self.lowerings = self.requests = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, _secs: float, **_) -> None:
        if self.on:
            self.lowerings += event == LOWERING
            self.requests += event == COMPILE_REQUEST

    def _event(self, event: str, **_) -> None:
        if self.on:
            self.misses += event == CACHE_MISS

    def counts(self) -> tuple[int, int, int]:
        return self.lowerings, self.requests, self.misses

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    @contextlib.contextmanager
    def counting(self):
        self.on = True
        try:
            yield self
        finally:
            self.on = False


@dataclasses.dataclass
class Window:
    """What the measured window did, on the host's clock."""

    seconds: float
    round_ids: list[int]
    latency_s: list[float]
    launch_s: list[float]
    passes: float
    flops: int
    lowerings: int
    compile_requests: int
    cache_misses: int
    # layer -> [(rows, output)] of its slices as last computed
    outputs: dict


def _call(gemm, plan: Plan, cut, ws, r: int):
    rnd = plan.rounds[r]
    return gemm(cut[r], [ws[s.layer] for s in rnd])


def warm(gemm, plan: Plan, cut, ws) -> int:
    """Run one round of each distinct shape signature, one after another;
    the count of rounds run."""
    first: dict[tuple, int] = {}
    for r, rnd in enumerate(plan.rounds):
        first.setdefault(plan.shapes(rnd), r)
    t0 = time.perf_counter()
    for done, r in enumerate(first.values(), 1):
        jax.block_until_ready(_call(gemm, plan, cut, ws, r))
        if done % 50 == 0:
            print(f"warm-up: {done}/{len(first)} round shapes, "
                  f"{time.perf_counter() - t0:.1f} s", file=sys.stderr,
                  flush=True)
    return len(first)


def window(gemm, plan: Plan, cut, ws, seconds: float,
           counter: CompileCounter, rounds: int | None = None) -> Window:
    """Replay passes of the plan for ``seconds``, or for exactly ``rounds``
    rounds where that is given; keep each slice's output."""
    round_flops = [work.flops(plan.shapes(rnd)) for rnd in plan.rounds]
    pass_flops = sum(round_flops)
    n = len(plan.rounds)
    latest: dict[tuple[int, int], object] = {}
    ids, lat, launch = [], [], []
    flops = 0
    before = counter.counts()
    with counter.counting(), jax.profiler.TraceAnnotation("window"):
        t_start = time.perf_counter()
        deadline = t_start + seconds
        i = 0
        while (i < rounds if rounds is not None
               else time.perf_counter() < deadline):
            r = i % n
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("launch", round=r):
                outs = _call(gemm, plan, cut, ws, r)
            t1 = time.perf_counter()
            with jax.profiler.TraceAnnotation("wait", round=r):
                jax.block_until_ready(outs)
            t2 = time.perf_counter()
            ids.append(r)
            launch.append(t1 - t0)
            lat.append(t2 - t0)
            flops += round_flops[r]
            outs = list(outs)
            for j in range(len(plan.rounds[r])):
                latest[r, j] = outs[j] if j < len(outs) else None
            i += 1
        t_end = time.perf_counter()
    rounds_done = len(ids)
    passes = rounds_done // n + sum(round_flops[:rounds_done % n]) / pass_flops
    return Window(seconds=t_end - t_start, round_ids=ids, latency_s=lat,
                  launch_s=launch, passes=passes, flops=flops,
                  lowerings=counter.lowerings - before[0],
                  compile_requests=counter.requests - before[1],
                  cache_misses=counter.misses - before[2],
                  outputs=_layer_outputs(plan, latest))


def _layer_outputs(plan: Plan, latest: dict) -> dict:
    """Each layer all of whose slices were launched: its slices' outputs,
    in the order the pass computes them."""
    parts: dict[int, list] = {}
    launched: dict[int, bool] = {}
    for r, rnd in enumerate(plan.rounds):
        for j, s in enumerate(rnd):
            launched[s.layer] = launched.get(s.layer, True) and (r, j) in latest
            parts.setdefault(s.layer, []).append((s.rows, latest.get((r, j))))
    return {li: parts[li] for li in sorted(parts) if launched[li]}
