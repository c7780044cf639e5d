"""Warm-up and the measured window: the plan's rounds, back to back.

Set-up turns each round into its calls: the slices whose kinds name the
same program entry share one call of it (for every GEMM, the program's
``fused_tenant_gemm`` on the tenants' activation row slices and whole
weights), in the order of their first array column.  A round is timed from
its first call to all its calls' outputs ready.  Passes follow one another
until the window's seconds are up; the round under way then finishes, and
the window ends with it.
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
    # layer -> [(rows, its pieces' outputs)] of its slices as last computed
    outputs: dict


@dataclasses.dataclass(frozen=True)
class Round:
    """A round's calls, each ``(entry, arguments)``, and for each of its
    slices where its outputs come back: ``(call, first piece, pieces)``."""

    calls: tuple
    slots: tuple

    def signature(self) -> tuple:
        """The calls' entries and argument shapes and types, which fix every
        program the round compiles."""
        return tuple((fn, tuple((a.shape, a.dtype) for col in args
                                for a in col)) for fn, args in self.calls)


def entries(plan: Plan, given: dict | None = None) -> dict:
    """The callable of each program entry the plan's kinds name, by name:
    the program's own, where ``given`` names none in its place."""
    out = dict(given or {})
    for layer in plan.layers:
        if layer.kind.ENTRY not in out:
            out[layer.kind.ENTRY] = layer.kind.program()
    return out


def calls(plan: Plan, fns: dict, cut, ws) -> tuple[Round, ...]:
    """Every round's calls, built in set-up from the cut activations and the
    weights; ``fns`` maps an entry's name to the callable that runs it."""
    out = []
    for r, rnd in enumerate(plan.rounds):
        pieces: dict[str, list] = {}
        slots = []
        for j, s in enumerate(rnd):
            layer = plan.layers[s.layer]
            got = pieces.setdefault(layer.kind.ENTRY, [])
            new = layer.kind.pieces(layer, s.row0, s.row1, cut[r][j],
                                     ws[s.layer])
            slots.append((list(pieces).index(layer.kind.ENTRY), len(got),
                          len(new)))
            got.extend(new)
        out.append(Round(
            calls=tuple((fns[name], [list(col) for col in zip(*p)])
                        for name, p in pieces.items()),
            slots=tuple(slots)))
    return tuple(out)


def _launch(rnd: Round) -> list:
    return [fn(*args) for fn, args in rnd.calls]


def warm(rounds: tuple[Round, ...]) -> int:
    """Run one round of each distinct signature, one after another; the
    count of rounds run."""
    first: dict[tuple, Round] = {}
    for rnd in rounds:
        first.setdefault(rnd.signature(), rnd)
    t0 = time.perf_counter()
    for done, rnd in enumerate(first.values(), 1):
        jax.block_until_ready(_launch(rnd))
        if done % 50 == 0:
            print(f"warm-up: {done}/{len(first)} round shapes, "
                  f"{time.perf_counter() - t0:.1f} s", file=sys.stderr,
                  flush=True)
    return len(first)


def window(plan: Plan, calls: tuple[Round, ...], seconds: float,
           counter: CompileCounter, rounds: int | None = None) -> Window:
    """Replay passes of the plan's ``calls`` for ``seconds``, or for exactly
    ``rounds`` rounds where that is given; keep each slice's output."""
    round_flops = [work.flops(plan, rnd) for rnd in plan.rounds]
    pass_flops = sum(round_flops)
    n = len(plan.rounds)
    latest: dict[int, list] = {}
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
                outs = _launch(calls[r])
            t1 = time.perf_counter()
            with jax.profiler.TraceAnnotation("wait", round=r):
                jax.block_until_ready(outs)
            t2 = time.perf_counter()
            ids.append(r)
            launch.append(t1 - t0)
            lat.append(t2 - t0)
            flops += round_flops[r]
            latest[r] = outs
            i += 1
        t_end = time.perf_counter()
    rounds_done = len(ids)
    passes = rounds_done // n + sum(round_flops[:rounds_done % n]) / pass_flops
    return Window(seconds=t_end - t_start, round_ids=ids, latency_s=lat,
                  launch_s=launch, passes=passes, flops=flops,
                  lowerings=counter.lowerings - before[0],
                  compile_requests=counter.requests - before[1],
                  cache_misses=counter.misses - before[2],
                  outputs=_layer_outputs(plan, calls, latest))


def _slice_outputs(outs: list, slot: tuple) -> list | None:
    """A slice's pieces' outputs from its round's call outputs, or None
    where its call returned fewer."""
    call, first, count = slot
    got = list(outs[call])[first:first + count]
    return got if len(got) == count else None


def _layer_outputs(plan: Plan, calls: tuple[Round, ...], latest: dict
                   ) -> dict:
    """Each layer all of whose slices were launched: per slice its rows and
    its pieces' outputs, in the order the pass computes them."""
    parts: dict[int, list] = {}
    launched: dict[int, bool] = {}
    for r, rnd in enumerate(plan.rounds):
        for j, s in enumerate(rnd):
            launched[s.layer] = launched.get(s.layer, True) and r in latest
            out = _slice_outputs(latest[r], calls[r].slots[j]) \
                if r in latest else None
            parts.setdefault(s.layer, []).append((s.rows, out))
    return {li: parts[li] for li in sorted(parts) if launched[li]}
