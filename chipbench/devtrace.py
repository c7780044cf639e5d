"""From a profiler trace to device busy time, idle gaps and top operations.

The traced run wraps its window in a host span ``window`` and each round in
``launch`` (the call into the program) and ``wait`` (until its outputs are
ready).  Device operations are the events of the device planes' ``XLA Ops``
line; on a CPU trace, which has no device plane, they are the host events
that carry an ``hlo_op`` stat.  All times are on the trace's own clock.
"""

from __future__ import annotations

import collections
from pathlib import Path

HOST_SPANS = ("window", "launch", "wait")


def _base(name: str) -> str:
    return name.split("#", 1)[0]


def load(path: str | Path) -> dict:
    """A trace's device operations ``(start_ns, end_ns, name)`` and host
    spans ``(start_ns, end_ns, name, round or None)``."""
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    device, host, hosts = [], [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device += [(e.start_ns, e.start_ns + e.duration_ns,
                                e.name) for e in line.events]
        elif plane.name.startswith("/host:"):
            hosts.append(plane)
    ops_on_host = not device  # a CPU trace: XLA ops run on host threads
    for plane in hosts:
        for line in plane.lines:
            for e in line.events:
                if _base(e.name) in HOST_SPANS:
                    host.append((e.start_ns, e.start_ns + e.duration_ns,
                                 _base(e.name), dict(e.stats).get("round")))
                elif ops_on_host and "hlo_op" in dict(e.stats):
                    device.append((e.start_ns, e.start_ns + e.duration_ns,
                                   e.name))
    return {"device": device, "host": host}


def union(intervals) -> list[tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    merged: list[list[float]] = []
    for s, e in sorted((s, e) for s, e, *_ in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi), *rest) for s, e, *rest in intervals
            if e > lo and s < hi]


def _cover(gap, spans) -> tuple[str, object]:
    """The host span that overlaps the gap most: ``(name, round)``, or
    ``("other", None)``."""
    best, found = 0.0, ("other", None)
    for s, e, n, r in spans:
        overlap = min(e, gap[1]) - max(s, gap[0])
        if overlap > best:
            best, found = overlap, (n, r)
    return found


def reduce(trace: dict, top: int = 10) -> dict | None:
    """Busy and idle time of the window, its longest idle gaps and the
    device operations that took most time.  None if no window or no device
    operation is in the trace."""
    windows = [(s, e) for s, e, n, _ in trace["host"] if n == "window"]
    if not windows:
        return None
    lo, hi = windows[0]
    ops = _clip(trace["device"], lo, hi)
    if not ops:
        return None
    busy = union(ops)
    busy_ns = sum(e - s for s, e in busy)
    spans = [(s, e, n, r) for s, e, n, r in _clip(trace["host"], lo, hi)
             if n != "window"]
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    named = sorted(((_cover(g, spans), (g[1] - g[0]) / 1e9)
                    for g in gaps), key=lambda x: -x[1])
    by_op = collections.Counter()
    for s, e, n in ops:
        by_op[n] += (e - s) / 1e9
    idle_by_span = collections.Counter()
    for (n, _), sec in named:
        idle_by_span[n] += sec
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy_ns / 1e9,
            "device_ops": [[n, s] for n, s in by_op.most_common(top)],
            "idle_gaps": [[n if r is None else f"{n} round {r}", s]
                          for (n, r), s in named[:top]],
            "idle_by_span": dict(idle_by_span)}
