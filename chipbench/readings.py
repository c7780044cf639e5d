#!/usr/bin/env python3
"""The readings that the kinds' ``REL_ERR_LIMIT`` are set from, on the chip.

    python3 chipbench/readings.py --workload light-closed --seconds 30 \
        --seeds 1 2 3 ... --control-seeds 101 102 103

In one process: set-up as a run makes it, then for each ``--seeds`` seed a
window of ``--seconds`` through the program, and for each
``--control-seeds`` seed one whole pass through the control (each kind's
``control()``, for a GEMM the reference with int8 operands, in the
program's place), each compared as a run compares.  Prints one JSON line a
seed and a last line with, per check, the largest program reading and the
smallest control reading.  The benchmark's runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import run  # noqa: F401  (puts the checkout and src/ on the path)

from chipbench import catalog, check, operands, replay  # noqa: E402
from chipbench import plan as plan_mod  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", type=int, nargs="*", default=[])
    p.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = p.parse_args(argv)
    cell = catalog.cell(args.workload)
    try:
        run.require_chips(cell.chips)
    except run.NoChip as e:
        print(f"readings: {e}", file=sys.stderr)
        return 2
    run.use_cache()

    plan, _ = plan_mod.build(cell.config, cell.traffic)
    controls = {la.kind.ENTRY: la.kind.control() for la in plan.layers}
    counter = replay.CompileCounter()
    found: dict[str, dict[str, list]] = {}
    for kind, seeds in (("program", args.seeds),
                        ("control", args.control_seeds)):
        fns = replay.entries(plan, None if kind == "program" else controls)
        for i, seed in enumerate(seeds):
            t0 = time.perf_counter()
            xs, ws, cut = operands.make(plan, seed)
            calls = replay.calls(plan, fns, cut, ws)
            if i == 0:
                replay.warm(calls)
            # the control runs one whole pass: every layer compared
            win = replay.window(
                plan, calls, args.seconds, counter,
                rounds=None if kind == "program" else len(plan.rounds))
            r = check.compare(plan, *check.to_host(win.outputs, xs, ws))
            del xs, ws, cut, calls, win
            for name, (value, _) in r["errors"].items():
                found.setdefault(name, {}).setdefault(kind, []).append(value)
            print(json.dumps({"kind": kind, "seed": seed, **r,
                              "seconds": time.perf_counter() - t0}),
                  flush=True)
    counter.close()
    limits = check.limits(plan)
    print(json.dumps({"workload": args.workload, "checks": {
        name: {"program_max": max(got.get("program", []), default=None),
               "control_min": min(got.get("control", []), default=None),
               "limit": limits[name]} for name, got in found.items()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
