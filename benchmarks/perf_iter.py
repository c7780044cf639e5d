"""Perf-iteration harness: one (arch × cell) under a candidate config.

Each §Perf hypothesis is one invocation: pick mesh factorization, sharding
rules, microbatches, attention chunk — re-lower, re-analyse, print the three
roofline terms.  Iterations are recorded in EXPERIMENTS.md §Perf.

    PYTHONPATH=src python -m benchmarks.perf_iter \
        --arch deepseek-coder-33b --cell train_4k --mesh-shape 32,8 \
        --microbatches 16

NOTE: must run in a fresh process per mesh-device-count (jax locks devices).
"""

import os

_SHAPE = os.environ.get("PERF_MESH_DEVICES", "256")
os.environ["XLA_FLAGS"] = \
    f"--xla_force_host_platform_device_count={_SHAPE}"

import argparse
import dataclasses
import json
import time


def main() -> int:
    from benchmarks.roofline import (
        HBM_BW,
        ICI_BW,
        PEAK_FLOPS,
        _model_flops,
    )
    from repro.configs import get
    from repro.distributed.sharding import FSDP_TP
    from repro.launch.hlo_analysis import collective_stats, loop_aware_cost
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import build_lowerable
    from repro.training.train_loop import TrainConfig

    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--cell", required=True)
    p.add_argument("--mesh-shape", default="16,16",
                   help="data,model factorization (product = devices)")
    p.add_argument("--microbatches", type=int, default=16)
    p.add_argument("--rules", default="fsdp_tp",
                   choices=["fsdp_tp", "embed_replicated", "tp_only",
                            "tp_experts"])
    p.add_argument("--attn-chunk", type=int, default=0,
                   help="override attention KV-chunk (0 = config default)")
    p.add_argument("--q-chunks", type=int, default=0,
                   help="Q-block count for static causal skipping")
    p.add_argument("--remat", default="on", choices=["on", "off"])
    p.add_argument("--tag", default="")
    args = p.parse_args()

    dims = tuple(int(x) for x in args.mesh_shape.split(","))
    mesh = make_mesh(dims, ("data", "model"))

    spec = get(args.arch)
    cfg = spec.model
    if args.attn_chunk:
        cfg = dataclasses.replace(cfg, attn_chunk=args.attn_chunk)
    if args.q_chunks:
        cfg = dataclasses.replace(cfg, attn_q_chunks=args.q_chunks)
    if args.remat == "off":
        cfg = dataclasses.replace(cfg, remat=False)
    spec = dataclasses.replace(spec, model=cfg)

    rules = {
        "fsdp_tp": FSDP_TP,
        # kill the vocab-sharded embedding gather (its GSPMD lowering
        # replicates-then-repartitions): embed table fully replicated
        "embed_replicated": dataclasses.replace(FSDP_TP, vocab=None),
        "tp_only": dataclasses.replace(FSDP_TP, embed=None),
        # MoE: shard expert FFN dims over "model" (like a dense MLP) and
        # leave the expert axis to FSDP — dispatch stays shard-local
        "tp_experts": dataclasses.replace(FSDP_TP, expert=None),
    }[args.rules]

    t0 = time.time()
    low = build_lowerable(spec, args.cell, mesh, rules=rules,
                          train=TrainConfig(microbatches=args.microbatches))
    compiled = low.lower().compile()
    dt = time.time() - t0
    txt = compiled.as_text()
    cost = loop_aware_cost(txt)
    coll = collective_stats(txt)
    ma = compiled.memory_analysis()

    chips = mesh.devices.size
    t_comp = cost.flops / PEAK_FLOPS
    t_mem = cost.bytes_hbm / HBM_BW
    t_coll = coll.total_bytes / ICI_BW
    terms = {"compute": t_comp, "memory": t_mem, "collective": t_coll}
    dom = max(terms, key=terms.get)
    mflops = _model_flops(args.arch, args.cell)
    frac = (mflops / chips / PEAK_FLOPS) / max(max(terms.values()), 1e-12)

    rec = {
        "tag": args.tag or f"{args.mesh_shape}/{args.rules}"
               f"/mb{args.microbatches}"
               + (f"/chunk{args.attn_chunk}" if args.attn_chunk else "")
               + (f"/qc{args.q_chunks}" if args.q_chunks else "")
               + (f"/remat-{args.remat}" if args.remat != "on" else ""),
        "arch": args.arch, "cell": args.cell,
        "mesh": args.mesh_shape, "rules": args.rules,
        "microbatches": args.microbatches,
        "compile_s": round(dt, 1),
        "t_compute_s": t_comp, "t_memory_s": t_mem,
        "t_collective_s": t_coll, "dominant": dom,
        "useful_ratio": mflops / chips / max(cost.flops, 1e-9),
        "roofline_fraction": frac,
        "temp_gb": ma.temp_size_in_bytes / 1e9,
        "collectives": coll.summary(),
    }
    print(json.dumps(rec, indent=1))
    # append to the iteration log
    log = os.path.join(os.path.dirname(__file__), "results",
                       "perf_iters.jsonl")
    os.makedirs(os.path.dirname(log), exist_ok=True)
    with open(log, "a") as f:
        f.write(json.dumps(rec) + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
