import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

This is the proof that the distribution config is coherent without real
hardware: 512 placeholder CPU devices host the production mesh topology;
``jax.jit(...).lower(ShapeDtypeStructs).compile()`` must succeed for every
cell, and the compiled artifact yields

* ``memory_analysis()``  — per-device bytes (does it fit 16 GB HBM?),
* ``cost_analysis()``    — per-device HLO FLOPs / bytes accessed,
* the collective schedule (parsed from the partitioned HLO text),

which EXPERIMENTS.md §Dry-run and §Roofline are built from.

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun                   # all cells
    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-3b \
        --cell train_4k --mesh both
    PYTHONPATH=src python -m repro.launch.dryrun --out results.json
"""

import argparse
import json
import time
import traceback


# NOTE: jax and repro imports happen *after* the XLA_FLAGS line above —
# jax locks the device count on first init.
def _run():
    import jax

    from repro.configs import ARCHS, get
    from repro.launch.hlo_analysis import collective_stats, loop_aware_cost
    from repro.launch.mesh import make_mesh, make_production_mesh
    from repro.launch.steps import build_lowerable
    from repro.training.train_loop import TrainConfig

    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="all")
    p.add_argument("--cell", default="all")
    p.add_argument("--mesh", default="both", choices=["single", "multi",
                                                      "both"])
    p.add_argument("--out", default="benchmarks/results/dryrun.json")
    p.add_argument("--microbatches", type=int, default=16,
                   help="grad-accumulation for train cells (memory)")
    p.add_argument("--tuned", action="store_true",
                   help="per-arch optimized profile (EXPERIMENTS.md §Perf): "
                        "choose_mesh_shape factorization + Q-chunked causal "
                        "attention + microbatch-32")
    p.add_argument("--quiet", action="store_true")
    args = p.parse_args()

    arch_ids = list(ARCHS) if args.arch == "all" else [args.arch]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    results: dict[str, dict] = {}
    if args.out and os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)

    import dataclasses

    from repro.distributed.sharding import choose_mesh_shape

    n_ok = n_fail = 0
    for multi_pod in meshes:
        mesh = make_production_mesh(multi_pod=multi_pod)
        mesh_name = "2x16x16" if multi_pod else "16x16"
        n_chips = 512 if multi_pod else 256
        for arch_id in arch_ids:
            spec = get(arch_id)
            mb = args.microbatches
            if args.tuned:
                from repro.configs import TUNED_PROFILES
                prof = TUNED_PROFILES.get(arch_id)
                data_w, model_w = (prof["mesh"] if prof
                                   else choose_mesh_shape(spec.model, 256))
                shape = ((2, data_w, model_w) if multi_pod
                         else (data_w, model_w))
                axes = (("pod", "data", "model") if multi_pod
                        else ("data", "model"))
                mesh = make_mesh(shape, axes)
                mesh_name = ("2x" if multi_pod else "") \
                    + f"{data_w}x{model_w}"
                spec = dataclasses.replace(
                    spec, model=dataclasses.replace(
                        spec.model,
                        attn_q_chunks=(prof or {}).get("q_chunks", 4),
                        attn_chunk=(prof or {}).get("attn_chunk", 1024)))
                mb = (prof or {}).get("microbatches", 32)
            cells = ([c.name for c in spec.shapes()] if args.cell == "all"
                     else [args.cell])
            for cell_name in cells:
                if cell_name in spec.skip_shapes:
                    continue
                key = f"{arch_id}|{cell_name}|{mesh_name}"
                t0 = time.time()
                try:
                    low = build_lowerable(
                        spec, cell_name, mesh,
                        train=TrainConfig(microbatches=mb))
                    lowered = low.lower()
                    compiled = lowered.compile()
                    ma = compiled.memory_analysis()
                    ca = compiled.cost_analysis()
                    hlo_text = compiled.as_text()
                    stats = collective_stats(hlo_text)
                    cost = loop_aware_cost(hlo_text)
                    rec = {
                        "arch": arch_id, "cell": cell_name,
                        "mesh": mesh_name, "chips": n_chips,
                        "ok": True,
                        "compile_s": round(time.time() - t0, 1),
                        # loop-aware (while bodies × trip counts) — XLA's
                        # cost_analysis counts scan bodies once, which is
                        # useless for scan-over-layers models
                        "flops_per_device": cost.flops,
                        "bytes_per_device": cost.bytes_hbm,
                        "flops_xla_raw": ca.get("flops", 0.0),
                        "bytes_xla_raw": ca.get("bytes accessed", 0.0),
                        "transcendentals": ca.get("transcendentals", 0.0),
                        "arg_bytes": ma.argument_size_in_bytes,
                        "out_bytes": ma.output_size_in_bytes,
                        "temp_bytes": ma.temp_size_in_bytes,
                        "collective_bytes": stats.total_bytes,
                        "collectives": {k: [stats.count_by_kind[k],
                                            stats.bytes_by_kind[k]]
                                        for k in stats.bytes_by_kind},
                    }
                    n_ok += 1
                    if not args.quiet:
                        print(f"OK   {key:55s} {rec['compile_s']:6.1f}s "
                              f"flops={rec['flops_per_device']:.3g} "
                              f"temp={rec['temp_bytes']/1e9:.2f}GB "
                              f"coll={rec['collective_bytes']/1e6:.1f}MB "
                              f"[{stats.summary()}]", flush=True)
                except Exception as e:  # noqa: BLE001 — record and continue
                    rec = {"arch": arch_id, "cell": cell_name,
                           "mesh": mesh_name, "ok": False,
                           "error": f"{type(e).__name__}: {e}",
                           "compile_s": round(time.time() - t0, 1)}
                    n_fail += 1
                    print(f"FAIL {key}: {rec['error'][:300]}", flush=True)
                    if not args.quiet:
                        traceback.print_exc()
                results[key] = rec
                if args.out:
                    os.makedirs(os.path.dirname(args.out), exist_ok=True)
                    with open(args.out, "w") as f:
                        json.dump(results, f, indent=1, sort_keys=True)

    print(f"\ndry-run: {n_ok} ok, {n_fail} failed "
          f"({len(results)} cells recorded)")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(_run())
