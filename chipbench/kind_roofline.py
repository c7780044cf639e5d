"""The roofline share of the window's rounds of one layer kind, on the host
clock: for the per-kind metrics under ``metrics/``.

This is a share of a whole round, not a kernel's roofline: the denominator
is the round's latency from the call to all outputs ready, host launch
included.  Where the launch dominates the round, as the 64-way fused
calls' packing does in an expert round, the share moves with the launch
and hardly with the kernels.  The kernels' own device time cannot be read
here yet: the reduced trace keeps only the ten busiest device ops by name,
and the expert calls' kernel is named ``tenant_gemm_dense`` like every
projection GEMM's, so a kernel roofline needs a reader of the full device
trace that splits it by round."""

from __future__ import annotations

from chipbench import work


def round_share(ctx, kind: str) -> float | None:
    """Over the window's rounds whose slices are all of layer kind ``kind``
    (its module's name under ``chipbench/kinds/``), the sum of ``max(flops
    / peak_flops, bytes / peak_bytes)``, as the kind counts them from the
    unpadded shapes, over the sum of those rounds' latencies, in %.  None
    where the window has no such round or the device has no peaks."""
    if ctx.peaks is None:
        return None
    least = seconds = 0.0
    for r, lat in zip(ctx.window.round_ids, ctx.window.latency_s):
        rnd = ctx.plan.rounds[r]
        if all(ctx.plan.layers[s.layer].kind.__name__.rsplit(".", 1)[-1]
               == kind for s in rnd):
            least += work.least_seconds(ctx.plan, rnd,
                                        ctx.peaks["bf16_flops_per_s"],
                                        ctx.peaks["hbm_bytes_per_s"])
            seconds += lat
    return 100.0 * least / seconds if seconds > 0 else None
