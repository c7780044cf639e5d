"""Share of the device's busy time in the traced window that the rounds'
work needs at the chip's peaks: the sum over the window's rounds of
``max(flops / peak_flops, bytes / peak_bytes)``, counted by
``chipbench.work`` from the unpadded slices, over the busy time."""

from chipbench import work


def read(ctx):
    if ctx.trace is None or ctx.peaks is None or ctx.trace["busy_s"] <= 0:
        return None
    least = sum(work.least_seconds(ctx.plan, ctx.plan.rounds[r],
                                   ctx.peaks["bf16_flops_per_s"],
                                   ctx.peaks["hbm_bytes_per_s"])
                for r in ctx.window.round_ids)
    return 100.0 * least / ctx.trace["busy_s"]
