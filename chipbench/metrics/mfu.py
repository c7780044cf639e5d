"""Whole-pass share of the chip's bf16 peak: the useful operations, as the
layers' kinds count them, completed in the traced window over the window's
seconds times the peak."""


def read(ctx):
    if ctx.peaks is None or ctx.window.seconds <= 0:
        return None
    return 100.0 * ctx.window.flops / (
        ctx.window.seconds * ctx.peaks["bf16_flops_per_s"])
