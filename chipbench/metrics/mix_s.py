"""Chip wall time per pass of the mix: the window's seconds over the passes
completed in it, the pass under way counted by its share of the pass's
useful GEMM operations done."""


def read(ctx):
    w = ctx.window
    return w.seconds / w.passes if w.passes > 0 else None
