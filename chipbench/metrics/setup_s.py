"""Set-up: from the start of the run to the window (device start, schedule,
operands, warm-up with any compilation)."""


def read(ctx):
    return ctx.setup_s
