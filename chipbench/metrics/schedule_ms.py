"""Host time of the program's ``Session`` scheduling call in set-up."""


def read(ctx):
    return ctx.schedule_s * 1e3
