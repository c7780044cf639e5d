"""Host time per round in the program's span ``tenant_gemm.kernel``: the
kernel call, dense or compact, with any tracing and lowering it does, over
the traced window's rounds."""

from chipbench import phases


def read(ctx):
    return phases.per_round_ms(ctx, "tenant_gemm.kernel")
