"""Host time per round in the program's planning phases, the spans
``tenant_gemm.plan`` (checks, autotune, grid choice, partition state) and
``tenant_gemm.tables`` (the compact grid's index tables and their upload):
their sum over the traced window over its rounds."""

from chipbench import phases


def read(ctx):
    return phases.per_round_ms(ctx, "tenant_gemm.plan", "tenant_gemm.tables")
