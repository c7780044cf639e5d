"""Host time per round in the program's packing phases, the spans
``tenant_gemm.pack`` (pads, stack and concatenate of the operands) and
``tenant_gemm.unpack`` (the compact mask and the per-tenant output
slices): their sum over the traced window over its rounds."""

from chipbench import phases


def read(ctx):
    return phases.per_round_ms(ctx, "tenant_gemm.pack", "tenant_gemm.unpack")
