"""Mean host time per round from the call into ``fused_tenant_gemm`` to its
return, before waiting for the outputs: autotune, packing, index tables,
lowering and dispatch."""


def read(ctx):
    s = ctx.window.launch_s
    return sum(s) / len(s) * 1e3 if s else None
