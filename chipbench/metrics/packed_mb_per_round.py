"""Megabytes of packed operands the program hands its kernel per round:
the stat ``packed_bytes`` of its ``tenant_gemm.pack`` spans, summed over
the traced window, over its rounds."""


def read(ctx):
    trace, rounds = ctx.trace or {}, len(ctx.window.round_ids)
    if "tenant_gemm.pack" not in (trace.get("span_s") or {}) or not rounds:
        return None
    return trace["packed_bytes"] / 1e6 / rounds
