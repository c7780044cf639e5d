"""Share of the device's busy time in the traced window spent in the fused
kernels: the union of device operations named ``tenant_gemm_dense`` or
``tenant_gemm_compact`` over the union of all of them.  None on a trace
with no device plane, or with no operation of that name."""


def read(ctx):
    t = ctx.trace
    if not t or not t.get("device_plane") or t["busy_s"] <= 0 \
            or not t.get("kernel_busy_s"):
        return None
    return 100.0 * t["kernel_busy_s"] / t["busy_s"]
