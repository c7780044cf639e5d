"""Share of the attention rounds' latency that their work needs at the
chip's peaks: over the window's rounds whose slices are all attention
cores (kind ``attention``), the least time their operations (visible
pairs only) and bytes take at the peaks over their latency on the host
clock.  A round's latency includes its host launch and the rotary
embedding before the kernel, so this reads the round and not the
``attention_prefill`` kernel alone; the kernel's share of its roofline
waits for a reader of the device trace (see
``chipbench/kind_roofline.py``)."""

from chipbench.kind_roofline import round_share


def read(ctx):
    return round_share(ctx, "attention")
