"""Share of the routed-expert rounds' latency that their work needs at the
chip's peaks: over the window's rounds whose slices are all routed-expert
layers (kind ``experts``), the least time their operations and bytes take
at the peaks over their latency on the host clock.  A round's latency
includes its host launch (checks, packing, the two fused calls' dispatch),
which takes most of it, so this reads the round and not the experts'
kernels: a faster kernel hardly moves it.  The kernels' share of their
roofline waits for a reader of the device trace (see
``chipbench/kind_roofline.py``)."""

from chipbench.kind_roofline import round_share


def read(ctx):
    return round_share(ctx, "experts")
