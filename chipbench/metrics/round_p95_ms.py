"""95th percentile of one round's latency, over every round of the window:
from the call into the program to all its outputs ready."""

import numpy as np


def read(ctx):
    lat = ctx.window.latency_s
    return float(np.percentile(lat, 95)) * 1e3 if lat else None
