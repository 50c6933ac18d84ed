"""95th percentile (linear interpolation) of the wall time of all the
window's balancer rounds, in ms (host clock)."""

import numpy as np


def read(ctx):
    if not ctx.requests:
        return None
    return float(np.percentile([r.seconds for r in ctx.requests], 95)) * 1e3
