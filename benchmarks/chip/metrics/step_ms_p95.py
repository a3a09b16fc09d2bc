"""95th percentile of every step interval of the window, in ms (host
clock, loop boundary to loop boundary)."""

import numpy as np


def read(r):
    if not r.intervals:
        return None
    return float(np.percentile(np.asarray(r.intervals) * 1e3, 95))
