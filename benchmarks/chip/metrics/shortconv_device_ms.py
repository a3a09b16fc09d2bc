"""Device time per step of the operations under the short-convolution
blocks' scopes (``shortconv<i>``: in_proj, the taps and gates, out_proj,
the block's norm), forward and backward, per chip."""


def read(r):
    if r.trace is None:
        return None
    ms = r.trace.stage_ms(r.stages("shortconv"))
    return ms if ms > 0 else None
