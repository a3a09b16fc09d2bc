"""Device time per step of the operations under the conv stages' scopes,
forward and backward, per chip."""


def read(r):
    if r.trace is None:
        return None
    ms = r.trace.stage_ms(r.stages("conv"))
    return ms if ms > 0 else None
