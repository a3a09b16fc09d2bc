"""Device time per step of the operations under the FC stages' scopes,
forward and backward, per chip."""


def read(r):
    if r.trace is None:
        return None
    ms = r.trace.stage_ms(r.stages("fc"))
    return ms if ms > 0 else None
