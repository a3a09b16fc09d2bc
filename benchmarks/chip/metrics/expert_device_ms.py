"""Device time per step of the operations under the held experts' scopes
(``experts<i>``: the grouped GEMMs and the gate between them), forward
and backward, per chip."""


def read(r):
    if r.trace is None:
        return None
    ms = r.trace.stage_ms(r.stages("experts"))
    return ms if ms > 0 else None
