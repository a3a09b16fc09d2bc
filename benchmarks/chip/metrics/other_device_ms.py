"""Device time per step of the operations under no stage scope (loss,
casts, optimizer), collectives left out, per chip."""


def read(r):
    if r.trace is None:
        return None
    ms = r.trace.stage_ms(["other"])
    return ms if ms > 0 else None
