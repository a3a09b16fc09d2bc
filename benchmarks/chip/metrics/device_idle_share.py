"""Share of the traced window in which no operation runs on a chip,
averaged over the chips: 1 - busy union / window."""


def read(r):
    if r.trace is None:
        return None
    return 100.0 * (1.0 - r.trace.busy_s / r.trace.window_s)
