"""Least time of the held experts' passes (each the larger of its compute
and HBM bound, from shapes at the mean routed rows, padding not credited)
over their measured device time."""

from chipbench import work


def read(r):
    if r.trace is None:
        return None
    stages = r.stages("experts")
    ms = r.trace.stage_ms(stages)
    if ms <= 0:
        return None
    return 100.0 * work.least_s(r.passes, r.peak, stages) * 1e3 / ms
