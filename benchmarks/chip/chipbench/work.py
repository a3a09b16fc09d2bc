"""Operations and bytes of a training step's passes, and their least time.

The family counts each pass from layer shapes alone
(``families/<family>.py`` ``passes``); this module turns those counts
into a least time on a chip and names the bound that sets it.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Pass:
    stage: str  # the stage (a family's named scope) the pass belongs to
    kind: str  # fwd, or the gradient it computes (dgrad, wgrad, dx, dw, bwd)
    flops: float
    bytes: float

    def least_s(self, peak: dict) -> float:
        """Least time on a chip: the larger of the compute bound and the
        HBM bound."""
        return max(self.flops / peak["bf16_flops_per_s"],
                   self.bytes / peak["hbm_bytes_per_s"])

    def bound(self, peak: dict) -> str:
        compute = self.flops / peak["bf16_flops_per_s"]
        memory = self.bytes / peak["hbm_bytes_per_s"]
        return "compute" if compute >= memory else "memory"


def least_s(passes, peak: dict, stages) -> float:
    """Least time of the named stages' passes on one chip."""
    return sum(p.least_s(peak) for p in passes if p.stage in stages)


def bounds(passes, peak: dict, stages) -> dict:
    """Which bound sets each pass's least time: ``{"<stage>.<kind>": ...}``."""
    return {f"{p.stage}.{p.kind}": p.bound(peak)
            for p in passes if p.stage in stages}
