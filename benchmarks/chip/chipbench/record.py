"""What one run measured, as the metric readers see it."""

from __future__ import annotations

import dataclasses

from chipbench import cell
from chipbench.trace import Reduction


@dataclasses.dataclass
class Record:
    config: dict
    traffic: dict
    batch: int  # examples per step, over all chips
    tokens: int | None  # tokens per step, over all chips, or None
    chips: int
    peak: dict  # this device's row of peaks.json
    setup_s: float  # process start to the window's first step
    intervals: list  # seconds of every step of the window
    trace: Reduction | None  # the traced window, or None untraced

    @property
    def window_s(self) -> float:
        return sum(self.intervals)

    @property
    def local_batch(self) -> int:
        return self.batch // self.chips

    @property
    def family(self):
        return cell.family(self.config)

    def stages(self, kind: str) -> list:
        """Names of the family's stages of one kind."""
        return [name for name, k in self.family.stages(self.config)
                if k == kind]

    @property
    def passes(self) -> list:
        """Every pass of one step on one chip's share of the batch."""
        return self.family.passes(self.config, self.traffic, self.local_batch)
