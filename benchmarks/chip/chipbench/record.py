"""What one run measured, as the metric readers see it."""

from __future__ import annotations

import dataclasses

from chipbench.trace import Reduction


@dataclasses.dataclass
class Record:
    config: dict
    batch: int  # images per step, over all chips
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

    def stages(self, prefix: str) -> list:
        """Stage names of one kind: ``conv`` or ``fc``."""
        return self.stages_of(self.config, prefix)

    @staticmethod
    def stages_of(config: dict, prefix: str) -> list:
        n = config["conv_layers"] if prefix == "conv" else 2
        first = 0 if prefix == "conv" else 1
        return [f"{prefix}{i}" for i in range(first, first + n)]
