"""The seeded batches the training loop is fed.

The family draws each batch (``families/<family>.py`` ``make_batch``).
Every batch of a pool is drawn in set-up, so the measured window spends
no host time making data.
"""

from __future__ import annotations

from chipbench import cell


class BatchPool:
    """``distinct`` different batches of one size, handed out in turn:
    step ``k`` gets batch ``k % distinct``."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        family = cell.family(config)
        self.batch = int(traffic["batch"])
        self.batches = [family.make_batch(seed, i, config, traffic)
                        for i in range(int(traffic["distinct_batches"]))]

    def __len__(self) -> int:
        return len(self.batches)

    def __getitem__(self, step: int) -> dict:
        return self.batches[step % len(self.batches)]
