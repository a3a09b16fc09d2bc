"""The seeded image batches the training loop is fed.

The arithmetic is that of the program's synthetic image source (standard
normal pixels, each image's mean shifted by its class), kept here so that
the benchmark owns its inputs.  Every batch of a pool is drawn in set-up,
so the measured window spends no host time making data.
"""

from __future__ import annotations

import numpy as np


def make_batch(seed: int, index: int, batch: int, image: int, channels: int,
               classes: int) -> dict:
    """One batch: ``images`` f32 [batch, image, image, channels] and
    ``labels`` int32 [batch], drawn from ``(seed, index)`` alone."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, index]))
    labels = rng.integers(0, classes, size=(batch,)).astype(np.int32)
    images = rng.standard_normal(
        (batch, image, image, channels)).astype(np.float32)
    images += (labels / max(1, classes - 1) - 0.5)[:, None, None, None]
    return {"images": images, "labels": labels}


class BatchPool:
    """``distinct`` different batches of one size, handed out in turn:
    step ``k`` gets batch ``k % distinct``."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.batch = int(traffic["batch"])
        self.batches = [
            make_batch(seed, i, self.batch, config["image_size"],
                       config["in_channels"], config["classes"])
            for i in range(int(traffic["distinct_batches"]))]

    def __len__(self) -> int:
        return len(self.batches)

    def __getitem__(self, step: int) -> dict:
        return self.batches[step % len(self.batches)]
