"""The numbers that decide ``correct``, and their limits.

Both sides train the same first steps from the same seed on the same
batches.  Three numbers compare them:

* ``loss``   - the largest relative gap of a step's loss;
* ``grad``   - the first step's gradient as the optimizer gets it (after
               clipping), by the worst leaf: the gap between the two norms
               of the leaf, over the larger of the reference's norm of that
               leaf and of the median leaf;
* ``update`` - the same for the parameters' change over all the steps,
               over the leaves whose reference gradient is at least a
               thousandth of the median leaf's (the others move under Adam
               by rounding alone).

Each is held to the cell's limit; a number that is not finite fails.
"""

from __future__ import annotations

import math
import statistics

NUMBERS = ("loss", "grad", "update")
MOVING = 1e-3  # a leaf moves when its gradient norm is >= this x the median


def worst_leaf(got: dict, want: dict, leaves) -> float:
    leaves = list(leaves)
    median = statistics.median(want[k] for k in leaves)
    gaps = [abs(got[k] - want[k]) / max(want[k], median, 1e-30)
            for k in leaves]
    return max(gaps) if all(map(math.isfinite, gaps)) else math.inf


def readings(got, want) -> dict:
    """``got`` and ``want`` are :class:`chipbench.reference.Side`;
    ``want`` is the float32 reference."""
    gaps = [abs(a - b) / max(abs(b), 1e-30)
            for a, b in zip(got.losses, want.losses, strict=True)]
    loss = max(gaps) if all(map(math.isfinite, gaps)) else math.inf
    raw = want.grad_raw
    median = statistics.median(raw.values())
    moving = [k for k, g in raw.items() if g >= MOVING * median]
    out = {"loss": loss,
           "grad": worst_leaf(got.grad, want.grad, want.grad),
           "update": worst_leaf(got.update, want.update, moving)}
    return {k: (v if math.isfinite(v) else math.inf) for k, v in out.items()}


def judge(numbers: dict, limits: dict) -> tuple[bool, list]:
    """(every number within its limit, one ``name number limit`` line
    each)."""
    ok = True
    lines = []
    for k in NUMBERS:
        within = numbers[k] <= limits[k]
        ok = ok and within
        lines.append(f"{k} {numbers[k]!r} limit {limits[k]!r}"
                     f"{'' if within else ' FAILED'}")
    return ok, lines
