#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark once.

    python3 benchmarks/chip/run.py --workload vgg11-b256 --seed 7 \\
        --seconds 10 --trace 0

The cell's configuration, traffic, limits and metric readers are found by
the names in ``BENCHMARK.json``, and its model family (inputs, reference,
stages and work counts: ``families/<family>.py``) by the configuration's
``family`` (see ``chipbench/cell.py``).  The run trains through the
program's own launcher and loop, times the window, checks the first steps
against the family's plain float32 reference, and prints one
JSON object as the last line of standard output:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and with
``--trace 1`` a ``breakdown``), with the numbers compared last.  With
``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace.

Exits 2 without a result when JAX finds no TPU, fewer chips than the
cell asks for, or a device missing from ``peaks.json``.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

from chipbench import cell as cells  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = cells.load(ROOT, args.workload)
    peaks = cells.peaks()
    import jax

    from chipbench.measure import device_problem, measure, print_result

    devices = jax.devices()
    problem = device_problem(devices, cell.chips, peaks)
    if problem:
        print(f"run.py: {problem}; nothing was measured", file=sys.stderr)
        return 2

    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    line, lines = measure(cell, args.seed, args.seconds, bool(args.trace),
                          t0=T0, peak=peaks["devices"][info["kind"]],
                          device_info=info)
    print_result(line, lines)
    return 0


if __name__ == "__main__":
    sys.exit(main())
