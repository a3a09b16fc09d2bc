#!/usr/bin/env python3
"""Trace one window of a cell with the program's host spans recorded.

    python3 benchmarks/chip/trace_host.py --workload vgg11-b256 --seed 7 \\
        --seconds 3 --host-level 1

Runs the cell's launcher and loop as ``run.py --trace 1`` does (same
inputs, warm-up and window), with the program's span recorder
(``repro.runtime.spans``) installed from the start and the profiler's
host tracer at ``--host-level``.  It runs no reference, so it decides
nothing about ``correct``.  Prints one JSON object as the last line of
standard output: the host spans' means over the window's steps
(``host_input_ms``, ``host_dispatch_ms``, ``host_wait_ms``), the set-up
spans (``setup_init_s``: ``setup.init``; ``setup_compile_s``: the first
step's ``step.dispatch``), the device time of each kind of the family's
stages (``<kind>_device_ms``), the rest of the step by scope
(``loss_device_ms``, ``optim_device_ms``, beside ``other_device_ms``),
the window's idle time by host span (``idle_by_host_span``,
``idle_in_input_ms``, with the share of executions outside every
dispatch and wait span), and the loop's mean step on the feed's clock.

Exits 2 without a result when JAX finds no TPU, as ``run.py`` does.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from unittest import mock  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

from chipbench import cell as cells  # noqa: E402


def traced(cell, seed: int, seconds: float, host_level: int) -> dict:
    """One traced window of ``cell``; the result line's numbers."""
    import jax

    from chipbench import hostspans, loop, measure, trace
    from chipbench.inputs import BatchPool
    from repro.runtime import spans

    config, traffic = cell.config, cell.traffic
    family = cells.family(config)
    start_trace = jax.profiler.start_trace

    # ``loop.launch`` starts the profiler with the host tracer off; the
    # level asked for replaces it, and nothing else of its options.
    def at_level(log_dir, profiler_options):
        profiler_options.host_tracer_level = host_level
        start_trace(log_dir, profiler_options=profiler_options)

    trace_dir = tempfile.mkdtemp(prefix="chipbench-host-")
    try:
        with spans.recording() as rec:
            pool = BatchPool(config, traffic, seed)
            warmup = int(traffic["warmup_steps"])
            feed = loop.Feed(pool, warmup, seconds, {})
            with mock.patch.object(jax.profiler, "start_trace", at_level):
                result, _ = loop.launch(measure.launcher_argv(
                    config, traffic, seed % measure.SEED_SPACE), feed,
                    trace_dir)
        hlo = measure.step_hlo(result, feed.run.mesh, pool[0])
        files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
        path = str(files[-1])
        chip_ops, modules = trace.read_xplane(
            path, hostspans.rest_scopes(trace.hlo_scopes(hlo),
                                        family.STAGE_PATTERN),
            family.STAGE_PATTERN)
        reduction = trace.reduce(chip_ops, modules)
        idle = hostspans.idle_by_host_span(chip_ops, modules,
                                           hostspans.read_host_spans(path))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)

    n = {name: hostspans.host_ms(rec.spans, name, warmup)
         for name in hostspans.PARTS}
    line = {
        "host_level": host_level,
        "window_steps": len(feed.intervals),
        "loop_step_ms": 1e3 * sum(feed.intervals) / len(feed.intervals),
        "host_input_ms": n["step.input"],
        "host_dispatch_ms": n["step.dispatch"],
        "host_wait_ms": n["step.wait"],
        "setup_init_s": hostspans.first(rec.spans, "setup.init"),
        "setup_compile_s": hostspans.first(rec.spans, "step.dispatch"),
        "setup_s": feed.marks[0] - T0,
    }
    if reduction is not None:
        kinds = {}
        for name, kind in family.stages(config):
            kinds.setdefault(kind, []).append(name)
        line.update(
            device_idle_share=100.0 * (1 - reduction.busy_s
                                       / reduction.window_s),
            **{f"{kind}_device_ms": reduction.stage_ms(names)
               for kind, names in kinds.items()},
            other_device_ms=reduction.stage_ms(["other"]),
            loss_device_ms=hostspans.rest_ms(reduction, "loss"),
            optim_device_ms=hostspans.rest_ms(reduction, "optimizer"),
            idle_gaps=reduction.top("gap_ns"))
    if idle is not None:
        line.update(idle_in_input_ms=idle.per_step_ms["step.input"],
                    idle_by_host_span=idle.idle_s,
                    idle_by_host_span_ms=idle.per_step_ms,
                    unaligned=idle.unaligned)
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--host-level", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)

    cell = cells.load(ROOT, args.workload)
    import jax

    from chipbench.measure import device_problem

    devices = jax.devices()
    problem = device_problem(devices, cell.chips, cells.peaks())
    if problem:
        print(f"trace_host.py: {problem}; nothing was measured",
              file=sys.stderr)
        return 2
    line = traced(cell, args.seed, args.seconds, args.host_level)
    line["device"] = {"platform": devices[0].platform,
                      "kind": devices[0].device_kind, "count": len(devices)}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
