"""One run of one cell: set-up, window, trace, comparison, result line."""

from __future__ import annotations

import gc
import json
import math
import pathlib
import shutil
import sys
import tempfile
import warnings

from chipbench import compare, loop, reference, trace, work
from chipbench.cell import Cell, family, reader
from chipbench.inputs import BatchPool
from chipbench.record import Record

TRACE_SECONDS = 3.0  # a traced window is at most this long
SEED_SPACE = 2**31  # the program's seed is the benchmark's seed modulo this
# A layer whose fit gate drops it to the XLA path is not the planned step
# this benchmark times: the run is refused.  The program's layers warn so
# (``UnfitScheduleWarning``, under every family); the warning is matched
# by its message, which names no layer.
UNFIT = r"backward schedule .* overflows VMEM"


def device_problem(devices, chips: int, peaks: dict) -> str | None:
    """Why the run cannot be measured here, or None."""
    if not devices or devices[0].platform != "tpu":
        platform = devices[0].platform if devices else "none"
        return f"no TPU: JAX's devices are {platform!r}"
    if len(devices) < chips:
        return f"the cell needs {chips} chips, JAX finds {len(devices)}"
    if devices[0].device_kind not in peaks["devices"]:
        return (f"device {devices[0].device_kind!r} is not in peaks.json "
                f"({sorted(peaks['devices'])})")
    return None


def launcher_argv(config: dict, traffic: dict, seed: int) -> list:
    """The launcher's command line for the configuration and traffic."""
    opt = config["optimizer"]
    return ["--arch", config["arch"], "--planned-kernels",
            "--mesh", config["mesh"], "--batch", str(traffic["batch"]),
            "--steps", str(opt["total_steps"]),
            "--lr", repr(opt["learning_rate"]), "--seed", str(seed),
            "--log-every", str(10**9), "--autotune", "off",
            *family(config).launcher_args(config, traffic)]


def _memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def step_hlo(result, mesh, batch: dict) -> str:
    """The compiled step's HLO text (read back from the compile cache)."""
    import jax

    shapes = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
              for k, v in batch.items()}
    with mesh:
        return result.step_fn.lower(result.state, shapes).compile().as_text()


def missing_kernels(hlo: str, stages, stage_pattern) -> list:
    """Stages and directions of the step that hold no planned Mosaic
    kernel: each stage's forward and backward must hold one."""
    want = {(s, d) for s in stages for d in ("fwd", "bwd")}
    return sorted(want - trace.kernel_sites(hlo, stage_pattern))


def _reduce_trace(trace_dir: str, hlo: str, stage_pattern):
    """The traced window's reduction, with the step's HLO for scopes."""
    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        return None
    chip_ops, modules = trace.read_xplane(
        str(files[-1]), trace.hlo_scopes(hlo), stage_pattern)
    return trace.reduce(chip_ops, modules)


def measure(cell: Cell, seed: int, seconds: float, traced: bool, *,
            t0: float, peak: dict, device_info: dict) -> tuple[dict, list]:
    """Run ``cell`` once.  ``t0`` is ``time.perf_counter()`` at process
    start.  Returns ``(result line, compared lines)``."""
    import jax

    config, traffic = cell.config, cell.traffic
    fam = family(config)
    program_seed = seed % SEED_SPACE
    fam.check_program(config)
    pool = BatchPool(config, traffic, seed)
    capture = loop.Capture(config["optimizer"]["beta1"])
    window = min(seconds, TRACE_SECONDS) if traced else seconds
    feed = loop.Feed(pool, int(traffic["warmup_steps"]), window,
                     capture.reads())
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if traced else None
    compiles = loop.CompileLog(feed)
    try:
        with compiles.listening(), warnings.catch_warnings():
            warnings.filterwarnings("error", message=UNFIT)
            result, warm = loop.launch(
                launcher_argv(config, traffic, program_seed), feed, trace_dir)
        intervals = feed.intervals
        setup_s = feed.marks[0] - t0
        devices = jax.devices()[:cell.chips]
        memory = _memory_peak(devices)
        hlo = step_hlo(result, feed.run.mesh, pool[0])
        stages = [name for name, _ in fam.stages(config)]
        missing = missing_kernels(hlo, stages, fam.STAGE_PATTERN)
        if missing and device_info["platform"] == "tpu":
            raise SystemExit(f"the compiled step holds no planned kernel for "
                             f"{missing}; nothing was measured")
        reduction = (_reduce_trace(trace_dir, hlo, fam.STAGE_PATTERN)
                     if traced else None)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    got = capture.side(warm)
    # Free the program's state before the reference runs.
    del result, feed.run
    gc.collect()
    want = reference.train(config, [pool[i] for i in
                                    range(loop.REFERENCE_STEPS)],
                           program_seed, device=devices[0])
    numbers = compare.readings(got, want)
    correct, lines = compare.judge(numbers, cell.limits)

    record = Record(config=config, traffic=traffic, batch=pool.batch,
                    tokens=fam.tokens(pool[0]), chips=cell.chips, peak=peak,
                    setup_s=setup_s, intervals=intervals, trace=reduction)
    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        value = reader(m["name"])(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = dict(device_info, memory_peak_bytes=memory)
    line = {"correct": correct, "attempted": len(intervals),
            "failed": feed.nonfinite,
            "metrics": metrics, "device": device}
    if reduction is not None:
        device.update(busy_s=reduction.busy_s, window_s=reduction.window_s)
        line["breakdown"] = {"device_ops": reduction.top("label_ns"),
                             "idle_gaps": reduction.top("gap_ns")}
        line["roofline_bounds"] = work.bounds(record.passes, peak, stages)
    line["setup"] = {"to_first_step_s": feed.first - t0,
                     "warmup_s": feed.marks[0] - feed.first,
                     "cache_hits": compiles.hits,
                     "cache_misses": compiles.misses,
                     "window_compiles": compiles.window_n}
    line["compared"] = {k: {"value": numbers[k], "limit": cell.limits[k]}
                        for k in compare.NUMBERS}
    return line, lines


def _finite(value):
    """The line with every non-finite number written as a string, so
    that it stays JSON."""
    if isinstance(value, dict):
        return {k: _finite(v) for k, v in value.items()}
    if isinstance(value, list):
        return [_finite(v) for v in value]
    if isinstance(value, float) and not math.isfinite(value):
        return repr(value)
    return value


def print_result(line: dict, lines: list) -> None:
    """The result line as the last line of standard output, the numbers
    compared as the last lines of standard error."""
    sys.stdout.flush()
    print(json.dumps(_finite(line)), flush=True)
    for text in lines:
        print(text, file=sys.stderr)
    sys.stderr.flush()
