"""One run of the data-parallel step on four virtual CPU devices at the
small size, with float32 compute; prints the result line.  Started in its own
process by test_bench_harness_dp.py, since the device count is fixed
when JAX starts:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python dp_run.py [fault]
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]


def main(fault: str | None) -> None:
    from chipbench import cell as cells
    from chipbench import faults
    from chipbench.measure import measure
    from repro.configs import registry
    from repro.configs.base import TrainConfig
    from repro.launch import train as launcher

    small = {"conv_layers": 2, "first_channels": 8, "last_channels": 16,
             "fc_hidden": 64, "classes": 10}
    program = dataclasses.replace(registry.get_config("cnn-vgg11"),
                                  n_layers=2, d_model=8, d_ff=64, vocab=10)
    launcher.get_config = lambda arch: program
    base = TrainConfig
    launcher.TrainConfig = lambda **kw: base(**dict(kw, compute_dtype="float32"))
    # The one-chip cell's configuration and limits on a 4x1 data mesh.
    cell = cells.load(ROOT, "vgg11-b256")
    cell.chips = 4
    cell.config = dict(cell.config, mesh="4x1", **small)
    cell.traffic = dict(cell.traffic, batch=16)
    peak = cells.peaks()["devices"]["TPU v5 lite"]
    plant = faults.FAULTS[fault]() if fault else contextlib.nullcontext()
    with plant:
        line, _ = measure(cell, 2**31 + 9, 0.3, False, t0=time.perf_counter(),
                          peak=peak, device_info={"platform": "cpu",
                                                  "kind": "cpu", "count": 4})
    print(json.dumps(line))


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else None)
