"""A whole run of a cell on the CPU at the small size, past the look for
a chip: correct when the timed path is sound, not correct with each
fault planted under it."""

from __future__ import annotations

import contextlib
import pathlib
import time

import pytest

from chipbench import cell as cells
from chipbench import faults
from chipbench.measure import measure

ROOT = pathlib.Path(__file__).resolve().parents[3]
INFO = {"platform": "cpu", "kind": "cpu", "count": 1}


def run(name: str, config: dict, batch: int, fault=None, traced=False):
    cell = cells.load(ROOT, name)
    cell.config = config
    cell.traffic = dict(cell.traffic, batch=batch)
    peak = cells.peaks()["devices"]["TPU v5 lite"]
    with fault() if fault else contextlib.nullcontext():
        line, lines = measure(cell, 2**31 + 5, 0.3, traced,
                              t0=time.perf_counter(), peak=peak,
                              device_info=INFO)
    return line, lines


def test_sound_run_is_correct_and_reports_its_metrics(small_program,
                                                      small_config):
    line, lines = run("vgg11-b256", small_config, 8)
    assert line["correct"], lines
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"images_per_s", "step_ms_p95", "setup_s"}
    assert list(line)[-1] == "compared"
    assert line["setup"]["window_compiles"] == 0
    assert [x.split()[0] for x in lines] == ["loss", "grad", "update"]


def test_traced_run_off_the_chip_reads_no_layer(small_program, small_config):
    # The CPU's trace has no TPU device plane: every reader finds nothing,
    # and no share of a roofline or a peak is made up.
    line, lines = run("vgg11-b256", small_config, 8, traced=True)
    assert line["correct"], lines
    assert line["metrics"] == {}
    assert "breakdown" not in line


# The numbers compared in the sound small run, as the harness read them
# before the CNN's pieces moved into families/cnn.py (parent commit of
# that move, this seed and size, on the CPU): the move changed no
# arithmetic of either side.
PINNED = {"loss": "0x1.b6b02eb652db5p-24", "grad": "0x1.44a9b44180e68p-21",
          "update": "0x1.cf81b1e30428ep-24"}


def test_sound_run_compares_the_numbers_pinned_before_the_family_move(
        small_program, small_config):
    line, lines = run("vgg11-b256", small_config, 8)
    got = {k: v["value"].hex() for k, v in line["compared"].items()}
    assert got == PINNED, lines


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "loss_altered"])
def test_fault_is_not_correct(small_program, small_config, fault):
    line, lines = run("vgg11-b256", small_config, 8, faults.FAULTS[fault])
    assert not line["correct"], lines


def test_no_tpu_exits_without_a_result():
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks/chip/run.py"), "--workload",
         "vgg11-b256", "--seed", str(2**31 + 3), "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert out.returncode == 2
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr
