"""The launcher's data-parallel step (a 4x1 mesh) on four virtual CPU
devices: correct, and not correct when the sum across chips is left
out."""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

import pytest

SCRIPT = pathlib.Path(__file__).resolve().parent / "dp_run.py"


def run(fault: str | None) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, str(SCRIPT)] + ([fault] if fault else []),
                         env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault, correct", [(None, True),
                                            ("no_exchange", False)])
def test_data_parallel_run(fault, correct):
    line = run(fault)
    assert line["correct"] is correct, line["compared"]
    assert line["attempted"] > 0
