"""Shared pieces of the benchmark's CPU tests: the package on the path,
and a cell of the CNN at a size the CPU runs in seconds."""

from __future__ import annotations

import dataclasses
import json
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
for p in (str(HERE), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# The smoke size of the CNN: two conv stages, 8 -> 16 channels on 32x32x3,
# fc 1024 -> 64 -> 10.
SMALL = {"conv_layers": 2, "first_channels": 8, "last_channels": 16,
         "fc_hidden": 64, "classes": 10}


@pytest.fixture
def small_config():
    """The cnn-vgg11 configuration file at the small size."""
    config = json.loads((HERE / "configs" / "cnn-vgg11.json").read_text())
    config.update(SMALL)
    return config


@pytest.fixture
def small_program(monkeypatch):
    """The launcher builds the CNN at the small size under the arch name
    the configuration gives, and computes in float32: at this size the
    bfloat16 path's rounding is not what the tests look at, and a sound
    run must read far inside the limits set for the full size."""
    from repro.configs import registry
    from repro.configs.base import TrainConfig
    from repro.launch import train as launcher

    full = registry.get_config("cnn-vgg11")
    small = dataclasses.replace(full, n_layers=SMALL["conv_layers"],
                                d_model=SMALL["first_channels"],
                                d_ff=SMALL["fc_hidden"],
                                vocab=SMALL["classes"])
    monkeypatch.setattr(launcher, "get_config", lambda arch: small)
    monkeypatch.setattr(
        launcher, "TrainConfig",
        lambda **kw: TrainConfig(**dict(kw, compute_dtype="float32")))
    return small
