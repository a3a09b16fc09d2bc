"""A whole run of the lfm2 cell on the CPU at a tiny size, through the
program's launcher and the harness: correct when the timed path is
sound, not correct with each fault a one-chip cell can have planted
under it."""

from __future__ import annotations

import contextlib
import dataclasses
import json
import pathlib
import time

import pytest

from chipbench import cell as cells
from chipbench import faults
from chipbench.measure import measure

ROOT = pathlib.Path(__file__).resolve().parents[3]
CELL = "lfm2-8b-a1b-b2-s8k"
INFO = {"platform": "cpu", "kind": "cpu", "count": 1}
# Three layers of width 64 (conv with the dense FFN, then attention and
# conv over routed experts), experts 2-5 of 8 held, top 2, 256 ids.
TINY = {"hidden_size": 64, "intermediate_size": 128,
        "moe_intermediate_size": 32, "num_hidden_layers": 3,
        "layer_types": ["conv", "full_attention", "conv"],
        "num_dense_layers": 1, "num_attention_heads": 4,
        "num_key_value_heads": 2, "vocab_size": 256, "num_experts": 4,
        "num_experts_per_tok": 2, "held_experts": {"first": 2},
        "published": {"num_experts": 8}}


@pytest.fixture
def tiny(monkeypatch):
    """The cell's configuration at the tiny size, and the launcher
    building the program's share at that size in float32."""
    from repro.configs.base import TrainConfig
    from repro.configs.registry import get_config
    from repro.launch import train as launcher

    config = json.loads(
        (ROOT / "benchmarks/chip/configs/lfm2-8b-a1b.json").read_text())
    config.update(TINY)
    program = dataclasses.replace(
        get_config(config["arch"]), n_layers=3, d_model=64, d_ff=128,
        d_ff_expert=32, layer_types=tuple(TINY["layer_types"]),
        n_heads=4, n_kv_heads=2, head_dim=16, vocab=256, n_experts=8,
        moe_top_k=2,
        experts_held=(2, 4))
    monkeypatch.setattr(launcher, "get_config", lambda arch: program)
    monkeypatch.setattr(
        launcher, "TrainConfig",
        lambda **kw: TrainConfig(**dict(kw, compute_dtype="float32")))
    return config


def run(config: dict, fault=None):
    cell = cells.load(ROOT, CELL)
    cell.config = config
    cell.traffic = dict(cell.traffic, batch=2, seq=16)
    peak = cells.peaks()["devices"]["TPU v5 lite"]
    with fault() if fault else contextlib.nullcontext():
        line, lines = measure(cell, 2**31 + 21, 0.3, False,
                              t0=time.perf_counter(), peak=peak,
                              device_info=INFO)
    return line, lines


def test_sound_run_is_correct_and_reports_its_metrics(tiny):
    line, lines = run(tiny)
    assert line["correct"], lines
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"step_ms_p95", "setup_s"}
    assert line["setup"]["window_compiles"] == 0


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "loss_altered"])
def test_fault_is_not_correct(tiny, fault):
    line, lines = run(tiny, faults.FAULTS[fault])
    assert not line["correct"], lines


def test_stages_hold_every_scope_with_a_kernel(tiny):
    from families import lfm2

    assert lfm2.stages(tiny) == [
        ("shortconv0", "shortconv"), ("ffn0", "ffn"), ("attn1", "attn"),
        ("experts1", "experts"), ("shortconv2", "shortconv"),
        ("experts2", "experts"), ("logits", "logits")]
    for name in ("route1", "attn1", "logits", "experts2", "shortconv0"):
        op = f"jit(train_step)/transpose(jvp({name}))/dot_general"
        assert lfm2.STAGE_PATTERN.search(op).group(1) == name
