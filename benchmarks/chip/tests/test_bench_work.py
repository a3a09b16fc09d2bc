"""Work counts, the roofline arithmetic, and the benchmark's files."""

from __future__ import annotations

import json

import pathlib

import pytest

from chipbench import cell, work
from families import cnn

HERE = pathlib.Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
CONFIG = json.loads((HERE / "configs" / "cnn-vgg11.json").read_text())
TRAFFIC = json.loads((HERE / "traffic" / "b256.json").read_text())
PEAK = cell.peaks()["devices"]["TPU v5 lite"]


def test_parameter_count():
    # conv 3x3: 3->64, 64->128, 128->256, 256->512; fc 2048->4096->1000;
    # every layer with its bias.
    want = ((27 * 64 + 64) + (576 * 128 + 128) + (1152 * 256 + 256)
            + (2304 * 512 + 512) + (2048 * 4096 + 4096) + (4096 * 1000 + 1000))
    assert want == 14_040_680
    assert cnn.param_count(CONFIG) == want == CONFIG["parameters"]


def test_training_flops_per_image():
    # Forward MACs per image: conv0 32*32*64*27, conv1..3 18,874,368 each,
    # fc1 2048*4096, fc2 4096*1000.  Training is 3x forward, less conv0's
    # input gradient: 421.7 MFLOP.
    fwd_macs = 32 * 32 * 64 * 27 + 3 * 18_874_368 + 2048 * 4096 + 4096 * 1000
    want = 2 * (3 * fwd_macs - 32 * 32 * 64 * 27)
    assert want == 421_724_160
    assert cnn.step_flops(CONFIG, TRAFFIC, 1) == want
    assert cnn.step_flops(CONFIG, TRAFFIC, 256) == 256 * want
    conv = sum(p.flops for p in cnn.passes(CONFIG, TRAFFIC, 1)
               if p.stage.startswith("conv") and p.kind == "fwd")
    fwd = sum(p.flops for p in cnn.passes(CONFIG, TRAFFIC, 1)
              if p.kind == "fwd")
    assert conv / fwd == pytest.approx(0.8238, abs=1e-4)


def test_roofline_arithmetic():
    # conv1 forward at batch 256: 2*256*32*32... on a 16x16 plane.
    p = next(p for p in cnn.passes(CONFIG, TRAFFIC, 256)
             if (p.stage, p.kind) == ("conv1", "fwd"))
    assert p.flops == 2 * 256 * 16 * 16 * 9 * 64 * 128
    x, w, y = 256 * 16 * 16 * 64, 9 * 64 * 128, 256 * 8 * 8 * 128
    assert p.bytes == 2 * (x + w + y)
    assert p.least_s(PEAK) == pytest.approx(p.flops / 197e12)
    assert p.bound(PEAK) == "compute"
    # fc2's weight gradient at batch 32 is bound by HBM: its bytes over
    # 819 GB/s outlast its operations over 197 TFLOP/s.
    q = next(p for p in cnn.passes(CONFIG, TRAFFIC, 32)
             if (p.stage, p.kind) == ("fc2", "dw"))
    assert q.bytes == 2 * (32 * 4096 + 32 * 1000 + 4096 * 1000)
    assert q.least_s(PEAK) == pytest.approx(q.bytes / 819e9)
    assert q.bound(PEAK) == "memory"
    stages = ["fc1", "fc2"]
    passes = cnn.passes(CONFIG, TRAFFIC, 32)
    assert work.least_s(passes, PEAK, stages) == pytest.approx(
        sum(p.least_s(PEAK) for p in passes if p.stage in stages))


def test_every_cell_finds_its_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        c = cell.load(ROOT, w["name"])
        assert set(c.limits) == {"loss", "grad", "update"}
        assert (cell.family(c.config).param_count(c.config)
                == c.config["parameters"])
        assert c.traffic["batch"] % c.chips == 0
        for m in c.end_to_end + c.per_layer:
            assert callable(cell.reader(m["name"]))
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
