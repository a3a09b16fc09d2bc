"""A whole CPU run of a token cell of the transformer family, in a copy of
the benchmark to which only new files and new entries are added: what a
configuration of a new family brings.  Correct when the timed path is
sound, not correct with each fault a one-chip cell can have planted under
it (``no_exchange`` needs several chips: test_bench_harness_dp.py)."""

from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys

HERE = pathlib.Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parents[1]
CELL = "tiny-dense-b4-s16"
FAULTS = ("state_unchanged", "half_batch", "loss_altered")


def copy_with_cell(dest: pathlib.Path) -> pathlib.Path:
    """``dest`` holding BENCHMARK.json and the benchmark's files, with the
    files of ``token_cell/`` added and its entries appended: a config, a
    cell and the end-to-end metric ``tokens_per_s`` that the cell reports."""
    shutil.copytree(BENCH, dest / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for kind in ("configs", "traffic", "limits"):
        for src in (HERE / "token_cell" / kind).iterdir():
            target = dest / "benchmarks" / "chip" / kind / src.name
            assert not target.exists(), target
            shutil.copy(src, target)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    add = json.loads((HERE / "token_cell" / "benchmark.json").read_text())
    names = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]}
    assert not names & {m["name"] for m in add["end_to_end"]}
    bench["configs"] += add["configs"]
    bench["workloads"] += add["workloads"]
    bench["end_to_end"] += add["end_to_end"]
    (dest / "BENCHMARK.json").write_text(json.dumps(bench, indent=2))
    return dest


def test_token_cell_added_alone_runs_correct_and_fails_each_fault(tmp_path):
    root = copy_with_cell(tmp_path)
    # Every file of the benchmark is as it was; only files were added.
    for src in BENCH.rglob("*"):
        if src.is_file() and "__pycache__" not in src.parts:
            copy = root / src.relative_to(ROOT)
            assert copy.read_bytes() == src.read_bytes(), src
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(HERE / "token_run.py"), str(root), CELL,
         *FAULTS], env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    lines = [json.loads(x) for x in out.stdout.splitlines()
             if x.startswith("{")]
    assert [x["fault"] for x in lines] == [None, *FAULTS]
    sound, *planted = lines
    assert sound["correct"], sound["compared"]
    assert set(sound["metrics"]) == {"tokens_per_s", "step_ms_p95",
                                     "setup_s"}
    assert sound["metrics"]["tokens_per_s"]["unit"] == "tokens/s"
    assert sound["attempted"] > 0 and sound["failed"] == 0
    for line in planted:
        assert not line["correct"], (line["fault"], line["compared"])
