"""Runs of a token cell from another checkout of the benchmark, on the
CPU, with float32 compute; prints one JSON line a run.  Started in its
own process by test_bench_token_cell.py, so that the harness it imports is
the one under ``root``:

    JAX_PLATFORMS=cpu python token_run.py <root> <cell> [fault ...]

The first run is sound; then one run under each named fault
(``chipbench/faults.py``).  The launcher builds the program's dense
decoder at the sizes of the cell's configuration file under the arch name
the file gives.
"""

from __future__ import annotations

import contextlib
import json
import pathlib
import sys
import time

PROGRAM = pathlib.Path(__file__).resolve().parents[3] / "src"


def main(root: str, workload: str, fault_names: list) -> None:
    root = pathlib.Path(root)
    sys.path[:0] = [str(root / "benchmarks" / "chip"), str(PROGRAM)]
    from chipbench import cell as cells
    from chipbench import faults
    from chipbench.measure import measure
    from repro.configs.base import ModelConfig, TrainConfig
    from repro.launch import train as launcher

    cell = cells.load(root, workload)
    c = cell.config
    program = ModelConfig(
        name=c["name"], family="dense", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], vocab=c["vocab_size"],
        d_ff=c["intermediate_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        qk_norm=c["qk_norm"], qkv_bias=c["attention_bias"],
        act=c["hidden_act"], norm_eps=c["rms_norm_eps"],
        tie_embeddings=c["tie_word_embeddings"],
        scale_embed=c["scale_embed"], rope_theta=c["rope_theta"])
    launcher.get_config = lambda arch: program
    launcher.TrainConfig = lambda **kw: TrainConfig(
        **dict(kw, compute_dtype="float32"))
    peak = cells.peaks()["devices"]["TPU v5 lite"]
    for name in [None, *fault_names]:
        plant = faults.FAULTS[name]() if name else contextlib.nullcontext()
        with plant:
            line, _ = measure(cell, 2**31 + 13, 0.3, False,
                              t0=time.perf_counter(), peak=peak,
                              device_info={"platform": "cpu", "kind": "cpu",
                                           "count": 1})
        print(json.dumps(dict(line, fault=name)), flush=True)


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], sys.argv[3:])
