#!/usr/bin/env python3
"""Read the numbers compared, to set a cell's limits (run on the chip).

    python3 benchmarks/chip/calibrate.py --workload vgg11-b256 \\
        --seeds 12 --control-seeds 3 --faults half_batch,loss_altered \\
        --fault-seeds 3 --out calibrate-vgg11-b256.json

In one process, for fresh seeds: the program's readings (the cell's own
path, a short window), the control's (the reference computed in float8 in
the program's place, on the same batches), and each named fault's
(planted under the program, see ``chipbench/faults.py``).  Writes every
reading, the lower reading (the largest of the program's) and the upper
reading of each number with where it comes from: the smallest of the
control's, where it is at least three times the lower, and of each
fault's that is at least ten times the lower.  A state left unchanged reads
1 on ``grad`` and ``update`` by construction and needs no run.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

from chipbench import cell as cells  # noqa: E402

FIRST_SEED = 2**31 + 1000  # calibration seeds: FIRST_SEED + i


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=FIRST_SEED)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax

    from chipbench import compare, faults, inputs, loop, reference
    from chipbench.measure import SEED_SPACE, device_problem, measure

    cell = cells.load(ROOT, args.workload)
    peaks = cells.peaks()
    devices = jax.devices()
    problem = device_problem(devices, cell.chips, peaks)
    if problem:
        print(f"calibrate.py: {problem}", file=sys.stderr)
        return 2
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    peak = peaks["devices"][info["kind"]]
    seeds = [args.first_seed + i for i in range(args.seeds)]
    out = {"workload": args.workload, "device": info, "program": {},
           "control": {}, "faults": {}}

    def program(seed):
        t = time.perf_counter()
        line, _ = measure(cell, seed, 0.5, False, t0=t, peak=peak,
                          device_info=info)
        return {k: v["value"] for k, v in line["compared"].items()}

    for seed in seeds:
        out["program"][seed] = program(seed)
        print(f"program seed {seed}: {out['program'][seed]}", flush=True)
    for name in filter(None, args.faults.split(",")):
        out["faults"][name] = {}
        for seed in seeds[:args.fault_seeds]:
            with faults.FAULTS[name]():
                out["faults"][name][seed] = program(seed)
            print(f"fault {name} seed {seed}: {out['faults'][name][seed]}",
                  flush=True)
    config, traffic = cell.config, cell.traffic
    for seed in seeds[:args.control_seeds]:
        pool = inputs.BatchPool(config, dict(traffic, distinct_batches=(
            loop.REFERENCE_STEPS)), seed)
        batches = [pool[i] for i in range(loop.REFERENCE_STEPS)]
        want = reference.train(config, batches, seed % SEED_SPACE)
        got = reference.train(config, batches, seed % SEED_SPACE,
                              precision="fp8")
        out["control"][seed] = compare.readings(got, want)
        print(f"control seed {seed}: {out['control'][seed]}", flush=True)

    lower = {k: max(r[k] for r in out["program"].values())
             for k in compare.NUMBERS}
    upper, upper_from = {}, {}
    for k in compare.NUMBERS:
        cands = {}
        if out["control"]:
            least = min(r[k] for r in out["control"].values())
            if least >= 3 * lower[k]:
                cands["control"] = least
        for name, runs in out["faults"].items():
            least = min(r[k] for r in runs.values())
            if least >= 10 * lower[k]:
                cands[name] = least
        if k in ("grad", "update") and 1.0 >= 3 * lower[k]:
            cands["state_unchanged"] = 1.0  # reads 1 by construction
        upper_from[k] = min(cands, key=cands.get) if cands else None
        upper[k] = cands.get(upper_from[k])
    out["upper_from"] = upper_from
    out["lower"], out["upper"] = lower, upper
    path = pathlib.Path(args.out)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(out, indent=1))
    print(json.dumps({"lower": lower, "upper": upper,
                      "upper_from": upper_from}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
