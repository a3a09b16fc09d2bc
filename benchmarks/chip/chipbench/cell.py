"""Find a cell's pieces by the names in ``BENCHMARK.json``.

A cell (one entry of ``workloads``) names a configuration and a traffic
mix.  Its pieces are files of their own beside this package:

* the configuration: the ``file`` that ``BENCHMARK.json`` gives for it;
* its model family:  ``families/<family>.py``, the configuration's
                     ``family`` (see ``families/__init__.py``);
* the traffic mix:   ``traffic/<traffic>.json``;
* the limits of the comparison that decides ``correct``:
                     ``limits/<cell>.json``;
* each metric's reader: ``metrics/<metric>.py``, a module with
  ``read(record) -> float | None``.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list  # metric entries of BENCHMARK.json this cell reports
    per_layer: list


def _for(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(root: pathlib.Path, workload: str) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; "
                         f"have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{w['traffic']}.json").read_text())
    limits = json.loads((HERE / "limits" / f"{workload}.json").read_text())
    end_to_end = [m for m in bench["end_to_end"] if _for(m, workload)]
    # A per-layer metric belongs to the cells that report what it moves.
    moved = {m["name"] for m in end_to_end}
    return Cell(
        name=workload, chips=int(w["chips"]), config=config, traffic=traffic,
        limits=limits["limits"], end_to_end=end_to_end,
        per_layer=[m for m in bench["per_layer"]
                   if m["moves"] in moved and _for(m, workload)])


def reader(metric: str):
    """The ``read`` function of ``metrics/<metric>.py``."""
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{metric}",
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def family(config: dict):
    """The module ``families/<config["family"]>.py``."""
    name = config["family"]
    have = sorted(p.stem for p in (HERE / "families").glob("*.py")
                  if p.stem != "__init__")
    if name not in have:
        raise SystemExit(f"no model family {name!r} in families/; have {have}")
    return importlib.import_module(f"families.{name}")


def peaks() -> dict:
    return json.loads((HERE / "peaks.json").read_text())
