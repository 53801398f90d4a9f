"""Find a workload's configuration, traffic mix and overrides by name.

``BENCHMARK.json`` at the checkout's root names each cell's configuration
and traffic mix; their files live here as data:

* ``configs/<config>.json``: the model's published config keys, plus
  ``serving`` (pool and verify settings), ``check`` (the limits that decide
  ``correct``) and ``reference`` (the module under ``references/`` that
  implements it);
* ``traffic/<mix>.json``: the parameters of the schedule generator;
* ``cells/<workload>.json`` (optional): overrides merged into the mix for
  that cell alone, such as its arrival rate.
"""
from __future__ import annotations

import json
from pathlib import Path

from schedule import merge

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def load(workload: str, root: Path = ROOT) -> dict:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    over = HERE / "cells" / f"{workload}.json"
    if over.exists():
        traffic = merge(traffic, json.loads(over.read_text()))
    per_layer = [
        m for m in bench["per_layer"] if workload in m.get("workloads", [workload])
    ]
    e2e = [m for m in bench["end_to_end"] if workload in m.get("workloads", [workload])]
    return {
        "cell": cell,
        "config": config,
        "traffic": traffic,
        "serving": config["serving"],
        "end_to_end": e2e,
        "per_layer": per_layer,
    }
