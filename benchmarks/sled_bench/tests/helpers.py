"""Shared set-up of the harness's CPU tests: a tiny spec built from the
committed traffic mixes, with small prompts and a short window."""
from __future__ import annotations

import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parents[1] / "src"))

import schedule  # noqa: E402


def tiny_spec(rate: float = 4.0) -> dict:
    config = json.loads((HERE / "data" / "tiny-dense.json").read_text())
    traffic = json.loads((BENCH / "traffic" / "jetson-steady.json").read_text())
    traffic = schedule.merge(traffic, {
        "arrivals": {"rate_per_s": rate},
        "prompt": {"median": 32, "min": 16, "max": 64, "round_to": 16},
        "answer_rounds": {"median": 6, "min": 3, "max": 12},
        "echo": {"streams": 2, "rounds": 6},
        "round_trip_s": 0.05,
    })
    bench = json.loads((BENCH.parents[1] / "BENCHMARK.json").read_text())
    cell = "qwen2-1.5b.jetson-steady"
    return {
        "cell": {"name": "tiny.jetson-steady", "config": "tiny-dense", "traffic": "jetson-steady", "chips": 1},
        "config": config,
        "traffic": traffic,
        "serving": config["serving"],
        "end_to_end": [m for m in bench["end_to_end"] if cell in m.get("workloads", [cell])],
        "per_layer": [m for m in bench["per_layer"] if cell in m.get("workloads", [cell])],
    }
