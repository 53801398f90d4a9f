"""The whole run on the CPU at the tiny size: server, fleet process over
localhost TCP, metrics and the reference check.  Also: a measuring run
without a TPU exits non-zero and prints no result."""
import json
import os
import subprocess
import sys
import time

import pytest

from helpers import BENCH, tiny_spec
import run


@pytest.fixture(scope="module")
def result():
    return run.run(tiny_spec(), seed=2**31 + 12345, seconds=4.0, trace=False,
                   t_start=time.monotonic())


def test_run_is_correct_and_reports_its_metrics(result):
    assert result["correct"], result["check"]
    assert set(result["metrics"]) == {"verify_rtt_p95_ms", "ttft_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result["check"]) == ["served_gap_max", "served_tokens", "accepted_drafts"]
    assert result["check"]["accepted_drafts"]["value"] > 0  # echo streams were accepted


def test_nothing_compiles_inside_the_window(result, capsys):
    ctx = result["_ctx"]
    assert ctx.rounds and all(r.size <= r.bucket for r in ctx.rounds)
    fleet = ctx.fleet
    assert fleet["timeouts"] == 0 and not fleet["errors"]
    assert fleet["lateness_p99_s"] < 0.05


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", "qwen2-1.5b.jetson-steady",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_workloads_resolve_by_name():
    import spec as spec_mod

    bench = json.loads((BENCH.parents[1] / "BENCHMARK.json").read_text())
    for cell in bench["workloads"]:
        s = spec_mod.load(cell["name"])
        assert s["serving"]["n_slots"] > 0
        names = {m["name"] for m in s["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2
        for m in s["per_layer"]:
            assert m["moves"] in names
            stem = m["name"].split(".", 1)[0]
            assert (BENCH / "metrics" / f"{stem}.py").exists()
