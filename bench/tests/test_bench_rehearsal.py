"""Every cell driven for about a second at a tiny size, on the CPU.

The harness's internal entry skips the look for a chip; the command line
must refuse to run without one.
"""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness
from bench.tests.bench_tiny import tiny_run

ROOT = Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in harness.manifest()["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_cell_runs_and_is_correct(cell, trace):
    out = tiny_run(cell, trace)
    assert list(out) == (["correct", "attempted", "failed", "metrics",
                          "device"] + (["breakdown"] if trace else [])
                         + ["checks"])
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert all(c["value"] == 0 == c["limit"] for c in out["checks"].values())
    bench = harness.manifest()
    want = {m["name"] for m in harness.metrics_of(bench, cell, trace)}
    assert set(out["metrics"]) <= want
    if not trace:      # host-clock metrics exist on any machine
        assert set(out["metrics"]) == want
        assert all(v["value"] > 0 for v in out["metrics"].values())
    else:
        assert {"busy_s", "window_s"} <= set(out["device"])
        assert len(out["breakdown"]["idle_gaps"]) <= 10
    json.dumps(out)


def test_command_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == 1
    assert p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


def test_command_refuses_without_the_program(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env=dict(os.environ, PYTHONPATH=""))
    assert p.returncode == 2 and p.stdout.strip() == ""
