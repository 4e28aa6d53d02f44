"""bench/run.py end to end on the CPU: it refuses a CPU device and a
checkout without the program; with the chip gate skipped, a tiny cell
runs through the whole harness and checks correct."""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from bench import harness
from bench.tests import tiny

RUN = tiny.REPO / "bench" / "run.py"


def run_script(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(cwd / "bench" / "run.py"), *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_a_cpu_device():
    p = run_script(tiny.REPO, "--workload", "grid256_d", "--seed", "1",
                   "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert "TPU" in p.stderr
    assert not p.stdout.strip()


def test_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(tiny.REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(tiny.REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".*", "__pycache__"))
    p = run_script(tmp_path, "--workload", "grid256_d", "--seed", "1",
                   "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert not p.stdout.strip()


def test_gate_refuses_cpu_in_process():
    with pytest.raises(harness.NoChip):
        harness.device_gate(1)


@pytest.mark.parametrize("cell,metrics", [
    ("tiny_pd", {"setup_s", "solve_s", "gap_rel", "bound_lift"}),
    ("tiny_d", {"setup_s", "solve_s", "bound_lift"}),
])
def test_tiny_cell_runs_correct(tmp_path, cell, metrics):
    root = tiny.make_root(tmp_path)
    out = harness.run_cell(root, cell, 2**31 + 5, 0.5, False,
                           time.perf_counter(), gate=False)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1
    assert set(out["metrics"]) == metrics
    assert list(out)[-1] == "checks"
    assert out["checks"]["compiles_in_window"]["value"] == 0
    json.dumps(out, allow_nan=False)
    assert (root / harness.CACHE_DIR).is_dir()
