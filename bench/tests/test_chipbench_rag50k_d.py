"""The region-graph d cell ``rag50k_d``: its entries in BENCHMARK.json,
every cell's limits file, and a small region graph solved d through a
whole run on the CPU (chip gate skipped): the program correct, the
bfloat16 control and the faults a d solve can have not."""
import json
import shutil
import time

import pytest

from bench import control, harness
from bench.faults import half_edges, lowered_bound, unchanged
from bench.tests import tiny
from bench.tests.test_chipbench_generators import small_rag

SPEC = json.loads((tiny.REPO / "BENCHMARK.json").read_text())
CELLS = {w["name"]: w for w in SPEC["workloads"]}
PER_LAYER = {m["name"]: m for m in SPEC["per_layer"]}
# the layers a d solve runs: the solver's rounds, separation and its parts
# (the long bucket too, which the region graph's degrees fill), message
# passing, and the device
D_METRICS = ("round.device_ms", "separation.device_ms",
             "message_passing.device_ms", "message_passing_roofline",
             "device.idle_share", "separation.select.device_ms",
             "separation.triangles.device_ms",
             "separation.long_bucket.device_ms",
             "separation.cycles45.device_ms", "separation.chords.device_ms")


def test_the_cell_and_its_workloads_lists():
    rd = CELLS["rag50k_d"]
    assert (rd["config"], rd["traffic"], rd["chips"]) == (
        "connectomics_rag50k", "solve_d", 1)
    for name in D_METRICS:
        assert "rag50k_d" in PER_LAYER[name]["workloads"], name
    for name, m in PER_LAYER.items():
        if name.startswith("contraction"):
            assert "rag50k_d" not in m["workloads"], name
    gap = [m for m in SPEC["end_to_end"] if m["name"] == "gap_rel"][0]
    assert "rag50k_d" not in gap["workloads"]
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) == 0


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_every_cell_has_its_limits(cell):
    lim = json.loads(
        (tiny.REPO / "bench/limits" / f"{cell}.json").read_text())
    assert set(lim) == {"objective_err", "bound_excess", "bound_deficit",
                        "bound_lift", "compiles_in_window"}
    assert 0 < lim["objective_err"] < 1e-3
    assert lim["bound_excess"] == 0 and lim["compiles_in_window"] == 0
    assert -0.01 < lim["bound_deficit"] < 0 < lim["bound_lift"] < 0.5


def make_root(tmp):
    """The tiny checkout plus a 600-supervoxel region graph solved d as
    the cell ``small_rag_d``."""
    root = tiny.make_root(tmp)
    cfg = dict(small_rag(), name="small_rag")
    (root / "bench/configs/small_rag.json").write_text(json.dumps(cfg))
    shutil.copy(tiny.REPO / "bench/configs/connectomics_rag50k.py",
                root / "bench/configs/small_rag.py")
    (root / "bench/limits/small_rag_d.json").write_text(
        json.dumps(tiny.TINY_LIMITS))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "small_rag", "source": "test",
                            "reduced": [], "why": "test",
                            "file": "bench/configs/small_rag.json"})
    spec["workloads"].append({"name": "small_rag_d", "config": "small_rag",
                              "traffic": "solve_d", "chips": 1,
                              "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def program():
    traffic = json.loads(
        (tiny.REPO / "bench/traffic/solve_d.json").read_text())
    return harness.make_solver(traffic)


def run(tmp_path, solve):
    return harness.run_cell(make_root(tmp_path), "small_rag_d",
                            2**31 + 37, 0.2, False, time.perf_counter(),
                            gate=False, solve=solve)


def test_program_is_correct_and_control_is_not(tmp_path):
    solve = program()
    out = run(tmp_path / "p", solve)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"setup_s", "solve_s", "bound_lift"}
    out = run(tmp_path / "c", control.control_solve(solve, "down"))
    assert out["correct"] is False
    assert out["failed"] == out["attempted"]
    assert out["checks"]["bound_deficit"]["value"] > \
        out["checks"]["bound_deficit"]["limit"]


@pytest.mark.parametrize("fault,number", [(unchanged, "bound_deficit"),
                                          (half_edges, "bound_lift"),
                                          (lowered_bound, "bound_deficit")])
def test_faults_are_not_correct(tmp_path, fault, number):
    out = run(tmp_path, fault(program()))
    assert out["correct"] is False
    assert out["failed"] >= 1
    assert out["checks"][number]["value"] > out["checks"][number]["limit"]
