"""The correctness check fails the control and the faults a cell can have.

The control is the plain reference put in the program's place and
computed in bfloat16 (bench/control.py). The faults (bench/faults.py)
break the timed path underneath a whole run (chip gate skipped): a solve
that returns its state unchanged, one that leaves half of the
instance's edges out, and an answer altered where it is produced. One
chip, so no exchange between chips to leave out."""
import json
import time

import pytest

from bench import control, harness
from bench.faults import altered_labels, half_edges, lowered_bound, unchanged
from bench.tests import tiny


def program(cell_traffic):
    traffic = json.loads(
        (tiny.REPO / "bench/traffic" / f"{cell_traffic}.json").read_text())
    return harness.make_solver(traffic)


# the number over its limit for each fault
CAUGHT_BY = {("tiny_pd", "unchanged"): "bound_deficit",
             ("tiny_pd", "half_edges"): "objective_err",
             ("tiny_pd", "altered_labels"): "objective_err",
             ("tiny_d", "unchanged"): "bound_deficit",
             ("tiny_d", "half_edges"): "bound_lift",
             ("tiny_d", "lowered_bound"): "bound_deficit"}


def run(tmp_path, cell, solve):
    root = tiny.make_root(tmp_path)
    return harness.run_cell(root, cell, 2**31 + 21, 0.2, False,
                            time.perf_counter(), gate=False, solve=solve)


@pytest.mark.parametrize("cell,traffic", [("tiny_pd", "solve_pd"),
                                          ("tiny_d", "solve_d")])
def test_program_is_correct_and_control_is_not(tmp_path, cell, traffic):
    solve = program(traffic)
    assert run(tmp_path / "p", cell, solve)["correct"] is True
    out = run(tmp_path / "c", cell, control.control_solve(solve, "down"))
    assert out["correct"] is False
    assert out["failed"] == out["attempted"]


@pytest.mark.parametrize("cell,traffic,fault", [
    ("tiny_pd", "solve_pd", unchanged),
    ("tiny_pd", "solve_pd", half_edges),
    ("tiny_pd", "solve_pd", altered_labels),
    ("tiny_d", "solve_d", unchanged),
    ("tiny_d", "solve_d", half_edges),
    ("tiny_d", "solve_d", lowered_bound),
])
def test_faults_are_not_correct(tmp_path, cell, traffic, fault):
    out = run(tmp_path, cell, fault(program(traffic)))
    assert out["correct"] is False
    assert out["failed"] >= 1
    number = CAUGHT_BY[cell, fault.__name__]
    assert out["checks"][number]["value"] > out["checks"][number]["limit"]
