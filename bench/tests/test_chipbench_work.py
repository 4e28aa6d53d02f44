"""The least-work counts of bench/work.py, worked out by hand."""
import pytest

from bench import peaks, work

SOLVER = {"max_neg": 2, "max_tri_per_edge": 3, "mp_iters": 5,
          "matching_rounds": 3, "forest_rounds": 4}


def test_triangles():
    assert work.triangles(SOLVER, cycles45=False) == 6
    assert work.triangles(SOLVER, cycles45=True) == 6 + 3 * 2


def test_message_passing_bytes_and_operations():
    # E = 10 edges, 6 triangles, 5 iterations
    flops, nbytes = work.message_passing(10, 4, SOLVER, cycles45=False)
    # per iteration and triangle: ids 12 B, edge costs 12, own costs 24,
    # contributions 12 = 60 B; the bound's edge term 10 x (4 + 1) B
    assert nbytes == 5 * 6 * 60 + 10 * 5
    assert flops == 5 * 6 * 45 + 2 * 10


def test_contraction_bytes_and_operations():
    flops, nbytes = work.contraction(10, 4, SOLVER)
    # 7 passes over 10 edges of 13 B and 4 nodes' labels read and written,
    # then the edge list read and written once more and 4 labels written
    assert nbytes == 7 * (10 * 13 + 2 * 4 * 4) + 2 * 10 * 13 + 4 * 4
    assert flops == 8 * 10


def test_least_seconds_takes_the_larger_bound():
    kind = "TPU v5 lite"
    assert peaks.least_seconds(0, 819e9, kind) == pytest.approx(1.0)
    assert peaks.least_seconds(197e12, 1.0, kind) == pytest.approx(1.0)


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
