"""Whether the answers of the timed solves are correct.

Each answer is held against the plain reference (``bench/reference.py``)
by four numbers, each with its limit in the cell's own
``bench/limits/<workload>.json``:

- ``objective_err``: |reported objective - the reference's objective of
  the returned labels| / max(1, |reference|). Solves without a primal
  answer (mode ``d``) report no objective and skip it.
- ``bound_excess``: (lower bound - the least known multicut cost) /
  |that cost|, the least of the planted labelling's cost and, where
  there is one, the returned labelling's. Weak duality puts it at or
  below 0.
- ``bound_deficit``: (sum of min(0, cost) - lower bound) / |that sum|.
  The zero dual, a solve that does no dual work, reads 0; the cell's
  limit lies below 0, so the dual has to lift the bound at least that
  far above the zero dual's.
- ``bound_lift``: (lower bound - sum of min(0, cost)) / |that sum|, the
  same lift from the other side. Its limit is a ceiling far above what
  the cell's separation and message passing reach, and below what a
  bound over half of the edges reads.

A run also counts the compiles inside its window (limit 0). A run's
number is the largest over its solves; a solve fails where any of its
numbers is over its limit."""
from __future__ import annotations

import json
import math
from pathlib import Path

import numpy as np

from bench import reference

NUMBERS = ("objective_err", "bound_excess", "bound_deficit", "bound_lift")


def limits(root: Path, workload: str) -> dict:
    """The limits of ``workload``: ``bench/limits/<workload>.json``."""
    return json.loads(
        (root / "bench" / "limits" / f"{workload}.json").read_text())


def solve_numbers(inst, labels, objective: float, lower_bound: float,
                  trivial: float, planted: float) -> dict:
    """The numbers of one answer. ``trivial`` and ``planted`` are the
    reference's trivial bound and planted cost of the instance."""
    out = {}
    upper = planted
    if math.isfinite(objective):
        ref = reference.objective(inst, labels)
        out["objective_err"] = abs(objective - ref) / max(1.0, abs(ref))
        upper = min(upper, ref)
    lb = lower_bound if not math.isnan(lower_bound) else -math.inf
    out["bound_excess"] = (lb - upper) / max(1.0, abs(upper))
    out["bound_deficit"] = (trivial - lb) / max(1.0, abs(trivial))
    out["bound_lift"] = -out["bound_deficit"]
    return out


def judge(per_solve: list[dict], compiles_in_window: int,
          lim: dict) -> tuple[bool, int, dict]:
    """(correct, solves failed, {number: {"value", "limit"}}) of a run."""
    failed = sum(any(v > lim[k] for k, v in nums.items())
                 for nums in per_solve)
    table = {}
    for name in NUMBERS:
        vals = [nums[name] for nums in per_solve if name in nums]
        if vals:
            table[name] = {"value": float(np.max(vals)),
                           "limit": lim[name]}
    table["compiles_in_window"] = {"value": compiles_in_window,
                                   "limit": lim["compiles_in_window"]}
    correct = (bool(per_solve) and failed == 0
               and all(t["value"] <= t["limit"] for t in table.values()))
    return correct, failed, table
