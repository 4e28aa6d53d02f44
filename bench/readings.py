#!/usr/bin/env python3
"""Readings of the correctness check's numbers, for setting the limits in
``bench/limits/<workload>.json``: the program's, the control's
(``bench/control.py``) and the planted faults' (``bench/faults.py``),
over many seeds, in one process.

    python3 bench/readings.py --workload grid256_pd --seeds 11 12 13 \
        --faults unchanged half_edges

Each seed is a generator seed of the cell's configuration: its instance
is solved once through the cell's own timed path, and the control's
numbers and the faults that alter an answer are read from the same
solve; a fault that changes what is solved (``half_edges``) solves
again. One JSON line per solve on stdout, and the least and largest
reading of each number for the program, each control and each fault at
the end."""
import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", nargs="*", default=[])
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import numpy as np

    from bench import check, control, faults, harness, reference
    from repro.core.graph import MulticutInstance
    cell = harness.load_cell(ROOT, args.workload)
    harness.device_gate(cell.chips)
    harness.enable_compile_cache(ROOT)
    solve = harness.make_solver(cell.traffic)
    worst = {}
    for seed in args.seeds:
        hi = cell.generate(cell.config, seed)
        inst = MulticutInstance(*jax.device_put(
            (hi.u, hi.v, hi.cost, hi.edge_valid, hi.node_valid)))
        t0 = time.perf_counter()
        res = jax.block_until_ready(solve(inst))
        wall = time.perf_counter() - t0
        refs = (reference.trivial_bound(hi),
                reference.planted_objective(hi))
        line = {"seed": seed, "wall_s": wall,
                "rounds": int(res.rounds),
                "objective": float(res.objective),
                "lower_bound": float(res.lower_bound),
                "trivial": refs[0], "planted": refs[1]}
        labels = np.asarray(jax.device_get(res.labels))
        answers = {"program": (float(res.objective), float(res.lower_bound))}
        answer_labels = {}
        for rounding in ("down", "nearest"):
            answers["control_" + rounding] = control.control_answer(
                hi, labels, float(res.objective), rounding)
        for name in args.faults:
            wrapped = faults.FAULTS[name]
            if name == "half_edges":
                fres = jax.block_until_ready(wrapped(solve)(inst))
            else:
                fres = wrapped(lambda _, r=res: r)(inst)
            answers["fault_" + name] = (float(fres.objective),
                                        float(fres.lower_bound))
            answer_labels["fault_" + name] = np.asarray(
                jax.device_get(fres.labels))
        for who, (obj, lb) in answers.items():
            nums = check.solve_numbers(hi, answer_labels.get(who, labels),
                                       obj, lb, *refs)
            line[who] = nums
            w = worst.setdefault(who, {})
            for key, v in nums.items():
                w.setdefault(key, []).append(v)
        print(json.dumps(line), flush=True)
    summary = {who: {k: {"max": max(v), "min": min(v)}
                     for k, v in nums.items()}
               for who, nums in worst.items()}
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
