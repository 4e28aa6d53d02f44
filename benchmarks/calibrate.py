"""Dense-vs-sparse crossover sweep: the measurement behind
:data:`repro.core.graph.DEFAULT_SPARSE_THRESHOLD`.

    PYTHONPATH=src python -m benchmarks.calibrate [--json OUT.json]

``--json`` additionally writes the sweep (per-size walls, the measured
crossover, and the committed threshold) as a machine-readable report —
CI uploads it as a build artifact (report-only, never gated: the
crossover is a same-machine ratio, but absolute walls are runner-class
noise) so threshold drift is visible across runs without failing them.

Runs the same PD solve through both separation data paths on
sparse-degree random instances of growing padded node count and prints
wall + peak-temp per size. The dense path carries (N, N) adjacency/cost
matrices, so its per-round cost grows with N even at fixed edge count;
the bucketed-CSR path is O(E·cap) and N-independent. The crossover —
the first size where sparse wall ≤ dense wall — is what
``DEFAULT_SPARSE_THRESHOLD`` (and the serve router's ``dense_max_nodes``)
should be set to. Re-run this after touching either separation path and
update the constant if the crossover moves by more than a bucket.

Keeps edge *density* fixed (expected degree ~5) so the sweep isolates
the N-scaling of the dense path rather than conflating it with a growing
edge set. Sizes are kept small enough for CPU CI-class machines; the
crossover is a ratio of same-machine numbers, so machine class mostly
cancels out.
"""
from __future__ import annotations

import dataclasses
import json
import sys

import jax

from repro import api
from repro.compile_cache import enable_compile_cache
from repro.core.graph import DEFAULT_SPARSE_THRESHOLD, random_instance
from repro.core.solver import solve_device

from benchmarks.common import Csv, timed

# modest solve so the whole sweep stays ~a minute on CPU
CAL_CFG = api.SolverConfig(max_neg=256, max_tri_per_edge=4, nbr_k=8,
                           mp_iters=3, max_rounds=4)
SIZES = (64, 128, 256, 512)
DEGREE = 5.0


def _case(n: int):
    pad_n = max(64, 1 << (n - 1).bit_length())
    return random_instance(n=n, p=min(1.0, DEGREE / max(n - 1, 1)), seed=0,
                           pad_edges=max(256, 8 * n), pad_nodes=pad_n)


def run(csv=None, json_path: str | None = None) -> int | None:
    """Sweep, print, and return the measured crossover size (None if the
    dense path won everywhere). ``json_path`` writes the machine-readable
    report CI archives as an artifact."""
    crossover = None
    sweep = []
    for n in SIZES:
        inst = _case(n)
        walls = {}
        for impl in ("dense", "sparse"):
            cfg = dataclasses.replace(CAL_CFG, graph_impl=impl)
            compiled = jax.jit(
                lambda i, c=cfg: solve_device(i, mode="pd", cfg=c)) \
                .lower(inst).compile()
            t, _ = timed(compiled, inst, iters=3)
            walls[impl] = t
            if csv is not None:
                csv.add("calibrate", f"n{n}/{impl}", "wall_s", round(t, 4))
        ratio = walls["sparse"] / walls["dense"]
        sweep.append({"n": n, "dense_wall_s": round(walls["dense"], 5),
                      "sparse_wall_s": round(walls["sparse"], 5),
                      "sparse_over_dense": round(ratio, 3)})
        print(f"  n={n:5d}: dense {walls['dense']*1e3:8.1f}ms  "
              f"sparse {walls['sparse']*1e3:8.1f}ms  "
              f"(sparse/dense {ratio:.2f}x)")
        if crossover is None and ratio <= 1.0:
            crossover = n
    print(f"crossover: {crossover} "
          f"(DEFAULT_SPARSE_THRESHOLD = {DEFAULT_SPARSE_THRESHOLD})")
    if json_path is not None:
        report = {
            "bench": "calibrate",
            "backend": jax.default_backend(),
            "jax": jax.__version__,
            "degree": DEGREE,
            "sweep": sweep,
            "crossover": crossover,
            "committed_threshold": DEFAULT_SPARSE_THRESHOLD,
        }
        with open(json_path, "w") as f:
            json.dump(report, f, indent=2)
            f.write("\n")
        print(f"wrote {json_path}")
    return crossover


def main(argv=None) -> None:
    enable_compile_cache()
    argv = list(sys.argv[1:] if argv is None else argv)
    json_path = None
    if "--json" in argv:
        i = argv.index("--json")
        try:
            json_path = argv[i + 1]
        except IndexError:
            raise SystemExit("--json needs an output path")
        del argv[i:i + 2]
    if argv:
        raise SystemExit(f"unknown arguments {argv}; usage: "
                         "python -m benchmarks.calibrate [--json OUT.json]")
    csv = Csv()
    csv.emit_header()
    run(csv, json_path=json_path)


if __name__ == "__main__":
    main()
