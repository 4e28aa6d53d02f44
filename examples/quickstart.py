"""Quickstart: solve a multicut instance with the unified RAMA solver API.

    PYTHONPATH=src python examples/quickstart.py

Builds a random signed graph, runs the paper's solver modes through
``repro.api`` (one device-resident executable per mode), a vmapped batch
solve, and the GAEC baseline, and prints objectives, the dual lower bound
and the primal-dual gap."""
import sys

sys.path.insert(0, "src")

from repro import api
from repro.compile_cache import enable_compile_cache
from repro.core.baselines import gaec, objective
from repro.core.graph import random_instance


def main():
    enable_compile_cache()
    inst = random_instance(n=200, p=0.08, seed=0, pad_edges=4096,
                           pad_nodes=256)
    cfg = api.SolverConfig(max_neg=1024, max_tri_per_edge=8, mp_iters=10)

    print("== RAMA quickstart: 200-node random signed graph ==")
    res_p = api.solve(inst, mode="p", config=cfg)
    print(f"P   (primal only)     objective {float(res_p.objective):10.3f}   "
          f"rounds {int(res_p.rounds)}")

    res_pd = api.solve(inst, mode="pd", config=cfg)
    gap = float(res_pd.objective) - float(res_pd.lower_bound)
    print(f"PD  (primal-dual)     objective {float(res_pd.objective):10.3f}   "
          f"LB {float(res_pd.lower_bound):10.3f}   gap {gap:.3f}")

    res_pdp = api.solve(inst, mode="pd+", config=cfg)
    print(f"PD+ (5-cycles always) objective {float(res_pdp.objective):10.3f}")
    # the contract_frac=0.5 'pd-opt' preset (see benchmarks/table1) helps on
    # structured grids; ER graphs do better with the paper configuration

    res_d = api.solve(inst, mode="d", config=cfg)
    per_round = ["%.1f" % x for x in res_d.lb_history.tolist()]
    print(f"D   (dual only)       LB {float(res_d.lower_bound):10.3f}   "
          f"per-round {per_round}")

    g = objective(inst, gaec(inst))
    print(f"GAEC (CPU baseline)   objective {g:10.3f}")

    n_clusters = len(set(res_pd.labels.tolist()))
    print(f"\nPD found {n_clusters} clusters; certificate: solution is within "
          f"{gap:.3f} ({abs(gap / max(abs(float(res_pd.objective)), 1e-9)) * 100:.1f}%) "
          f"of the optimum.")

    # batched serving: where several instances are in flight, route them
    # through the serving engine — it buckets shapes, micro-batches
    # same-bucket requests into one vmapped executable, and strips the
    # padding on the way out. (api.solve above stays the single-solve
    # path; api.stack_instances/solve_batch remain for same-shape stacks
    # you assemble yourself.)
    from repro.serve import SolveEngine

    insts = [random_instance(n=200, p=0.08, seed=s, pad_edges=4096,
                             pad_nodes=256) for s in range(4)]
    engine = SolveEngine(batch_cap=4, flush_timeout_s=None)
    res_b = engine.solve_stream(insts)
    objs = ", ".join(f"{float(r.objective):.1f}" for r in res_b)
    print(f"\nserved {len(insts)} instances through the engine "
          f"({engine.stats.n_dispatches} dispatch, "
          f"{engine.stats.compiles} compile): objectives [{objs}]")


if __name__ == "__main__":
    main()
