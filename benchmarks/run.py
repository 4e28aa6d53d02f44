"""Benchmark harness entry point — one module per paper table/figure:

    table1     paper Table 1 (objectives + runtimes, all solvers)
    scaling    paper Fig. 6  (runtime scaling vs instance size)
    breakdown  paper Table 2 (PD phase breakdown)
    kernels    Pallas kernel micro-benches vs oracles

Usage: PYTHONPATH=src python -m benchmarks.run [module ...]
Emits ``bench,case,metric,value`` CSV on stdout.

``--state-shards=N`` (scaling module) adds the fully sharded solve to the
sweep — per-size wall plus per-device peak-memory rows vs the replicated
CSR path; combine with ``XLA_FLAGS=--xla_force_host_platform_device_count``
for a virtual mesh and ``RAMA_SMOKE_XL=1`` for the XL sharded row.

``--smoke`` runs the fast per-mode solver benchmark instead and writes
``BENCH_solver.json`` (per-mode wall-clock + objective/LB) for CI perf
tracking, plus the incremental delta-churn row (warm ``solve_delta``
after 1% churn vs a cold re-solve — see benchmarks/delta_smoke.py).
``--smoke --serve`` additionally pushes a mixed-size stream through the
serving engine and records throughput + latency-percentile rows into the
same report (see benchmarks/serve_smoke.py).
``--profile`` (alone or with ``--smoke``) runs the per-phase roofline
attribution of one solver round on both data paths and writes
``BENCH_profile.json`` (report-only; see benchmarks/profile_smoke.py).
"""
from __future__ import annotations

import sys
import time

from repro.compile_cache import enable_compile_cache

from benchmarks.common import Csv


def main(argv=None) -> None:
    argv = list(argv if argv is not None else sys.argv[1:])
    enable_compile_cache()
    state_shards = 0
    for a in list(argv):
        if a.startswith("--state-shards"):
            argv.remove(a)
            try:
                state_shards = int(a.split("=", 1)[1])
            except (IndexError, ValueError):
                raise SystemExit("usage: --state-shards=N (forwarded to "
                                 "the scaling module; shards clamp to the "
                                 "devices present)")
    csv = Csv()
    csv.emit_header()
    if "--smoke" in argv or "--profile" in argv:
        smoke = "--smoke" in argv
        serve = "--serve" in argv
        profile = "--profile" in argv
        extra = [a for a in argv
                 if a not in ("--smoke", "--serve", "--profile")]
        if extra:
            raise SystemExit(f"--smoke/--profile run alone; "
                             f"unexpected args: {extra}")
        if serve and not smoke:
            raise SystemExit("--serve composes with --smoke "
                             "(python -m benchmarks.run --smoke --serve)")
        if smoke:
            from benchmarks import delta_smoke, solver_smoke
            report = solver_smoke.run_smoke(csv=csv)
            report = delta_smoke.run_delta(csv=csv, report=report)
            if serve:
                from benchmarks import serve_smoke
                serve_smoke.run_serve(csv=csv, report=report)
        if profile:
            from benchmarks import profile_smoke
            profile_smoke.run_profile(csv=csv)
        return
    if "--serve" in argv:
        raise SystemExit("--serve composes with --smoke "
                         "(python -m benchmarks.run --smoke --serve)")
    from benchmarks import breakdown, kernels, scaling, table1
    mods = {"table1": table1, "scaling": scaling, "breakdown": breakdown,
            "kernels": kernels}
    wanted = argv or list(mods)
    for name in wanted:
        t0 = time.time()
        if name == "scaling":
            mods[name].run(csv, state_shards=state_shards)
        else:
            mods[name].run(csv)
        csv.add(name, "_total", "wall_s", round(time.time() - t0, 1))


if __name__ == "__main__":
    main()
