"""Solver smoke benchmark: per-mode wall-clock + objective/LB on one small
seeded instance, written to ``BENCH_solver.json`` so CI can track the perf
trajectory across PRs (see benchmarks/compare.py for the delta report).

    PYTHONPATH=src python -m benchmarks.run --smoke

Each mode is AOT-compiled once (`jit(...).lower(...).compile()`); the same
executable serves the timed runs (compile excluded via one warmup) and the
peak-memory estimate (XLA's ``temp_size_in_bytes``: the dense path carries
the (N, N) matrices, the CSR path O(N + E)). Every mode is recorded for
BOTH separation data paths (``graph_impl`` "dense" and "sparse"); a
batched PD solve through :mod:`repro.api` covers the vmapped path.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import platform

import jax

from repro import api
from repro.core.graph import grid_instance, random_instance
from repro.core.solver import solve_device
from repro.roofline.solver import profile_solve_round

from benchmarks.common import timed

SMOKE_CFG = api.SolverConfig(max_neg=512, max_tri_per_edge=8, nbr_k=8,
                             mp_iters=8)
SMOKE_BATCH = 4
GRAPH_IMPLS = ("dense", "sparse")
# chunked separation: same solve, peak separation memory bounded by the
# chunk instead of max_neg (results bit-identical to pd/sparse)
CHUNKED_CFG = dataclasses.replace(SMOKE_CFG, graph_impl="sparse",
                                  separation_chunk=64)
# the beyond-dense-ceiling grid (RAMA_SMOKE_XL=1; ~1 min CPU — kept out of
# the default CI smoke, refreshed manually alongside the baseline)
XL_HW = 192
XL_CFG = api.SolverConfig(max_neg=256, mp_iters=3, max_rounds=8,
                          graph_impl="sparse", separation_chunk=64)
# the fully sharded solve (repro.core.sharded): shards clamp to the devices
# present, so this row degrades to a single-shard shard_map on default CI
# and exercises the real edge partition under the dist-4dev job
STATE_SHARDED_CFG = api.SolverConfig(max_neg=512, max_tri_per_edge=8,
                                     nbr_k=8, mp_iters=8,
                                     graph_impl="sparse",
                                     first_round_cycles45=False,
                                     state_shards=4)
# traced-solve overhead gate: trace=True stacks the SolveTrace pytree into
# the while-carry (extra leaves, zero host syncs), so the traced wall must
# track the untraced pd solve. Gated here (not compare.py) because the
# bound is machine-independent: same executable pair, same machine, back
# to back. The absolute floor absorbs sub-second jitter on shared runners.
TRACE_OVERHEAD = 1.05
TRACE_JITTER_S = 0.25


def smoke_instance():
    """The seeded smoke instance every smoke/profile bench runs on."""
    return random_instance(n=100, p=0.1, seed=0, pad_edges=1024,
                           pad_nodes=128)


def _finite(x):
    x = float(x)
    return x if math.isfinite(x) else None   # strict-JSON (no Infinity)


def _compile_solve(inst, mode, cfg):
    """AOT-compile the solve once; the same executable serves the timed
    runs and the peak-memory estimate (no double compile)."""
    return jax.jit(
        lambda i: solve_device(i, mode=mode, cfg=cfg)).lower(inst).compile()


def _peak_memory_bytes(compiled):
    """XLA's compiled temp-buffer estimate."""
    return int(compiled.memory_analysis().temp_size_in_bytes)


def run_smoke(out_path: str = "BENCH_solver.json", csv=None) -> dict:
    inst = smoke_instance()
    report = {
        "bench": "solver_smoke",
        "instance": {"n": 100, "p": 0.1, "seed": 0,
                     "pad_edges": 1024, "pad_nodes": 128},
        "config": {"max_neg": SMOKE_CFG.max_neg, "mp_iters": SMOKE_CFG.mp_iters,
                   "max_tri_per_edge": SMOKE_CFG.max_tri_per_edge,
                   "nbr_k": SMOKE_CFG.nbr_k},
        "backend": jax.default_backend(),
        "platform": platform.platform(),
        "modes": {},
    }

    for mode in api.MODES:
        entry = {}
        for impl in GRAPH_IMPLS:
            cfg = dataclasses.replace(SMOKE_CFG, graph_impl=impl)
            compiled = _compile_solve(inst, mode, cfg)
            t, res = timed(compiled, inst)
            entry[impl] = {
                "wall_s": round(t, 4),
                "objective": _finite(res.objective),
                "lower_bound": _finite(res.lower_bound),
                "rounds": int(res.rounds),
                "peak_mem_bytes": _peak_memory_bytes(compiled),
            }
            if csv is not None:
                csv.add("smoke", f"{mode}/{impl}", "wall_s",
                        entry[impl]["wall_s"])
                if entry[impl]["objective"] is not None:
                    csv.add("smoke", f"{mode}/{impl}", "objective",
                            entry[impl]["objective"])
                csv.add("smoke", f"{mode}/{impl}", "peak_mem_bytes",
                        entry[impl]["peak_mem_bytes"])
        report["modes"][mode] = entry

    # traced pd solve: wall_traced_s rides in the pd rows (report-only in
    # compare.py); the overhead bound itself hard-fails right here
    for impl in GRAPH_IMPLS:
        cfg = dataclasses.replace(SMOKE_CFG, graph_impl=impl)
        compiled = jax.jit(lambda i, cfg=cfg: solve_device(
            i, mode="pd", cfg=cfg, trace=True)).lower(inst).compile()
        t_tr, (res_tr, _tr) = timed(compiled, inst)
        base = report["modes"]["pd"][impl]
        if _finite(res_tr.objective) != base["objective"]:
            raise SystemExit(
                f"trace=True changed pd/{impl} objective: "
                f"{base['objective']} -> {_finite(res_tr.objective)}")
        base["wall_traced_s"] = round(t_tr, 4)
        if base["wall_s"] > 0:
            base["trace_overhead"] = round(t_tr / base["wall_s"], 4)
        limit = max(TRACE_OVERHEAD * base["wall_s"],
                    base["wall_s"] + TRACE_JITTER_S)
        if t_tr > limit:
            raise SystemExit(
                f"traced pd/{impl} solve too slow: {t_tr:.4f}s vs "
                f"untraced {base['wall_s']:.4f}s "
                f"(limit {limit:.4f}s = max({TRACE_OVERHEAD}x, "
                f"+{TRACE_JITTER_S}s))")
        if csv is not None:
            csv.add("smoke", f"pd/{impl}", "wall_traced_s",
                    base["wall_traced_s"])

    compiled = _compile_solve(inst, "pd", CHUNKED_CFG)
    t, res = timed(compiled, inst)
    report["modes"]["pd-chunked64"] = {"sparse": {
        "wall_s": round(t, 4),
        "objective": _finite(res.objective),
        "lower_bound": _finite(res.lower_bound),
        "rounds": int(res.rounds),
        "peak_mem_bytes": _peak_memory_bytes(compiled),
    }}
    if csv is not None:
        csv.add("smoke", "pd-chunked64/sparse", "wall_s", round(t, 4))

    # fully sharded solve: peak_mem here is XLA's PER-DEVICE temp estimate
    # (the SPMD module is per-device), recorded under its own key so the
    # compare report can show the per-device footprint next to the
    # replicated rows without gating on it (shard count varies by runner)
    from repro.core.dist import resolve_state_shards
    shards = resolve_state_shards(STATE_SHARDED_CFG.state_shards)
    compiled = _compile_solve(inst, "pd", STATE_SHARDED_CFG)
    t, res = timed(compiled, inst)
    report["modes"]["pd-state-sharded"] = {"sparse": {
        "wall_s": round(t, 4),
        "objective": _finite(res.objective),
        "lower_bound": _finite(res.lower_bound),
        "rounds": int(res.rounds),
        "state_shards": shards,
        "peak_mem_per_device_bytes": _peak_memory_bytes(compiled),
    }}
    if csv is not None:
        csv.add("smoke", "pd-state-sharded/sparse", "wall_s", round(t, 4))
        csv.add("smoke", "pd-state-sharded/sparse",
                "peak_mem_per_device_bytes",
                report["modes"]["pd-state-sharded"]["sparse"][
                    "peak_mem_per_device_bytes"])

    if os.environ.get("RAMA_SMOKE_XL"):
        xl = grid_instance(XL_HW, XL_HW, seed=0)
        compiled = _compile_solve(xl, "pd", XL_CFG)
        t, res = timed(compiled, xl)
        rounds = int(res.rounds)
        report["modes"][f"pd-xl-grid{XL_HW}"] = {"sparse": {
            "wall_s": round(t, 2),
            "wall_per_round_s": round(t / max(rounds, 1), 3),
            "objective": _finite(res.objective),
            "lower_bound": _finite(res.lower_bound),
            "rounds": rounds,
            "peak_mem_bytes": _peak_memory_bytes(compiled),
        }}
        if csv is not None:
            csv.add("smoke", f"pd-xl-grid{XL_HW}/sparse", "wall_s",
                    round(t, 2))

    # per-phase wall breakdown of one round (report-only in compare.py —
    # localises a wall regression to separation/MP/contraction; the full
    # flops/bytes attribution lives in BENCH_profile.json via --profile)
    report["phases"] = {}
    for impl in GRAPH_IMPLS:
        cfg = dataclasses.replace(SMOKE_CFG, graph_impl=impl)
        prof = profile_solve_round(inst, cfg)
        report["phases"][impl] = {
            ph: round(rec["wall_s"], 4)
            for ph, rec in prof["phases"].items()}
        if csv is not None:
            for ph, w in report["phases"][impl].items():
                csv.add("smoke", f"phase-{ph}/{impl}", "wall_s", w)

    batch = api.stack_instances([
        random_instance(n=100, p=0.1, seed=s, pad_edges=1024, pad_nodes=128)
        for s in range(SMOKE_BATCH)])
    t, res = timed(api.solve_batch, batch, mode="pd", config=SMOKE_CFG)
    report["modes"][f"pd-batch{SMOKE_BATCH}"] = {
        "wall_s": round(t, 4),
        "objective": [float(o) for o in res.objective],
    }
    if csv is not None:
        csv.add("smoke", f"pd-batch{SMOKE_BATCH}", "wall_s", round(t, 4))

    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(f"wrote {out_path}")
    return report
