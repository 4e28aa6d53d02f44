"""Paper Fig. 6: runtime scaling w.r.t. instance size, RAMA (P/PD) vs GAEC.

On CPU both sides slow down, but the SHAPE of the curve is the claim: GAEC
is O(E log E) sequential with poor constants at scale, while RAMA's rounds
are a constant number of bulk data-parallel primitives. We report the
fitted log-log slope per solver. (Wall-clock absolute numbers on a CPU
container do not reproduce the paper's GPU speedups; the dry-run/roofline
covers device-level throughput.)

The sweep runs PD on both separation data paths (dense (N, N) vs CSR), and
finishes with an XL grid that the dense path *cannot represent at all*:
at N = 192·192 = 36 864 nodes the dense matrices would need
N²·(4 + 1 + 4) ≈ 12.2 GiB — the CSR path's working set is O(N + E)
(~0.5 GiB incl. XLA temps) and solves it outright. That instance is ~90×
more nodes than the dense ceiling the seed capped out at.
"""
from __future__ import annotations

import dataclasses
import os
import time

import numpy as np

from repro import api
from repro.core.baselines import gaec, objective
from repro.core.graph import grid_instance

SIZES = [8, 12, 16, 24, 32]
CFG = api.SolverConfig(max_neg=2048, mp_iters=5)
XL_HW = 192                      # 36 864 nodes; dense (N, N) ≈ 12.2 GiB
# chunked separation + the carried-CSR round loop (PR 3): per-round work no
# longer pays the 2×build_csr rebuild, and peak separation memory is bound
# by separation_chunk instead of max_neg
XL_CFG = api.SolverConfig(max_neg=256, mp_iters=3, max_rounds=8,
                          graph_impl="sparse", separation_chunk=64)


def _timed_solve(inst, mode, cfg):
    # warm the jit cache out-of-measurement at each new padded shape
    api.solve(inst, mode=mode, config=cfg).labels.block_until_ready()
    t0 = time.perf_counter()
    res = api.solve(inst, mode=mode, config=cfg)
    res.labels.block_until_ready()
    return time.perf_counter() - t0, res


def run(csv, state_shards: int = 0):
    cfg_sparse = dataclasses.replace(CFG, graph_impl="sparse")
    rows = {"GAEC": [], "P": [], "PD": [], "PD-sparse": []}
    edges = []
    for hw in SIZES:
        inst = grid_instance(hw, hw, seed=0)
        n_edges = int(np.asarray(inst.edge_valid).sum())
        edges.append(n_edges)
        t0 = time.perf_counter()
        gaec(inst)
        rows["GAEC"].append(time.perf_counter() - t0)
        rows["P"].append(_timed_solve(inst, "p", CFG)[0])
        rows["PD"].append(_timed_solve(inst, "pd", CFG)[0])
        rows["PD-sparse"].append(_timed_solve(inst, "pd", cfg_sparse)[0])
        for name in rows:
            csv.add("scaling", f"{name}/E={n_edges}", "time_s",
                    round(rows[name][-1], 4))
    le = np.log(edges)
    for name, ts in rows.items():
        slope = np.polyfit(le, np.log(ts), 1)[0]
        csv.add("scaling", name, "loglog_slope", round(float(slope), 3))

    if state_shards:
        run_state_sharded(csv, state_shards)
    run_xl(csv)


def _sharded_cfg(state_shards: int):
    # 3-cycle separation only; shards clamp to the devices present
    return dataclasses.replace(CFG, graph_impl="sparse",
                               first_round_cycles45=False,
                               state_shards=state_shards)


def _per_device_peak(inst, mode, cfg):
    """XLA's per-device temp estimate from a compile-only lowering (no
    execution — the SPMD module already is per-device)."""
    import jax
    from repro.core.solver import solve_device
    compiled = jax.jit(
        lambda i: solve_device(i, mode=mode, cfg=cfg)).lower(inst).compile()
    return int(compiled.memory_analysis().temp_size_in_bytes)


def run_state_sharded(csv, state_shards: int):
    """--state-shards: the fully sharded solve (edge-range-partitioned
    SolverState, repro.core.sharded) across the same grid sweep, plus the
    per-device peak-memory comparison against the replicated CSR path on
    the largest sweep size. Run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (or on a real
    mesh) to get N-way partitions; shards clamp to the devices present."""
    from repro.core.dist import resolve_state_shards
    from repro.core.graph import round_up_edges, to_host_edges
    from repro.core.graph import make_instance

    shards = resolve_state_shards(state_shards)
    cfg = _sharded_cfg(state_shards)
    for hw in SIZES:
        inst0 = grid_instance(hw, hw, seed=0)
        u, v, c = to_host_edges(inst0)
        inst = make_instance(u, v, c, hw * hw,
                             pad_edges=round_up_edges(len(u), shards))
        n_edges = len(u)
        t, _ = _timed_solve(inst, "pd", cfg)
        csv.add("scaling", f"PD-state-sharded{shards}/E={n_edges}",
                "time_s", round(t, 4))

    # per-device footprint on the largest sweep instance: sharded vs
    # replicated CSR (compile-only; report-only downstream)
    hw = SIZES[-1]
    inst0 = grid_instance(hw, hw, seed=0)
    u, v, c = to_host_edges(inst0)
    inst = make_instance(u, v, c, hw * hw,
                         pad_edges=round_up_edges(len(u), shards))
    rep = _per_device_peak(inst, "pd", dataclasses.replace(
        CFG, graph_impl="sparse", first_round_cycles45=False))
    sh = _per_device_peak(inst, "pd", cfg)
    csv.add("scaling", f"mem-replicated/hw={hw}", "peak_temp_bytes", rep)
    csv.add("scaling", f"mem-state-sharded{shards}/hw={hw}",
            "peak_temp_bytes_per_device", sh)
    if rep:
        csv.add("scaling", f"mem-state-sharded{shards}/hw={hw}",
                "per_device_vs_replicated", round(sh / rep, 3))

    if os.environ.get("RAMA_SMOKE_XL"):
        run_xl_sharded(csv, state_shards)


def run_xl_sharded(csv, state_shards: int, hw: int = XL_HW):
    """The XL grid on the sharded solve (RAMA_SMOKE_XL-gated like the
    replicated XL row): wall, per-round wall, and the per-device peak
    next to the replicated number."""
    from repro.core.dist import resolve_state_shards
    from repro.core.graph import make_instance, round_up_edges, \
        to_host_edges

    shards = resolve_state_shards(state_shards)
    cfg = dataclasses.replace(XL_CFG, separation_chunk=0,
                              first_round_cycles45=False,
                              state_shards=state_shards)
    inst0 = grid_instance(hw, hw, seed=0)
    u, v, c = to_host_edges(inst0)
    inst = make_instance(u, v, c, hw * hw,
                         pad_edges=round_up_edges(len(u), shards))
    n_edges = len(u)
    t, res = _timed_solve(inst, "pd", cfg)
    rounds = int(res.rounds)
    case = f"xl-state-sharded{shards}/N={hw * hw}"
    csv.add("scaling", case, "edges", n_edges)
    csv.add("scaling", case, "wall_s", round(t, 2))
    csv.add("scaling", case, "wall_per_round_s",
            round(t / max(rounds, 1), 3))
    csv.add("scaling", case, "objective", round(float(res.objective), 2))
    csv.add("scaling", case, "rounds", rounds)
    sh = _per_device_peak(inst, "pd", cfg)
    rep = _per_device_peak(inst, "pd", dataclasses.replace(
        XL_CFG, separation_chunk=0, first_round_cycles45=False))
    csv.add("scaling", case, "peak_temp_bytes_per_device", sh)
    csv.add("scaling", case, "peak_temp_bytes_replicated", rep)
    if rep:
        csv.add("scaling", case, "per_device_vs_replicated",
                round(sh / rep, 3))


def run_xl(csv, hw: int = XL_HW):
    """The beyond-dense-ceiling solve (CSR path only — the dense matrices
    at this size would not fit in memory, which is the point)."""
    inst = grid_instance(hw, hw, seed=0)
    n = hw * hw
    n_edges = int(np.asarray(inst.edge_valid).sum())
    dense_bytes = n * n * 9      # f32 A + bool Apos + int32 eidx
    t0 = time.perf_counter()
    api.solve(inst, mode="pd", config=XL_CFG).labels.block_until_ready()
    cold = time.perf_counter() - t0          # compile + first solve
    t0 = time.perf_counter()
    res = api.solve(inst, mode="pd", config=XL_CFG)
    obj = float(res.objective)   # blocks
    wall = time.perf_counter() - t0          # warm, comparable to the sweep
    rounds = int(res.rounds)
    csv.add("scaling", f"xl-sparse/N={n}", "edges", n_edges)
    csv.add("scaling", f"xl-sparse/N={n}", "wall_s", round(wall, 2))
    csv.add("scaling", f"xl-sparse/N={n}", "wall_cold_s", round(cold, 2))
    csv.add("scaling", f"xl-sparse/N={n}", "wall_per_round_s",
            round(wall / max(rounds, 1), 3))
    csv.add("scaling", f"xl-sparse/N={n}", "objective", round(obj, 2))
    csv.add("scaling", f"xl-sparse/N={n}", "rounds", rounds)
    csv.add("scaling", f"xl-sparse/N={n}", "dense_matrices_would_need_GiB",
            round(dense_bytes / 2 ** 30, 1))
