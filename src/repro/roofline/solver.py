"""Per-phase roofline attribution for the multicut solver hot path.

Wires the static roofline model (analysis.py) to the *actual* compiled
artifacts of one solver round, split at the phase boundaries the solver
itself uses — separation, message passing, contraction — so a perf
regression localises to a phase and each tile/bucket choice in the sparse
path is justified by measured flops/bytes/wall instead of folklore.

Two XLA counting caveats this module corrects for:

* ``HloCostAnalysis`` counts a ``while``/``scan`` body ONCE regardless of
  trip count. Message passing runs ``mp_iters`` sweeps inside a scan, so
  its flops/bytes are extrapolated from two *unrolled* compiles (depth 1
  and 2): X(L) ≈ X(1) + (L−1)·(X(2) − X(1)) — the depth-1 compile carries
  the loop-invariant setup, the delta is the true per-iteration cost
  (:func:`loop_corrected`). Wall time is still measured on the real
  scan-mode executable.
* ``cost_analysis`` on a sharded executable reports per-program numbers;
  collective traffic is recovered from the optimized HLO text instead
  (:func:`repro.roofline.analysis.collective_bytes`).

With ``cfg.state_shards > 1`` the profile runs the *sharded* round
anatomy (:mod:`repro.core.sharded`): each phase is a ``jit(shard_map(
...))`` executable over the state mesh, records carry both per-device
and whole-job numbers (``flops = flops_per_device × shards`` — SPMD
programs are identical, so the job total is exactly the per-program
count summed across shards), and the roofline terms are computed from
the per-device numbers (devices run concurrently, so per-device work
bounds the wall).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core.contraction import (
    choose_contraction_set, contract, contract_csr,
)
from repro.core.cycles import separate
from repro.core.graph import (
    MulticutInstance, csr_from_instance, resolve_graph_impl,
)
from repro.core.message_passing import init_mp, run_message_passing
from repro.core.solver import SolverConfig, resolve_intersect, resolve_sweep
from repro.roofline.analysis import (
    Hardware, collective_bytes, dominant_term, roofline_terms,
    step_time_estimate,
)

PHASES = ("separation", "message_passing", "contraction")


def loop_corrected(x1: float, x2: float, iters: int) -> float:
    """Two-point trip-count correction: cost at depth ``iters`` from the
    depth-1 and depth-2 unrolled measurements (setup + per-iter delta)."""
    return x1 + (iters - 1) * (x2 - x1)


def _wall(fn, *args, warmup: int = 1, iters: int = 5) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return min(ts)


def _compiled_stats(compiled) -> dict:
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):    # older jax: one dict per device
        cost = cost[0]
    mem = compiled.memory_analysis()
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes": collective_bytes(compiled.as_text())["total"],
        "peak_temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0) or 0),
    }


def _roofline(flops: float, bytes_accessed: float, coll_bytes: float,
              hw: Hardware | None) -> dict:
    """Roofline fields of a phase record; all None ("not measured") when
    the run has no chip peaks to hold its counts against."""
    if hw is None:
        return {"terms": None, "dominant": None, "roofline_s": None}
    terms = roofline_terms(flops, bytes_accessed, coll_bytes, hw)
    return {"terms": terms, "dominant": dominant_term(terms),
            "roofline_s": step_time_estimate(terms)}


def _phase_record(stats: dict, wall_s: float, hw: Hardware | None) -> dict:
    return {**stats, "wall_s": wall_s,
            **_roofline(stats["flops"], stats["bytes_accessed"],
                        stats["collective_bytes"], hw)}


def _profile(fn, args, hw: Hardware | None) -> tuple[dict, object]:
    compiled = jax.jit(fn).lower(*args).compile()
    rec = _phase_record(_compiled_stats(compiled), _wall(compiled, *args),
                        hw)
    return rec, compiled(*args)


def profile_solve_round(inst: MulticutInstance,
                        cfg: SolverConfig = SolverConfig(),
                        backend: str | None = None,
                        hw: Hardware | None = None) -> dict:
    """Per-phase flops/bytes/wall attribution of one full separation +
    message-passing + contraction round on ``inst`` under ``cfg``.

    Each phase is compiled and run standalone at exactly the shapes the
    fused round uses, feeding the next phase its real outputs, so the
    attribution decomposes the round the solver actually runs (modulo
    XLA's cross-phase fusion, which the per-phase walls deliberately
    exclude — their sum bounds the fused round from above).

    ``hw`` holds the counts against the peaks of the chip the walls were
    measured on (:func:`repro.roofline.analysis.hardware`); without it the
    roofline fields are None, never another chip's shares of this wall.

    ``cfg.state_shards > 1`` profiles the edge-range-partitioned round
    instead (see :func:`_profile_solve_round_sharded`).
    """
    if cfg.state_shards:
        return _profile_solve_round_sharded(inst, cfg, backend, hw)
    impl = resolve_graph_impl(cfg.graph_impl, inst.num_nodes,
                              cfg.sparse_threshold)
    sweep = resolve_sweep(backend)
    intersect = resolve_intersect(backend)
    phases = {}

    # --- separation -------------------------------------------------------
    # first-round separation shape: 4/5-cycles exactly when the solver's
    # first PD round would run them under this cfg
    with45 = cfg.always_cycles45 or cfg.first_round_cycles45

    def sep_fn(i, c):
        return separate(i, max_neg=cfg.max_neg,
                        max_tri_per_edge=cfg.max_tri_per_edge,
                        with_cycles45=with45, nbr_k=cfg.nbr_k,
                        graph_impl=impl,
                        sparse_row_cap=cfg.sparse_row_cap,
                        sparse_row_cap_short=cfg.sparse_row_cap_short,
                        sparse_threshold=cfg.sparse_threshold,
                        intersect=intersect, csr=c,
                        separation_chunk=cfg.separation_chunk,
                        separation_shards=cfg.separation_shards)

    csr = csr_from_instance(inst) if impl == "sparse" else None
    phases["separation"], sep = _profile(sep_fn, (inst, csr), hw)

    # --- message passing (loop-corrected over mp_iters) -------------------
    inst2 = sep.instance
    state0 = init_mp(sep.triangles)

    def mp_fn(cost, valid, st):
        return run_message_passing(cost, valid, st, cfg.mp_iters,
                                   sweep=sweep)

    mp_args = (inst2.cost, inst2.edge_valid, state0)
    compiled_mp = jax.jit(mp_fn).lower(*mp_args).compile()
    unrolled = []
    for depth in (1, 2):
        c = jax.jit(lambda cost, valid, st, d=depth: run_message_passing(
            cost, valid, st, d, sweep=sweep, unroll=True)) \
            .lower(*mp_args).compile()
        unrolled.append(_compiled_stats(c))
    stats = {
        k: loop_corrected(unrolled[0][k], unrolled[1][k], cfg.mp_iters)
        for k in ("flops", "bytes_accessed", "collective_bytes")
    }
    # peak temp comes from the real scan-mode executable (unrolling inflates
    # live ranges); wall is measured on it too
    stats["peak_temp_bytes"] = _compiled_stats(compiled_mp)[
        "peak_temp_bytes"]
    rec = _phase_record(stats, _wall(compiled_mp, *mp_args), hw)
    rec["loop"] = {"iters": cfg.mp_iters,
                   "flops_depth1": unrolled[0]["flops"],
                   "flops_depth2": unrolled[1]["flops"]}
    phases["message_passing"] = rec
    _, c_rep, _ = compiled_mp(*mp_args)

    # --- contraction ------------------------------------------------------
    inst3 = inst2._replace(cost=c_rep)

    if impl == "sparse":
        def con_fn(i):
            S = choose_contraction_set(
                i, matching_rounds=cfg.matching_rounds,
                forest_rounds=cfg.forest_rounds,
                switch_frac=cfg.switch_frac,
                contract_frac=cfg.contract_frac)
            res, _ = contract_csr(i, S)
            return res
    else:
        def con_fn(i):
            S = choose_contraction_set(
                i, matching_rounds=cfg.matching_rounds,
                forest_rounds=cfg.forest_rounds,
                switch_frac=cfg.switch_frac,
                contract_frac=cfg.contract_frac)
            return contract(i, S)

    phases["contraction"], _ = _profile(con_fn, (inst3,), hw)

    return {
        "impl": impl,
        "hw": None if hw is None else hw.name,
        "mp_iters": cfg.mp_iters,
        "phases": phases,
        "round_wall_s": sum(p["wall_s"] for p in phases.values()),
        "round_roofline_s": (None if hw is None else
                             sum(p["roofline_s"] for p in phases.values())),
    }


def _sharded_phase_record(per_dev: dict, wall_s: float,
                          hw: Hardware | None,
                          shards: int) -> dict:
    """Phase record for a shard_map'd executable: ``cost_analysis`` is
    per-program, and SPMD programs are identical, so the whole-job total
    of every additive quantity is exactly ``per_device × shards`` — the
    accounting identity tests/test_roofline.py pins. The roofline terms
    (and the time estimate) use the per-device numbers: shards run
    concurrently, so per-device work is what bounds the wall.
    ``peak_temp_bytes`` stays per-device — it is a memory bound, not an
    additive cost."""
    job = {
        "flops": per_dev["flops"] * shards,
        "bytes_accessed": per_dev["bytes_accessed"] * shards,
        "collective_bytes": per_dev["collective_bytes"] * shards,
        "peak_temp_bytes": per_dev["peak_temp_bytes"],
        "flops_per_device": per_dev["flops"],
        "bytes_accessed_per_device": per_dev["bytes_accessed"],
        "collective_bytes_per_device": per_dev["collective_bytes"],
    }
    return {**job, "wall_s": wall_s,
            **_roofline(per_dev["flops"], per_dev["bytes_accessed"],
                        per_dev["collective_bytes"], hw)}


def _profile_solve_round_sharded(inst: MulticutInstance, cfg: SolverConfig,
                                 backend: str | None,
                                 hw: Hardware | None) -> dict:
    """Per-phase attribution of one edge-range-partitioned PD round
    (:mod:`repro.core.sharded`): separation / message passing /
    contraction each compiled as its own ``jit(shard_map(...))`` over the
    state mesh, at exactly the local shapes ``solve_state_sharded``
    carries, feeding the next phase its real (sharded) outputs.

    Differences from the replicated profile, by construction: the
    separation record includes the local CSR build (the real solve builds
    it once and carries it through contraction — here it must be rebuilt
    inside the phase executable); and each record carries
    ``*_per_device`` alongside the whole-job totals (see
    :func:`_sharded_phase_record`)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.core.contraction import (
        choose_contraction_set_sharded, contract_sharded,
    )
    from repro.core.dist import STATE_AXIS, state_mesh
    from repro.core.graph import build_csr
    from repro.core.message_passing import run_message_passing_sharded
    from repro.core.sharded import (
        _separate_triangles_state_sharded, validate_state_sharded,
    )
    from repro.kernels.cycle_intersect.ref import intersect_rows_ref

    shards = validate_state_sharded(inst, cfg, "pd")
    sweep = resolve_sweep(backend)
    intersect = resolve_intersect(backend) or intersect_rows_ref
    N = inst.num_nodes
    mesh = state_mesh(shards)
    espec = P(STATE_AXIS)
    phases = {}

    def smap(fn, in_specs, out_specs):
        return shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

    def profile(fn, args):
        compiled = jax.jit(fn).lower(*args).compile()
        rec = _sharded_phase_record(_compiled_stats(compiled),
                                    _wall(compiled, *args), hw, shards)
        return rec, compiled(*args)

    # --- separation (incl. the local CSR build; see docstring) ------------
    def sep_fn(u, v, c, ev):
        csr = build_csr(u, v, ev, N)
        return _separate_triangles_state_sharded(u, v, c, ev, csr, N, cfg,
                                                 shards, intersect)

    phases["separation"], (tri, tri_ok) = profile(
        smap(sep_fn, (espec,) * 4, (P(), P())),
        (inst.u, inst.v, inst.cost, inst.edge_valid))

    # --- message passing (loop-corrected over mp_iters) -------------------
    def mp_fn(c, ev, t, ok):
        return run_message_passing_sharded(c, ev, t, ok, cfg.mp_iters,
                                           shards, sweep=sweep)

    mp_specs = ((espec, espec, P(), P()), (espec, P()))
    mp_args = (inst.cost, inst.edge_valid, tri, tri_ok)
    compiled_mp = jax.jit(smap(mp_fn, *mp_specs)).lower(*mp_args).compile()
    unrolled = []
    for depth in (1, 2):
        c = jax.jit(smap(
            lambda c_, ev, t, ok, d=depth: run_message_passing_sharded(
                c_, ev, t, ok, d, shards, sweep=sweep, unroll=True),
            *mp_specs)).lower(*mp_args).compile()
        unrolled.append(_compiled_stats(c))
    per_dev = {
        k: loop_corrected(unrolled[0][k], unrolled[1][k], cfg.mp_iters)
        for k in ("flops", "bytes_accessed", "collective_bytes")
    }
    per_dev["peak_temp_bytes"] = _compiled_stats(compiled_mp)[
        "peak_temp_bytes"]
    rec = _sharded_phase_record(per_dev, _wall(compiled_mp, *mp_args), hw,
                                shards)
    rec["loop"] = {"iters": cfg.mp_iters,
                   "flops_depth1": unrolled[0]["flops"],
                   "flops_depth2": unrolled[1]["flops"]}
    phases["message_passing"] = rec
    c_rep, _lb = compiled_mp(*mp_args)

    # --- contraction ------------------------------------------------------
    def con_fn(u, v, c, ev, nv):
        S_loc = choose_contraction_set_sharded(
            u, v, c, ev, nv, cfg.matching_rounds, cfg.forest_rounds,
            cfg.switch_frac, cfg.contract_frac, shards, STATE_AXIS)
        con = contract_sharded(u, v, c, ev, nv, S_loc, shards, STATE_AXIS)
        return (con.u2, con.v2, con.c2, con.ev2, con.node_valid,
                con.mapping, con.n_contracted)

    phases["contraction"], _ = profile(
        smap(con_fn, (espec, espec, espec, espec, P()),
             (espec, espec, espec, espec, P(), P(), P())),
        (inst.u, inst.v, c_rep, inst.edge_valid, inst.node_valid))

    return {
        "impl": "sparse",
        "hw": None if hw is None else hw.name,
        "mp_iters": cfg.mp_iters,
        "state_shards": shards,
        "phases": phases,
        "round_wall_s": sum(p["wall_s"] for p in phases.values()),
        "round_roofline_s": (None if hw is None else
                             sum(p["roofline_s"] for p in phases.values())),
    }
