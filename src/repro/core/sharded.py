"""Fully sharded solve: edge-range-partitioned SolverState (PR 9).

The replicated sparse solve (``solver._solve_pd_sparse``) carries the
whole padded instance + CSR on every device; this module runs the SAME
PD recursion with the per-edge state partitioned by contiguous edge
range across :func:`repro.core.dist.state_mesh` — each device owns the
range ``[shard * E/S, (shard+1) * E/S)`` of every per-edge leaf
(u/v/cost/edge_valid and the CSR entries) for the life of the solve,
while per-node arrays (node_valid, component labels, the original→
cluster ``mapping``) stay replicated and are refreshed once per round.

Round anatomy (all under ONE ``shard_map``, whole solve device-resident):

  separation  — repulsive-edge selection is a hierarchical top-k
                (per-shard top-k → all_gather → final top-k; the gather
                order preserves the replicated tie-break); CSR row
                windows are merged across shards by one argsort per
                query batch; the triangle math itself is the shared
                :func:`repro.core.cycles.triangles_from_windows`.
  MP          — :func:`repro.core.message_passing.run_message_passing_sharded`:
                triangle slot costs cross shards in ONE halo exchange
                before the iteration scan (costs are constant during
                MP), so the scan body is collective-free.
  contraction — :func:`repro.core.contraction.contract_sharded`: local
                dedupe + lexsort per shard, two boundary exchanges merge
                parallel edges across shard cuts; the node relabelling
                is all-gathered once per round (it is replicated by
                construction — every shard computes the same labels).

BIT-IDENTITY: every per-edge array the loop carries is the exact local
slice of what the replicated sparse solve would carry — labels, final
clusters and contraction history match the replicated path bitwise for
EVERY shard count (asserted across S ∈ {1, 2, 4} in
tests/test_state_sharded.py). The only quantities that differ from the
replicated path in float bits are the reported scalars (lower bound,
objective, self-loop gain): they go through
:func:`repro.core.dist.blocked_sum`'s fixed-range reduction, which makes
them identical across shard counts but a different (equally valid)
summation order than the replicated ``jnp.sum``.

Constraints (checked in :func:`validate_state_sharded`): sparse data
path, 3-cycle separation only, padded E divisible by
``dist.STATE_BLOCKS``, E < 2^30 (int32 tie-key headroom), no
separation_chunk/separation_shards/batch sharding stacking.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core.contraction import (
    choose_contraction_set_sharded, contract_sharded,
)
from repro.core.cycles import triangles_from_windows
from repro.core.dist import (
    STATE_AXIS, STATE_BLOCKS, blocked_sum, edge_range_start,
    gather_edge_field, resolve_state_shards, state_mesh,
)
from repro.core.graph import (
    MulticutInstance, build_csr, csr_filter, csr_row_window,
    resolve_graph_impl,
)
from repro.core.message_passing import run_message_passing_sharded
from repro.kernels.cycle_intersect.ref import intersect_rows_ref


def validate_state_sharded(inst: MulticutInstance, cfg, mode: str) -> int:
    """Static preconditions of the sharded solve; returns the resolved
    shard count. Raises actionable ``ValueError``s — every constraint
    here is a trace-time property, so nothing can fail silently later."""
    E, N = inst.num_edges, inst.num_nodes
    if mode != "pd":
        raise ValueError(
            f"state_shards requires mode='pd' (got {mode!r}); the sharded "
            f"solve supports 3-cycle separation only, which rules out "
            f"pd+/d, and p has no dual state to shard")
    if cfg.always_cycles45 or cfg.first_round_cycles45:
        raise ValueError(
            "state_shards supports 3-cycle separation only; set "
            "first_round_cycles45=False (and always_cycles45=False) — "
            "4/5-cycle chord splicing grows the edge set, which a "
            "fixed edge-range partition cannot absorb")
    if resolve_graph_impl(cfg.graph_impl, N, cfg.sparse_threshold) \
            != "sparse":
        raise ValueError(
            f"state_shards runs the CSR data path only; graph_impl="
            f"{cfg.graph_impl!r} resolves dense at N={N} (threshold "
            f"{cfg.sparse_threshold}) — pass graph_impl='sparse'")
    if cfg.separation_shards > 1 or cfg.separation_chunk > 0:
        raise ValueError(
            "state_shards already partitions separation by edge range; "
            "it does not stack with separation_shards/separation_chunk")
    if E % STATE_BLOCKS:
        raise ValueError(
            f"state_shards needs pad_edges divisible by {STATE_BLOCKS} "
            f"(dist.STATE_BLOCKS, the shard-count-invariant reduction "
            f"ranges); got E={E}. graph.round_up_edges picks a valid pad")
    if E >= 2 ** 30:
        raise ValueError(
            f"state_shards tie-break keys use direction * E + edge_id in "
            f"int32, requiring E < 2^30; got E={E}. Split the instance or "
            f"widen the key policy first")
    return resolve_state_shards(cfg.state_shards)


# ---------------------------------------------------------------------------
# Sharded separation (3-cycles)
# ---------------------------------------------------------------------------

def _select_repulsive_sharded(cost_loc, ev_loc, max_neg: int, shards: int,
                              axis: str = STATE_AXIS):
    """Sharded :func:`repro.core.cycles.select_repulsive_edges`: per-shard
    top-k of the local repulsion scores, all_gathered shard-major and
    re-topped. Shard-major flat order is ascending global id among equal
    values (top_k is stable within a shard, shard s's ids all precede
    shard s+1's), so the final top-k reproduces the replicated
    lowest-index tie-break exactly; every global top-M edge appears in
    its shard's top-k because it has fewer than M ≤ k predecessors
    locally. Returns (global edge ids, ok mask), replicated."""
    E_loc = cost_loc.shape[0]
    sel = ev_loc & (cost_loc < 0.0)
    score = jnp.where(sel, -cost_loc, -jnp.inf)
    k_loc = min(max_neg, E_loc)
    vals, lidx = jax.lax.top_k(score, k_loc)
    gidx = edge_range_start(E_loc, axis) + lidx.astype(jnp.int32)
    gv = jax.lax.all_gather(vals, axis).reshape(-1)
    gi = jax.lax.all_gather(gidx, axis).reshape(-1)
    M = min(max_neg, E_loc * shards)
    fv, fpos = jax.lax.top_k(gv, M)
    return gi[fpos], fv > 0


def _merged_windows(csr_loc, nodes, cap: int, axis: str = STATE_AXIS):
    """The global CSR row windows of ``nodes``, merged from the per-shard
    local windows: each shard contributes the first ``cap`` entries of its
    own row slice (LOCAL edge ids lifted to global), one argsort by
    neighbour id merges them. The simple-graph invariant guarantees
    distinct neighbour ids within a row, so sorting by column alone
    reproduces the replicated (col, edge id) entry order; any entry of
    the global first-``cap`` window has fewer than ``cap`` predecessors
    in its own shard, so no merge candidate is ever truncated away.
    Returns (cols, global eids, ok) shaped like the replicated
    :func:`repro.core.graph.csr_row_window` over the query batch."""
    N = csr_loc.num_nodes
    E_loc = (csr_loc.col.shape[0]) // 2
    e0 = edge_range_start(E_loc, axis)
    window = jax.vmap(lambda n: csr_row_window(csr_loc, n, cap))
    c, e, ok = window(nodes)                       # (B, cap) local windows
    ge = jnp.where(ok, e + e0, -1)
    gc = jax.lax.all_gather(c, axis)               # (S, B, cap)
    gge = jax.lax.all_gather(ge, axis)
    B = nodes.shape[0]
    cols = jnp.moveaxis(gc, 0, 1).reshape(B, -1)   # (B, S*cap)
    eids = jnp.moveaxis(gge, 0, 1).reshape(B, -1)
    order = jnp.argsort(cols, axis=1)
    cols_s = jnp.take_along_axis(cols, order, axis=1)[:, :cap]
    eids_s = jnp.take_along_axis(eids, order, axis=1)[:, :cap]
    return cols_s, eids_s, cols_s < N


def _separate_triangles_state_sharded(u_loc, v_loc, cost_loc, ev_loc,
                                      csr_loc, num_nodes: int, cfg,
                                      shards: int, intersect,
                                      with_aux: bool = False):
    """Sharded 3-cycle separation over the carried local CSR. The local E⁺
    view is a sort-free ``csr_filter`` (local attractive mask); candidate
    windows merge across shards; the triangle assembly is the exact
    replicated :func:`triangles_from_windows`. Output (tri, valid) is
    replicated and bitwise equal to the replicated separation's.

    ``with_aux`` also returns the replicated repulsive-anchor selection
    (neg_idx, neg_ok) so telemetry can attribute top-k slots to owner
    shards without recomputing the hierarchical top-k."""
    keep = ev_loc & (cost_loc > 0)
    csr_pos = csr_filter(csr_loc, keep)
    neg_idx, neg_ok = _select_repulsive_sharded(cost_loc, ev_loc,
                                                cfg.max_neg, shards)
    i = gather_edge_field(u_loc, neg_idx)
    j = gather_edge_field(v_loc, neg_idx)
    K = min(cfg.max_tri_per_edge, num_nodes)
    W = max(K, min(cfg.sparse_row_cap, num_nodes))
    ci, ei, oki = _merged_windows(csr_pos, i, W)
    cj, ej, _ = _merged_windows(csr_pos, j, W)
    tris, goods = triangles_from_windows(ci, ei, oki, cj, ej, neg_idx,
                                         neg_ok, K, intersect)
    tris = jnp.where(goods[:, None], tris, 0)
    if with_aux:
        return tris, goods, neg_idx, neg_ok
    return tris, goods


# ---------------------------------------------------------------------------
# The sharded PD round + solve loop
# ---------------------------------------------------------------------------

def _sharded_pd_round(u_loc, v_loc, cost_loc, ev_loc, node_valid, csr_loc,
                      cfg, shards: int, sweep, intersect,
                      with_aux: bool = False):
    """One full PD round on the edge-range-partitioned state — the sharded
    mirror of ``solver.fused_pd_round_state`` (3-cycles only). Returns the
    next round's local state + the round's (replicated) scalars.

    ``with_aux`` (static) appends replicated telemetry
    ``(n_cycles, mp_improvement, shard_edges, shard_topk, shard_halo)``:
    conflicted cycles, the MP lower-bound gain over the trivial edge
    bound, and the (S,) per-shard balance signals — live edges owned
    entering the round, repulsive-anchor slots won in the global top-k,
    and triangle-slot edge references landing on each shard (the halo
    pressure of the merged windows). Scalar float telemetry goes through
    :func:`blocked_sum`, so it is identical across shard counts like the
    result scalars; off by default, leaving the untraced jaxpr unchanged."""
    N = node_valid.shape[0]
    E_loc = u_loc.shape[0]
    with jax.named_scope("repro.separation"):
        sep = _separate_triangles_state_sharded(
            u_loc, v_loc, cost_loc, ev_loc, csr_loc, N, cfg, shards,
            intersect, with_aux=with_aux)
        tri, tri_ok = sep[0], sep[1]
    with jax.named_scope("repro.message_passing"):
        c_rep_loc, lb = run_message_passing_sharded(
            cost_loc, ev_loc, tri, tri_ok, cfg.mp_iters, shards, sweep=sweep)
    with jax.named_scope("repro.contraction"):
        S_loc = choose_contraction_set_sharded(
            u_loc, v_loc, c_rep_loc, ev_loc, node_valid,
            cfg.matching_rounds, cfg.forest_rounds, cfg.switch_frac,
            cfg.contract_frac, shards, STATE_AXIS)
        con = contract_sharded(u_loc, v_loc, c_rep_loc, ev_loc, node_valid,
                               S_loc, shards, STATE_AXIS)
    if not with_aux:
        return con, lb
    neg_idx, neg_ok = sep[2], sep[3]
    sid = jnp.arange(shards, dtype=jnp.int32)
    sh_edges = jax.lax.all_gather(jnp.sum(ev_loc).astype(jnp.int32),
                                  STATE_AXIS)
    owner = (neg_idx // E_loc).astype(jnp.int32)
    sh_topk = jnp.sum((owner[:, None] == sid[None, :]) & neg_ok[:, None],
                      axis=0).astype(jnp.int32)
    towner = (tri // E_loc).astype(jnp.int32)
    sh_halo = jnp.sum((towner[..., None] == sid[None, None, :])
                      & tri_ok[:, None, None], axis=(0, 1)).astype(jnp.int32)
    trivial_lb = blocked_sum(
        jnp.where(ev_loc, jnp.minimum(0.0, cost_loc), 0.0), shards)
    n_cyc = jnp.sum(tri_ok).astype(jnp.int32)
    return con, lb, (n_cyc, lb - trivial_lb, sh_edges, sh_topk, sh_halo)


def solve_state_sharded(inst: MulticutInstance, cfg, mode: str = "pd",
                        sweep=None, intersect=None, trace: bool = False):
    """The fully sharded PD solve — ``solver._solve_pd_sparse`` with every
    per-edge leaf partitioned by contiguous edge range over the "state"
    mesh. One ``shard_map`` wraps the entire round loop, so the state is
    device-resident for the life of the solve; the per-round collectives
    are the halo/boundary exchanges documented in the module docstring.
    Returns a replicated ``SolveResult`` whose labels and histories are
    bitwise identical across shard counts (and to the replicated sparse
    path), with lower bound/objective identical across shard counts.

    ``trace`` (static) returns ``(SolveResult, SolveTrace)`` with the
    per-shard balance leaves filled at width S: ``shard_edges`` /
    ``shard_topk`` / ``shard_halo`` per round (see
    :func:`_sharded_pd_round`). The traced per-round objective and MP
    gain go through :func:`blocked_sum`, keeping every traced float
    identical across shard counts; trace leaves are (R,)/(R, S) and
    replicated, so the no-full-E-array carry invariant holds."""
    from repro.core.solver import SolveResult
    from repro.obs.trace import init_trace, trace_set_round
    shards = validate_state_sharded(inst, cfg, mode)
    if intersect is None:
        intersect = intersect_rows_ref
    N, R = inst.num_nodes, cfg.max_rounds
    mesh = state_mesh(shards)
    espec = P(STATE_AXIS)

    def shard_fn(u0, v0, c0, ev0, node_valid):
        csr0 = build_csr(u0, v0, ev0, N)
        mapping0 = jnp.arange(N, dtype=jnp.int32)

        def round_(u, v, c, ev, nv, csr, mapping):
            out = _sharded_pd_round(u, v, c, ev, nv, csr, cfg, shards,
                                    sweep, intersect, with_aux=trace)
            con, lb = out[0], out[1]
            base = (con.u2, con.v2, con.c2, con.ev2, con.node_valid,
                    con.csr, con.mapping[mapping], lb,
                    con.n_contracted.astype(jnp.int32),
                    con.n_new.astype(jnp.int32))
            return base + ((out[2],) if trace else ())

        def traced_objective(mapping):
            cut = mapping[u0] != mapping[v0]
            return blocked_sum(jnp.where(ev0 & cut, c0, 0.0), shards)

        r0 = round_(u0, v0, c0, ev0, node_valid, csr0, mapping0)
        u, v, c, ev, nv, csr, mapping, lb0, nc0, nk0 = r0[:10]
        hist_lb = jnp.full((R,), -jnp.inf, jnp.float32).at[0].set(lb0)
        hist_nc = jnp.zeros((R,), jnp.int32).at[0].set(nc0)
        hist_nk = jnp.zeros((R,), jnp.int32).at[0].set(nk0)

        def cond(carry):
            r, nc_last = carry[0], carry[2]
            return (r < R) & (nc_last != 0)

        def body(carry):
            r, st = carry[0], carry[1]
            hist_lb, hist_nc, hist_nk = carry[3], carry[4], carry[5]
            u, v, c, ev, nv, csr, mapping = st
            rr = round_(u, v, c, ev, nv, csr, mapping)
            u, v, c, ev, nv, csr, mapping, lb, nc, nk = rr[:10]
            hist_lb = hist_lb.at[r].set(lb)
            hist_nc = hist_nc.at[r].set(nc)
            hist_nk = hist_nk.at[r].set(nk)
            out = (r + 1, (u, v, c, ev, nv, csr, mapping), nc,
                   hist_lb, hist_nc, hist_nk)
            if trace:
                n_cyc, mp_gain, she, shk, shh = rr[10]
                tr = trace_set_round(
                    carry[6], r, lower_bound=lb,
                    objective=traced_objective(mapping),
                    n_cycles=n_cyc, n_contracted=nc, n_clusters=nk,
                    mp_improvement=mp_gain, shard_edges=she,
                    shard_topk=shk, shard_halo=shh)
                out = out + (tr,)
            return out

        init = (jnp.int32(1), (u, v, c, ev, nv, csr, mapping), nc0,
                hist_lb, hist_nc, hist_nk)
        if trace:
            n_cyc0, mp_gain0, she0, shk0, shh0 = r0[10]
            tr0 = trace_set_round(
                init_trace(R, shards), jnp.int32(0), lower_bound=lb0,
                objective=traced_objective(mapping),
                n_cycles=n_cyc0, n_contracted=nc0, n_clusters=nk0,
                mp_improvement=mp_gain0, shard_edges=she0,
                shard_topk=shk0, shard_halo=shh0)
            init = init + (tr0,)
        fin = jax.lax.while_loop(cond, body, init)
        r, st = fin[0], fin[1]
        hist_lb, hist_nc, hist_nk = fin[3], fin[4], fin[5]
        labels = st[6]
        cut = labels[u0] != labels[v0]
        objective = blocked_sum(jnp.where(ev0 & cut, c0, 0.0), shards)
        out = (labels, objective, lb0, r, hist_lb, hist_nc, hist_nk)
        return out + ((fin[6],) if trace else ())

    n_out = 8 if trace else 7
    out = shard_map(
        shard_fn, mesh=mesh,
        in_specs=(espec, espec, espec, espec, P()),
        out_specs=(P(),) * n_out, check_vma=False,
    )(inst.u, inst.v, inst.cost, inst.edge_valid, inst.node_valid)
    labels, obj, lb0, r, hist_lb, hist_nc, hist_nk = out[:7]
    res = SolveResult(labels=labels, objective=obj, lower_bound=lb0,
                      rounds=r, lb_history=hist_lb, n_contracted=hist_nc,
                      n_clusters=hist_nk)
    if trace:
        return res, out[7]
    return res
