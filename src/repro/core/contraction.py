"""Primal: parallel edge contraction (RAMA §3.1, Alg. 1/4).

Fixed-shape TPU adaptations of the paper's GPU primitives:

* connected components — min-label propagation + pointer jumping
  (replaces [23]'s GPU CC); O(log N) rounds inside a ``lax.while_loop``.
* maximum matching — Luby–Jones handshaking [16] as mutual-argmax over
  segment reductions.
* maximum spanning forest — Borůvka rounds (per-component best edge) with
  *component freezing* instead of path-edge removal for repulsive-edge
  conflicts (see DESIGN.md §2).
* contraction — Lemma 4's ``KᵀAK`` computed sparsely: gather the component
  relabelling, then ONE fused lexsort over the 2E directed edge copies that
  simultaneously merges parallel edges (Alg. 4's sort + reduce_by_key) AND
  emits the contracted graph's :class:`~repro.core.graph.CsrGraph`
  (:func:`contract_csr`). The CSR is a free byproduct of the sort the
  dedupe must do anyway — which is what lets the solver carry a live CSR
  across rounds instead of rebuilding it from COO before every separation
  (PR 3's SolverState; ``build_csr`` runs once per solve). Both data paths
  run this same arithmetic, so dense/sparse solves stay bit-identical.
  This is the ONLY contraction path the solver runs — it allocates O(N + E)
  for any graph_impl, so the solve jaxpr stays free of (N, N) temporaries.
  The one-hot-matmul form survives solely as the small-N test oracle
  (:func:`contract_dense`, mirrored by the ``contract_matmul`` Pallas
  kernel benchmark).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.graph import (
    CsrGraph, MulticutInstance, csr_lookup_edge, tree_sum,
)
from repro.sparse.segment_ops import segment_argmax


# ---------------------------------------------------------------------------
# Connected components
# ---------------------------------------------------------------------------

def connected_components(u, v, edge_mask, num_nodes: int):
    """Min-label propagation with pointer jumping. Returns (N,) labels where
    each node's label is the smallest node id in its component (w.r.t. edges
    where ``edge_mask`` is True)."""
    labels0 = jnp.arange(num_nodes, dtype=jnp.int32)

    def cond(state):
        _, changed = state
        return changed

    def body(state):
        labels, _ = state
        lu, lv = labels[u], labels[v]
        m = jnp.minimum(lu, lv)
        new = labels.at[u].min(jnp.where(edge_mask, m, lu))
        new = new.at[v].min(jnp.where(edge_mask, m, lv))
        # pointer jumping (path halving twice)
        new = new[new]
        new = new[new]
        changed = jnp.any(new != labels)
        return new, changed

    labels, _ = jax.lax.while_loop(cond, body, (labels0, jnp.bool_(True)))
    return labels


# ---------------------------------------------------------------------------
# Contraction set strategies
# ---------------------------------------------------------------------------

def _node_best_positive_edge(u, v, cost, active, num_nodes: int):
    """For every node, the index of its best (max-cost) active incident edge.
    Returns (N,) edge index or -1."""
    E = u.shape[0]
    eidx = jnp.arange(E, dtype=jnp.int32)
    seg = jnp.concatenate([u, v])
    val = jnp.concatenate([cost, cost])
    msk = jnp.concatenate([active, active])
    edge_of = jnp.concatenate([eidx, eidx])
    arg, _ = segment_argmax(val, seg, num_nodes, mask=msk)
    return jnp.where(arg >= 0, edge_of[jnp.clip(arg, 0)], -1)


def maximum_matching(inst: MulticutInstance, rounds: int = 3,
                     min_cost: float = 0.0):
    """Handshaking matching on attractive edges: an edge joins the matching
    when both endpoints pick it as their best incident edge. ``rounds``
    re-runs on still-free nodes to thicken the matching."""
    N, E = inst.num_nodes, inst.num_edges
    u, v, cost = inst.u, inst.v, inst.cost
    S = jnp.zeros(E, dtype=bool)
    free = inst.node_valid

    def one_round(carry, _):
        S, free = carry
        active = inst.edge_valid & (cost > min_cost) & free[u] & free[v]
        best = _node_best_positive_edge(u, v, cost, active, N)
        eidx = jnp.arange(E, dtype=jnp.int32)
        sel = active & (best[u] == eidx) & (best[v] == eidx)
        S = S | sel
        matched = jnp.zeros(N, dtype=bool).at[u].max(sel).at[v].max(sel)
        return (S, free & ~matched), None

    (S, _), _ = jax.lax.scan(one_round, (S, free), None, length=rounds)
    return S


def spanning_forest_contraction(inst: MulticutInstance, rounds: int = 4,
                                min_cost: float = 0.0):
    """Borůvka-style maximum spanning forest on attractive edges with
    conflict freezing: a Borůvka round that would place a repulsive edge
    inside a component is reverted for that component (fixed-shape stand-in
    for the paper's remove-weakest-path-edge repair)."""
    N, E = inst.num_nodes, inst.num_edges
    u, v, cost = inst.u, inst.v, inst.cost
    neg = inst.edge_valid & (cost < 0)
    S = jnp.zeros(E, dtype=bool)

    def one_round(carry, _):
        S, labels = carry
        cl_u, cl_v = labels[u], labels[v]
        active = inst.edge_valid & (cost > min_cost) & (cl_u != cl_v)
        # best outgoing edge per component (keyed by component root label)
        eidx = jnp.arange(E, dtype=jnp.int32)
        seg = jnp.concatenate([cl_u, cl_v])
        val = jnp.concatenate([cost, cost])
        msk = jnp.concatenate([active, active])
        edge_of = jnp.concatenate([eidx, eidx])
        arg, _ = segment_argmax(val, seg, N, mask=msk)
        best_edge = jnp.where(arg >= 0, edge_of[jnp.clip(arg, 0)], -1)
        cand = jnp.zeros(E, dtype=bool).at[jnp.clip(best_edge, 0)].max(best_edge >= 0)
        cand = cand & active
        S_try = S | cand
        labels_try = connected_components(u, v, S_try, N)
        # conflict: repulsive edge newly internal to a merged component
        conflict = neg & (labels_try[u] == labels_try[v]) & (labels[u] != labels[v])
        frozen = jnp.zeros(N, dtype=bool).at[labels_try[u]].max(conflict)
        keep = cand & ~frozen[labels_try[u]] & ~frozen[labels_try[v]]
        S_new = S | keep
        labels_new = connected_components(u, v, S_new, N)
        return (S_new, labels_new), None

    labels0 = jnp.arange(N, dtype=jnp.int32)
    (S, _), _ = jax.lax.scan(one_round, (S, labels0), None, length=rounds)
    return S


def choose_contraction_set(inst: MulticutInstance, matching_rounds: int = 3,
                           forest_rounds: int = 4, switch_frac: float = 0.1,
                           contract_frac: float = 0.0):
    """Paper §3.1: matching first; if it matched fewer than
    ``switch_frac * |V|`` edges, use the spanning-forest strategy instead.
    Both branches are computed (fixed-shape) and selected with ``where``.

    ``contract_frac`` > 0 restricts candidates to edges with cost above that
    fraction of the round's maximum positive cost — a GAEC-like conservatism
    knob (strong joins first; weaker ones wait for later rounds where merged
    costs are visible). 0 reproduces the paper exactly.

    The forest branch (component freezing) can legitimately return *fewer*
    edges than the matching it was meant to improve on; falling back to an
    empty set would terminate the outer solver while positive edges remain.
    We therefore never return fewer edges than the matching found."""
    min_cost = 0.0
    if contract_frac > 0.0:
        cmax = jnp.max(jnp.where(inst.edge_valid, inst.cost, 0.0))
        min_cost = contract_frac * jnp.maximum(cmax, 0.0)
    S_match = maximum_matching(inst, rounds=matching_rounds,
                               min_cost=min_cost)
    n_nodes = jnp.sum(inst.node_valid)
    enough = jnp.sum(S_match) >= switch_frac * n_nodes
    S_forest = spanning_forest_contraction(inst, rounds=forest_rounds,
                                           min_cost=min_cost)
    use_match = enough | (jnp.sum(S_forest) < jnp.sum(S_match))
    return jnp.where(use_match, S_match, S_forest)


# ---------------------------------------------------------------------------
# Contraction (Lemma 4)
# ---------------------------------------------------------------------------

class ContractionResult(NamedTuple):
    instance: MulticutInstance
    mapping: jax.Array      # (N,) old node -> new compact node id
    n_new: jax.Array        # scalar: number of live clusters
    self_loop_gain: jax.Array  # Lemma 4(b): total cost absorbed into clusters
    n_contracted: jax.Array    # edges contracted this round


def _contract_core(inst: MulticutInstance, S: jax.Array):
    """Shared contraction kernel: relabel endpoints by component, then one
    lexsort over the 2E directed edge copies that both merges parallel
    edges (sum costs, first-occurrence edge ids in (lo, hi) order — the
    same assignment ``coo_dedupe_sum`` used to produce) and yields the
    contracted graph's CSR: unique directed pairs, compacted in place,
    ARE the CSR entries, so ``row_ptr``/``col``/``edge_id`` fall out of
    the sort the dedupe needs anyway. Returns (ContractionResult, CsrGraph).
    """
    N, E = inst.num_nodes, inst.num_edges
    labels = connected_components(inst.u, inst.v, S & inst.edge_valid, N)
    is_root = (labels == jnp.arange(N, dtype=jnp.int32)) & inst.node_valid
    new_id = jnp.cumsum(is_root.astype(jnp.int32)) - 1
    f = new_id[labels].astype(jnp.int32)
    f = jnp.where(inst.node_valid, f, 0)
    n_new = jnp.sum(is_root)

    fu, fv = f[inst.u], f[inst.v]
    self_loop = inst.edge_valid & (fu == fv)
    gain = tree_sum(jnp.where(self_loop, inst.cost, 0.0))
    valid = inst.edge_valid & ~self_loop

    # the one sort: 2E directed copies by (src, dst, original edge id);
    # dead copies get sentinel endpoints that sort past every live row
    eid0 = jnp.arange(E, dtype=jnp.int32)
    m = jnp.concatenate([valid, valid])
    src = jnp.where(m, jnp.concatenate([fu, fv]), N).astype(jnp.int32)
    dst = jnp.where(m, jnp.concatenate([fv, fu]), N).astype(jnp.int32)
    order = jnp.lexsort((jnp.tile(eid0, 2), dst, src))
    s, d = src[order], dst[order]
    w_s = jnp.tile(inst.cost, 2)[order]
    live = m[order]
    nnz = 2 * E

    # runs of equal (src, dst) = unique directed pairs = CSR entries;
    # compacting run heads to their run rank keeps them sorted (no re-sort)
    head = jnp.concatenate([jnp.ones((1,), bool),
                            (s[1:] != s[:-1]) | (d[1:] != d[:-1])])
    is_new = live & head
    rid = jnp.cumsum(is_new.astype(jnp.int32)) - 1      # run id per entry
    cpos = jnp.where(is_new, rid, nnz)
    cs = jnp.full((nnz,), N, jnp.int32).at[cpos].set(s, mode="drop")
    cd = jnp.full((nnz,), N, jnp.int32).at[cpos].set(d, mode="drop")
    row_ptr = jnp.searchsorted(
        cs, jnp.arange(N + 1, dtype=jnp.int32), side="left").astype(jnp.int32)

    # undirected edge ids: forward pairs (src < dst) appear in exactly the
    # (lo, hi) lexicographic order, so their rank is the new edge id; each
    # backward pair bisects row ``dst`` for its forward partner's id
    fwd = cs < cd
    new_eid = jnp.cumsum(fwd.astype(jnp.int32)) - 1
    n_unique = jnp.sum(fwd)
    probe = CsrGraph(row_ptr=row_ptr, col=cd,
                     edge_id=jnp.where(fwd, new_eid, -1))
    partner = jax.vmap(lambda a, b: csr_lookup_edge(probe, a, b))(
        jnp.where(cs < N, cd, 0), cs)
    eid_c = jnp.where(fwd, new_eid, partner)
    eid_c = jnp.where(cs < N, eid_c, -1).astype(jnp.int32)
    csr = CsrGraph(row_ptr=row_ptr, col=cd, edge_id=eid_c)

    # contracted COO: scatter run heads by new id, segment-sum the costs of
    # each forward run (entries ascend by original edge id — stable sort —
    # so the accumulation order is deterministic)
    fw_dest = jnp.where(fwd, new_eid, E)
    u2 = jnp.zeros(E, jnp.int32).at[fw_dest].set(cs, mode="drop")
    v2 = jnp.zeros(E, jnp.int32).at[fw_dest].set(cd, mode="drop")
    fw_entry = live & (s < d)
    seg = jnp.where(fw_entry, eid_c[jnp.clip(rid, 0, nnz - 1)], E - 1)
    c2 = jax.ops.segment_sum(jnp.where(fw_entry, w_s, 0.0), seg,
                             num_segments=E)
    ev2 = jnp.arange(E) < n_unique
    c2 = jnp.where(ev2, c2, 0.0)

    node_valid = jnp.arange(N) < n_new
    out = MulticutInstance(u=u2, v=v2, cost=c2, edge_valid=ev2,
                           node_valid=node_valid)
    res = ContractionResult(instance=out, mapping=f, n_new=n_new,
                            self_loop_gain=gain,
                            n_contracted=jnp.sum(S & inst.edge_valid))
    return res, csr


def contract(inst: MulticutInstance, S: jax.Array) -> ContractionResult:
    """Contract edge set S: relabel endpoints by component, merge parallel
    edges by summing costs (Alg. 4's sort + reduce_by_key)."""
    return _contract_core(inst, S)[0]


def contract_csr(inst: MulticutInstance, S: jax.Array):
    """Contract edge set S and also return the contracted graph's
    :class:`CsrGraph` — maintained from the contraction's own sort, NOT a
    fresh ``build_csr`` (bit-identical to one; asserted in
    tests/test_solver_state.py). This is the round-loop path: the solver
    carries the returned CSR to the next round's separation."""
    return _contract_core(inst, S)


# ---------------------------------------------------------------------------
# Edge-range-sharded contraction (SolverConfig.state_shards)
#
# Every function below runs under shard_map over the "state" mesh: per-edge
# arrays are the local (E/S,) contiguous-range slices, per-node arrays are
# replicated (N,). The engineering constraint throughout is BIT-IDENTITY
# with the replicated kernels above for every shard count: min/max/or
# scatters combine across shards with pmin/pmax (order-invariant exactly),
# argmax tie-breaks travel as integer keys encoding the replicated concat
# index (dist.combine_node_best), float accumulations either go through
# dist.blocked_sum (scalars) or reproduce the replicated segment_sum's
# per-destination accumulation order entry for entry (merged costs).
# ---------------------------------------------------------------------------

def connected_components_sharded(u_loc, v_loc, edge_mask_loc, num_nodes: int,
                                 axis: str):
    """Sharded :func:`connected_components`: each shard min-scatters its own
    edges, an elementwise ``pmin`` fuses the partial scatters (min is
    associative/commutative/idempotent, so this equals the full scatter
    exactly), then the pointer jumping runs replicated. The label
    trajectory — and hence the iteration count — is bitwise identical to
    the replicated loop, so every shard's ``while_loop`` stays in
    lockstep."""
    labels0 = jnp.arange(num_nodes, dtype=jnp.int32)

    def cond(state):
        _, changed = state
        return changed

    def body(state):
        labels, _ = state
        lu, lv = labels[u_loc], labels[v_loc]
        m = jnp.minimum(lu, lv)
        new = labels.at[u_loc].min(jnp.where(edge_mask_loc, m, lu))
        new = new.at[v_loc].min(jnp.where(edge_mask_loc, m, lv))
        new = jax.lax.pmin(new, axis)
        new = new[new]
        new = new[new]
        changed = jnp.any(new != labels)
        return new, changed

    labels, _ = jax.lax.while_loop(cond, body, (labels0, jnp.bool_(True)))
    return labels


def _node_best_edge_sharded(seg0, seg1, cost_loc, active_loc,
                            num_segments: int, shards: int, axis: str):
    """Sharded :func:`_node_best_positive_edge` (segments = node ids for the
    matching, component labels for the forest). Returns the (N,) GLOBAL
    edge id each segment picks, or -1.

    The replicated kernel argmaxes over the 2E concat [u-copies, v-copies]
    and tie-breaks to the smallest concat index. Each shard's local
    ``segment_argmax`` already picks its smallest-local-index max, and the
    local concat index order coincides with the global tie key
    ``direction * E + global_eid``, so folding the per-shard winners by
    (max value, min key) in :func:`~repro.core.dist.combine_node_best`
    reproduces the replicated pick exactly — including the degenerate
    all-masked segment, where every value ties at the mask sentinel and
    the smallest key wins, just as the replicated argmin-over-ties does."""
    from repro.core.dist import combine_node_best, edge_range_start
    E_loc = cost_loc.shape[0]
    E = E_loc * shards
    e0 = edge_range_start(E_loc, axis)
    seg = jnp.concatenate([seg0, seg1])
    val = jnp.concatenate([cost_loc, cost_loc])
    msk = jnp.concatenate([active_loc, active_loc])
    arg, vmax = segment_argmax(val, seg, num_segments, mask=msk)
    dir_ = (arg >= E_loc).astype(jnp.int32)
    lid = arg - dir_ * E_loc
    key = jnp.where(arg >= 0, dir_ * E + e0 + lid,
                    jnp.iinfo(jnp.int32).max)
    pay = jnp.where(arg >= 0, e0 + lid, -1)
    _, _, best = combine_node_best(vmax, key, pay, axis)
    return best


def maximum_matching_sharded(u_loc, v_loc, cost_loc, ev_loc, node_valid,
                             rounds: int, min_cost, shards: int, axis: str):
    """Sharded :func:`maximum_matching`; returns the local (E/S,) slice of
    the replicated matching, bitwise."""
    from repro.core.dist import edge_range_start
    N = node_valid.shape[0]
    E_loc = u_loc.shape[0]
    geid = edge_range_start(E_loc, axis) + jnp.arange(E_loc, dtype=jnp.int32)
    S = jnp.zeros(E_loc, dtype=bool)
    free = node_valid

    def one_round(carry, _):
        S, free = carry
        active = ev_loc & (cost_loc > min_cost) & free[u_loc] & free[v_loc]
        best = _node_best_edge_sharded(u_loc, v_loc, cost_loc, active, N,
                                       shards, axis)
        sel = active & (best[u_loc] == geid) & (best[v_loc] == geid)
        S = S | sel
        m_loc = jnp.zeros(N, jnp.int32).at[u_loc].max(sel.astype(jnp.int32))
        m_loc = m_loc.at[v_loc].max(sel.astype(jnp.int32))
        matched = jax.lax.pmax(m_loc, axis) > 0
        return (S, free & ~matched), None

    (S, _), _ = jax.lax.scan(one_round, (S, free), None, length=rounds)
    return S


def spanning_forest_sharded(u_loc, v_loc, cost_loc, ev_loc, node_valid,
                            rounds: int, min_cost, shards: int, axis: str):
    """Sharded :func:`spanning_forest_contraction`; returns the local slice
    of the replicated forest, bitwise (component labels, freezing masks and
    best-edge picks are all replicated-exact per round)."""
    from repro.core.dist import edge_range_start
    N = node_valid.shape[0]
    E_loc = u_loc.shape[0]
    e0 = edge_range_start(E_loc, axis)
    neg = ev_loc & (cost_loc < 0)
    S = jnp.zeros(E_loc, dtype=bool)
    labels0 = jnp.arange(N, dtype=jnp.int32)

    def one_round(carry, _):
        S, labels = carry
        cl_u, cl_v = labels[u_loc], labels[v_loc]
        active = ev_loc & (cost_loc > min_cost) & (cl_u != cl_v)
        best_edge = _node_best_edge_sharded(cl_u, cl_v, cost_loc, active, N,
                                            shards, axis)
        own = (best_edge >= e0) & (best_edge < e0 + E_loc)
        idx = jnp.where(own, best_edge - e0, E_loc)
        cand = jnp.zeros(E_loc, dtype=bool).at[idx].max(own, mode="drop")
        cand = cand & active
        S_try = S | cand
        labels_try = connected_components_sharded(u_loc, v_loc, S_try, N,
                                                  axis)
        conflict = neg & (labels_try[u_loc] == labels_try[v_loc]) \
            & (labels[u_loc] != labels[v_loc])
        fr_loc = jnp.zeros(N, jnp.int32).at[labels_try[u_loc]].max(
            conflict.astype(jnp.int32))
        frozen = jax.lax.pmax(fr_loc, axis) > 0
        keep = cand & ~frozen[labels_try[u_loc]] & ~frozen[labels_try[v_loc]]
        S_new = S | keep
        labels_new = connected_components_sharded(u_loc, v_loc, S_new, N,
                                                  axis)
        return (S_new, labels_new), None

    (S, _), _ = jax.lax.scan(one_round, (S, labels0), None, length=rounds)
    return S


def choose_contraction_set_sharded(u_loc, v_loc, cost_loc, ev_loc,
                                   node_valid, matching_rounds: int,
                                   forest_rounds: int, switch_frac: float,
                                   contract_frac: float, shards: int,
                                   axis: str):
    """Sharded :func:`choose_contraction_set`. Edge counts cross shards as
    exact integer psums and the cost ceiling as a pmax (max is
    order-invariant), so the matching/forest switch decides identically to
    the replicated kernel."""
    min_cost = 0.0
    if contract_frac > 0.0:
        cmax = jax.lax.pmax(jnp.max(jnp.where(ev_loc, cost_loc, 0.0)), axis)
        min_cost = contract_frac * jnp.maximum(cmax, 0.0)
    S_match = maximum_matching_sharded(u_loc, v_loc, cost_loc, ev_loc,
                                       node_valid, matching_rounds, min_cost,
                                       shards, axis)
    n_match = jax.lax.psum(jnp.sum(S_match.astype(jnp.int32)), axis)
    n_nodes = jnp.sum(node_valid)
    enough = n_match >= switch_frac * n_nodes
    S_forest = spanning_forest_sharded(u_loc, v_loc, cost_loc, ev_loc,
                                       node_valid, forest_rounds, min_cost,
                                       shards, axis)
    n_forest = jax.lax.psum(jnp.sum(S_forest.astype(jnp.int32)), axis)
    use_match = enough | (n_forest < n_match)
    return jnp.where(use_match, S_match, S_forest)


def _lex2_count_less(lo_sorted, hi_sorted, l, h):
    """Count of entries of the lex-sorted pair list strictly before (l, h) —
    fixed-iteration bisect (the 2-key sibling of
    :func:`~repro.core.graph._lex_count_less`). Scalar in/out; vmap for
    batches."""
    import math
    n = lo_sorted.shape[0]
    iters = max(1, int(math.ceil(math.log2(max(2, n)))) + 1)

    def body(_, lohi):
        lo, hi = lohi
        mid = jnp.clip((lo + hi) // 2, 0, n - 1)
        less = (lo_sorted[mid] < l) | ((lo_sorted[mid] == l)
                                       & (hi_sorted[mid] < h))
        go_right = (lo < hi) & less
        lo2 = jnp.where(go_right, mid + 1, lo)
        hi2 = jnp.where(lo < hi, jnp.where(go_right, hi, mid), hi)
        return lo2, hi2

    lo, _ = jax.lax.fori_loop(0, iters, body, (jnp.int32(0), jnp.int32(n)))
    return lo


class ShardedContraction(NamedTuple):
    """Per-shard view of one contraction: per-edge leaves are the local
    (E/S,) slice of the contracted instance (global new-edge range
    ``[shard * E/S, (shard+1) * E/S)``), per-node leaves replicated."""
    u2: jax.Array          # (E/S,) local contracted COO
    v2: jax.Array
    c2: jax.Array
    ev2: jax.Array
    node_valid: jax.Array  # (N,) replicated
    mapping: jax.Array     # (N,) replicated old node -> new compact id
    n_new: jax.Array
    self_loop_gain: jax.Array
    n_contracted: jax.Array
    csr: CsrGraph          # local CSR over the shard's range (LOCAL edge ids)


def contract_sharded(u_loc, v_loc, cost_loc, ev_loc, node_valid, S_loc,
                     shards: int, axis: str):
    """Sharded :func:`_contract_core`: local dedupe + lexsort per shard,
    then a two-step boundary exchange merging parallel edges whose
    endpoints collapsed across shard cuts.

    Exchange 1 all_gathers the shard-local deduped (lo, hi) pair lists and
    re-derives the global unique pair list + ranks on every shard — the
    rank in (lo, hi) order equals the replicated kernel's forward-run rank,
    so new edge ids match bitwise. Exchange 2 all_gathers each original
    edge's (cost, target new id) twice — once for (fu < fv)-oriented
    entries, once for (fu > fv) — concatenated in exactly the replicated
    lexsort's within-run entry order (orientation-major, ascending original
    id), so the per-target segment_sum reproduces the replicated merged
    costs bit for bit. Both exchange buffers are transient; nothing full-E
    persists past the round."""
    from repro.core.dist import blocked_sum, edge_range_start
    from repro.core.graph import build_csr
    N = node_valid.shape[0]
    E_loc = u_loc.shape[0]
    E = E_loc * shards
    e0 = edge_range_start(E_loc, axis)
    labels = connected_components_sharded(u_loc, v_loc, S_loc & ev_loc, N,
                                          axis)
    is_root = (labels == jnp.arange(N, dtype=jnp.int32)) & node_valid
    new_id = jnp.cumsum(is_root.astype(jnp.int32)) - 1
    f = new_id[labels].astype(jnp.int32)
    f = jnp.where(node_valid, f, 0)
    n_new = jnp.sum(is_root)

    fu, fv = f[u_loc], f[v_loc]
    self_loop = ev_loc & (fu == fv)
    gain = blocked_sum(jnp.where(self_loop, cost_loc, 0.0), shards, axis)
    valid2 = ev_loc & ~self_loop

    lo = jnp.where(valid2, jnp.minimum(fu, fv), N).astype(jnp.int32)
    hi = jnp.where(valid2, jnp.maximum(fu, fv), N).astype(jnp.int32)

    # local dedupe: sort my pairs, compact run heads (stays sorted)
    order = jnp.lexsort((hi, lo))
    lo_s, hi_s = lo[order], hi[order]
    head = jnp.concatenate([jnp.ones((1,), bool),
                            (lo_s[1:] != lo_s[:-1])
                            | (hi_s[1:] != hi_s[:-1])])
    is_new = (lo_s < N) & head
    lrid = jnp.cumsum(is_new.astype(jnp.int32)) - 1
    cpos = jnp.where(is_new, lrid, E_loc)
    ulo = jnp.full((E_loc,), N, jnp.int32).at[cpos].set(lo_s, mode="drop")
    uhi = jnp.full((E_loc,), N, jnp.int32).at[cpos].set(hi_s, mode="drop")

    # exchange 1: merge the shard-local unique pair lists globally
    glo = jax.lax.all_gather(ulo, axis).reshape(-1)     # (E,) transient
    ghi = jax.lax.all_gather(uhi, axis).reshape(-1)
    gord = jnp.lexsort((ghi, glo))
    glo_s, ghi_s = glo[gord], ghi[gord]
    ghead = jnp.concatenate([jnp.ones((1,), bool),
                             (glo_s[1:] != glo_s[:-1])
                             | (ghi_s[1:] != ghi_s[:-1])])
    gnew = (glo_s < N) & ghead
    grank = jnp.cumsum(gnew.astype(jnp.int32)) - 1
    n_unique = jnp.sum(gnew)
    gpos = jnp.where(gnew, grank, E)
    cglo = jnp.full((E,), N, jnp.int32).at[gpos].set(glo_s, mode="drop")
    cghi = jnp.full((E,), N, jnp.int32).at[gpos].set(ghi_s, mode="drop")

    # new edge id of each surviving original edge: rank of its pair
    target = jax.vmap(lambda l, h: _lex2_count_less(cglo, cghi, l, h))(lo, hi)
    target = jnp.where(valid2, target, -1).astype(jnp.int32)

    # exchange 2: merged costs, gathered in the replicated accumulation
    # order — (fu < fv)-oriented entries ascend by global id first, then
    # the (fu > fv)-oriented ones (= the replicated lexsort's within-run
    # tile order)
    fo = valid2 & (fu < fv)
    bo = valid2 & (fu > fv)
    gc = jnp.concatenate([
        jax.lax.all_gather(jnp.where(fo, cost_loc, 0.0), axis).reshape(-1),
        jax.lax.all_gather(jnp.where(bo, cost_loc, 0.0), axis).reshape(-1)])
    gt = jnp.concatenate([
        jax.lax.all_gather(jnp.where(fo, target, -1), axis).reshape(-1),
        jax.lax.all_gather(jnp.where(bo, target, -1), axis).reshape(-1)])
    mine = (gt >= e0) & (gt < e0 + E_loc)
    seg = jnp.where(mine, gt - e0, E_loc)
    c2 = jax.ops.segment_sum(gc, seg, num_segments=E_loc + 1)[:E_loc]

    idx = e0 + jnp.arange(E_loc, dtype=jnp.int32)
    ev2 = idx < n_unique
    u2 = jnp.where(ev2, cglo[idx], 0)
    v2 = jnp.where(ev2, cghi[idx], 0)
    c2 = jnp.where(ev2, c2, 0.0)
    node_valid2 = jnp.arange(N) < n_new
    n_contracted = jax.lax.psum(
        jnp.sum((S_loc & ev_loc).astype(jnp.int32)), axis)
    csr = build_csr(u2, v2, ev2, N)
    return ShardedContraction(u2=u2, v2=v2, c2=c2, ev2=ev2,
                              node_valid=node_valid2, mapping=f, n_new=n_new,
                              self_loop_gain=gain, n_contracted=n_contracted,
                              csr=csr)


def adjacency_dense(inst: MulticutInstance) -> jax.Array:
    """Dense symmetric adjacency (Definition 2) — small-N / test path."""
    N = inst.num_nodes
    A = jnp.zeros((N, N), dtype=inst.cost.dtype)
    c = jnp.where(inst.edge_valid, inst.cost, 0.0)
    A = A.at[inst.u, inst.v].add(c)
    A = A.at[inst.v, inst.u].add(c)
    return A


def contract_dense(A: jax.Array, f: jax.Array, n_new: int) -> jax.Array:
    """Lemma 4(a): A' = KᵀAK − diag(KᵀAK) with K the one-hot contraction
    matrix. Dense oracle for the Pallas ``contract_matmul`` kernel."""
    K = jax.nn.one_hot(f, n_new, dtype=A.dtype)
    M = K.T @ A @ K
    return M - jnp.diag(jnp.diag(M))
