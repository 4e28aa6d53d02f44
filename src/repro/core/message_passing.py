"""Dual: parallel message passing / dual block coordinate ascent (Alg. 2).

Lagrange decomposition (5): edge subproblems (min(0, c^λ_e)) + triangle
subproblems over M_T = {(0,0,0),(1,1,0),(1,0,1),(0,1,1),(1,1,1)}.

The scheme is schedule-invariant (Def. 14) — every edge→triangle message and
every triangle's internal sweep is independent — which is exactly what makes
it map onto SIMD lanes: we vectorise over all triangles at once. The
triangle→edge sweep (lines 8–13) is the compute hot-spot and is mirrored by
the Pallas kernel in ``repro.kernels.triangle_mp``.

Cost bookkeeping: triangle costs are c_t^λ = −(λ_t,1, λ_t,2, λ_t,3) (eq. 6b).
We store per-triangle *costs* (t_cost = −λ) directly; the reparametrized edge
cost is c^λ_e = c_e + Σ_t λ_{t,e} = c_e − Σ_t t_cost[t, slot(e)].
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.cycles import Triangles
from repro.core.graph import MulticutInstance, tree_sum


class MPState(NamedTuple):
    t_cost: jax.Array   # (T, 3) triangle subproblem costs c_t^λ = -λ_t
    tri: jax.Array      # (T, 3) edge ids
    tri_valid: jax.Array  # (T,)


def init_mp(triangles: Triangles) -> MPState:
    T = triangles.edges.shape[0]
    return MPState(t_cost=jnp.zeros((T, 3), dtype=jnp.float32),
                   tri=triangles.edges, tri_valid=triangles.valid)


def edge_degree(state: MPState, num_edges: int) -> jax.Array:
    """Number of triangles containing each edge."""
    ids = state.tri
    ones = jnp.broadcast_to(state.tri_valid[:, None].astype(jnp.int32),
                            state.tri.shape)
    return jax.ops.segment_sum(ones.reshape(-1), ids.reshape(-1),
                               num_segments=num_edges)


def ordered_segment_sum(x: jax.Array, ids: jax.Array,
                        num_segments: int) -> jax.Array:
    """``jax.ops.segment_sum`` with its float order pinned: each segment
    sums its entries in ascending position, ``((0 + x_a) + x_b) + ...``,
    the order a sequential scatter-add applies them in.

    A float scatter-add leaves that order to the compiler, and XLA's CPU
    backend changes it with the surrounding program, so the same
    message-passing step gave other bits in the solve with sharded
    separation. Here each pass lets every segment take its lowest-position
    entry not yet taken (an integer scatter-min, exact in any order) and
    add it: one float add per segment per pass, as many passes as the
    longest segment has entries. No sort. Ids outside
    ``[0, num_segments)`` are dropped, as ``segment_sum`` drops them."""
    n = x.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    live0 = (ids >= 0) & (ids < num_segments)

    def body(carry):
        live, acc = carry
        tgt = jnp.where(live, ids, num_segments)
        first = jnp.full((num_segments,), n, jnp.int32).at[tgt].min(
            pos, mode="drop")
        take = live & (first[jnp.clip(ids, 0, num_segments - 1)] == pos)
        acc = acc.at[jnp.where(take, ids, num_segments)].add(x, mode="drop")
        return live & ~take, acc

    _, acc = jax.lax.while_loop(
        lambda c: jnp.any(c[0]), body,
        (live0, jnp.zeros((num_segments,), x.dtype)))
    return acc


def reparametrized_costs(cost, state: MPState) -> jax.Array:
    """c^λ_e = c_e + Σ_{t ∋ e} λ_{t,e} = c_e − Σ t_cost."""
    E = cost.shape[0]
    ids = jnp.where(state.tri_valid[:, None], state.tri, -1).reshape(-1)
    contrib = jnp.where(state.tri_valid[:, None], -state.t_cost, 0.0).reshape(-1)
    return cost + ordered_segment_sum(contrib, ids, E)


def triangle_min_marginals(t_cost: jax.Array):
    """Closed-form min-marginals (Def. 7) for all three edges of each
    triangle. t_cost: (..., 3) = (a, b, c). State costs over M_T:
    0, a+b, a+c, b+c, a+b+c.
    m_1 = a + min(b, c, b+c) − min(0, b+c), and cyclically."""
    a, b, c = t_cost[..., 0], t_cost[..., 1], t_cost[..., 2]

    def m(x, y, z):
        return x + jnp.minimum(jnp.minimum(y, z), y + z) \
            - jnp.minimum(0.0, y + z)

    return jnp.stack([m(a, b, c), m(b, a, c), m(c, a, b)], axis=-1)


def _mm_single(t_cost, slot):
    """Min-marginal of one edge slot (0/1/2) of each triangle."""
    a = t_cost[..., slot]
    b = t_cost[..., (slot + 1) % 3]
    c = t_cost[..., (slot + 2) % 3]
    return a + jnp.minimum(jnp.minimum(b, c), b + c) - jnp.minimum(0.0, b + c)


def edges_to_triangles(state: MPState, cost: jax.Array):
    """Lines 1–6: each edge pushes its reparametrized cost uniformly onto the
    triangles containing it. λ_{t,e} −= α/deg ⇔ t_cost += α/deg.
    After the update c^λ_e = 0 for every covered edge."""
    E = cost.shape[0]
    c_rep = reparametrized_costs(cost, state)
    deg = edge_degree(state, E)
    share = jnp.where(deg > 0, c_rep / jnp.maximum(deg, 1), 0.0)
    upd = share[state.tri] * state.tri_valid[:, None]
    return state._replace(t_cost=state.t_cost + upd)


def triangles_to_edges(state: MPState, sweep=None):
    """Lines 7–14: per-triangle sequential sweep distributing min-marginals
    back to the edges. λ_{t,e} += γ·m ⇔ t_cost[e] −= γ·m. Returns the new
    state; the edge reparametrization is recovered from the t_cost delta.

    ``sweep`` lets callers swap in the Pallas kernel (same signature:
    (T,3) costs → (T,3) costs)."""
    if sweep is None:
        sweep = mp_sweep_reference
    new_cost = sweep(state.t_cost)
    new_cost = jnp.where(state.tri_valid[:, None], new_cost, state.t_cost)
    return state._replace(t_cost=new_cost)


def mp_sweep_reference(t_cost: jax.Array) -> jax.Array:
    """Pure-jnp oracle of the triangle sweep (Alg. 2 lines 8–13):
    e1 += 1/3·m1; e2 += 1/2·m2; e3 += 1·m3; e1 += 1/2·m1; e2 += 1·m2;
    e1 += 1·m1 — each on the *current* costs (λ += γm ⇔ cost −= γm)."""
    def step(tc, slot, gamma):
        m = _mm_single(tc, slot)
        return tc.at[..., slot].add(-gamma * m)

    tc = t_cost
    tc = step(tc, 0, 1.0 / 3.0)
    tc = step(tc, 1, 1.0 / 2.0)
    tc = step(tc, 2, 1.0)
    tc = step(tc, 0, 1.0 / 2.0)
    tc = step(tc, 1, 1.0)
    tc = step(tc, 0, 1.0)
    return tc


def lower_bound(cost, edge_valid, state: MPState) -> jax.Array:
    """LB(λ) of (5): Σ_e min(0, c^λ_e) + Σ_t min_{y∈M_T} ⟨c_t^λ, y⟩."""
    c_rep = reparametrized_costs(cost, state)
    lb_e = tree_sum(jnp.where(edge_valid, jnp.minimum(0.0, c_rep), 0.0))
    a, b, c = state.t_cost[:, 0], state.t_cost[:, 1], state.t_cost[:, 2]
    states = jnp.stack([jnp.zeros_like(a), a + b, a + c, b + c, a + b + c],
                       axis=-1)
    lb_t = tree_sum(jnp.where(state.tri_valid, jnp.min(states, axis=-1),
                              0.0))
    return lb_e + lb_t


def run_message_passing_sharded(cost_local, edge_valid_local, tri, tri_valid,
                                iters: int, shards: int, sweep=None,
                                axis: str = None, unroll: bool = False):
    """Sharded Alg. 2 under ``shard_map``: per-edge cost/validity arrays are
    the local (E/S,) edge-range slices; triangles (replicated, global edge
    ids) are swept by every shard. Returns (c_rep_local, lb).

    All halo exchanges are hoisted out of the iteration scan — costs are
    constant during MP, so the (T, 3) slot costs are gathered ONCE
    (``gather_edge_field``) and the per-slot degrees and contribution sums
    run over *compact* triangle-edge ids (the ≤3T distinct edge ids
    relabelled to [0, 3T)), making the scan body collective-free. The
    compact segment_sum accumulates the same contributions at the same
    flat positions as the replicated per-edge segment_sum, so every slot
    quantity — and hence the sweep — is bitwise identical to
    :func:`run_message_passing`; the final reduced costs land back on
    owned edges via one local segment_sum and the lower bound's edge term
    goes through :func:`~repro.core.dist.blocked_sum`, keeping the scalar
    invariant to the shard count.

    ``unroll`` inlines the iteration loop (the body is collective-free,
    so unrolling is safe under shard_map) — used by the roofline's
    two-depth trip-count correction, exactly like the replicated
    :func:`run_message_passing`."""
    from repro.core.dist import STATE_AXIS, blocked_sum, edge_range_start, \
        gather_edge_field
    if axis is None:
        axis = STATE_AXIS
    T = tri.shape[0]
    E_loc = cost_local.shape[0]
    flat_ids = tri.reshape(-1)                                   # (3T,)
    # one halo exchange for the whole MP phase
    cost_at = gather_edge_field(cost_local, flat_ids, axis).reshape(T, 3)
    # compact ids: distinct triangle-edge ids relabelled to [0, 3T)
    uniq = jnp.unique(flat_ids, size=flat_ids.shape[0],
                      fill_value=jnp.iinfo(jnp.int32).max)
    comp = jnp.searchsorted(uniq, flat_ids).astype(jnp.int32)
    ones = jnp.broadcast_to(tri_valid[:, None].astype(jnp.int32),
                            tri.shape).reshape(-1)
    deg_at = jax.ops.segment_sum(ones, comp,
                                 num_segments=flat_ids.shape[0])[comp] \
        .reshape(T, 3)
    if sweep is None:
        sweep = mp_sweep_reference

    def slot_contrib(t_cost):
        contrib = jnp.where(tri_valid[:, None], -t_cost, 0.0).reshape(-1)
        sums = jax.ops.segment_sum(contrib, comp,
                                   num_segments=flat_ids.shape[0])
        return contrib, cost_at + sums[comp].reshape(T, 3)

    def body(t_cost, _):
        _, c_rep_at = slot_contrib(t_cost)
        share_at = jnp.where(deg_at > 0,
                             c_rep_at / jnp.maximum(deg_at, 1), 0.0)
        t_cost = t_cost + share_at * tri_valid[:, None]
        swept = sweep(t_cost)
        t_cost = jnp.where(tri_valid[:, None], swept, t_cost)
        return t_cost, None

    t_cost0 = jnp.zeros((T, 3), dtype=jnp.float32)
    if unroll:
        t_cost = t_cost0
        for _ in range(iters):
            t_cost, _ = body(t_cost, None)
    else:
        t_cost, _ = jax.lax.scan(body, t_cost0, None, length=iters)

    # land the final reparametrization back on owned edges: contributions
    # at out-of-range ids fall into a dead segment
    contrib, _ = slot_contrib(t_cost)
    e0 = edge_range_start(E_loc, axis)
    local = flat_ids - e0
    seg = jnp.where((local >= 0) & (local < E_loc), local, E_loc)
    c_rep_local = cost_local + jax.ops.segment_sum(
        contrib, seg, num_segments=E_loc + 1)[:E_loc]

    lb_e = blocked_sum(jnp.where(edge_valid_local,
                                 jnp.minimum(0.0, c_rep_local), 0.0),
                       shards, axis)
    a, b, c = t_cost[:, 0], t_cost[:, 1], t_cost[:, 2]
    states = jnp.stack([jnp.zeros_like(a), a + b, a + c, b + c, a + b + c],
                       axis=-1)
    # tri arrays are replicated (same T on every S), but jnp.sum's reduce
    # order is a compile-time choice that can shift with the surrounding
    # program — use the width-pinned tree so the scalar matches across S
    lb_t = tree_sum(jnp.where(tri_valid, jnp.min(states, axis=-1), 0.0))
    return c_rep_local, lb_e + lb_t


@partial(jax.jit, static_argnames=("iters", "sweep", "unroll"))
def run_message_passing(cost, edge_valid, state: MPState, iters: int,
                        sweep=None, unroll: bool = False):
    """k iterations of Alg. 2. Returns (state, reparametrized costs, LB).
    ``unroll`` inlines the iterations for HLO flop accounting (roofline)."""
    def body(state, _):
        state = edges_to_triangles(state, cost)
        state = triangles_to_edges(state, sweep=sweep)
        return state, None

    if unroll:
        for _ in range(iters):
            state, _ = body(state, None)
    else:
        state, _ = jax.lax.scan(body, state, None, length=iters)
    c_rep = reparametrized_costs(cost, state)
    lb = lower_bound(cost, edge_valid, state)
    return state, c_rep, lb
