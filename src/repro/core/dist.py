"""Domain-decomposed multicut across the device mesh (shard_map).

The paper's conclusion names multi-GPU decomposition as the path past
single-GPU memory limits; this module implements it on the TPU mesh:

  1. nodes are partitioned into per-device blocks (host-side partitioner);
  2. every device runs a full RAMA primal-dual round on its *interior*
     subproblem — separation, message passing, contraction — completely
     locally (the core solver is fixed-shape, so it shard_maps untouched);
  3. block lower bounds are combined with a ``psum``; boundary edges are
     scored against the all-gathered block labelings and folded into the
     global objective estimate; periodically the quotient graph of
     contracted blocks + boundary edges is solved on a single device
     (it is orders of magnitude smaller).

LB validity: interior-block LBs + Σ min(0, c_boundary) is a valid global
lower bound (dropping the boundary constraints only relaxes the problem).

Besides the domain decomposition, this module owns the device mesh for
*separation sharding* (``SolverConfig.separation_shards``): a 1-D "sep"
mesh over which :func:`repro.core.cycles._map_repulsive_batches` splits
the repulsive-edge axis of cycle separation. Unlike the block
decomposition above, separation sharding is exact — per-shard candidate
searches are stitched back in edge order and the chord allocator runs on
the gathered winners, so the sharded solve is bit-identical to the
single-device one.
"""
from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core.graph import MulticutInstance, tree_sum
from repro.core.solver import SolverConfig, fused_pd_round


@lru_cache(maxsize=None)
def separation_mesh(shards: int):
    """1-D mesh over the first ``shards`` devices, axis name "sep" — the
    mesh behind ``SolverConfig.separation_shards``. Cached so repeated
    traces of the same config share one mesh object."""
    n = jax.device_count()
    if shards > n:
        raise ValueError(f"separation_shards={shards} exceeds the "
                         f"{n} available device(s)")
    return jax.sharding.Mesh(np.array(jax.devices()[:shards]), ("sep",))


@lru_cache(maxsize=None)
def batch_mesh(shards: int):
    """1-D mesh over the first ``shards`` devices, axis name "batch" — the
    mesh behind batch-axis sharding (``api.solve_batch(batch_shards=...)``
    and the serving engine's routed dispatches). Instances on the batch
    axis are independent solves, so the shard_map over this mesh needs no
    collectives and is bit-identical to the single-device batch. Cached so
    every executable for the same shard count shares one mesh object."""
    n = jax.device_count()
    if shards > n:
        raise ValueError(f"batch_shards={shards} exceeds the "
                         f"{n} available device(s)")
    return jax.sharding.Mesh(np.array(jax.devices()[:shards]), ("batch",))


def resolve_batch_shards(shards: int) -> int:
    """Clamp a requested batch-shard count to the devices present — a
    router asking for 4-way batch sharding still serves on a 1-device
    host (mirrors ``cycles.resolve_separation_shards``)."""
    if shards is None or shards <= 1:
        return 1
    return min(int(shards), jax.device_count())


# ---------------------------------------------------------------------------
# Edge-range state sharding (SolverConfig.state_shards): the mesh + the
# collective primitives the fully sharded solve is built from. Everything
# here is engineered for SHARD-COUNT INVARIANCE — bit-identical results for
# every state_shards setting — which rules out float psum (reduction order
# varies with S): scalars go through fixed-range blocked sums, per-edge
# values through ownership gathers (all_gather + integer select, no float
# arithmetic).
# ---------------------------------------------------------------------------

STATE_AXIS = "state"

# Fixed number of reduction ranges for S-invariant scalar sums over the
# edge axis: the padded edge count is split into STATE_BLOCKS contiguous
# ranges, each summed locally, and the (STATE_BLOCKS,) partials are
# combined in the same fixed order on every device. Any S dividing
# STATE_BLOCKS computes the identical float result because the per-range
# partial sums and their combine order never depend on S.
STATE_BLOCKS = 16


@lru_cache(maxsize=None)
def state_mesh(shards: int):
    """1-D mesh over the first ``shards`` devices, axis name "state" — the
    mesh the edge-range-partitioned :class:`~repro.core.solver.SolverState`
    lives on for the lifetime of a solve. Cached like the sep/batch
    meshes."""
    n = jax.device_count()
    if shards > n:
        raise ValueError(f"state_shards={shards} exceeds the "
                         f"{n} available device(s)")
    return jax.sharding.Mesh(np.array(jax.devices()[:shards]), (STATE_AXIS,))


def resolve_state_shards(shards: int) -> int:
    """Clamp a requested state-shard count to the devices present AND to a
    divisor of STATE_BLOCKS (the blocked reductions require S | blocks;
    divisors keep every padded-E constraint a single 'divisible by 16')."""
    if shards is None or shards <= 1:
        return 1
    s = min(int(shards), jax.device_count())
    while STATE_BLOCKS % s:
        s -= 1
    return s


def edge_range_start(num_local_edges: int, axis: str = STATE_AXIS):
    """Global edge id of this shard's first slot (traced int32)."""
    return (jax.lax.axis_index(axis) * num_local_edges).astype(jnp.int32)


def gather_edge_field(x_local: jax.Array, ids: jax.Array,
                      axis: str = STATE_AXIS, fill=0):
    """Ownership halo gather: the value of a sharded per-edge field at
    arbitrary *global* edge ids, replicated on every shard.

    Each shard contributes its owned values (everything else masked to
    ``fill``); one ``all_gather`` + an integer owner-select recovers the
    exact stored bits — no float arithmetic touches the values, so the
    result is invariant to the shard count by construction.
    """
    E_loc = x_local.shape[0]
    e0 = edge_range_start(E_loc, axis)
    local = ids - e0
    mine = (local >= 0) & (local < E_loc)
    vals = jnp.where(mine, x_local[jnp.clip(local, 0, E_loc - 1)], fill)
    gathered = jax.lax.all_gather(vals, axis)          # (S, ...) halo buffer
    owner = jnp.clip(ids // E_loc, 0, gathered.shape[0] - 1)
    return jnp.take_along_axis(gathered, owner[None], axis=0)[0]


def blocked_sum(x_local: jax.Array, shards: int, axis: str = STATE_AXIS,
                blocks: int = STATE_BLOCKS) -> jax.Array:
    """Shard-count-invariant sum of a sharded (E/S,) float array.

    The global edge axis is cut into ``blocks`` fixed ranges (``shards``
    must divide ``blocks`` and ``blocks`` the padded E; both enforced at
    the solve entry): each shard reduces its ``blocks/S`` ranges locally
    with the deterministic :func:`tree_sum`, the per-range partials are
    all_gathered in shard-major order — which IS ascending range order —
    and combined by the same fixed tree on every device. The float result
    is identical for every S dividing ``blocks`` (each range's summand
    set, tree shape and combine order never change), which is what lets
    lower bounds / objectives / gains match bitwise across
    ``state_shards`` settings. ``shards`` is the static mesh size (shapes
    depend on it).
    """
    local_ranges = blocks // shards
    parts_local = tree_sum(x_local.reshape(local_ranges, -1))
    parts = jax.lax.all_gather(parts_local, axis).reshape(-1)   # (blocks,)
    return tree_sum(parts)


def combine_node_best(val_local: jax.Array, key_local: jax.Array,
                      payload_local: jax.Array, axis: str = STATE_AXIS):
    """Combine per-shard (value, tie-key, payload) node tables into the
    global per-node argmax with the replicated tie-break (max value; ties
    to the smallest key).

    Every shard contributes its local winner per node; the fold over the
    all_gathered (S, N) tables runs in shard order with pure
    compare-and-select (no float accumulation), so the result is exact and
    identical for every shard count: it is the element the replicated
    ``segment_argmax`` would pick, because keys encode the replicated
    global tie order and each shard's local winner is already its
    smallest-key max. Empty segments carry val = -inf and survive as
    (-inf, key, payload) for the caller to mask."""
    vals = jax.lax.all_gather(val_local, axis)        # (S, N)
    keys = jax.lax.all_gather(key_local, axis)
    pays = jax.lax.all_gather(payload_local, axis)
    S = vals.shape[0]
    bv, bk, bp = vals[0], keys[0], pays[0]
    for s in range(1, S):
        better = (vals[s] > bv) | ((vals[s] == bv) & (keys[s] < bk))
        bv = jnp.where(better, vals[s], bv)
        bk = jnp.where(better, keys[s], bk)
        bp = jnp.where(better, pays[s], bp)
    return bv, bk, bp


def local_pd_round(u, v, cost, edge_valid, node_valid, *, mp_iters: int,
                   max_neg: int, max_tri_per_edge: int):
    """One PD round on a single block — the same fused separation → message
    passing → contraction unit the single-device solver loops over. All
    arrays carry a leading block axis of size 1 inside shard_map."""
    inst = MulticutInstance(u=u[0], v=v[0], cost=cost[0],
                            edge_valid=edge_valid[0],
                            node_valid=node_valid[0])
    cfg = SolverConfig(mp_iters=mp_iters, max_neg=max_neg,
                       max_tri_per_edge=max_tri_per_edge)
    res, lb = fused_pd_round(inst, cfg, with45=False)
    out = res.instance
    return (out.u[None], out.v[None], out.cost[None], out.edge_valid[None],
            out.node_valid[None], res.mapping[None], lb[None])


def make_dist_pd_round(mesh, *, mp_iters: int = 3, max_neg: int = 128,
                       max_tri_per_edge: int = 4,
                       block_axes=("pod", "data", "model")):
    """Builds the shard_mapped distributed PD round for ``mesh``.

    Inputs (global shapes): u/v/cost/edge_valid (n_blocks, E_blk),
    node_valid (n_blocks, N_blk), boundary_cost (B_edges,) replicated.
    Returns (contracted blocks..., mapping, global LB).
    """
    axes = tuple(a for a in block_axes if a in mesh.axis_names)
    blk = P(axes)

    local = partial(local_pd_round, mp_iters=mp_iters, max_neg=max_neg,
                    max_tri_per_edge=max_tri_per_edge)

    def dist_round(u, v, cost, edge_valid, node_valid, boundary_cost):
        def shard_fn(u, v, cost, ev, nv, bc):
            uu, vv, cc, ee, nn, mapping, lb = local(u, v, cost, ev, nv)
            lb_tot = jax.lax.psum(lb[0], axes)
            # valid global LB: interior LBs + all always-cuttable boundaries
            lb_tot = lb_tot + jnp.sum(jnp.minimum(0.0, bc))
            return uu, vv, cc, ee, nn, mapping, lb_tot[None]

        return shard_map(
            shard_fn, mesh=mesh,
            in_specs=(blk, blk, blk, blk, blk, P()),
            out_specs=(blk, blk, blk, blk, blk, blk, P(axes[:1])),
            check_vma=False,
        )(u, v, cost, edge_valid, node_valid, boundary_cost)

    return dist_round


def partition_instance(inst: MulticutInstance, n_blocks: int,
                       blk_nodes: int, blk_edges: int):
    """Host-side partitioner: contiguous node ranges -> per-block padded COO
    + boundary edge list. Returns dict of numpy arrays shaped for
    ``make_dist_pd_round``."""
    import numpy as np
    from repro.core.graph import to_host_edges
    u, v, c = to_host_edges(inst)
    N = inst.num_nodes
    block_of = np.minimum(np.arange(N) // blk_nodes, n_blocks - 1)
    bu, bv = block_of[u], block_of[v]
    interior = bu == bv
    out = {
        "u": np.zeros((n_blocks, blk_edges), np.int32),
        "v": np.zeros((n_blocks, blk_edges), np.int32),
        "cost": np.zeros((n_blocks, blk_edges), np.float32),
        "edge_valid": np.zeros((n_blocks, blk_edges), bool),
        "node_valid": np.zeros((n_blocks, blk_nodes), bool),
    }
    for b in range(n_blocks):
        sel = interior & (bu == b)
        uu = u[sel] - b * blk_nodes
        vv = v[sel] - b * blk_nodes
        cc = c[sel]
        k = min(len(uu), blk_edges)
        out["u"][b, :k] = uu[:k]
        out["v"][b, :k] = vv[:k]
        out["cost"][b, :k] = cc[:k]
        out["edge_valid"][b, :k] = True
        n_in_block = min(blk_nodes, max(N - b * blk_nodes, 0))
        out["node_valid"][b, :n_in_block] = True
    out["boundary_cost"] = c[~interior].astype(np.float32)
    out["boundary_u"] = u[~interior].astype(np.int32)
    out["boundary_v"] = v[~interior].astype(np.int32)
    return out


def merge_blocks_quotient(block_labels, boundary_u, boundary_v,
                          boundary_cost, blk_nodes: int, pad_edges: int):
    """Build the quotient multicut instance over contracted block clusters +
    boundary edges (solved on one device by the standard solver)."""
    import numpy as np
    n_blocks, N_blk = block_labels.shape
    # global cluster id = block * N_blk + local label, densified
    flat = (np.arange(n_blocks)[:, None] * N_blk
            + np.asarray(block_labels)).reshape(-1)
    uniq, dense = np.unique(flat, return_inverse=True)
    gl = dense.reshape(n_blocks * N_blk)
    qu = gl[boundary_u // blk_nodes * N_blk + boundary_u % blk_nodes]
    qv = gl[boundary_v // blk_nodes * N_blk + boundary_v % blk_nodes]
    from repro.core.graph import make_instance
    keep = qu != qv
    return make_instance(qu[keep], qv[keep], boundary_cost[keep],
                         num_nodes=len(uniq), pad_edges=pad_edges), gl
