"""Owner-partitioned GNN message passing under shard_map (the §Perf
hillclimb for the full-graph-large cells).

The pjit lowering of 61M-edge full-graph message passing replicates the
edge-message tensor on every device (GSPMD resolves the arbitrary-index
gather/scatter by replication: 124 GiB/device for dimenet/ogb_products).
This module is the production formulation instead:

  * the HOST partitioner assigns every edge to the shard that owns its
    receiving endpoint and every triplet (k→j, j→i) to the shard owning
    edge j→i, then precomputes a fixed-size HALO EXCHANGE plan:
    per-shard send lists (local edge slots each peer needs) and the
    local+halo index space the triplet gathers read from;
  * on device, one block is: gather send buffer → ragged all-to-all
    (fixed cap) → concat local‖halo → triplet gather/compute →
    segment_sum into LOCAL edges only. No tensor ever exceeds
    O(E/n_dev + halo).

Per-device memory for dimenet/ogb_products on 256 chips: messages 120 MiB
+ halo ≤ 480 MiB + triplet buffers ~240 MiB ≈ 1 GiB (vs 124 GiB), and the
collective traffic is one capped all-to-all per block instead of
full-tensor all-gather + all-reduce.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P


class PartitionedTriplets(NamedTuple):
    """Device-sharded triplet message-passing plan (leading dim = shard)."""
    send_idx: jax.Array    # (D, D, H) int32 — local edge slots shard d sends to peer p
    send_mask: jax.Array   # (D, D, H) bool
    tri_kj: jax.Array      # (D, T_l) int32 — into [local ‖ halo] edge space
    tri_ji: jax.Array      # (D, T_l) int32 — into LOCAL edge space
    tri_mask: jax.Array    # (D, T_l) bool
    e_local: int           # local edge count (padded, per shard)
    halo: int              # D * H — halo buffer length


def build_plan(tri_kj: np.ndarray, tri_ji: np.ndarray, tri_mask: np.ndarray,
               n_edges: int, n_shards: int, halo_per_peer: int,
               tri_per_shard: int) -> PartitionedTriplets:
    """Host-side partitioner. Edges are block-partitioned (edge e lives on
    shard e // e_local). Triplets go to the owner of their receiving edge
    tri_ji; tri_kj references either a local slot or a halo slot."""
    D, H = n_shards, halo_per_peer
    e_local = n_edges // n_shards
    assert n_edges % n_shards == 0
    owner_ji = tri_ji // e_local
    owner_kj = tri_kj // e_local

    send_idx = np.zeros((D, D, H), np.int32)
    send_mask = np.zeros((D, D, H), bool)
    t_kj = np.zeros((D, tri_per_shard), np.int32)
    t_ji = np.zeros((D, tri_per_shard), np.int32)
    t_mask = np.zeros((D, tri_per_shard), bool)

    # per (src shard, dst shard): unique remote edges needed
    fill = np.zeros(D, np.int32)
    halo_maps = [dict() for _ in range(D)]   # global edge -> halo slot
    send_fill = np.zeros((D, D), np.int32)
    for t in range(len(tri_ji)):
        if not tri_mask[t]:
            continue
        d = owner_ji[t]
        if fill[d] >= tri_per_shard:
            continue
        ji_local = tri_ji[t] - d * e_local
        src = owner_kj[t]
        if src == d:
            kj_slot = tri_kj[t] - d * e_local
        else:
            hm = halo_maps[d]
            g = tri_kj[t]
            if g not in hm:
                if send_fill[src, d] >= H:
                    continue                       # halo cap hit: drop
                slot = send_fill[src, d]
                send_idx[src, d, slot] = g - src * e_local
                send_mask[src, d, slot] = True
                hm[g] = src * H + slot
                send_fill[src, d] += 1
            kj_slot = e_local + hm[g]
        i = fill[d]
        t_kj[d, i] = kj_slot
        t_ji[d, i] = ji_local
        t_mask[d, i] = True
        fill[d] += 1
    return PartitionedTriplets(
        send_idx=jnp.asarray(send_idx), send_mask=jnp.asarray(send_mask),
        tri_kj=jnp.asarray(t_kj), tri_ji=jnp.asarray(t_ji),
        tri_mask=jnp.asarray(t_mask), e_local=e_local, halo=D * H)


def abstract_plan(n_edges: int, n_shards: int, halo_per_peer: int,
                  tri_per_shard: int):
    """ShapeDtypeStructs of a plan (dry-run path — no host partitioning)."""
    D, H, T = n_shards, halo_per_peer, tri_per_shard
    i32, b = jnp.int32, jnp.bool_
    return PartitionedTriplets(
        send_idx=jax.ShapeDtypeStruct((D, D, H), i32),
        send_mask=jax.ShapeDtypeStruct((D, D, H), b),
        tri_kj=jax.ShapeDtypeStruct((D, T), i32),
        tri_ji=jax.ShapeDtypeStruct((D, T), i32),
        tri_mask=jax.ShapeDtypeStruct((D, T), b),
        e_local=n_edges // n_shards, halo=D * H)


def make_triplet_block(mesh, axes=("data", "model")):
    """Returns block(m, plan, w) -> new m, running one triplet
    message-passing block under shard_map.

    m: (E, d) edge messages, sharded (axes, None).
    w: dict of small replicated block weights:
       w_tri (d, d), w_upd (d, d) — the DimeNet-style bilinear stage is
       abstracted to one dense triplet transform; the point of this module
       is the data movement, which is identical.
    """
    ax = tuple(a for a in axes if a in mesh.axis_names)

    def body(m_loc, send_idx, send_mask, tri_kj, tri_ji, tri_mask, w_tri,
             w_upd):
        # shapes inside: m_loc (1*, E_l, d) leading shard axis stripped
        m_loc = m_loc[0]
        send_idx, send_mask = send_idx[0], send_mask[0]
        tri_kj, tri_ji, tri_mask = tri_kj[0], tri_ji[0], tri_mask[0]
        D, H = send_idx.shape[0], send_idx.shape[1]
        d = m_loc.shape[-1]
        # 1. gather what peers need and exchange (capped all-to-all)
        send = m_loc[send_idx.reshape(-1)].reshape(D, H, d)
        send = send * send_mask[..., None].astype(send.dtype)
        recv = jax.lax.all_to_all(send, ax, split_axis=0, concat_axis=0,
                                  tiled=False)
        halo = recv.reshape(D * H, d)
        # 2. local + halo edge space
        m_ext = jnp.concatenate([m_loc, halo], axis=0)
        # 3. triplet compute (gather -> transform -> mask)
        x_kj = m_ext[tri_kj]
        msg = jax.nn.silu(x_kj @ w_tri.astype(x_kj.dtype))
        msg = msg * tri_mask[:, None].astype(msg.dtype)
        # 4. scatter into local edges (tri_ji local by construction)
        agg = jax.ops.segment_sum(msg, tri_ji,
                                  num_segments=m_loc.shape[0])
        out = m_loc + jax.nn.silu(agg @ w_upd.astype(agg.dtype))
        return out[None]

    blk = P(ax)

    def block(m, plan: PartitionedTriplets, w):
        D = plan.send_idx.shape[0]
        m_blocked = m.reshape(D, plan.e_local, -1)
        out = shard_map(
            body, mesh=mesh,
            in_specs=(blk, blk, blk, blk, blk, blk, P(), P()),
            out_specs=blk, check_vma=False,
        )(m_blocked, plan.send_idx, plan.send_mask, plan.tri_kj,
          plan.tri_ji, plan.tri_mask, w["w_tri"], w["w_upd"])
        return out.reshape(m.shape)

    return block
