"""Dual: conflicted-cycle separation (RAMA §3.2.2, Alg. 5).

A conflicted cycle contains exactly one repulsive edge (Def. 5). Two
interchangeable data paths implement the enumeration:

* **dense** (``graph_impl="dense"``) — the MXU formulation: 2-path existence
  between v1 and v3 is ``(A⁺A⁺)[v1, v3] > 0`` over (N, N) boolean
  adjacency/edge-index matrices. Fast for small N, O(N²) HBM.
* **sparse** (``graph_impl="sparse"``) — the paper's CSR formulation:
  common neighbours come from sorted-row intersection over
  :class:`repro.core.graph.CsrGraph` windows (merge-path membership via
  ``searchsorted`` / the ``cycle_intersect`` Pallas kernel + segment ops).
  O(N + E) memory — the data path for instances the dense matrices cannot
  allocate. Row windows are capped at ``row_cap`` entries; the two paths
  produce *identical* triangles whenever ``row_cap`` ≥ the maximum
  attractive degree (see tests/test_graph_impl.py).

Enumeration is capped per repulsive edge (fixed shapes) rather than
globally deduplicated. Cycles of length 4/5 are triangulated by chord edges
of cost 0 (Lemma of [15]: chordal triangulation preserves the cycle
relaxation); chords are allocated from the instance's padded free edge
slots by :func:`_alloc_chords`, which is graph-impl-agnostic.

Both sparse enumerations are split into a *candidate* phase (read-only
per-repulsive-edge search — the memory/compute hot spot) and an
*allocate/assemble* phase (chord allocation + triangle rows, cheap but
order-dependent). The candidate phase streams the repulsive batch in
fixed-size chunks through ``lax.scan`` (peak memory O(chunk·nbr_k²·row_cap)
instead of O(max_neg·nbr_k²·row_cap)) and optionally splits the chunk axis
across devices with ``shard_map``; chord slots are assigned in a canonical
(repulsive-edge-index, chord-kind) order, so results are bit-identical for
every ``separation_chunk``/``separation_shards`` setting — including the
un-chunked whole-batch case (tests/test_chunked_separation.py).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core.graph import (
    CsrGraph, DEFAULT_SPARSE_THRESHOLD, MulticutInstance, csr_filter,
    csr_from_instance, csr_lookup_edge, csr_row_window, resolve_graph_impl,
    splice_csr,
)
from repro.kernels.cycle_intersect.ref import intersect_rows_ref


class DenseAdj(NamedTuple):
    """The (N, N) boolean/index pair separation reads — no cost matrix, so
    nothing here can be mistaken for one (the old ``with_costs=False`` path
    returned a bool array in the f32 ``A`` slot)."""
    Apos: jax.Array   # (N, N) bool attractive adjacency
    eidx: jax.Array   # (N, N) int32 edge index or -1


class DenseGraph(NamedTuple):
    A: jax.Array      # (N, N) symmetric f32 costs
    Apos: jax.Array   # (N, N) bool attractive adjacency
    eidx: jax.Array   # (N, N) int32 edge index or -1


def build_adjacency(inst: MulticutInstance) -> DenseAdj:
    """Boolean adjacency + edge-index matrices (what separation reads).
    Skipping the (N, N) f32 cost scatter+read is ~25% of the separation
    round's HBM traffic (EXPERIMENTS.md §Perf cell C iter 2)."""
    N, E = inst.num_nodes, inst.num_edges
    pos = inst.edge_valid & (inst.cost > 0)
    su = jnp.where(inst.edge_valid, inst.u, 0)
    sv = jnp.where(inst.edge_valid, inst.v, 0)
    Apos = jnp.zeros((N, N), dtype=bool)
    Apos = Apos.at[su, sv].max(pos).at[sv, su].max(pos)
    eidx = jnp.full((N, N), -1, dtype=jnp.int32)
    e = jnp.arange(E, dtype=jnp.int32)
    eid = jnp.where(inst.edge_valid, e, -1)
    eidx = eidx.at[su, sv].max(eid)
    eidx = eidx.at[sv, su].max(eid)
    # repair the (0,0) cell polluted by invalid rows (a true (0,0)
    # self-entry is impossible anyway)
    eidx = eidx.at[0, 0].set(-1)
    return DenseAdj(Apos=Apos, eidx=eidx)


def build_dense(inst: MulticutInstance) -> DenseGraph:
    """Full dense view including the f32 cost matrix (tests / oracles)."""
    adj = build_adjacency(inst)
    c = jnp.where(inst.edge_valid, inst.cost, 0.0)
    A = jnp.zeros((inst.num_nodes,) * 2, dtype=inst.cost.dtype)
    A = A.at[inst.u, inst.v].add(c).at[inst.v, inst.u].add(c)
    return DenseGraph(A=A, Apos=adj.Apos, eidx=adj.eidx)


def select_repulsive_edges(inst: MulticutInstance, max_neg: int,
                           threshold: float = 0.0, node_mask=None):
    """Indices of the ``max_neg`` most repulsive valid edges (+ mask).

    ``node_mask`` ((N,) bool, optional) restricts candidates to edges with
    at least one endpoint in the mask — the frontier restriction warm
    delta re-solves apply on their first rounds (shape-preserving, so the
    same ``top_k`` serves both data paths; a ``None`` mask compiles to
    exactly the unrestricted jaxpr)."""
    sel = inst.edge_valid & (inst.cost < threshold)
    if node_mask is not None:
        sel = sel & (node_mask[inst.u] | node_mask[inst.v])
    score = jnp.where(sel, -inst.cost, -jnp.inf)
    k = min(max_neg, score.shape[0])
    vals, idx = jax.lax.top_k(score, k)
    return idx.astype(jnp.int32), vals > 0


class Triangles(NamedTuple):
    """Triangle subproblems: rows of edge indices into the instance arrays.
    Invalid rows are zeroed (scatter-safe, impl-independent)."""
    edges: jax.Array   # (T, 3) int32 edge ids
    valid: jax.Array   # (T,) bool


class CycleSeparationResult(NamedTuple):
    instance: MulticutInstance  # possibly with new zero-cost chord edges
    triangles: Triangles
    # all-edges CSR of ``instance`` (chords spliced in), maintained only
    # when the caller asks (``separate(..., update_csr=True)``) on the
    # sparse path — lets D-mode carry its CSR across rounds instead of
    # re-running build_csr's 2E-lexsort every round
    csr: CsrGraph | None = None


# ---------------------------------------------------------------------------
# 3-cycles
# ---------------------------------------------------------------------------

def separate_triangles(inst: MulticutInstance, adj: DenseAdj,
                       max_neg: int, max_tri_per_edge: int,
                       node_mask=None) -> Triangles:
    """3-cycles, dense path: for each repulsive edge (i, j) pick up to K
    common attractive neighbours k; triangle edges (ij, ik, jk). (Lemma 6
    specialised to hop distance 2 — the common-neighbour test is one
    row-AND, i.e. the matmul ``A⁺A⁺`` restricted to the repulsive pairs.)
    top_k over the 0/1 row picks the K smallest common neighbour ids."""
    neg_idx, neg_ok = select_repulsive_edges(inst, max_neg,
                                             node_mask=node_mask)
    i = inst.u[neg_idx]
    j = inst.v[neg_idx]
    max_tri_per_edge = min(max_tri_per_edge, inst.num_nodes)

    def per_edge(i_, j_, e_, ok_):
        common = (adj.Apos[i_] & adj.Apos[j_]).astype(jnp.float32)
        vals, ks = jax.lax.top_k(common, max_tri_per_edge)
        good = (vals > 0) & ok_
        e_ik = adj.eidx[i_, ks]
        e_jk = adj.eidx[j_, ks]
        tri = jnp.stack([jnp.full_like(ks, e_), e_ik, e_jk], axis=-1)
        good = good & (e_ik >= 0) & (e_jk >= 0)
        return tri, good

    tris, goods = jax.vmap(per_edge)(i, j, neg_idx, neg_ok)
    tris = tris.reshape(-1, 3).astype(jnp.int32)
    goods = goods.reshape(-1)
    return Triangles(edges=jnp.where(goods[:, None], tris, 0), valid=goods)


def triangles_from_windows(ci, ei, oki, cj, ej, e_, ok_, K, intersect):
    """Triangle candidates from prefetched endpoint windows.

    ``ci``/``ei``/``oki`` are the (B, W) column/edge-id/validity windows of
    the repulsive edges' first endpoints, ``cj``/``ej`` the second
    endpoints'; ``e_``/``ok_`` the (B,) repulsive edge ids and masks. The
    common-neighbour test is ``intersect`` over the sorted windows; the
    first K matches reproduce the dense top_k (K smallest common
    neighbours). Shared by the replicated and edge-sharded separation
    paths so their triangle math is identical by construction."""
    Wb = ci.shape[1]
    pos = intersect(ci, cj)                 # (B, Wb) match position or -1
    pc = jnp.clip(pos, 0, Wb - 1)
    found = (pos >= 0) & oki                # mask ci's sentinel padding

    def per_edge(found_, ei_, ej_, pc_, e__, ok__):
        vals, idxs = jax.lax.top_k(found_.astype(jnp.float32), K)
        good = (vals > 0) & ok__
        e_ik = ei_[idxs]
        e_jk = ej_[pc_[idxs]]
        tri = jnp.stack([jnp.full((K,), e__, dtype=jnp.int32), e_ik,
                         e_jk], axis=-1)
        good = good & (e_ik >= 0) & (e_jk >= 0)
        return tri, good

    tris, goods = jax.vmap(per_edge)(found, ei, ej, pc, e_, ok_)
    return (tris.reshape(-1, 3).astype(jnp.int32), goods.reshape(-1))


def separate_triangles_sparse(inst: MulticutInstance, csr_pos: CsrGraph,
                              max_neg: int, max_tri_per_edge: int,
                              row_cap: int = 128, row_cap_short: int = 0,
                              intersect=None,
                              chunk: int = 0, shards: int = 1,
                              node_mask=None) -> Triangles:
    """3-cycles, CSR path: the common-neighbour test is a sorted-row
    intersection of the two endpoints' attractive rows (the paper's CSR
    kernel). Windows are ascending by node id, so taking the first K
    matches reproduces the dense top_k exactly (same K smallest common
    neighbours) whenever ``row_cap`` covers the rows. The per-edge search
    streams through :func:`_map_repulsive_batches` (``chunk``/``shards``);
    each edge's triangles depend on its own rows only, so the output is
    invariant to both settings. ``row_cap_short`` > 0 splits edges into
    degree buckets: edges whose endpoint rows fit in the short window take
    the narrow pass, the rest a chunk-gated pass at full ``row_cap`` —
    bit-identical to the single-cap path (see the bucketing note above
    :func:`_combine_buckets`)."""
    if intersect is None:
        intersect = intersect_rows_ref
    N = inst.num_nodes
    K = min(max_tri_per_edge, N)
    W = max(K, min(row_cap, N))
    Ws = max(K, min(row_cap_short, N)) if row_cap_short > 0 else W
    neg_idx, neg_ok = select_repulsive_edges(inst, max_neg,
                                             node_mask=node_mask)
    i = inst.u[neg_idx]
    j = inst.v[neg_idx]

    def make_batch(Wb):
        def batch(csr_pos, i_, j_, e_, ok_):
            window = jax.vmap(lambda n: csr_row_window(csr_pos, n, Wb))
            ci, ei, oki = window(i_)            # (B, Wb) each
            cj, ej, _ = window(j_)
            return triangles_from_windows(ci, ei, oki, cj, ej, e_, ok_, K,
                                          intersect)
        return batch

    if Ws >= W:
        tris, goods = _map_repulsive_batches(make_batch(W), csr_pos,
                                             (i, j, neg_idx, neg_ok),
                                             chunk, shards)
    else:
        deg = csr_pos.degrees
        is_long = (deg[i] > Ws) | (deg[j] > Ws)
        out_s = _map_repulsive_batches(
            make_batch(Ws), csr_pos, (i, j, neg_idx, neg_ok & ~is_long),
            chunk, shards)
        out_l = _run_long_bucket(
            make_batch(W), csr_pos, (i, j, neg_idx, neg_ok & is_long),
            is_long, chunk, shards, Ws, W)
        tris, goods = _combine_buckets(is_long, out_s, out_l)
    return Triangles(edges=jnp.where(goods[:, None], tris, 0), valid=goods)


# ---------------------------------------------------------------------------
# Chord allocation (graph-impl-agnostic)
# ---------------------------------------------------------------------------

class ChordAlloc(NamedTuple):
    instance: MulticutInstance  # with chords written into free slots
    eid: jax.Array       # (M,) chord edge id per request or -1
    ok: jax.Array        # (M,) request satisfied
    # the raw allocation rows, in splice_csr's argument shape — lets a
    # caller holding a live CSR splice the fresh chords in instead of
    # rebuilding from the instance (add_eid rows with add_ok False are
    # placeholders)
    add_u: jax.Array     # (M,) lo endpoint per request
    add_v: jax.Array     # (M,) hi endpoint per request
    add_eid: jax.Array   # (M,) allocated slot (edge id) per fresh chord
    add_ok: jax.Array    # (M,) request allocated a fresh slot


def _alloc_chords(inst: MulticutInstance, exists_eid, ch_u, ch_v,
                  ch_ok) -> ChordAlloc:
    """Allocate chord edges (cost 0) from free padded slots; reuse existing
    edges where the chord already exists.

    ``exists_eid``: (M,) id of an already-existing valid edge (lo, hi), or
    -1 — the one graph lookup the caller performs (dense eidx gather or CSR
    bisect), which is what makes this routine shared by both data paths.
    Duplicates within the batch resolve to the first requester's slot
    (first occurrence wins), and fresh slots are packed in request order —
    so for a fixed request order, allocation is fully deterministic.
    """
    E = inst.num_edges
    M = ch_u.shape[0]
    lo = jnp.minimum(ch_u, ch_v)
    hi = jnp.maximum(ch_u, ch_v)
    exists = exists_eid >= 0
    need = ch_ok & ~exists & (lo != hi)
    # dedupe within batch: keep the first occurrence of each (lo, hi) key.
    # One small lexsort over the M requests (stable, so runs keep request
    # order and the run head IS the first occurrence) — O(M log M), not the
    # O(M²) pairwise-compare this used to be.
    sent = jnp.int32(2 ** 31 - 1)
    kl = jnp.where(need, lo, sent)
    kh = jnp.where(need, hi, sent)
    order = jnp.lexsort((kh, kl))
    kl_s, kh_s = kl[order], kh[order]
    run_head = jnp.concatenate([
        jnp.ones((1,), bool),
        (kl_s[1:] != kl_s[:-1]) | (kh_s[1:] != kh_s[:-1])])
    run = jnp.cumsum(run_head.astype(jnp.int32)) - 1
    head_of_run = jax.ops.segment_min(order.astype(jnp.int32), run,
                                      num_segments=M)
    first_idx = jnp.zeros(M, jnp.int32).at[order].set(head_of_run[run])
    fresh = need & (first_idx == jnp.arange(M, dtype=jnp.int32))

    # assign free slots in edge arrays: rank the fresh chords and map rank ->
    # index of the rank-th free slot (scatter-max into a rank table)
    free = ~inst.edge_valid
    free_rank = jnp.cumsum(free.astype(jnp.int32)) - 1      # rank among free
    slot_of_rank = jnp.full(E, -1, dtype=jnp.int32)
    slot_of_rank = slot_of_rank.at[jnp.where(free, free_rank, E - 1)].max(
        jnp.where(free, jnp.arange(E, dtype=jnp.int32), -1))
    want_rank = jnp.cumsum(fresh.astype(jnp.int32)) - 1
    n_free = jnp.sum(free)
    fits = want_rank < n_free
    ok_alloc = fresh & fits
    slot = jnp.where(ok_alloc, slot_of_rank[jnp.clip(want_rank, 0)], E - 1)

    # per-slot incoming values (each allocated slot written by exactly one
    # fresh chord, so segment-max recovers it; -1 marks "no allocation")
    new_u = jax.ops.segment_max(jnp.where(ok_alloc, lo, -1), slot,
                                num_segments=E)
    new_v = jax.ops.segment_max(jnp.where(ok_alloc, hi, -1), slot,
                                num_segments=E)
    alloc_here = new_u >= 0
    # slot E-1 also collects the -1 sentinels of non-allocating rows; the max
    # keeps a real allocation there if one exists.
    u2 = jnp.where(alloc_here, new_u, inst.u).astype(jnp.int32)
    v2 = jnp.where(alloc_here, new_v, inst.v).astype(jnp.int32)
    c2 = jnp.where(alloc_here, 0.0, inst.cost)
    ev2 = inst.edge_valid | alloc_here
    inst2 = MulticutInstance(u=u2, v=v2, cost=c2, edge_valid=ev2,
                             node_valid=inst.node_valid)

    # resolve each request to its chord id: existing edge, own fresh slot,
    # or the first equal requester's slot (if that one got a slot)
    own = jnp.where(need & ok_alloc[first_idx], slot[first_idx], -1)
    chord_eid = jnp.where(exists, exists_eid, own).astype(jnp.int32)
    chord_ok = ch_ok & (chord_eid >= 0) & (lo != hi)
    return ChordAlloc(instance=inst2, eid=chord_eid, ok=chord_ok,
                      add_u=lo, add_v=hi, add_eid=slot, add_ok=ok_alloc)


# ---------------------------------------------------------------------------
# 4/5-cycles
# ---------------------------------------------------------------------------

def _assemble_cycles45(v0, v4, b1, b2, b3, is4, found, lookup,
                       ch1, ch1_ok, ch2, ch2_ok):
    """Shared tail of both 4/5-cycle paths: chord-triangulate the best pair
    per repulsive edge into triangle rows. ``lookup(a, b)`` resolves an
    original edge id (dense eidx gather or CSR bisect)."""
    e = lookup
    # triangles for 4-cycle: {v0v1, v1v4, v4v0}, {v1v3, v3v4, v4v1}
    t4a = jnp.stack([e(v0, b1), ch1, e(v4, v0)], axis=-1)
    t4b = jnp.stack([e(b1, b3), e(b3, v4), ch1], axis=-1)
    ok4 = found & is4 & ch1_ok
    # triangles for 5-cycle: {v0v1,v1v4,v4v0}, {v1v2,v2v4,v4v1}, {v2v3,v3v4,v4v2}
    t5b = jnp.stack([e(b1, b2), ch2, ch1], axis=-1)
    t5c = jnp.stack([e(b2, b3), e(b3, v4), ch2], axis=-1)
    ok5 = found & ~is4 & ch1_ok & ch2_ok

    tris = jnp.concatenate([t4a, t4b, t5b, t5c], axis=0).astype(jnp.int32)
    oks = jnp.concatenate([ok4 | ok5, ok4, ok5, ok5], axis=0)
    oks = oks & jnp.all(tris >= 0, axis=-1)
    tris = jnp.where(oks[:, None], tris, 0)
    return Triangles(edges=tris, valid=oks)


def _alloc_and_assemble(inst: MulticutInstance, lookup, v0, v4, b1, b2, b3,
                        is4, found,
                        splice_into: CsrGraph | None = None,
                        ) -> CycleSeparationResult:
    """Allocate/assemble phase shared by both data paths: resolve the
    winning pairs' chords in canonical (repulsive-edge-index, chord-kind)
    order — chord 1 = (v1, v4) and chord 2 = (v2, v4) interleaved in ONE
    batch — then triangulate. The canonical order makes chord slot
    assignment a function of the candidates alone, independent of how the
    candidate phase was chunked or sharded. ``splice_into`` (the caller's
    all-edges CSR of ``inst``) additionally splices the fresh chords into
    that CSR — bit-identical to rebuilding it from the chorded instance."""
    lo1, hi1 = jnp.minimum(b1, v4), jnp.maximum(b1, v4)
    lo2, hi2 = jnp.minimum(b2, v4), jnp.maximum(b2, v4)
    ex = jnp.stack([lookup(lo1, hi1), lookup(lo2, hi2)], axis=1).reshape(-1)
    ch_u = jnp.stack([b1, b2], axis=1).reshape(-1)
    ch_v = jnp.stack([v4, v4], axis=1).reshape(-1)
    need = jnp.stack([found, found & ~is4], axis=1).reshape(-1)
    a = _alloc_chords(inst, ex, ch_u, ch_v, need)
    eid = a.eid.reshape(-1, 2)
    ok = a.ok.reshape(-1, 2)
    tri = _assemble_cycles45(v0, v4, b1, b2, b3, is4, found, lookup,
                             eid[:, 0], ok[:, 0], eid[:, 1], ok[:, 1])
    csr2 = None
    if splice_into is not None:
        no_drop = jnp.zeros((inst.num_edges,), bool)
        csr2 = splice_csr(splice_into, no_drop, a.add_u, a.add_v,
                          a.add_eid, a.add_ok)
    return CycleSeparationResult(instance=a.instance, triangles=tri,
                                 csr=csr2)


def resolve_separation_shards(shards: int) -> int:
    """Clamp the requested separation shard count to the devices present —
    a preset asking for 4 shards still traces on a 1-device runner."""
    if shards is None or shards <= 1:
        return 1
    return min(int(shards), jax.device_count())


def _pad_batch_axis(a, pad: int):
    """Zero-pad an edge arg along its leading (batch) axis only — edge args
    may be (M,) scalars-per-edge or (M, k) precomputed fans."""
    return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))


def _map_repulsive_batches(fn, consts, edge_args, chunk: int, shards: int):
    """Stream a per-repulsive-edge candidate function over the batch axis.

    ``edge_args`` are arrays with leading axis M (one of them the validity
    mask — padding rows are zero/False and must be masked by it); ``consts``
    is a pytree
    of read-only arrays (CSR views) every batch needs, replicated under
    sharding. ``fn(consts, *batch)`` maps a (C,)-batch to arrays whose
    leading axis is a multiple of C and must treat edges independently —
    that independence is what makes the output invariant to ``chunk`` and
    ``shards`` (asserted bit-for-bit in tests/test_chunked_separation.py).

    chunk <= 0 runs the whole batch as one ``lax.scan`` step (the legacy
    peak-memory shape); 0 < chunk < M bounds live candidate arrays at
    O(chunk·nbr_k²·row_cap). shards > 1 additionally splits the (padded)
    batch axis across devices with ``shard_map``, each shard scanning its
    own chunks; per-shard outputs concatenate back in edge order. Returns
    exactly what ``fn(consts, *edge_args)`` whole-batch would.
    """
    M = edge_args[0].shape[0]
    C = M if chunk <= 0 else max(1, min(chunk, M))
    S = resolve_separation_shards(shards)
    if S > 1 and chunk <= 0:
        # default chunk under sharding: one chunk per shard — C = M would
        # pad the batch to S·M and land every REAL edge on shard 0 (the
        # split is contiguous), leaving the other shards chewing padding
        C = -(-M // S)
    if S == 1 and C >= M:
        # trivial streaming: skip the scan wrapper entirely — a length-1
        # lax.scan is a fusion barrier (XLA can't fuse the candidate search
        # with downstream message passing across it; measured ~25% on the
        # smoke dual round)
        return fn(consts, *edge_args)
    Mp = -(-M // (S * C)) * (S * C)
    padded = tuple(_pad_batch_axis(a, Mp - M) for a in edge_args)

    def scan_chunks(consts, *local):
        n_chunks = local[0].shape[0] // C
        if n_chunks == 1:
            return fn(consts, *local)
        xs = tuple(a.reshape((n_chunks, C) + a.shape[1:]) for a in local)
        _, ys = jax.lax.scan(lambda _, x: (None, fn(consts, *x)), None, xs)
        return jax.tree.map(
            lambda y: y.reshape((y.shape[0] * y.shape[1],) + y.shape[2:]),
            ys)

    if S == 1:
        out = scan_chunks(consts, *padded)
    else:
        from repro.core.dist import separation_mesh   # lazy: dist → solver
        mesh = separation_mesh(S)
        out = shard_map(
            scan_chunks, mesh=mesh,
            in_specs=(P(),) + (P("sep"),) * len(padded),
            out_specs=P("sep"), check_vma=False)(consts, *padded)
    return jax.tree.map(lambda y: y[: (y.shape[0] // Mp) * M], out)


# ---------------------------------------------------------------------------
# Two-level degree bucketing
# ---------------------------------------------------------------------------
#
# One global padded ``row_cap`` sizes every window to the *maximum*
# attractive degree, so the O(chunk·nbr_k²·row_cap) candidate working set —
# and most of its compare work — is spent on padding whenever the degree
# distribution is skewed (the per-row work-skew the paper's warp-per-row
# CUDA kernels absorb dynamically; here the shapes are static, so we bucket
# instead). Edges whose relevant rows all fit in a narrow ``short`` window
# stream through windows of that width; the rest take a second pass at the
# full ``row_cap`` width, streamed in proportionally smaller chunks (same
# elements-per-chunk budget) and skipped entirely (``lax.cond``) for chunks
# holding no long edge. For short rows the narrow window is a prefix of the
# wide one with identical match positions, and long edges re-run the exact
# single-cap computation — so the combined result is bit-identical to the
# unbucketed path whenever ``row_cap`` covers its rows
# (tests/test_graph_impl.py, tests/test_chunked_separation.py).

def _combine_buckets(is_long, out_s, out_l):
    """Per-edge select between the short- and long-bucket outputs. Output
    leading axes are k outputs per edge, edge-major (edge i owns lanes
    [i*k, (i+1)*k))."""
    M = is_long.shape[0]

    def sel(s, l):
        k = s.shape[0] // M
        m = jnp.repeat(is_long, k) if k > 1 else is_long
        return jnp.where(m.reshape((s.shape[0],) + (1,) * (s.ndim - 1)),
                         l, s)

    return jax.tree.map(sel, out_s, out_l)


def _map_long_chunks(fn, consts, edge_args, is_long, chunk: int):
    """Single-device long-bucket streamer: scan fixed-size chunks, running
    ``fn`` only on chunks that contain at least one long edge (lax.cond;
    skipped chunks emit zeros — discarded by :func:`_combine_buckets`, which
    never reads short lanes from the long pass). Under vmap the cond lowers
    to a select (both branches run) — correct, just without the skip."""
    M = edge_args[0].shape[0]
    C = max(1, min(chunk, M))
    Mp = -(-M // C) * C
    padded = tuple(_pad_batch_axis(a, Mp - M) for a in edge_args)
    lng = _pad_batch_axis(is_long, Mp - M)
    n_chunks = Mp // C
    xs = tuple(a.reshape((n_chunks, C) + a.shape[1:]) for a in padded)
    shapes = jax.eval_shape(lambda *a: fn(consts, *a),
                            *(x[0] for x in xs))

    def step(_, x):
        *args, l = x
        out = jax.lax.cond(
            jnp.any(l),
            lambda: fn(consts, *args),
            lambda: jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                 shapes))
        return None, out

    _, ys = jax.lax.scan(step, None, xs + (lng.reshape(n_chunks, C),))
    flat = jax.tree.map(
        lambda y: y.reshape((y.shape[0] * y.shape[1],) + y.shape[2:]), ys)
    return jax.tree.map(lambda y: y[: (y.shape[0] // Mp) * M], flat)


def _run_long_bucket(fn, consts, edge_args, is_long, chunk: int, shards: int,
                     w_short: int, w_long: int):
    """Long-bucket pass: every edge lane evaluated at the wide window (its
    validity mask already restricted to long edges), chunk size scaled by
    w_short/w_long so peak memory matches the short pass's per-chunk
    element budget. Sharded runs reuse the ungated streamer — the gate is a
    data-dependent skip that would break the static shard split; bit-
    identity holds either way because only long lanes are ever read."""
    M = edge_args[0].shape[0]
    base = chunk if chunk > 0 else M
    C = max(1, (base * w_short) // w_long)
    if resolve_separation_shards(shards) > 1:
        return _map_repulsive_batches(fn, consts, edge_args, C, shards)
    return _map_long_chunks(fn, consts, edge_args, is_long, C)


def separate_cycles45(inst: MulticutInstance, adj: DenseAdj, max_neg: int,
                      nbr_k: int = 4,
                      node_mask=None) -> CycleSeparationResult:
    """4/5-cycles per Alg. 5, dense path: for repulsive edge (v0, v4), scan
    pairs (v1, v3) ∈ N⁺(v0) × N⁺(v4); a 4-cycle needs v1v3 ∈ E⁺, a 5-cycle a
    common attractive neighbour v2 (via the A⁺A⁺ matmul). The best pair per
    repulsive edge is triangulated with zero-cost chords."""
    N = inst.num_nodes
    nbr_k = min(nbr_k, N)
    # (bf16 rows were tried here and measured 3% WORSE — the convert op
    # costs more than the halved gather at nbr_k=4; §Perf cell C iter 3)
    Aposf = adj.Apos.astype(jnp.float32)
    # 2-path existence is only needed for the (v1, v3) candidate pairs of
    # the selected repulsive edges — max_neg·nbr_k² pairs. The full P2 =
    # A⁺A⁺ product costs 2N³ FLOPs (137 GF at the pd_round_lg shape); the
    # per-edge row-dot form below costs 2·max_neg·nbr_k²·N (34 MF, 4000x
    # less) with identical results. EXPERIMENTS.md §Perf cell C iter 1.
    neg_idx, neg_ok = select_repulsive_edges(inst, max_neg,
                                             node_mask=node_mask)
    v0 = inst.u[neg_idx]
    v4 = inst.v[neg_idx]

    def per_edge(v0_, v4_, ok_):
        w0, n0 = jax.lax.top_k(Aposf[v0_], nbr_k)     # neighbours of v0
        w4, n4 = jax.lax.top_k(Aposf[v4_], nbr_k)     # neighbours of v4
        ok0 = w0 > 0
        ok4 = w4 > 0
        pair_ok = ok0[:, None] & ok4[None, :] & ok_
        v1 = jnp.broadcast_to(n0[:, None], (nbr_k, nbr_k))
        v3 = jnp.broadcast_to(n4[None, :], (nbr_k, nbr_k))
        distinct = (v1 != v3) & (v1 != v4_) & (v3 != v0_)
        is4 = pair_ok & distinct & adj.Apos[v1, v3]
        # (nbr_k, N) @ (N, nbr_k) batched row-dot == P2[v1, v3]
        pair_counts = Aposf[n0] @ Aposf[n4].T
        has2path = pair_counts > 0
        is5 = pair_ok & distinct & ~is4 & has2path
        # score: prefer 4-cycles, strongest attractive support
        score = jnp.where(is4, 2.0, jnp.where(is5, 1.0, -jnp.inf)) \
            + jnp.minimum(w0[:, None], w4[None, :]) * 1e-3
        flat = jnp.argmax(score)
        bi, bj = flat // nbr_k, flat % nbr_k
        found = score.reshape(-1)[flat] > -jnp.inf
        b_v1 = v1[bi, bj]
        b_v3 = v3[bi, bj]
        b_is4 = is4[bi, bj]
        # for the 5-cycle pick v2 = common attractive neighbour of v1, v3
        common = (adj.Apos[b_v1] & adj.Apos[b_v3]).astype(jnp.float32)
        common = common.at[v0_].set(0.0).at[v4_].set(0.0)
        b_v2 = jnp.argmax(common).astype(jnp.int32)
        has_v2 = common[b_v2] > 0
        found = found & (b_is4 | has_v2)
        return (b_v1.astype(jnp.int32), b_v2, b_v3.astype(jnp.int32),
                b_is4, found)

    b1, b2, b3, is4, found = jax.vmap(per_edge)(v0, v4, neg_ok)

    # chords: 4-cycle v0-v1-v3-v4 needs chord (v1, v4);
    #         5-cycle v0-v1-v2-v3-v4 needs chords (v1, v4) and (v2, v4)
    return _alloc_and_assemble(inst, lambda a, b: adj.eidx[a, b],
                               v0, v4, b1, b2, b3, is4, found)


def separate_cycles45_sparse(inst: MulticutInstance, csr_pos: CsrGraph,
                             csr_all: CsrGraph, max_neg: int, nbr_k: int = 4,
                             row_cap: int = 128, row_cap_short: int = 0,
                             intersect=None,
                             chunk: int = 0, shards: int = 1,
                             node_mask=None,
                             splice_into: CsrGraph | None = None,
                             ) -> CycleSeparationResult:
    """4/5-cycles, CSR path. Mirrors the dense scan pair for pair:

    * neighbour fans N⁺(v0)/N⁺(v4) = the first ``nbr_k`` entries of each
      sorted attractive row (== dense top_k over the 0/1 row);
    * the 4-cycle edge test v1v3 ∈ E⁺ = membership of v3 in v1's window —
      one more row intersection over windows already resident, replacing
      the global O(log E) bisect per pair (identical under covering caps;
      at non-covering caps it is the more conservative window-local test);
    * 2-path existence (the A⁺A⁺ row-dot) = sorted-row intersection of the
      fan nodes' windows — per-chunk·nbr_k² window pairs through
      ``intersect`` (ref searchsorted or the cycle_intersect kernel);
    * v2 = first surviving element of the winning pair's intersection.

    The candidate search streams the repulsive batch through
    :func:`_map_repulsive_batches` (``chunk``/``shards``), degree-bucketed
    into a short/long two-pass when ``row_cap_short`` > 0 (the fans are
    computed once, up front, and decide each edge's bucket); chord
    allocation + triangulation run on the gathered winners in canonical
    order. ``splice_into`` maintains the caller's all-edges CSR through
    chord allocation (see :func:`_alloc_and_assemble`).
    """
    if intersect is None:
        intersect = intersect_rows_ref
    N = inst.num_nodes
    nbr_k = min(nbr_k, N)
    W = max(1, min(row_cap, N))
    Ws = max(1, min(row_cap_short, N)) if row_cap_short > 0 else W
    neg_idx, neg_ok = select_repulsive_edges(inst, max_neg,
                                             node_mask=node_mask)
    v0 = inst.u[neg_idx]
    v4 = inst.v[neg_idx]
    fan = jax.vmap(lambda n: csr_row_window(csr_pos, n, nbr_k))
    n0, _, ok0 = fan(v0)                            # (M, nbr_k)
    n4, _, ok4 = fan(v4)

    def make_candidates(Wb):
        def candidates(csr_pos, v0_, v4_, n0_, n4_, ok0_, ok4_, ok_):
            B = v0_.shape[0]
            # windows of every fan node's attractive row: (B, nbr_k, Wb)
            window = jax.vmap(jax.vmap(
                lambda n: csr_row_window(csr_pos, n, Wb)))
            r1c, _, r1ok = window(n0_)
            r3c, _, _ = window(n4_)

            # 2-path existence for every (v1, v3) pair, looped over the j
            # fan so only (B·nbr_k, Wb) windows are live at once; only the
            # boolean (B, nbr_k, nbr_k) result is kept
            ci_flat = r1c.reshape(B * nbr_k, Wb)
            oki_flat = r1ok.reshape(B * nbr_k, Wb)
            has2 = []
            for j in range(nbr_k):
                cj_j = jnp.broadcast_to(r3c[:, None, j, :], (B, nbr_k, Wb)) \
                    .reshape(B * nbr_k, Wb)
                pos_j = intersect(ci_flat, cj_j)
                has2.append(jnp.any((pos_j >= 0) & oki_flat, axis=-1)
                            .reshape(B, nbr_k))
            has2path = jnp.stack(has2, axis=-1)         # (B, nbr_k, nbr_k)

            v1 = jnp.broadcast_to(n0_[:, :, None], (B, nbr_k, nbr_k))
            v3 = jnp.broadcast_to(n4_[:, None, :], (B, nbr_k, nbr_k))
            # v1v3 ∈ E⁺ ⇔ v3 appears in v1's window: intersect the v4-fan
            # (each row i of edge b asks for all of n4[b]) against r1c —
            # invalid fan slots carry the N sentinel on both sides and are
            # masked by pair_ok below
            fan3 = jnp.broadcast_to(n4_[:, None, :], (B, nbr_k, nbr_k)) \
                .reshape(B * nbr_k, nbr_k)
            e13pos = intersect(fan3, ci_flat).reshape(B, nbr_k, nbr_k)

            pair_ok = ok0_[:, :, None] & ok4_[:, None, :] & ok_[:, None, None]
            distinct = (v1 != v3) & (v1 != v4_[:, None, None]) & \
                (v3 != v0_[:, None, None])
            is4 = pair_ok & distinct & (e13pos >= 0)
            is5 = pair_ok & distinct & ~is4 & has2path
            w0 = ok0_.astype(jnp.float32)
            w4 = ok4_.astype(jnp.float32)
            score = jnp.where(is4, 2.0, jnp.where(is5, 1.0, -jnp.inf)) \
                + jnp.minimum(w0[:, :, None], w4[:, None, :]) * 1e-3
            flat = jnp.argmax(score.reshape(B, -1), axis=1)
            bi, bj = flat // nbr_k, flat % nbr_k
            m = jnp.arange(B)
            found = score.reshape(B, -1)[m, flat] > -jnp.inf
            b1 = n0_[m, bi]
            b3 = n4_[m, bj]
            b_is4 = is4[m, bi, bj]
            # v2 = smallest common attractive neighbour of (b1, b3),
            # excluding the repulsive endpoints — first surviving element
            # of the winning pair's (ascending) intersection, == dense
            # argmax over the 0/1 common row. Re-intersect just the winning
            # pair per repulsive edge ((B, Wb), cheap) instead of keeping
            # the full pair batch alive.
            win_cols = r1c[m, bi]                               # (B, Wb)
            win_pos = intersect(win_cols, r3c[m, bj])
            win_common = (win_pos >= 0) & r1ok[m, bi] & \
                (win_cols != v0_[:, None]) & (win_cols != v4_[:, None])
            has_v2 = jnp.any(win_common, axis=1)
            first = jnp.argmax(win_common, axis=1)
            b2 = jnp.where(has_v2, win_cols[m, first], 0).astype(jnp.int32)
            found = found & (b_is4 | has_v2)
            return (b1.astype(jnp.int32), b2, b3.astype(jnp.int32), b_is4,
                    found)
        return candidates

    if Ws >= W:
        b1, b2, b3, is4, found = _map_repulsive_batches(
            make_candidates(W), csr_pos,
            (v0, v4, n0, n4, ok0, ok4, neg_ok), chunk, shards)
    else:
        # an edge is long iff ANY window it reads (its fan nodes' rows)
        # overflows the short cap
        deg = csr_pos.degrees
        dl0 = jnp.where(ok0, deg[jnp.clip(n0, 0, N - 1)], 0)
        dl4 = jnp.where(ok4, deg[jnp.clip(n4, 0, N - 1)], 0)
        is_long = (jnp.max(dl0, axis=1) > Ws) | (jnp.max(dl4, axis=1) > Ws)
        out_s = _map_repulsive_batches(
            make_candidates(Ws), csr_pos,
            (v0, v4, n0, n4, ok0, ok4, neg_ok & ~is_long), chunk, shards)
        out_l = _run_long_bucket(
            make_candidates(W), csr_pos,
            (v0, v4, n0, n4, ok0, ok4, neg_ok & is_long),
            is_long, chunk, shards, Ws, W)
        b1, b2, b3, is4, found = _combine_buckets(is_long, out_s, out_l)
    lookup_all = jax.vmap(lambda a, b: csr_lookup_edge(csr_all, a, b))
    return _alloc_and_assemble(inst, lookup_all, v0, v4, b1, b2, b3, is4,
                               found, splice_into=splice_into)


# ---------------------------------------------------------------------------
# Full separation round
# ---------------------------------------------------------------------------

def separate(inst: MulticutInstance, max_neg: int, max_tri_per_edge: int,
             with_cycles45: bool = True, nbr_k: int = 4,
             graph_impl: str = "dense", sparse_row_cap: int = 128,
             sparse_row_cap_short: int = 0,
             sparse_threshold: int = DEFAULT_SPARSE_THRESHOLD, intersect=None,
             csr: CsrGraph | None = None, separation_chunk: int = 0,
             separation_shards: int = 1,
             sep_node_mask=None,
             update_csr: bool = False) -> CycleSeparationResult:
    """Full separation round: 3-cycles always; 4/5-cycles optionally
    (PD uses 5 on the original graph, 3 on contracted graphs; PD+ always 5).

    ``graph_impl`` selects the data path ("auto" flips to CSR above
    ``sparse_threshold`` nodes); ``intersect`` swaps the sorted-row
    intersection implementation (None = jnp ref, or the cycle_intersect
    Pallas kernel via ``backend="pallas"``).

    ``csr`` is the caller's live all-edges CSR of ``inst`` (the solver's
    carried SolverState CSR); when given, the sparse path builds nothing —
    the attractive E⁺ view is a sort-free :func:`csr_filter` over it. When
    absent, one ``build_csr`` runs here (still only one: E⁺ is filtered
    from it, not rebuilt). ``separation_chunk``/``separation_shards``
    stream/shard the sparse candidate search (dense ignores both: it is
    the small-N path where the whole batch fits trivially).

    ``sep_node_mask`` ((N,) bool, optional) restricts repulsive-edge
    selection to edges touching the mask — the frontier restriction of
    warm delta re-solves. Applies identically on both data paths; ``None``
    compiles to the unrestricted jaxpr.

    ``sparse_row_cap_short`` > 0 enables the two-level degree buckets on
    the sparse candidate search (see :func:`_combine_buckets`);
    ``update_csr`` asks the sparse path to also return its all-edges CSR
    with the round's fresh chords spliced in (``result.csr``) so a dual
    loop can carry it — requested explicitly because eager callers would
    otherwise pay the splice for an output they drop (jit DCE removes it
    for free, eager does not).
    """
    impl = resolve_graph_impl(graph_impl, inst.num_nodes, sparse_threshold)
    if impl == "dense":
        adj = build_adjacency(inst)
        tri3 = separate_triangles(inst, adj, max_neg, max_tri_per_edge,
                                  node_mask=sep_node_mask)
        if not with_cycles45:
            return CycleSeparationResult(instance=inst, triangles=tri3)
        res45 = separate_cycles45(inst, adj, max_neg, nbr_k=nbr_k,
                                  node_mask=sep_node_mask)
    else:
        csr_all = csr_from_instance(inst) if csr is None else csr
        csr_pos = csr_filter(csr_all, inst.edge_valid & (inst.cost > 0))
        tri3 = separate_triangles_sparse(inst, csr_pos, max_neg,
                                         max_tri_per_edge,
                                         row_cap=sparse_row_cap,
                                         row_cap_short=sparse_row_cap_short,
                                         intersect=intersect,
                                         chunk=separation_chunk,
                                         shards=separation_shards,
                                         node_mask=sep_node_mask)
        if not with_cycles45:
            return CycleSeparationResult(
                instance=inst, triangles=tri3,
                csr=csr_all if update_csr else None)
        res45 = separate_cycles45_sparse(inst, csr_pos, csr_all, max_neg,
                                         nbr_k=nbr_k,
                                         row_cap=sparse_row_cap,
                                         row_cap_short=sparse_row_cap_short,
                                         intersect=intersect,
                                         chunk=separation_chunk,
                                         shards=separation_shards,
                                         node_mask=sep_node_mask,
                                         splice_into=(csr_all if update_csr
                                                      else None))
    edges = jnp.concatenate([tri3.edges, res45.triangles.edges], axis=0)
    valid = jnp.concatenate([tri3.valid, res45.triangles.valid], axis=0)
    return CycleSeparationResult(
        instance=res45.instance,
        triangles=Triangles(edges=edges, valid=valid),
        csr=res45.csr)
