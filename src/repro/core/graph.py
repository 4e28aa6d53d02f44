"""Padded-COO multicut instance, padded-CSR graph view + instance generators.

RAMA's graphs shrink across contraction rounds; XLA needs static shapes. We
keep (N, E) fixed for the lifetime of a solve and track validity masks:
``node_valid`` marks live cluster representatives, ``edge_valid`` live edges.
Costs follow the paper's sign convention: c > 0 attractive (want joined),
c < 0 repulsive (want cut).

:class:`CsrGraph` is the device-resident sparse adjacency the large-N data
path runs on (the paper's CSR representation, §3.2.2): a symmetric, padded
CSR whose rows are sorted by neighbour id, built jit-safely from the padded
COO arrays each round. Memory is O(N + E) instead of the O(N²) dense
adjacency/edge-index matrices, which is what lets separation run on
instances two orders of magnitude beyond the dense ceiling.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

GRAPH_IMPLS = ("dense", "sparse", "auto")

INT32_MAX = 2 ** 31 - 1

# --------------------------------------------------------------------------
# Addressing dtype policy (>2^31-edge safety)
# --------------------------------------------------------------------------
#
# Edge ids stay int32 *within a shard* (the sharded solve partitions the
# edge range, so per-shard counts are E/S); what can overflow first are the
# CSR *offsets*: ``row_ptr`` counts directed entries, i.e. runs to 2E.
# The policy: offsets widen to int64 once 2E exceeds int32 — but int64 on
# device requires x64 mode, so without ``jax.config.jax_enable_x64`` any
# build that would need wide offsets raises an actionable ValueError
# instead of silently wrapping (see :func:`check_edge_addressing`).


def offset_dtype(num_edges: int):
    """Dtype for CSR offsets (values run to 2·num_edges): int32 while they
    fit, int64 beyond (requires x64 — checked by
    :func:`check_edge_addressing` before any array is built)."""
    return jnp.int64 if 2 * num_edges > INT32_MAX else jnp.int32


def index_dtype(num_edges: int):
    """Dtype for edge ids (values run to num_edges): int32 while they fit.
    Within a shard of the edge-partitioned solve this is always int32 —
    only a replicated build over >2^31 edges widens."""
    return jnp.int64 if num_edges > INT32_MAX else jnp.int32


def check_edge_addressing(num_edges: int, where: str = "build_csr") -> None:
    """Raise an actionable ValueError when ``num_edges`` needs int64
    addressing (edge count or 2E CSR offsets past int32) but x64 mode is
    off — the failure mode otherwise is silent int32 wraparound producing
    wrong CSR rows with no error."""
    if 2 * num_edges <= INT32_MAX:
        return
    if not jax.config.jax_enable_x64:
        raise ValueError(
            f"{where}: {num_edges} edges need int64 addressing (CSR "
            f"offsets run to 2E = {2 * num_edges} > int32 max "
            f"{INT32_MAX}), but jax x64 mode is off — offsets would "
            f"silently wrap. Enable the int64 offset policy with "
            f"jax.config.update('jax_enable_x64', True) (edge ids stay "
            f"int32 within a shard; see graph.py 'Addressing dtype "
            f"policy'), or shard the instance so each shard holds "
            f"<= {INT32_MAX // 2} edges.")

# "auto" flips the separation data path to CSR above this padded node count.
# Derived, not guessed: the dense path's per-round cost is dominated by the
# (N, N) adjacency build + the per-repulsive-edge (nbr_k, N)·(N, nbr_k)
# row-dot (linear in N), while the bucketed-CSR path's cost is independent
# of N (windows scale with degree caps). ``benchmarks/calibrate.py`` sweeps
# the crossover on a fixed-degree family: since the degree-bucketed
# windows landed, sparse reaches parity at N = 128 (1.04x dense), stays
# within ~10% at 256, and wins outright from 512 (0.83x) while needing
# less peak memory, so "auto" flips as early as the measurement supports.
# Re-run the sweep and update this constant when separation economics
# change.
DEFAULT_SPARSE_THRESHOLD = 256


def tree_sum(x: jax.Array) -> jax.Array:
    """Sum along the LAST axis by an explicit pairwise halving tree of
    elementwise adds. ``jnp.sum`` lowers to an XLA reduce whose float
    accumulation order is a compiler choice: it changes with the
    surrounding program (fusion context, shard count) and, on the TPU,
    with a vmapped batch axis, so the same instance solved alone and in a
    batch would report lower bounds that differ in the last bits.
    Spelling the tree out as adds of distinct tensors pins the float DAG
    to the (static) width alone: same width → same bits, batched or not,
    on every backend."""
    while x.shape[-1] > 1:
        half = x.shape[-1] // 2
        paired = x[..., : 2 * half : 2] + x[..., 1: 2 * half : 2]
        if x.shape[-1] % 2:
            paired = jnp.concatenate([paired, x[..., -1:]], axis=-1)
        x = paired
    return x[..., 0]


class MulticutInstance(NamedTuple):
    u: jax.Array            # (E,) int32, u < v for valid edges
    v: jax.Array            # (E,) int32
    cost: jax.Array         # (E,) float32
    edge_valid: jax.Array   # (E,) bool
    node_valid: jax.Array   # (N,) bool

    @property
    def num_nodes(self) -> int:
        return self.node_valid.shape[0]

    @property
    def num_edges(self) -> int:
        return self.edge_valid.shape[0]

    def objective(self, labels: jax.Array) -> jax.Array:
        """Multicut objective <c, y>: sum of costs of cut edges under a node
        labeling (y_e = 1 iff endpoints in distinct clusters)."""
        cut = labels[self.u] != labels[self.v]
        return tree_sum(jnp.where(self.edge_valid & cut, self.cost, 0.0))


def make_instance(u, v, cost, num_nodes: int, pad_edges: int | None = None,
                  pad_nodes: int | None = None) -> MulticutInstance:
    """Build a padded instance from (possibly unordered) host edge arrays.

    Parallel edges are merged by summing their costs (the multicut
    objective is linear in the cut indicator, so this is loss-free). Every
    instance is therefore a simple graph — the invariant both separation
    data paths rely on for their bit-identical equivalence (``contract``
    re-establishes it after each round via ``coo_dedupe_sum``, and chord
    allocation never duplicates an edge). First-occurrence order is kept,
    so duplicate-free inputs get identical edge ids as before.

    Raises ``ValueError`` on mismatched ``u``/``v``/``cost`` lengths, node
    ids outside ``[0, num_nodes)``, or self-loops with nonzero cost —
    any of these would silently misindex the padded arrays downstream
    (wrong rows in the CSR, costs attributed to the wrong edges) with no
    error until results are wrong. Zero-cost self-loops stay admissible:
    they are exactly the neutral filler slots padding already emits.
    """
    u = np.asarray(u, dtype=np.int32)
    v = np.asarray(v, dtype=np.int32)
    cost = np.asarray(cost, dtype=np.float32)
    if not (u.shape == v.shape == cost.shape and u.ndim == 1):
        raise ValueError(
            f"u/v/cost must be 1-D arrays of equal length; got shapes "
            f"u={u.shape}, v={v.shape}, cost={cost.shape}")
    if len(u) and (u.min() < 0 or v.min() < 0
                   or max(u.max(), v.max()) >= num_nodes):
        bad = np.where((u < 0) | (v < 0) | (u >= num_nodes)
                       | (v >= num_nodes))[0]
        raise ValueError(
            f"node ids must lie in [0, {num_nodes}); {len(bad)} edge(s) out "
            f"of range, first at index {int(bad[0])}: "
            f"({int(u[bad[0]])}, {int(v[bad[0]])})")
    if len(u):
        bad = np.where((u == v) & (cost != 0.0))[0]
        if len(bad):
            raise ValueError(
                f"self-loops must have zero cost (a nonzero self-loop cost "
                f"can never be cut and would silently shift the objective); "
                f"{len(bad)} offending edge(s), first at index "
                f"{int(bad[0])}: ({int(u[bad[0]])}, {int(u[bad[0]])}) with "
                f"cost {float(cost[bad[0]])}")
    lo, hi = np.minimum(u, v), np.maximum(u, v)
    if len(lo):
        pairs = np.stack([lo, hi], axis=1)
        _, first_idx, inv = np.unique(pairs, axis=0, return_index=True,
                                      return_inverse=True)
        if len(first_idx) < len(lo):
            order = np.argsort(first_idx)          # first-occurrence order
            rank = np.empty_like(order)
            rank[order] = np.arange(len(order))
            merged = np.zeros(len(first_idx), dtype=np.float32)
            np.add.at(merged, rank[inv], cost)
            keep = first_idx[order]
            lo, hi, cost = lo[keep], hi[keep], merged
    E = len(lo)
    Ep = pad_edges if pad_edges is not None else E
    Np = pad_nodes if pad_nodes is not None else num_nodes
    assert Ep >= E and Np >= num_nodes
    check_edge_addressing(Ep, where="make_instance")
    uu = np.zeros(Ep, dtype=np.int32); uu[:E] = lo
    vv = np.zeros(Ep, dtype=np.int32); vv[:E] = hi
    cc = np.zeros(Ep, dtype=np.float32); cc[:E] = cost
    ev = np.zeros(Ep, dtype=bool); ev[:E] = True
    nv = np.zeros(Np, dtype=bool); nv[:num_nodes] = True
    return MulticutInstance(u=jnp.asarray(uu), v=jnp.asarray(vv),
                            cost=jnp.asarray(cc), edge_valid=jnp.asarray(ev),
                            node_valid=jnp.asarray(nv))


def to_host_edges(inst: MulticutInstance):
    """Valid edges as host numpy arrays (u, v, cost)."""
    ev = np.asarray(inst.edge_valid)
    return (np.asarray(inst.u)[ev], np.asarray(inst.v)[ev],
            np.asarray(inst.cost)[ev])


class StreamStats(NamedTuple):
    """Host-memory accounting of :func:`make_instance_streamed` — what the
    allocation test pins: the ingest never buffers more than one shard
    range plus one chunk of COO on the host."""
    n_chunks: int           # COO chunks consumed
    n_edges: int            # valid edges ingested
    peak_host_elems: int    # max host-resident edge slots at any instant
                            # (shard buffer + in-flight chunk)


def make_instance_streamed(chunks, num_nodes: int, pad_edges: int,
                           state_shards: int = 1,
                           pad_nodes: int | None = None,
                           ) -> tuple[MulticutInstance, StreamStats]:
    """Streaming instance ingest: build the padded edge arrays shard range
    by shard range from an iterable of COO ``(u, v, cost)`` chunks, so the
    full edge list is never materialized on one host.

    ``chunks`` yields host arrays in final edge-id order; the input must be
    **duplicate-free** (cross-chunk parallel-edge merging would require the
    full list — exactly what streaming avoids; :func:`make_instance` merges
    duplicates for callers who can afford materialization). Each chunk is
    validated like ``make_instance`` (id range, self-loop costs).

    Edges are accumulated into one host buffer of ``pad_edges /
    state_shards`` slots; every time a contiguous shard range fills, it is
    shipped to its device (``jax.device_put`` onto the state mesh's
    devices) and the buffer is reused — peak host memory is one shard
    range + one chunk, not E (returned in :class:`StreamStats`, pinned by
    tests/test_state_sharded.py). With ``state_shards=1`` this degrades to
    chunked assembly of a single-device instance (still bounded by the one
    reusable buffer since S=1 means the buffer IS the edge range).

    Returns ``(instance, stats)``; the instance's edge leaves are sharded
    jax Arrays (leading axis split over the state mesh) ready for
    ``api.solve(config=SolverConfig(state_shards=...))``.
    """
    from repro.core.dist import resolve_state_shards, state_mesh
    S = resolve_state_shards(state_shards)
    if pad_edges % S:
        raise ValueError(f"pad_edges={pad_edges} must be divisible by the "
                         f"{S} resolved state shard(s)")
    check_edge_addressing(pad_edges, where="make_instance_streamed")
    E_loc = pad_edges // S
    mesh = state_mesh(S)
    devices = list(mesh.devices.ravel())
    sharding = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("state"))

    buf_u = np.zeros(E_loc, np.int32)
    buf_v = np.zeros(E_loc, np.int32)
    buf_c = np.zeros(E_loc, np.float32)
    buf_ev = np.zeros(E_loc, bool)
    shard_arrays: list[tuple] = []
    fill = 0            # edges placed into the current shard buffer
    shard = 0
    n_edges = 0
    n_chunks = 0
    peak = 0

    def flush_shard():
        nonlocal shard, fill
        dev = devices[shard]
        shard_arrays.append(tuple(
            jax.device_put(a.copy(), dev)
            for a in (buf_u, buf_v, buf_c, buf_ev)))
        buf_u[:] = 0; buf_v[:] = 0; buf_c[:] = 0.0; buf_ev[:] = False
        shard += 1
        fill = 0

    for cu, cv, cc in chunks:
        cu = np.asarray(cu, dtype=np.int32)
        cv = np.asarray(cv, dtype=np.int32)
        cc = np.asarray(cc, dtype=np.float32)
        if not (cu.shape == cv.shape == cc.shape and cu.ndim == 1):
            raise ValueError(
                f"chunk {n_chunks}: u/v/cost must be 1-D arrays of equal "
                f"length; got shapes {cu.shape}, {cv.shape}, {cc.shape}")
        if len(cu) and (cu.min() < 0 or cv.min() < 0
                        or max(cu.max(), cv.max()) >= num_nodes):
            raise ValueError(f"chunk {n_chunks}: node ids must lie in "
                             f"[0, {num_nodes})")
        if len(cu) and np.any((cu == cv) & (cc != 0.0)):
            raise ValueError(f"chunk {n_chunks}: self-loops must have zero "
                             f"cost (see make_instance)")
        lo = np.minimum(cu, cv); hi = np.maximum(cu, cv)
        n_chunks += 1
        peak = max(peak, E_loc + len(lo))
        off = 0
        while off < len(lo):
            if n_edges + (len(lo) - off) > pad_edges:
                raise ValueError(
                    f"streamed edges exceed pad_edges={pad_edges}; raise "
                    f"the pad (round_up_edges helps pick a shardable one)")
            take = min(E_loc - fill, len(lo) - off)
            sl = slice(fill, fill + take)
            buf_u[sl] = lo[off:off + take]
            buf_v[sl] = hi[off:off + take]
            buf_c[sl] = cc[off:off + take]
            buf_ev[sl] = True
            fill += take
            off += take
            n_edges += take
            if fill == E_loc:
                flush_shard()
    while shard < S:
        flush_shard()

    def assemble(i):
        return jax.make_array_from_single_device_arrays(
            (pad_edges,), sharding, [p[i] for p in shard_arrays])

    u, v, c, ev = assemble(0), assemble(1), assemble(2), assemble(3)
    Np = pad_nodes if pad_nodes is not None else num_nodes
    nv = np.zeros(Np, bool); nv[:num_nodes] = True
    inst = MulticutInstance(u=u, v=v, cost=c, edge_valid=ev,
                            node_valid=jnp.asarray(nv))
    return inst, StreamStats(n_chunks=n_chunks, n_edges=n_edges,
                             peak_host_elems=peak)


def round_up_edges(num_edges: int, state_shards: int = 1,
                   blocks: int = 16) -> int:
    """Smallest pad_edges >= num_edges compatible with the sharded solve:
    divisible by ``blocks`` (the S-invariant blocked-reduction ranges,
    ``repro.core.dist.STATE_BLOCKS``) and by ``state_shards``."""
    import math
    q = math.lcm(max(1, int(blocks)), max(1, int(state_shards)))
    return ((max(1, num_edges) + q - 1) // q) * q


ROW_CAP_FLOOR = 8   # never tune sparse_row_cap_short below this: tiny
                    # windows make every row "long" and the short pass
                    # pure overhead (shared by the serving engine's
                    # per-bucket tuner and api.solve's one-shot tuner)


def attractive_degree_p95(inst: MulticutInstance, floor: int = ROW_CAP_FLOOR,
                          cap: int = 128) -> int:
    """Host-side p95 of the per-node attractive (cost > 0) degree over
    valid nodes, clamped to ``[floor, cap]`` — the one-shot
    ``sparse_row_cap_short`` tuning shared by the serving engine's
    per-bucket self-tuning and ``api.solve(tune_sparse_caps=True)``. The
    covering caps in degree-bucketed separation make any value
    bit-identical; this picks the wall-clock sweet spot (windows wide
    enough for ~95% of rows to take the narrow pass)."""
    import math
    u = np.asarray(inst.u)
    v = np.asarray(inst.v)
    att = np.asarray(inst.edge_valid) & (np.asarray(inst.cost) > 0)
    deg = (np.bincount(u[att], minlength=inst.num_nodes)
           + np.bincount(v[att], minlength=inst.num_nodes))
    deg = deg[np.asarray(inst.node_valid)]
    p95 = float(np.percentile(deg, 95)) if deg.size else 0.0
    return int(np.clip(math.ceil(p95), floor, cap))


# ---------------------------------------------------------------------------
# Sparse CSR graph view (the paper's representation; memory O(N + E))
# ---------------------------------------------------------------------------

class CsrGraph(NamedTuple):
    """Symmetric padded CSR adjacency over a masked edge subset.

    Fixed shapes for jit: ``col``/``edge_id`` always hold 2E slots (each
    masked-in undirected edge contributes both directions). Row i's entries
    live at ``col[row_ptr[i]:row_ptr[i+1]]``, sorted ascending by neighbour
    id (ties by edge id, so duplicate parallel edges resolve to the largest
    id under :func:`csr_lookup_edge`, matching the dense scatter-max).
    Dead slots are compacted to the tail and hold the sentinel ``N`` in
    ``col`` and ``-1`` in ``edge_id``; ``row_ptr[N]`` is the live count.
    """
    row_ptr: jax.Array   # (N+1,) int32 CSR offsets
    col: jax.Array       # (2E,) int32 neighbour node, N = dead sentinel
    edge_id: jax.Array   # (2E,) int32 edge index into instance arrays, -1 dead

    @property
    def num_nodes(self) -> int:
        return self.row_ptr.shape[0] - 1

    @property
    def degrees(self) -> jax.Array:
        return self.row_ptr[1:] - self.row_ptr[:-1]


def build_csr(u, v, mask, num_nodes: int) -> CsrGraph:
    """Jit-safe COO→CSR: lexsort the 2E directed copies by (src, dst, eid);
    masked-out edges get sentinel endpoints that sort past every live row,
    and ``row_ptr`` falls out of one searchsorted over the sorted src column
    (Alg. 4's sort_by_key, shape-static). Offsets follow the module's
    addressing dtype policy: int32 while 2E fits, int64 past that (x64
    required — :func:`check_edge_addressing` raises before anything
    wraps)."""
    E = u.shape[0]
    check_edge_addressing(E, where="build_csr")
    src = jnp.concatenate([u, v]).astype(jnp.int32)
    dst = jnp.concatenate([v, u]).astype(jnp.int32)
    eid = jnp.tile(jnp.arange(E, dtype=index_dtype(E)), 2)
    m = jnp.concatenate([mask, mask])
    src = jnp.where(m, src, num_nodes)
    dst = jnp.where(m, dst, num_nodes)
    order = jnp.lexsort((eid, dst, src))
    src_s = src[order]
    row_ptr = jnp.searchsorted(
        src_s, jnp.arange(num_nodes + 1, dtype=jnp.int32),
        side="left").astype(offset_dtype(E))
    return CsrGraph(row_ptr=row_ptr, col=dst[order],
                    edge_id=jnp.where(m[order], eid[order], -1))


def csr_from_instance(inst: MulticutInstance,
                      attractive_only: bool = False) -> CsrGraph:
    """CSR over the valid edges; ``attractive_only`` restricts to c > 0
    (the E⁺ view the paper's cycle kernels intersect over)."""
    mask = inst.edge_valid & (inst.cost > 0) if attractive_only \
        else inst.edge_valid
    return build_csr(inst.u, inst.v, mask, inst.num_nodes)


def csr_row_window(csr: CsrGraph, node, cap: int):
    """First ``cap`` entries of a node's CSR row (ascending neighbour id).

    Returns (cols, eids, valid): (cap,) each, padded with the N sentinel /
    -1 past the row's degree. Exact (loss-free) whenever cap ≥ degree;
    larger rows are truncated to their cap smallest neighbours — the same
    greedy cap the dense path applies through top_k. Gather-based so it
    vmaps over ``node``.
    """
    N = csr.num_nodes
    start = csr.row_ptr[node]
    deg = csr.row_ptr[node + 1] - start
    pos = jnp.arange(cap, dtype=jnp.int32)
    idx = jnp.clip(start + pos, 0, csr.col.shape[0] - 1)
    ok = pos < deg
    cols = jnp.where(ok, csr.col[idx], N)
    eids = jnp.where(ok, csr.edge_id[idx], -1)
    return cols, eids, ok


def csr_filter(csr: CsrGraph, keep_edge: jax.Array) -> CsrGraph:
    """Sort-free row filter: the CSR restricted to edges with
    ``keep_edge[edge_id]`` True.

    Entries of a ``CsrGraph`` are globally sorted by (row, neighbour, edge
    id); dropping a subset preserves that order, so the filtered CSR falls
    out of one prefix-sum + scatter — no sort. This is how the attractive
    E⁺ view is derived each round from the solver's carried all-edges CSR
    (bit-identical to ``csr_from_instance(inst, attractive_only=True)``
    whenever ``keep_edge`` is the attractive mask).
    """
    nnz = csr.col.shape[0]
    N = csr.num_nodes
    keep = (csr.edge_id >= 0) & keep_edge[jnp.clip(csr.edge_id, 0)]
    kept_before = jnp.concatenate([
        jnp.zeros((1,), jnp.int32), jnp.cumsum(keep.astype(jnp.int32))])
    row_ptr = kept_before[csr.row_ptr].astype(jnp.int32)
    dest = jnp.where(keep, kept_before[1:] - 1, nnz)   # compacted position
    col = jnp.full((nnz,), N, jnp.int32).at[dest].set(csr.col, mode="drop")
    eid = jnp.full((nnz,), -1, jnp.int32).at[dest].set(csr.edge_id,
                                                       mode="drop")
    return CsrGraph(row_ptr=row_ptr, col=col, edge_id=eid)


def csr_lookup_edge(csr: CsrGraph, a, b) -> jax.Array:
    """Edge id of (a, b) or -1 — bisect-right over row a's sorted slice.

    Fixed ceil(log2(2E))+1 iterations (jit-safe); duplicate parallel edges
    resolve to the largest edge id, matching dense eidx's scatter-max.
    Scalar in, scalar out; vmap for batches.
    """
    nnz = csr.col.shape[0]
    a = jnp.clip(jnp.asarray(a, jnp.int32), 0, csr.num_nodes - 1)
    lo0 = csr.row_ptr[a]
    lo, hi = lo0, csr.row_ptr[a + 1]
    iters = max(1, int(np.ceil(np.log2(max(2, nnz)))) + 1)

    def body(_, lohi):
        lo, hi = lohi
        mid = jnp.clip((lo + hi) // 2, 0, nnz - 1)
        go_right = (lo < hi) & (csr.col[mid] <= b)
        lo2 = jnp.where(go_right, mid + 1, lo)
        hi2 = jnp.where(lo < hi, jnp.where(go_right, hi, mid), hi)
        return lo2, hi2

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    p = jnp.clip(lo - 1, 0, nnz - 1)
    found = (lo > lo0) & (csr.col[p] == b)
    return jnp.where(found, csr.edge_id[p], -1)


def _lex_count_less(rows, cols, eids, live, r, c, e):
    """Count of live CSR entries whose (row, col, edge_id) key sorts
    strictly before (r, c, e) — a fixed-iteration lexicographic bisect over
    the globally sorted entry arrays (same jit-safe shape as
    :func:`csr_lookup_edge`). Scalar in, scalar out; vmap for batches."""
    nnz = cols.shape[0]
    iters = max(1, int(np.ceil(np.log2(max(2, nnz)))) + 1)

    def body(_, lohi):
        lo, hi = lohi
        mid = jnp.clip((lo + hi) // 2, 0, nnz - 1)
        less = (rows[mid] < r) | (
            (rows[mid] == r) & ((cols[mid] < c) | (
                (cols[mid] == c) & (eids[mid] < e))))
        go_right = (lo < hi) & less
        lo2 = jnp.where(go_right, mid + 1, lo)
        hi2 = jnp.where(lo < hi, jnp.where(go_right, hi, mid), hi)
        return lo2, hi2

    lo, _ = jax.lax.fori_loop(0, iters, body,
                              (jnp.int32(0), live.astype(jnp.int32)))
    return lo


def splice_csr(csr: CsrGraph, drop_edge: jax.Array, add_u: jax.Array,
               add_v: jax.Array, add_eid: jax.Array,
               add_ok: jax.Array) -> CsrGraph:
    """Merge an edge patch into a live CSR without a COO→CSR rebuild.

    ``drop_edge`` is an (E,) mask of edge ids whose entries leave the CSR;
    ``add_u``/``add_v``/``add_eid`` are (P,) new undirected edges (masked
    by ``add_ok``) to insert under their instance edge ids. Cost reweights
    never touch a CSR (it stores no costs) — only deletions/insertions do.

    Deletion is the sort-free prefix-sum compaction of :func:`csr_filter`;
    insertion lexsorts only the 2P new directed entries (the one *bounded*
    sort — O(P log P), never O(E log E)) and merges them into the already
    sorted live region with a lexicographic bisect per new entry plus one
    ``searchsorted`` for the old entries' shift. The result is
    **bit-identical** to ``build_csr`` of the patched instance (asserted
    in tests/test_incremental.py): same live ordering by (src, dst, eid),
    same sentinel dead tail (``col == N``, ``edge_id == -1``), same
    ``row_ptr``.
    """
    nnz = csr.col.shape[0]
    N = csr.num_nodes
    P = add_u.shape[0]

    # 1. drop: compact out every entry of a dropped edge (csr_filter shape)
    keep = (csr.edge_id >= 0) & ~drop_edge[jnp.clip(csr.edge_id, 0)]
    kept_before = jnp.concatenate([
        jnp.zeros((1,), jnp.int32), jnp.cumsum(keep.astype(jnp.int32))])
    row_ptr_c = kept_before[csr.row_ptr].astype(jnp.int32)
    dest = jnp.where(keep, kept_before[1:] - 1, nnz)
    col_c = jnp.full((nnz,), N, jnp.int32).at[dest].set(csr.col, mode="drop")
    eid_c = jnp.full((nnz,), -1, jnp.int32).at[dest].set(csr.edge_id,
                                                         mode="drop")
    live = row_ptr_c[N]
    # per-entry row id, recovered from row_ptr (dead tail lands on row N)
    row_c = (jnp.searchsorted(row_ptr_c, jnp.arange(nnz, dtype=jnp.int32),
                              side="right") - 1).astype(jnp.int32)

    # 2. the one bounded lexsort: 2P new directed entries by (src, dst, eid)
    src_n = jnp.concatenate([add_u, add_v]).astype(jnp.int32)
    dst_n = jnp.concatenate([add_v, add_u]).astype(jnp.int32)
    eid_n = jnp.concatenate([add_eid, add_eid]).astype(jnp.int32)
    ok_n = jnp.concatenate([add_ok, add_ok])
    src_n = jnp.where(ok_n, src_n, N)
    dst_n = jnp.where(ok_n, dst_n, N)
    order = jnp.lexsort((eid_n, dst_n, src_n))
    src_s, ok_s = src_n[order], ok_n[order]
    dst_s = jnp.where(ok_s, dst_n[order], N)
    eid_s = jnp.where(ok_s, eid_n[order], -1)

    # 3. merge positions: each new entry bisects the live region; keys never
    # collide (an inserted edge id's old entries were dropped in step 1)
    ins = jax.vmap(lambda r, c, e: _lex_count_less(
        row_c, col_c, eid_c, live, r, c, e))(src_s, dst_s, eid_s)
    new_pos = ins + jnp.arange(2 * P, dtype=jnp.int32)
    # old entries shift by the number of new entries inserted at-or-before
    # them; ``ins`` is nondecreasing (keys sorted), so one searchsorted
    shift = jnp.searchsorted(ins, jnp.arange(nnz, dtype=jnp.int32),
                             side="right").astype(jnp.int32)
    old_pos = jnp.arange(nnz, dtype=jnp.int32) + shift

    col2 = jnp.full((nnz,), N, jnp.int32) \
        .at[old_pos].set(col_c, mode="drop") \
        .at[new_pos].set(dst_s, mode="drop")
    eid2 = jnp.full((nnz,), -1, jnp.int32) \
        .at[old_pos].set(eid_c, mode="drop") \
        .at[new_pos].set(eid_s, mode="drop")
    row_ptr2 = row_ptr_c + jnp.searchsorted(
        src_s, jnp.arange(N + 1, dtype=jnp.int32),
        side="left").astype(jnp.int32)
    return CsrGraph(row_ptr=row_ptr2, col=col2, edge_id=eid2)


def resolve_graph_impl(graph_impl: str, num_nodes: int,
                       threshold: int = DEFAULT_SPARSE_THRESHOLD) -> str:
    """Static dense/sparse dispatch: "auto" flips to the CSR data path once
    the padded node count crosses ``threshold`` (where the dense (N, N)
    matrices start to dominate HBM)."""
    if graph_impl == "auto":
        return "sparse" if num_nodes > threshold else "dense"
    if graph_impl not in ("dense", "sparse"):
        raise ValueError(f"unknown graph_impl {graph_impl!r}; expected one "
                         f"of {GRAPH_IMPLS}")
    return graph_impl


# ---------------------------------------------------------------------------
# Instance generators (synthetic datasets standing in for the paper's
# Cityscapes / Connectomics instances; same structural regimes).
# ---------------------------------------------------------------------------

def random_instance(n: int, p: float, seed: int = 0, mu: float = 0.0,
                    sigma: float = 1.0, pad_edges: int | None = None,
                    pad_nodes: int | None = None) -> MulticutInstance:
    """Erdos-Renyi graph with gaussian signed costs."""
    rng = np.random.default_rng(seed)
    iu, ju = np.triu_indices(n, k=1)
    keep = rng.random(len(iu)) < p
    u, v = iu[keep], ju[keep]
    c = rng.normal(mu, sigma, size=len(u)).astype(np.float32)
    return make_instance(u, v, c, n, pad_edges=pad_edges, pad_nodes=pad_nodes)


def grid_instance(h: int, w: int, seed: int = 0, noise: float = 0.4,
                  n_segments: int = 6, long_range: bool = True,
                  pad_edges: int | None = None,
                  pad_nodes: int | None = None) -> MulticutInstance:
    """Cityscapes-like grid instance: 4-connectivity + coarse long-range
    edges, costs derived from a planted segmentation + noise (so ground-truth
    structure exists and objective values are meaningful)."""
    rng = np.random.default_rng(seed)
    # planted segmentation: Voronoi cells of random centers
    cy = rng.uniform(0, h, n_segments); cx = rng.uniform(0, w, n_segments)
    yy, xx = np.mgrid[0:h, 0:w]
    d = (yy[..., None] - cy) ** 2 + (xx[..., None] - cx) ** 2
    seg = d.argmin(-1)

    def edge_cost(a_idx, b_idx):
        same = (seg.ravel()[a_idx] == seg.ravel()[b_idx]).astype(np.float32)
        base = np.where(same, 1.0, -1.0)
        return base + rng.normal(0, noise * 2, size=len(a_idx)).astype(np.float32)

    idx = np.arange(h * w).reshape(h, w)
    us, vs = [], []
    # 4-connectivity
    us.append(idx[:, :-1].ravel()); vs.append(idx[:, 1:].ravel())
    us.append(idx[:-1, :].ravel()); vs.append(idx[1:, :].ravel())
    if long_range:
        for (dy, dx) in [(0, 4), (4, 0), (3, 3)]:
            if h > dy and w > dx:
                us.append(idx[: h - dy, : w - dx].ravel())
                vs.append(idx[dy:, dx:].ravel())
    u = np.concatenate(us); v = np.concatenate(vs)
    c = edge_cost(u, v)
    return make_instance(u, v, c, h * w, pad_edges=pad_edges,
                         pad_nodes=pad_nodes)


def cluster_instance(n: int, k: int = 4, p_in: float = 0.6,
                     p_out: float = 0.1, seed: int = 0, noise: float = 0.5,
                     pad_edges: int | None = None,
                     pad_nodes: int | None = None) -> MulticutInstance:
    """Planted-partition instance (connectomics-like regime): k ground-truth
    clusters, dense attractive edges inside, sparse repulsive edges across,
    gaussian cost noise on both."""
    rng = np.random.default_rng(seed)
    assign = rng.integers(0, k, size=n)
    iu, ju = np.triu_indices(n, k=1)
    same = assign[iu] == assign[ju]
    keep = rng.random(len(iu)) < np.where(same, p_in, p_out)
    u, v = iu[keep], ju[keep]
    base = np.where(same[keep], 1.0, -1.0).astype(np.float32)
    c = base + rng.normal(0, noise, size=len(u)).astype(np.float32)
    return make_instance(u, v, c, n, pad_edges=pad_edges, pad_nodes=pad_nodes)


def to_networkx(inst: MulticutInstance):
    import networkx as nx
    g = nx.Graph()
    g.add_nodes_from(range(int(np.asarray(inst.node_valid).sum())))
    u, v, c = to_host_edges(inst)
    for a, b, w_ in zip(u.tolist(), v.tolist(), c.tolist()):
        g.add_edge(a, b, weight=w_)
    return g
