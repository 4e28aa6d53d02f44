"""Jit-safe graph deltas: the :class:`DeltaPatch` COO patch, its host
builder/validator, and the device-side application onto a carried
(instance, CSR) pair.

A patch is a padded array of undirected edge operations:

* **upsert** (``delete=False``) — set the edge's cost to ``cost``,
  inserting the edge into a free padded slot if it does not exist
  (``make_patch``'s ``insert=`` and ``reweight=`` both lower to this; the
  distinction is host-side intent, not device semantics — whether the
  edge exists is device state);
* **delete** (``delete=True``) — remove the edge if present (its padded
  slot is freed and zeroed), no-op if absent.

Validation mirrors :func:`repro.core.graph.make_instance`: equal 1-D
lengths, node ids in range, **duplicate (u, v) pairs within one patch
rejected** (two ops on one edge in one tick have no defined order), and
self-loops rejected outright (no patch op is meaningful on one).

:func:`apply_patch` is pure and fixed-shape — it jits, vmaps (the serving
tier batches patches across sessions) and keeps the carried CSR live via
:func:`repro.core.graph.splice_csr`. Slot policy, mirrored exactly by the
host reference :func:`apply_patch_host` (and therefore by the cold-path
property tests): deletions free their slot in place; insertions fill free
slots ascending, in patch-entry order. Insertions beyond the instance's
free-slot capacity are dropped (the returned ``PatchInfo.n_dropped``
counts them) — size ``pad_edges`` for the churn you expect.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import (
    CsrGraph, MulticutInstance, csr_lookup_edge, splice_csr, tree_sum,
)

__all__ = ["DeltaPatch", "PatchInfo", "apply_patch", "apply_patch_host",
           "make_patch", "pad_patch"]


class DeltaPatch(NamedTuple):
    """Padded COO edge patch. ``valid`` masks live entries; a valid entry
    upserts (sets the cost of) edge (u, v), or deletes it when ``delete``.
    A pytree of fixed-shape arrays — jit/vmap-safe."""
    u: jax.Array        # (P,) int32
    v: jax.Array        # (P,) int32
    cost: jax.Array     # (P,) float32 new cost (upserts; 0 for deletes)
    delete: jax.Array   # (P,) bool
    valid: jax.Array    # (P,) bool

    @property
    def num_entries(self) -> int:
        return self.u.shape[0]


class PatchInfo(NamedTuple):
    """Device-side application report (scalars, jit-safe)."""
    n_inserted: jax.Array   # () i32 edges newly allocated
    n_deleted: jax.Array    # () i32 edges removed
    n_reweighted: jax.Array  # () i32 existing edges with cost set
    n_dropped: jax.Array    # () i32 inserts lost to missing free slots
    lb_slack: jax.Array     # () f32 Σ_e min(0, Δcost_e) over the applied
                            # ops — the additive correction that keeps a
                            # pre-patch dual bound valid for the patched
                            # problem: for any clustering y and cost change
                            # Δ, ⟨c+Δ, y⟩ ≥ ⟨c, y⟩ + Σ min(0, Δ). Deletes
                            # contribute −old_cost, inserts +new_cost,
                            # reweights new−old; dropped inserts nothing.


def make_patch(num_nodes: int, *, insert=None, delete=None, reweight=None,
               pad_entries: int | None = None) -> DeltaPatch:
    """Build a validated, padded :class:`DeltaPatch` from host arrays.

    ``insert``/``reweight`` are (u, v, cost) triples, ``delete`` a (u, v)
    pair — each entry arrays or lists. Validation mirrors
    ``make_instance`` (see module docstring). ``pad_entries`` fixes the
    patch capacity P (a jit shape); defaults to the entry count (min 1).
    """
    groups = []
    for name, grp, has_cost in (("insert", insert, True),
                                ("reweight", reweight, True),
                                ("delete", delete, False)):
        if grp is None:
            continue
        if has_cost:
            if len(grp) != 3:
                raise ValueError(f"{name} must be a (u, v, cost) triple")
            gu, gv, gc = grp
        else:
            if len(grp) != 2:
                raise ValueError(f"{name} must be a (u, v) pair")
            gu, gv = grp
            gc = np.zeros(len(np.atleast_1d(gu)), dtype=np.float32)
        gu = np.asarray(gu, dtype=np.int32)
        gv = np.asarray(gv, dtype=np.int32)
        gc = np.asarray(gc, dtype=np.float32)
        if not (gu.shape == gv.shape == gc.shape and gu.ndim == 1):
            raise ValueError(
                f"{name}: u/v/cost must be 1-D arrays of equal length; got "
                f"shapes {gu.shape}/{gv.shape}/{gc.shape}")
        groups.append((name, gu, gv, gc, name == "delete"))

    u = np.concatenate([g[1] for g in groups]) if groups \
        else np.zeros(0, np.int32)
    v = np.concatenate([g[2] for g in groups]) if groups \
        else np.zeros(0, np.int32)
    c = np.concatenate([g[3] for g in groups]) if groups \
        else np.zeros(0, np.float32)
    is_del = np.concatenate(
        [np.full(len(g[1]), g[4]) for g in groups]) if groups \
        else np.zeros(0, bool)

    if len(u):
        if u.min() < 0 or v.min() < 0 or max(u.max(), v.max()) >= num_nodes:
            bad = np.where((u < 0) | (v < 0) | (u >= num_nodes)
                           | (v >= num_nodes))[0][0]
            raise ValueError(
                f"patch node ids must lie in [0, {num_nodes}); entry "
                f"{int(bad)} is ({int(u[bad])}, {int(v[bad])})")
        if (u == v).any():
            bad = int(np.where(u == v)[0][0])
            raise ValueError(
                f"patch entries may not be self-loops; entry {bad} is "
                f"({int(u[bad])}, {int(u[bad])})")
        lo, hi = np.minimum(u, v), np.maximum(u, v)
        pairs = np.stack([lo, hi], axis=1)
        uniq, counts = np.unique(pairs, axis=0, return_counts=True)
        if (counts > 1).any():
            dup = uniq[np.argmax(counts > 1)]
            raise ValueError(
                f"duplicate (u, v) pair within one patch: "
                f"({int(dup[0])}, {int(dup[1])}) appears "
                f"{int(counts.max())} times — two ops on one edge in one "
                f"tick have no defined order; merge them host-side")
    P = len(u)
    Pp = pad_entries if pad_entries is not None else max(1, P)
    if Pp < max(1, P):
        raise ValueError(f"pad_entries={Pp} cannot hold {P} patch entries")
    uu = np.zeros(Pp, np.int32); uu[:P] = u
    vv = np.zeros(Pp, np.int32); vv[:P] = v
    cc = np.zeros(Pp, np.float32); cc[:P] = c
    dd = np.zeros(Pp, bool); dd[:P] = is_del
    ok = np.zeros(Pp, bool); ok[:P] = True
    return DeltaPatch(u=jnp.asarray(uu), v=jnp.asarray(vv),
                      cost=jnp.asarray(cc), delete=jnp.asarray(dd),
                      valid=jnp.asarray(ok))


def pad_patch(patch: DeltaPatch, pad_entries: int) -> DeltaPatch:
    """Re-pad a patch to capacity ``pad_entries`` (a larger jit shape) —
    how the serving tier lifts per-session patches onto their bucket's
    static patch capacity."""
    P = patch.num_entries
    if pad_entries < P:
        if np.asarray(patch.valid)[pad_entries:].any():
            raise ValueError(
                f"patch has live entries past index {pad_entries}; "
                f"capacity {pad_entries} cannot hold it")
        keep = slice(0, pad_entries)
        return DeltaPatch(*(x[keep] for x in patch))
    d = pad_entries - P
    return DeltaPatch(u=jnp.pad(patch.u, (0, d)),
                      v=jnp.pad(patch.v, (0, d)),
                      cost=jnp.pad(patch.cost, (0, d)),
                      delete=jnp.pad(patch.delete, (0, d)),
                      valid=jnp.pad(patch.valid, (0, d)))


def apply_patch(inst: MulticutInstance, csr: CsrGraph, patch: DeltaPatch):
    """Apply a patch on device: returns ``(inst2, csr2, PatchInfo)`` with
    ``csr2`` spliced (never rebuilt) and bit-identical to
    ``build_csr``-from-scratch of ``inst2`` (tests/test_incremental.py).

    Pure + fixed-shape: jit/vmap-safe. Existence checks are data-dependent
    and resolve on device: an upsert of an existing edge sets its cost, of
    a missing edge allocates a free slot; a delete of a missing edge is a
    no-op. Slot policy is documented in the module docstring and mirrored
    by :func:`apply_patch_host`.
    """
    E = inst.num_edges
    lo = jnp.minimum(patch.u, patch.v).astype(jnp.int32)
    hi = jnp.maximum(patch.u, patch.v).astype(jnp.int32)
    valid = patch.valid & (lo != hi)
    eid = jax.vmap(lambda a, b: csr_lookup_edge(csr, a, b))(lo, hi)
    exists = valid & (eid >= 0)

    # deletes: free the slot in place (zeroed, like make_instance padding)
    is_del = exists & patch.delete
    drop = jnp.zeros(E, bool).at[jnp.clip(eid, 0)].max(is_del)

    # upserts on existing edges: cost-only (the CSR is untouched by these)
    upd = exists & ~patch.delete
    cost1 = inst.cost.at[jnp.where(upd, eid, E)].set(patch.cost,
                                                     mode="drop")

    u1 = jnp.where(drop, 0, inst.u)
    v1 = jnp.where(drop, 0, inst.v)
    c1 = jnp.where(drop, 0.0, cost1)
    ev1 = inst.edge_valid & ~drop

    # inserts: missing upserts fill free slots ascending, patch-entry order
    fresh = valid & ~patch.delete & (eid < 0)
    free = ~inst.edge_valid | drop
    free_rank = jnp.cumsum(free.astype(jnp.int32)) - 1
    slot_of_rank = jnp.full(E, -1, jnp.int32).at[
        jnp.where(free, free_rank, E - 1)].max(
        jnp.where(free, jnp.arange(E, dtype=jnp.int32), -1))
    want_rank = jnp.cumsum(fresh.astype(jnp.int32)) - 1
    ok_alloc = fresh & (want_rank < jnp.sum(free))
    slot = jnp.where(ok_alloc, slot_of_rank[jnp.clip(want_rank, 0)], E)

    u2 = u1.at[slot].set(lo, mode="drop")
    v2 = v1.at[slot].set(hi, mode="drop")
    c2 = c1.at[slot].set(patch.cost, mode="drop")
    ev2 = ev1.at[slot].set(True, mode="drop")
    inst2 = MulticutInstance(u=u2, v=v2, cost=c2, edge_valid=ev2,
                             node_valid=inst.node_valid)
    csr2 = splice_csr(csr, drop, lo, hi,
                      jnp.where(ok_alloc, slot, 0).astype(jnp.int32),
                      ok_alloc)
    # per-entry cost delta: the old cost for resolved entries (0 for
    # inserts — the edge did not exist, so its implicit old cost is 0)
    old_cost = jnp.where(exists, inst.cost[jnp.clip(eid, 0)], 0.0)
    delta = jnp.where(is_del, -old_cost,
                      jnp.where(upd | ok_alloc, patch.cost - old_cost, 0.0))
    info = PatchInfo(
        n_inserted=jnp.sum(ok_alloc).astype(jnp.int32),
        n_deleted=jnp.sum(is_del).astype(jnp.int32),
        n_reweighted=jnp.sum(upd).astype(jnp.int32),
        n_dropped=jnp.sum(fresh & ~ok_alloc).astype(jnp.int32),
        lb_slack=tree_sum(jnp.minimum(0.0, delta)).astype(jnp.float32))
    return inst2, csr2, info


def apply_patch_host(inst: MulticutInstance,
                     patch: DeltaPatch) -> MulticutInstance:
    """Host (numpy) reference of :func:`apply_patch`'s instance update —
    the cold side of the bit-exactness property tests. Mirrors the device
    slot policy exactly: same slots, same values, slot for slot."""
    u = np.array(inst.u); v = np.array(inst.v)
    c = np.array(inst.cost); ev = np.array(inst.edge_valid)
    pu = np.asarray(patch.u); pv = np.asarray(patch.v)
    pc = np.asarray(patch.cost)
    pdel = np.asarray(patch.delete); pval = np.asarray(patch.valid)
    lo, hi = np.minimum(pu, pv), np.maximum(pu, pv)

    # pass 1: resolve against the PRE-patch edge set (what the CSR lookup
    # sees on device), recording deletes/updates/inserts per entry
    def find(a, b):
        m = ev & (u == a) & (v == b)
        return int(np.argmax(m)) if m.any() else -1

    eid = np.array([find(lo[i], hi[i]) if pval[i] and lo[i] != hi[i]
                    else -1 for i in range(len(pu))])
    valid = pval & (lo != hi)
    is_del = valid & pdel & (eid >= 0)
    upd = valid & ~pdel & (eid >= 0)
    fresh = valid & ~pdel & (eid < 0)

    for i in np.where(upd)[0]:
        c[eid[i]] = pc[i]
    for i in np.where(is_del)[0]:
        u[eid[i]] = 0; v[eid[i]] = 0; c[eid[i]] = 0.0; ev[eid[i]] = False

    free_slots = list(np.where(~ev)[0])
    for i in np.where(fresh)[0]:
        if not free_slots:
            break                     # dropped, like the device path
        s = free_slots.pop(0)
        u[s] = lo[i]; v[s] = hi[i]; c[s] = pc[i]; ev[s] = True
    return MulticutInstance(u=jnp.asarray(u), v=jnp.asarray(v),
                            cost=jnp.asarray(c), edge_valid=jnp.asarray(ev),
                            node_valid=inst.node_valid)
