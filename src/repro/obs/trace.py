"""Structured solver telemetry: a :class:`SolveTrace` pytree captured
*inside* the jitted round loop with zero additional host syncs.

RAMA's primal-dual loop is valuable precisely because the per-round lower
bound / objective pair "estimates the distance to optimum" — but until
now the solver only surfaced a final ``lb_history`` stack, and the
sharded path surfaced nothing about shard balance at all. ``SolveTrace``
captures the full per-round trajectory as stacked device arrays inside
the ``lax.while_loop`` carry — exactly like ``lb_history`` has always
been captured, just wider — so tracing adds NO callbacks, NO
``device_get``, NO extra dispatch: the trace rides back to the host with
the result in the same transfer.

Bit-identity contract: a traced solve must return *bitwise identical*
labels / objective / lower bound to the untraced one. Capture is
therefore strictly additive — trace fields are extra leaves in the loop
carry computed from values the round already produced; when tracing is
off the jaxpr is byte-for-byte the old one (the trace arguments simply
don't exist — tracing is a static Python flag, not a ``lax.cond``).

Shape convention: per-round leaves are padded to ``(max_rounds,)`` (or
``(max_rounds, shards)`` for per-shard leaves), with the padding value
chosen so :func:`summarize` can mask it out (``rounds`` says how many
entries are live). The per-shard leaves have ``shards == 1`` on the
unsharded paths so a trace always has the same treedef regardless of
which solve path produced it. Likewise every path carries every leaf: a
path that does not run the code a leaf counts leaves it as padding (0) —
the separation counters in mode p, the contraction counters in mode d,
``long_rows`` on the dense data path, and all six counters on the
state-sharded solve.

Named scopes: the solver's phases run under ``repro.<phase>``
(``separation``, ``message_passing``, ``contraction``) and their parts
under ``repro.<phase>.<part>``, nested inside the phase's scope, so a
device profile's ``op_name`` metadata says which part an op belongs to
(innermost part wins): ``repro.contraction.{matching, forest,
components, merge, csr}`` and ``repro.separation.{select, triangles,
long_bucket, cycles45, chords}``.

:func:`summarize` is the opt-in host-side view — it is the ONLY place
that calls ``float()``/``int()`` on trace leaves, keeping every sync off
the hot path and behind an explicit user action.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

__all__ = ["SolveTrace", "init_trace", "trace_set_round", "summarize"]

# Per-round i32 counters of the contraction and separation parts, padded
# with 0; summarize() reports each one's total over the solve's rounds.
COUNTERS = ("cc_iters", "forest_chosen", "forest_ran", "chords_added",
            "chords_dropped", "long_rows")

# Padding sentinels (masked out by `summarize` via `rounds`): +inf for
# minimised quantities keeps best-so-far scans monotone; -inf for the LB.
_PAD_OBJ = jnp.inf
_PAD_LB = -jnp.inf


class SolveTrace(NamedTuple):
    """Per-round solver telemetry. All leaves are device arrays; rows
    ``>= rounds`` are padding. ``shard_*`` leaves have a trailing shard
    axis (size 1 on unsharded paths).

    - ``rounds``: () i32 — number of live rows.
    - ``lower_bound``: (R,) f32 — dual bound after the round's MP sweep.
    - ``objective``: (R,) f32 — primal objective of the labeling held
      after the round's contraction.
    - ``n_cycles``: (R,) i32 — conflicted cycles found by separation.
    - ``n_contracted``: (R,) i32 — edges contracted this round.
    - ``n_clusters``: (R,) i32 — clusters remaining after the round.
    - ``mp_improvement``: (R,) f32 — LB gain of the MP sweep over the
      trivial bound Σ min(0, cost) on the round's reparametrized costs.
    - ``cc_iters``: (R,) i32 — while-loop trips of every
      connected-components call the round's contraction ran: the
      contraction's one, plus the forest's ``2 * forest_rounds`` in a
      round that ran the forest.
    - ``forest_chosen``: (R,) i32 — 1 where the round contracted the
      spanning forest's set rather than the matching's.
    - ``forest_ran``: (R,) i32 — 1 where the round computed the spanning
      forest: the matching fell short of ``switch_frac`` of the live
      nodes.
    - ``chords_added``: (R,) i32 — fresh 4/5-cycle chords written into
      a free edge slot.
    - ``chords_dropped``: (R,) i32 — fresh chords that found no free
      slot (their cycles are not separated; an instance padded with no
      free slots drops them all).
    - ``long_rows``: (R,) i32 — repulsive edges that separation ran in
      the long degree bucket (3- and 4/5-cycle searches together).
    - ``shard_edges``: (R, S) i32 — live (valid) edges owned per shard.
    - ``shard_topk``: (R, S) i32 — repulsive-anchor slots won per shard
      in the global top-k (top-k imbalance: one shard hogging anchors
      means its windows dominate separation).
    - ``shard_halo``: (R, S) i32 — triangle-slot edge references landing
      on each shard (halo/ownership pressure of the merged windows).
    """

    rounds: jnp.ndarray
    lower_bound: jnp.ndarray
    objective: jnp.ndarray
    n_cycles: jnp.ndarray
    n_contracted: jnp.ndarray
    n_clusters: jnp.ndarray
    mp_improvement: jnp.ndarray
    cc_iters: jnp.ndarray
    forest_chosen: jnp.ndarray
    forest_ran: jnp.ndarray
    chords_added: jnp.ndarray
    chords_dropped: jnp.ndarray
    long_rows: jnp.ndarray
    shard_edges: jnp.ndarray
    shard_topk: jnp.ndarray
    shard_halo: jnp.ndarray


def init_trace(max_rounds: int, shards: int = 1) -> SolveTrace:
    """An all-padding trace with room for ``max_rounds`` rows."""
    r = max(int(max_rounds), 1)
    s = max(int(shards), 1)
    f = jnp.float32
    i = jnp.int32
    return SolveTrace(
        rounds=jnp.zeros((), i),
        lower_bound=jnp.full((r,), _PAD_LB, f),
        objective=jnp.full((r,), _PAD_OBJ, f),
        n_cycles=jnp.zeros((r,), i),
        n_contracted=jnp.zeros((r,), i),
        n_clusters=jnp.zeros((r,), i),
        mp_improvement=jnp.zeros((r,), f),
        cc_iters=jnp.zeros((r,), i),
        forest_chosen=jnp.zeros((r,), i),
        forest_ran=jnp.zeros((r,), i),
        chords_added=jnp.zeros((r,), i),
        chords_dropped=jnp.zeros((r,), i),
        long_rows=jnp.zeros((r,), i),
        shard_edges=jnp.zeros((r, s), i),
        shard_topk=jnp.zeros((r, s), i),
        shard_halo=jnp.zeros((r, s), i),
    )


def trace_set_round(trace: SolveTrace, r, *, lower_bound=None,
                    objective=None, n_cycles=None, n_contracted=None,
                    n_clusters=None, mp_improvement=None, cc_iters=None,
                    forest_chosen=None, forest_ran=None, chords_added=None,
                    chords_dropped=None, long_rows=None, shard_edges=None,
                    shard_topk=None, shard_halo=None) -> SolveTrace:
    """Write row ``r`` (a traced i32 scalar) of the per-round leaves and
    bump ``rounds``. Fields left as None keep their padding — the dual
    phase e.g. has no contraction to report. Pure functional scatter
    (``.at[r].set``), safe inside jit / while_loop bodies."""
    updates = dict(lower_bound=lower_bound, objective=objective,
                   n_cycles=n_cycles, n_contracted=n_contracted,
                   n_clusters=n_clusters, mp_improvement=mp_improvement,
                   cc_iters=cc_iters, forest_chosen=forest_chosen,
                   forest_ran=forest_ran,
                   chords_added=chords_added, chords_dropped=chords_dropped,
                   long_rows=long_rows, shard_edges=shard_edges, shard_topk=shard_topk,
                   shard_halo=shard_halo)
    out = {}
    for name, val in updates.items():
        leaf = getattr(trace, name)
        if val is None:
            out[name] = leaf
        else:
            val = jnp.asarray(val, leaf.dtype)
            out[name] = leaf.at[r].set(val)
    out["rounds"] = jnp.maximum(trace.rounds,
                                jnp.asarray(r, jnp.int32) + 1)
    return SolveTrace(**out)


def _rows(trace: SolveTrace) -> list[dict]:
    """Host-side per-round dict rows (this is where the sync happens)."""
    n = int(trace.rounds)
    rows = []
    shards = int(trace.shard_edges.shape[-1])
    for r in range(n):
        row = {
            "round": r,
            "lower_bound": float(trace.lower_bound[r]),
            "objective": float(trace.objective[r]),
            "n_cycles": int(trace.n_cycles[r]),
            "n_contracted": int(trace.n_contracted[r]),
            "n_clusters": int(trace.n_clusters[r]),
            "mp_improvement": float(trace.mp_improvement[r]),
        }
        row.update({name: int(getattr(trace, name)[r])
                    for name in COUNTERS})
        if shards > 1:
            row["shard_edges"] = [int(x) for x in trace.shard_edges[r]]
            row["shard_topk"] = [int(x) for x in trace.shard_topk[r]]
            row["shard_halo"] = [int(x) for x in trace.shard_halo[r]]
        rows.append(row)
    return rows


def _imbalance(per_shard: list[int]) -> float:
    """max/mean load ratio: 1.0 = perfectly balanced; 0 total -> 1.0."""
    if not per_shard:
        return 1.0
    mean = sum(per_shard) / len(per_shard)
    return max(per_shard) / mean if mean > 0 else 1.0


def summarize(trace: SolveTrace) -> dict:
    """Host-side digest of a trace: per-round rows, convergence
    trajectory (first/best/final LB + objective, duality gap), the
    per-solve totals of the contraction and separation counters
    (``total_cc_iters``, ``total_forest_chosen``, ``total_forest_ran``,
    ``total_chords_added``, ``total_chords_dropped``, ``total_long_rows``),
    and — for sharded solves — per-round imbalance ratios for edges /
    top-k anchors / halo pressure. This is the ONLY trace consumer that pulls
    device values to the host; call it off the hot path."""
    rows = _rows(trace)
    out = {"rounds": len(rows), "per_round": rows}
    if not rows:
        return out

    finite_obj = [r["objective"] for r in rows
                  if r["objective"] != float("inf")]
    lbs = [r["lower_bound"] for r in rows
           if r["lower_bound"] != float("-inf")]
    if lbs:
        out["lower_bound"] = {"first": lbs[0], "best": max(lbs),
                              "final": lbs[-1]}
    if finite_obj:
        out["objective"] = {"first": finite_obj[0], "best": min(finite_obj),
                            "final": finite_obj[-1]}
    if lbs and finite_obj:
        out["gap"] = finite_obj[-1] - max(lbs)
    out["total_contracted"] = sum(r["n_contracted"] for r in rows)
    out["total_cycles"] = sum(r["n_cycles"] for r in rows)
    for name in COUNTERS:
        out[f"total_{name}"] = sum(r[name] for r in rows)

    shards = int(trace.shard_edges.shape[-1])
    if shards > 1:
        out["state_shards"] = shards
        out["shard_balance"] = {
            key: {
                "per_round_imbalance": [
                    round(_imbalance(r[field]), 4) for r in rows],
                "max_imbalance": round(
                    max(_imbalance(r[field]) for r in rows), 4),
            }
            for key, field in (("edges", "shard_edges"),
                               ("topk", "shard_topk"),
                               ("halo", "shard_halo"))
        }
    return out
