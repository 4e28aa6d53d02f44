"""Faults planted underneath the timed path, to show that the check
catches them: each wraps a ``solve(inst)`` and breaks its answer.

A multicut solve on one chip can have these: a solve that returns its
state unchanged, one that leaves half of the instance's edges out, and
an answer altered where it is produced. It has no exchange between
chips to leave out."""
from __future__ import annotations

import math


def _with_mode(fn, solve):
    fn.mode = getattr(solve, "mode", "pd")
    return fn


def unchanged(solve):
    """The solver's state before its first round: every node its own
    cluster, and the bound of the zero dual, the sum of min(0, cost)."""
    import jax.numpy as jnp

    def f(inst):
        res = solve(inst)
        labels = jnp.arange(inst.num_nodes, dtype=jnp.int32)
        obj = (inst.objective(labels)
               if math.isfinite(float(res.objective)) else res.objective)
        lb = jnp.sum(jnp.where(inst.edge_valid,
                               jnp.minimum(inst.cost, 0.0), 0.0))
        return res._replace(labels=labels, objective=obj,
                            lower_bound=lb.astype(jnp.float32),
                            rounds=jnp.int32(0))
    return _with_mode(f, solve)


def half_edges(solve):
    """Every other valid edge left out of the solve."""
    import jax.numpy as jnp

    def f(inst):
        rank = jnp.cumsum(inst.edge_valid) - 1
        keep = inst.edge_valid & (rank % 2 == 0)
        return solve(inst._replace(edge_valid=keep))
    return _with_mode(f, solve)


def altered_labels(solve):
    """Node 0 moved to another cluster after the solve reported."""
    import jax.numpy as jnp

    def f(inst):
        res = solve(inst)
        lab = res.labels
        new = jnp.where(lab[0] != lab[1], lab[1], inst.num_nodes)
        return res._replace(labels=lab.at[0].set(new))
    return _with_mode(f, solve)


def lowered_bound(solve):
    """The reported bound moved a thousandth of itself below the zero
    dual's, which the dual never falls under."""
    import jax.numpy as jnp

    def f(inst):
        res = solve(inst)
        lb = jnp.sum(jnp.where(inst.edge_valid,
                               jnp.minimum(inst.cost, 0.0), 0.0))
        return res._replace(lower_bound=lb - 1e-3 * jnp.abs(lb))
    return _with_mode(f, solve)


FAULTS = {f.__name__: f for f in (unchanged, half_edges, altered_labels,
                                  lowered_bound)}
