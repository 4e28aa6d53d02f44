"""Primal-dual multicut solver (RAMA Alg. 3) + the paper's solver variants.

  P   — purely primal: matching / spanning-forest contraction only.
  PD  — interleaved: cycle separation (5-cycles on the original graph,
        3-cycles on contracted graphs) → k message-passing iterations →
        reparametrize → contract. LB recorded from the first (original-graph)
        dual round.
  PD+ — PD with 5-cycle separation in every round.
  D   — dual only: separation + message passing on the original graph,
        producing the lower bound.

The whole solve is DEVICE-RESIDENT: one jitted executable per
(mode, config, backend) combination. The outer recursion runs as a
``jax.lax.while_loop`` over the fixed-shape padded instance (the padded
arrays never change size; contraction shrinks the set of valid
nodes/edges), with early exit driven by the carried contraction count —
no host round-trips inside the loop, and history is accumulated into
stacked per-round arrays written in place. The only host synchronisation
happens when the caller reads the returned :class:`SolveResult`.

Because every step is a pure fixed-shape jaxpr, the solve composes with
``jax.vmap`` over a leading instance-batch axis (see
:func:`repro.api.solve_batch`) and with ``shard_map`` (see
:mod:`repro.core.dist`).

``SolverConfig.graph_impl`` selects the separation data path: "dense"
keeps the (N, N) MXU formulation, "sparse" runs everything over the
padded-CSR :class:`repro.core.graph.CsrGraph` (O(N + E) memory), and
"auto" (default) flips to sparse once the padded node count crosses
``sparse_threshold``. Contraction and message passing are sparse in both
cases — with ``graph_impl="sparse"`` the whole solve jaxpr is free of
(N, N) allocations (asserted in tests/test_graph_impl.py).

Entrypoints live in :mod:`repro.api`; the old ``solve_p`` / ``solve_pd``
/ ``solve_dual`` shims were removed after PR 1's migration window.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.contraction import (
    choose_contraction_set, contract, contract_csr,
)
from repro.core.cycles import separate
from repro.core.graph import (
    DEFAULT_SPARSE_THRESHOLD, GRAPH_IMPLS, CsrGraph, MulticutInstance,
    csr_from_instance, resolve_graph_impl, tree_sum,
)
from repro.core.message_passing import init_mp, run_message_passing
from repro.obs.trace import init_trace, trace_set_round

MODES = ("p", "pd", "pd+", "d")
BACKENDS = ("reference", "pallas")


@dataclasses.dataclass(frozen=True)
class SolverConfig:
    """RAMA solver hyper-parameters (paper defaults in brackets).

    Hashable + frozen so a config can serve as a jit static argument — each
    distinct config keys its own compiled executable.
    """
    max_rounds: int = 16            # outer PD rounds
    mp_iters: int = 5               # k message-passing iterations per round
    max_neg: int = 256              # repulsive edges separated per round
    max_tri_per_edge: int = 4       # triangles per repulsive edge
    nbr_k: int = 4                  # neighbour fan for 4/5-cycle search
    first_round_cycles45: bool = True   # PD: length-5 on the original graph
    always_cycles45: bool = False       # PD+: length-5 every round
    matching_rounds: int = 3
    forest_rounds: int = 4
    switch_frac: float = 0.1
    contract_frac: float = 0.0      # GAEC-like conservatism (0 = paper)
    dual_rounds: int = 4            # D: separation+MP rounds
    graph_impl: str = "auto"        # separation data path: dense|sparse|auto
    sparse_row_cap: int = 128       # CSR row window (≥ max attractive degree
                                    # for exact dense parity)
    sparse_row_cap_short: int = 16  # two-level degree buckets: edges whose
                                    # windows all fit in this narrow cap
                                    # stream at this width; the rest take a
                                    # chunk-gated pass at sparse_row_cap
                                    # (0 disables; bit-identical either
                                    # way). 16 covers the typical sparse-
                                    # graph attractive degree (smoke: max
                                    # 11) so the long pass usually runs 0
                                    # chunks; see README "Performance"
    sparse_threshold: int = DEFAULT_SPARSE_THRESHOLD
                                    # auto: sparse above this padded N
                                    # (derived — see core/graph.py)
    separation_chunk: int = 0       # sparse: repulsive edges per scan step
                                    # (0 = whole batch at once); bounds the
                                    # candidate-search peak memory at
                                    # O(chunk·nbr_k²·row_cap)
    separation_shards: int = 1      # sparse: devices to split the repulsive
                                    # chunk axis over (shard_map; clamped to
                                    # the devices present; bit-identical to
                                    # the single-device solve)
    state_shards: int = 0           # >=1: edge-range-partitioned solve — the
                                    # whole SolverState (CSR included) lives
                                    # sharded across the "state" mesh for the
                                    # life of the solve (repro.core.sharded;
                                    # PD + 3-cycles + sparse only; clamped to
                                    # devices and to a divisor of
                                    # dist.STATE_BLOCKS; bit-identical across
                                    # shard counts). 0 = the replicated path,
                                    # byte-for-byte untouched
    delta_halo: int = 2             # warm delta re-solve: hops of halo
                                    # around patched endpoints included in
                                    # the round-0 separation frontier (see
                                    # repro.incremental.solve)

    def cache_key(self) -> tuple:
        """The canonical cache key, spelled out: the ordered tuple of field
        values. External caches (``api._compiled``'s LRU, the serving
        engine's queue keys) key on the frozen dataclass's own
        ``__hash__``/``__eq__``, which hash exactly this tuple — the method
        makes that contract explicit and testable (tests/test_api.py
        asserts it covers every field), so adding a field that should NOT
        differentiate executables is a conscious decision, not drift."""
        return tuple(getattr(self, f.name)
                     for f in dataclasses.fields(self))


class SolveResult(NamedTuple):
    """Solve output. A NamedTuple of arrays, i.e. a pytree — it passes
    transparently through ``jit``/``vmap`` (under :func:`repro.api.solve_batch`
    every leaf gains a leading batch axis).

    History is stacked per-round arrays of static length ``max_rounds``
    (P/PD/PD+) or ``dual_rounds`` (D); slots past ``rounds`` keep their
    initial values (lb = -inf, counts = 0).
    """
    labels: jax.Array        # (N,) final cluster id per original node
    objective: jax.Array     # () primal objective on the original (+inf for D)
    lower_bound: jax.Array   # () dual LB (PD/PD+/D; -inf for P)
    rounds: jax.Array        # () i32: rounds actually run
    lb_history: jax.Array    # (R,) f32 per-round dual LB
    n_contracted: jax.Array  # (R,) i32 edges contracted per round
    n_clusters: jax.Array    # (R,) i32 live clusters after each round

    @property
    def history(self) -> list:
        """Legacy diagnostics view: per-round dicts rebuilt from the stacked
        arrays. Syncs to host; single-instance results only (not batched)."""
        r = int(self.rounds)
        return [{"round": i, "lb": float(self.lb_history[i]),
                 "n_contracted": int(self.n_contracted[i]),
                 "n_clusters": int(self.n_clusters[i])} for i in range(r)]


def resolve_sweep(backend: str | None):
    """Map a backend name to the triangle-sweep implementation."""
    if backend is None or backend == "reference":
        return None     # run_message_passing falls back to the jnp oracle
    if backend == "pallas":
        from repro.kernels.triangle_mp.ops import mp_sweep
        return mp_sweep
    raise ValueError(f"unknown backend {backend!r}; expected one of "
                     f"{BACKENDS}")


def resolve_intersect(backend: str | None):
    """Map a backend name to the sorted-row intersection used by sparse
    separation (None/"reference" → the jnp searchsorted oracle)."""
    if backend is None or backend == "reference":
        return None     # separate falls back to intersect_rows_ref
    if backend == "pallas":
        from repro.kernels.cycle_intersect.ops import intersect_rows
        return intersect_rows
    raise ValueError(f"unknown backend {backend!r}; expected one of "
                     f"{BACKENDS}")


# ---------------------------------------------------------------------------
# Round primitives (pure, traceable; shapes in == shapes out)
# ---------------------------------------------------------------------------

class SolverState(NamedTuple):
    """Device-resident solver state threaded through the outer round loop.

    The CSR is built once per solve (``build_csr``'s sort) and then
    *maintained*: each round's :func:`repro.core.contraction.contract_csr`
    emits the contracted graph's CSR from the one sort its dedupe performs
    anyway, so separation never triggers a COO→CSR rebuild inside the loop
    (asserted on the jaxpr in tests/test_solver_state.py). The dual state
    lives in ``instance.cost`` — message passing hands the reparametrized
    costs to the next round through it; per-round triangle multipliers are
    not carried (each round re-separates its own cycle bundle, per Alg. 3).
    """
    instance: MulticutInstance   # current contracted instance (padded)
    csr: CsrGraph                # live all-valid-edges CSR of ``instance``
    mapping: jax.Array           # (N,) original node -> current cluster id


def _dual_round_core(inst: MulticutInstance, cfg: SolverConfig,
                     with45: bool, sweep=None, intersect=None, csr=None,
                     node_mask=None, update_csr: bool = False,
                     with_aux: bool = False):
    """One separation + message-passing round. Returns
    (inst', c_rep, lb, csr') — ``csr'`` is the chord-spliced all-edges CSR
    when ``update_csr`` (sparse path), else None.

    ``with_aux`` (static) appends a telemetry dict of
    :class:`repro.obs.trace.SolveTrace` row values: ``n_cycles``
    (conflicted cycles found by separation), ``mp_improvement`` (the LB
    gain of the MP sweep over the trivial bound Σ_e min(0, c) on the
    round's pre-MP costs) and separation's ``chords_added``,
    ``chords_dropped`` and ``long_rows``. Off by default so untraced
    jaxprs are byte-for-byte unchanged."""
    with jax.named_scope("repro.separation"):
        sep = separate(inst, max_neg=cfg.max_neg,
                       max_tri_per_edge=cfg.max_tri_per_edge,
                       with_cycles45=with45, nbr_k=cfg.nbr_k,
                       graph_impl=cfg.graph_impl,
                       sparse_row_cap=cfg.sparse_row_cap,
                       sparse_row_cap_short=cfg.sparse_row_cap_short,
                       sparse_threshold=cfg.sparse_threshold,
                       intersect=intersect, csr=csr,
                       separation_chunk=cfg.separation_chunk,
                       separation_shards=cfg.separation_shards,
                       sep_node_mask=node_mask,
                       update_csr=update_csr, with_aux=with_aux)
    inst2 = sep.instance
    with jax.named_scope("repro.message_passing"):
        state = init_mp(sep.triangles)
        state, c_rep, lb = run_message_passing(
            inst2.cost, inst2.edge_valid, state, cfg.mp_iters, sweep=sweep)
    if not with_aux:
        return inst2, c_rep, lb, sep.csr
    n_cyc = jnp.sum(sep.triangles.valid).astype(jnp.int32)
    trivial_lb = tree_sum(jnp.where(inst2.edge_valid,
                                    jnp.minimum(0.0, inst2.cost), 0.0))
    aux = dict(n_cycles=n_cyc, mp_improvement=lb - trivial_lb,
               **sep.counts._asdict())
    return inst2, c_rep, lb, sep.csr, aux


def _primal_round_core(inst: MulticutInstance, cfg: SolverConfig,
                       with_aux: bool = False):
    """Choose the contraction set and contract it. Returns
    (ContractionResult, CsrGraph) — plus, when ``with_aux``, a telemetry
    dict: ``cc_iters`` (trips of every connected-components loop the
    round ran), ``forest_chosen`` and ``forest_ran``."""
    with jax.named_scope("repro.contraction"):
        chosen = choose_contraction_set(
            inst, matching_rounds=cfg.matching_rounds,
            forest_rounds=cfg.forest_rounds, switch_frac=cfg.switch_frac,
            contract_frac=cfg.contract_frac, with_aux=with_aux)
        if not with_aux:
            return contract_csr(inst, chosen)
        S, forest_iters, forest_chosen, forest_ran = chosen
        res, csr, cc_iters = contract_csr(inst, S, with_aux=True)
    return res, csr, dict(cc_iters=forest_iters + cc_iters,
                          forest_chosen=forest_chosen, forest_ran=forest_ran)


def _live_edges1(inst: MulticutInstance) -> jnp.ndarray:
    """(1,) i32 live-edge count — the S=1 row of SolveTrace.shard_edges."""
    return jnp.sum(inst.edge_valid).astype(jnp.int32).reshape(1)


def fused_pd_round(inst: MulticutInstance, cfg: SolverConfig,
                   with45: bool, sweep=None, intersect=None, node_mask=None,
                   with_aux: bool = False):
    """Alg. 3 lines 3–8 as one traceable unit: separation → message passing
    → reparametrize → contract. Returns (ContractionResult, lb) — plus the
    telemetry of :func:`_dual_round_core` and :func:`_primal_round_core`
    (one dict) when ``with_aux``. Input and output instances share shapes,
    so the outer while_loop carries it."""
    out = _dual_round_core(inst, cfg, with45, sweep, intersect,
                           node_mask=node_mask, with_aux=with_aux)
    inst2, c_rep = out[0], out[1]
    con = _primal_round_core(inst2._replace(cost=c_rep), cfg,
                             with_aux=with_aux)
    if with_aux:
        return con[0], out[2], {**out[4], **con[2]}
    return con[0], out[2]


def fused_pd_round_state(state: SolverState, cfg: SolverConfig, with45: bool,
                         sweep=None, intersect=None, node_mask=None,
                         with_aux: bool = False):
    """The state-carrying PD round (sparse data path): separation reads the
    carried CSR (no rebuild), contraction maintains it, and the original→
    cluster mapping composes in place. Returns (SolverState', lb, res) —
    plus the telemetry dict of :func:`fused_pd_round` when ``with_aux``."""
    out = _dual_round_core(state.instance, cfg, with45, sweep, intersect,
                           csr=state.csr, node_mask=node_mask,
                           with_aux=with_aux)
    inst2, c_rep = out[0], out[1]
    con = _primal_round_core(inst2._replace(cost=c_rep), cfg,
                             with_aux=with_aux)
    res, csr2 = con[0], con[1]
    state2 = SolverState(instance=res.instance, csr=csr2,
                         mapping=res.mapping[state.mapping])
    if with_aux:
        return state2, out[2], res, {**out[4], **con[2]}
    return state2, out[2], res


# ---------------------------------------------------------------------------
# Device-resident solves (one executable per mode; no host sync inside)
# ---------------------------------------------------------------------------

def _solve_p_device(inst: MulticutInstance, cfg: SolverConfig,
                    trace: bool = False):
    """Purely primal Algorithm 1 loop (paper's P).

    ``trace`` (static) additionally stacks a per-round
    :class:`repro.obs.trace.SolveTrace` into the loop carry — extra
    leaves only, no callbacks, so the untraced jaxpr is unchanged and
    traced results stay bitwise identical."""
    N, R = inst.num_nodes, cfg.max_rounds
    mapping0 = jnp.arange(N, dtype=jnp.int32)
    hist_lb = jnp.full((R,), -jnp.inf, dtype=jnp.float32)
    hist_nc = jnp.zeros((R,), dtype=jnp.int32)
    hist_nk = jnp.zeros((R,), dtype=jnp.int32)

    def cond(carry):
        r, nc_last = carry[0], carry[3]
        return (r < R) & (nc_last != 0)

    def body(carry):
        r, cur, mapping, _, hist_nc, hist_nk = carry[:6]
        con = _primal_round_core(cur, cfg, with_aux=trace)
        res = con[0]
        nc = res.n_contracted.astype(jnp.int32)
        hist_nc = hist_nc.at[r].set(nc)
        hist_nk = hist_nk.at[r].set(res.n_new.astype(jnp.int32))
        mapping2 = res.mapping[mapping]
        out = (r + 1, res.instance, mapping2, nc, hist_nc, hist_nk)
        if trace:
            tr = trace_set_round(
                carry[6], r, objective=inst.objective(mapping2),
                n_contracted=nc, n_clusters=res.n_new.astype(jnp.int32),
                shard_edges=_live_edges1(res.instance), **con[2])
            out = out + (tr,)
        return out

    init = (jnp.int32(0), inst, mapping0, jnp.int32(1), hist_nc, hist_nk)
    if trace:
        init = init + (init_trace(R),)
    out = jax.lax.while_loop(cond, body, init)
    r, mapping, hist_nc, hist_nk = out[0], out[2], out[4], out[5]
    res = SolveResult(labels=mapping, objective=inst.objective(mapping),
                      lower_bound=jnp.float32(-jnp.inf), rounds=r,
                      lb_history=hist_lb, n_contracted=hist_nc,
                      n_clusters=hist_nk)
    if trace:
        return res, out[6]
    return res


def _solve_pd_sparse(inst: MulticutInstance, cfg: SolverConfig, plus: bool,
                     sweep=None, intersect=None, csr0=None,
                     sep_mask0=None, trace: bool = False):
    """Sparse-path PD/PD+: the :class:`SolverState` recursion. ``build_csr``
    runs exactly once, before round 0; every later round's separation reads
    the CSR maintained by the previous round's ``contract_csr``, so the
    round loop contains no COO→CSR rebuild — one sort per round (the fused
    contract's) instead of the three the rebuild-per-round path paid.

    ``csr0`` is a caller-supplied live all-edges CSR of ``inst`` — when
    given, even the initial ``build_csr`` is skipped (delta re-solves carry
    one). ``sep_mask0`` restricts round 0's separation frontier (warm delta
    re-solves; later rounds always separate over the whole contracted
    graph)."""
    N, R = inst.num_nodes, cfg.max_rounds
    with45_first = cfg.always_cycles45 or plus or cfg.first_round_cycles45
    with45_rest = cfg.always_cycles45 or plus

    state0 = SolverState(
        instance=inst,
        csr=csr_from_instance(inst) if csr0 is None else csr0,
        mapping=jnp.arange(N, dtype=jnp.int32))
    out0 = fused_pd_round_state(state0, cfg, with45_first, sweep, intersect,
                                node_mask=sep_mask0, with_aux=trace)
    state, lb0, res0 = out0[0], out0[1], out0[2]
    nc0 = res0.n_contracted.astype(jnp.int32)
    hist_lb = jnp.full((R,), -jnp.inf, dtype=jnp.float32).at[0].set(lb0)
    hist_nc = jnp.zeros((R,), dtype=jnp.int32).at[0].set(nc0)
    hist_nk = jnp.zeros((R,), dtype=jnp.int32).at[0].set(
        res0.n_new.astype(jnp.int32))

    def cond(carry):
        r, nc_last = carry[0], carry[2]
        return (r < R) & (nc_last != 0)

    def body(carry):
        r, st, _, hist_lb, hist_nc, hist_nk = carry[:6]
        rnd = fused_pd_round_state(st, cfg, with45_rest, sweep, intersect,
                                   with_aux=trace)
        st2, lb, res = rnd[0], rnd[1], rnd[2]
        nc = res.n_contracted.astype(jnp.int32)
        hist_lb = hist_lb.at[r].set(lb)
        hist_nc = hist_nc.at[r].set(nc)
        hist_nk = hist_nk.at[r].set(res.n_new.astype(jnp.int32))
        out = (r + 1, st2, nc, hist_lb, hist_nc, hist_nk)
        if trace:
            tr = trace_set_round(
                carry[6], r, lower_bound=lb,
                objective=inst.objective(st2.mapping),
                n_contracted=nc, n_clusters=res.n_new.astype(jnp.int32),
                shard_edges=_live_edges1(st2.instance), **rnd[3])
            out = out + (tr,)
        return out

    init = (jnp.int32(1), state, nc0, hist_lb, hist_nc, hist_nk)
    if trace:
        tr0 = trace_set_round(
            init_trace(R), jnp.int32(0), lower_bound=lb0,
            objective=inst.objective(state.mapping),
            n_contracted=nc0, n_clusters=res0.n_new.astype(jnp.int32),
            shard_edges=_live_edges1(state.instance), **out0[3])
        init = init + (tr0,)
    out = jax.lax.while_loop(cond, body, init)
    r, state, hist_lb, hist_nc, hist_nk = \
        out[0], out[1], out[3], out[4], out[5]
    labels = state.mapping
    res = SolveResult(labels=labels, objective=inst.objective(labels),
                      lower_bound=lb0, rounds=r, lb_history=hist_lb,
                      n_contracted=hist_nc, n_clusters=hist_nk)
    if trace:
        return res, out[6]
    return res


def _solve_pd_device(inst: MulticutInstance, cfg: SolverConfig, plus: bool,
                     sweep=None, intersect=None, csr0=None,
                     sep_mask0=None, trace: bool = False):
    """Interleaved primal-dual Algorithm 3 (paper's PD / PD+).

    Round 0 runs outside the while_loop: it may use 4/5-cycle separation
    (a different — still static — trace than later rounds) and its LB is the
    one computed on the original graph, hence the only globally valid one.

    Static dispatch: the sparse data path runs the :class:`SolverState`
    recursion (CSR built once, maintained by contraction); the dense path
    rebuilds its (N, N) adjacency per round — at dense sizes that rebuild
    is a cheap scatter, and the matrices could not be "maintained" more
    cheaply than rebuilt. ``csr0``/``sep_mask0`` seed delta re-solves (see
    :func:`_solve_pd_sparse`; dense ignores ``csr0`` — it has no CSR to
    carry — but honours the round-0 frontier mask).
    """
    if cfg.state_shards:
        from repro.core.sharded import solve_state_sharded
        if csr0 is not None or sep_mask0 is not None:
            raise ValueError("state_shards does not take warm-start seeds "
                             "(csr/sep_node_mask): the carried CSR is "
                             "per-shard with local edge ids, not the "
                             "replicated one delta re-solves splice")
        return solve_state_sharded(inst, cfg, mode="pd+" if plus else "pd",
                                   sweep=sweep, intersect=intersect,
                                   trace=trace)
    if resolve_graph_impl(cfg.graph_impl, inst.num_nodes,
                          cfg.sparse_threshold) == "sparse":
        return _solve_pd_sparse(inst, cfg, plus, sweep, intersect,
                                csr0=csr0, sep_mask0=sep_mask0, trace=trace)
    N, R = inst.num_nodes, cfg.max_rounds
    mapping0 = jnp.arange(N, dtype=jnp.int32)
    with45_first = cfg.always_cycles45 or plus or cfg.first_round_cycles45
    with45_rest = cfg.always_cycles45 or plus

    out0 = fused_pd_round(inst, cfg, with45_first, sweep, intersect,
                          node_mask=sep_mask0, with_aux=trace)
    res0, lb0 = out0[0], out0[1]
    nc0 = res0.n_contracted.astype(jnp.int32)
    hist_lb = jnp.full((R,), -jnp.inf, dtype=jnp.float32).at[0].set(lb0)
    hist_nc = jnp.zeros((R,), dtype=jnp.int32).at[0].set(nc0)
    hist_nk = jnp.zeros((R,), dtype=jnp.int32).at[0].set(
        res0.n_new.astype(jnp.int32))
    mapping = res0.mapping[mapping0]

    def cond(carry):
        r, nc_last = carry[0], carry[3]
        return (r < R) & (nc_last != 0)

    def body(carry):
        r, cur, mapping, _, hist_lb, hist_nc, hist_nk = carry[:7]
        rnd = fused_pd_round(cur, cfg, with45_rest, sweep, intersect,
                             with_aux=trace)
        res, lb = rnd[0], rnd[1]
        nc = res.n_contracted.astype(jnp.int32)
        hist_lb = hist_lb.at[r].set(lb)
        hist_nc = hist_nc.at[r].set(nc)
        hist_nk = hist_nk.at[r].set(res.n_new.astype(jnp.int32))
        mapping2 = res.mapping[mapping]
        out = (r + 1, res.instance, mapping2, nc,
               hist_lb, hist_nc, hist_nk)
        if trace:
            tr = trace_set_round(
                carry[7], r, lower_bound=lb,
                objective=inst.objective(mapping2),
                n_contracted=nc, n_clusters=res.n_new.astype(jnp.int32),
                shard_edges=_live_edges1(res.instance), **rnd[2])
            out = out + (tr,)
        return out

    init = (jnp.int32(1), res0.instance, mapping, nc0,
            hist_lb, hist_nc, hist_nk)
    if trace:
        tr0 = trace_set_round(
            init_trace(R), jnp.int32(0), lower_bound=lb0,
            objective=inst.objective(mapping),
            n_contracted=nc0, n_clusters=res0.n_new.astype(jnp.int32),
            shard_edges=_live_edges1(res0.instance), **out0[2])
        init = init + (tr0,)
    out = jax.lax.while_loop(cond, body, init)
    r, mapping, hist_lb, hist_nc, hist_nk = \
        out[0], out[2], out[4], out[5], out[6]
    res = SolveResult(labels=mapping, objective=inst.objective(mapping),
                      lower_bound=lb0, rounds=r, lb_history=hist_lb,
                      n_contracted=hist_nc, n_clusters=hist_nk)
    if trace:
        return res, out[7]
    return res


def _solve_d_device(inst: MulticutInstance, cfg: SolverConfig, sweep=None,
                    intersect=None, trace: bool = False):
    """Dual-only solver (paper's D): repeated separation + MP on the original
    graph; LB is monotone across rounds. Returns (SolveResult, final inst).

    LB accounting across rounds: for any multicut y,
      ⟨c, y⟩ = ⟨c^rep_1, y⟩ + Σ_t ⟨c_t, y_t⟩ ≥ ⟨c^rep_1, y⟩ + triLB_1,
    and recursively for later rounds on the reparametrized costs, so
      LB_total = Σ_r triLB_r + Σ_e min(0, c^rep_final).
    run_message_passing returns lb_r = edgeLB_r + triLB_r; we split out the
    edge part each round and keep only the final one. (Validity of
    LB_total ≤ OPT is asserted against brute force in tests/test_solver.py.)

    On the sparse data path the all-edges CSR is built once and carried
    through the scan — each round's fresh chords are spliced in
    (``update_csr``), so no round re-runs ``build_csr``'s 2E-lexsort
    (D-mode used to rebuild per round; the dense path has no CSR).
    """
    R = cfg.dual_rounds
    sparse = resolve_graph_impl(cfg.graph_impl, inst.num_nodes,
                                cfg.sparse_threshold) == "sparse"

    def lb_parts(cur2, c_rep, lb, tri_lb_sum):
        edge_lb = tree_sum(jnp.where(cur2.edge_valid,
                                     jnp.minimum(0.0, c_rep), 0.0))
        tri_lb_sum = tri_lb_sum + (lb - edge_lb)
        return tri_lb_sum, tri_lb_sum + edge_lb

    if sparse:
        def body(carry, _):
            cur, csr, tri_lb_sum = carry
            rnd = _dual_round_core(cur, cfg, True, sweep, intersect,
                                   csr=csr, update_csr=True, with_aux=trace)
            cur2, c_rep, lb, csr2 = rnd[0], rnd[1], rnd[2], rnd[3]
            tri_lb_sum, total = lb_parts(cur2, c_rep, lb, tri_lb_sum)
            ys = (total,) + ((rnd[4],) if trace else ())
            return (cur2._replace(cost=c_rep), csr2, tri_lb_sum), ys

        (final, _, _), ys = jax.lax.scan(
            body, (inst, csr_from_instance(inst), jnp.float32(0.0)),
            None, length=R)
    else:
        def body(carry, _):
            cur, tri_lb_sum = carry
            rnd = _dual_round_core(cur, cfg, True, sweep, intersect,
                                   with_aux=trace)
            cur2, c_rep, lb = rnd[0], rnd[1], rnd[2]
            tri_lb_sum, total = lb_parts(cur2, c_rep, lb, tri_lb_sum)
            ys = (total,) + ((rnd[4],) if trace else ())
            return (cur2._replace(cost=c_rep), tri_lb_sum), ys

        (final, _), ys = jax.lax.scan(body, (inst, jnp.float32(0.0)),
                                      None, length=R)
    per_round = ys[0]
    N = inst.num_nodes
    n_nodes = jnp.sum(inst.node_valid).astype(jnp.int32)
    res = SolveResult(labels=jnp.arange(N, dtype=jnp.int32),
                      objective=jnp.float32(jnp.inf),
                      lower_bound=per_round[-1], rounds=jnp.int32(R),
                      lb_history=per_round,
                      n_contracted=jnp.zeros((R,), dtype=jnp.int32),
                      n_clusters=jnp.broadcast_to(n_nodes, (R,)))
    tr = None
    if trace:
        # D has no primal: objective and contraction rows stay padding;
        # the stacked scan outputs land in the trace wholesale (no in-loop
        # scatter needed)
        tr = init_trace(R)
        tr = tr._replace(
            rounds=jnp.int32(R),
            lower_bound=per_round.astype(jnp.float32),
            n_clusters=jnp.broadcast_to(n_nodes, (R,)),
            **{k: v.astype(getattr(tr, k).dtype) for k, v in ys[1].items()},
            shard_edges=jnp.broadcast_to(
                jnp.sum(inst.edge_valid).astype(jnp.int32), (R, 1)))
    return res, final, tr


def solve_device(inst: MulticutInstance, mode: str = "pd",
                 cfg: SolverConfig = SolverConfig(),
                 sweep=None, intersect=None, csr=None,
                 sep_node_mask=None, trace: bool = False):
    """The unified, pure, traceable solve: dispatches on the (static) mode.
    Safe to wrap in ``jax.jit`` / ``jax.vmap`` / ``shard_map``; prefer the
    cached entrypoints in :mod:`repro.api` — ``api._compiled`` is the one
    jit cache (bounded, instrumented); no second jitted alias lives here.

    ``csr``/``sep_node_mask`` seed delta re-solves (PD/PD+ only): ``csr``
    is a live all-edges CSR of ``inst`` (spliced by the previous tick —
    skips the initial ``build_csr`` on the sparse path), ``sep_node_mask``
    restricts round 0's separation frontier. Modes "p" and "d" ignore both
    (no separation to seed / no carried CSR).

    ``trace`` (static) switches the return to ``(SolveResult, SolveTrace)``
    — per-round telemetry captured inside the round loop as extra carry
    leaves (zero additional host syncs; labels/objective/LB stay bitwise
    identical to the untraced solve, pinned in tests/test_obs_trace.py).
    Untraced callers see the exact pre-trace jaxpr: the flag is static
    Python, not a ``lax.cond``."""
    if cfg.graph_impl not in GRAPH_IMPLS:
        raise ValueError(f"unknown graph_impl {cfg.graph_impl!r}; expected "
                         f"one of {GRAPH_IMPLS}")
    if cfg.state_shards and mode in ("p", "d"):
        raise ValueError(
            f"state_shards requires mode='pd' (got {mode!r}); the sharded "
            f"solve supports 3-cycle separation only, and p/d have no "
            f"edge-partitioned round to run")
    if mode == "p":
        return _solve_p_device(inst, cfg, trace=trace)
    if mode == "pd":
        return _solve_pd_device(inst, cfg, plus=False, sweep=sweep,
                                intersect=intersect, csr0=csr,
                                sep_mask0=sep_node_mask, trace=trace)
    if mode == "pd+":
        return _solve_pd_device(inst, cfg, plus=True, sweep=sweep,
                                intersect=intersect, csr0=csr,
                                sep_mask0=sep_node_mask, trace=trace)
    if mode == "d":
        res, _final, tr = _solve_d_device(inst, cfg, sweep, intersect,
                                          trace=trace)
        return (res, tr) if trace else res
    raise ValueError(f"unknown mode {mode!r}; expected one of {MODES}")



# ---------------------------------------------------------------------------
# Legacy round entrypoints (kept for configs/rama_multicut.py and dist.py)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("mp_iters", "max_neg", "max_tri_per_edge",
                                   "nbr_k", "with_cycles45", "sweep",
                                   "unroll"))
def _dual_round(inst: MulticutInstance, mp_iters: int, max_neg: int,
                max_tri_per_edge: int, nbr_k: int, with_cycles45: bool,
                sweep=None, unroll: bool = False):
    """One separation + message-passing round. Returns (inst', c_rep, lb)."""
    sep = separate(inst, max_neg=max_neg, max_tri_per_edge=max_tri_per_edge,
                   with_cycles45=with_cycles45, nbr_k=nbr_k)
    inst2 = sep.instance
    state = init_mp(sep.triangles)
    state, c_rep, lb = run_message_passing(
        inst2.cost, inst2.edge_valid, state, mp_iters, sweep=sweep,
        unroll=unroll)
    return inst2, c_rep, lb


@partial(jax.jit, static_argnames=("matching_rounds", "forest_rounds",
                                   "switch_frac", "contract_frac"))
def _primal_round(inst: MulticutInstance, matching_rounds: int,
                  forest_rounds: int, switch_frac: float,
                  contract_frac: float = 0.0):
    S = choose_contraction_set(inst, matching_rounds=matching_rounds,
                               forest_rounds=forest_rounds,
                               switch_frac=switch_frac,
                               contract_frac=contract_frac)
    return contract(inst, S)


# (The deprecated solve_p / solve_pd / solve_dual shims from PR 1's
# migration window were removed here — use repro.api.solve.)
