"""Solver telemetry (PR 10): ``api.solve(trace=True)`` and SolveTrace.

The contract everything hangs on: tracing is *strictly additive*. A
traced solve returns bitwise-identical results — every SolveResult leaf —
to the untraced one, on every mode, both data paths, and every shard
count; the trace rides the while-loop carry as extra leaves (zero host
callbacks, pinned on the jaxpr); and the traced/untraced executables are
separate registry entries so flipping the flag never recompiles the
other. :func:`repro.obs.summarize` is the only host-side consumer.
"""
import dataclasses
import functools
import math
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro import api
from repro.core.contraction import (
    connected_components, maximum_matching, spanning_forest_contraction,
)
from repro.core.cycles import separate
from repro.core.graph import grid_instance, random_instance
from repro.core.solver import (
    MODES, SolverConfig, _solve_d_device, solve_device,
)
from repro.obs import SolveTrace, init_trace, summarize, trace_set_round

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

CFG = SolverConfig(max_neg=128, max_tri_per_edge=8, nbr_k=8, mp_iters=4)


def _inst():
    return random_instance(40, 0.2, seed=0, pad_edges=512, pad_nodes=64)


def _leaves_bit_eq(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert np.asarray(x).tobytes() == np.asarray(y).tobytes(), \
            (np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# bit identity: the acceptance criterion
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["dense", "sparse"])
@pytest.mark.parametrize("mode", MODES)
def test_traced_solve_is_bitwise_identical(mode, impl):
    inst = _inst()
    cfg = dataclasses.replace(CFG, graph_impl=impl)
    ref = api.solve(inst, mode=mode, config=cfg)
    res, tr = api.solve(inst, mode=mode, config=cfg, trace=True)
    _leaves_bit_eq(res, ref)
    assert isinstance(tr, SolveTrace)
    assert int(tr.rounds) >= 1


def test_trace_registry_entries_are_separate():
    inst = _inst()
    api.clear_cache()
    api.solve(inst, mode="pd", config=CFG)
    m0 = api.cache_info().misses
    api.solve(inst, mode="pd", config=CFG, trace=True)
    assert api.cache_info().misses == m0 + 1     # own executable
    h0 = api.cache_info().hits
    api.solve(inst, mode="pd", config=CFG)       # untraced entry survived
    api.solve(inst, mode="pd", config=CFG, trace=True)
    assert api.cache_info().hits == h0 + 2


# ---------------------------------------------------------------------------
# trace content
# ---------------------------------------------------------------------------

def test_trace_rows_are_live_then_padding():
    inst = _inst()
    res, tr = api.solve(inst, mode="pd", config=CFG, trace=True)
    R = int(tr.rounds)
    assert 1 <= R <= CFG.max_rounds
    assert tr.lower_bound.shape == (CFG.max_rounds,)
    assert tr.shard_edges.shape == (CFG.max_rounds, 1)   # unsharded: S=1
    lb = np.asarray(tr.lower_bound)
    obj = np.asarray(tr.objective)
    assert np.all(np.isfinite(lb[:R]))
    assert np.all(np.isfinite(obj[:R]))
    assert np.all(lb[R:] == -np.inf)                     # padding sentinels
    assert np.all(obj[R:] == np.inf)
    # each round's LB stays below the feasible objective it pairs with
    assert np.all(lb[:R] <= obj[:R] + 1e-4)
    # counts are non-negative ints; clusters never increase
    nc = np.asarray(tr.n_clusters)[:R]
    assert np.all(np.asarray(tr.n_cycles)[:R] >= 0)
    assert np.all(np.asarray(tr.n_contracted)[:R] >= 0)
    assert np.all(nc[:-1] >= nc[1:])


def test_dual_mode_trace_has_lb_no_contraction():
    inst = _inst()
    _, tr = api.solve(inst, mode="d", config=CFG, trace=True)
    R = int(tr.rounds)
    lb = np.asarray(tr.lower_bound)[:R]
    assert np.all(np.isfinite(lb))
    # dual-only: no contraction happens, the padding zeros stay
    assert np.all(np.asarray(tr.n_contracted)[:R] == 0)


def test_traced_jaxpr_has_no_callbacks():
    """The zero-sync pin: the traced program contains NO host callback
    primitives anywhere (so tracing cannot stall the device), and the
    trace arrays ride a while loop like lb_history always has."""
    inst = _inst()
    jx = jax.make_jaxpr(lambda i: solve_device(i, mode="pd", cfg=CFG,
                                               trace=True))(inst)

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn.primitive.name
            for v in eqn.params.values():
                sub = getattr(v, "jaxpr", v)
                if hasattr(sub, "eqns"):
                    yield from walk(sub)

    prims = list(walk(jx.jaxpr))
    assert not any("callback" in p or "outside_call" in p for p in prims)
    assert "while" in prims


# ---------------------------------------------------------------------------
# summarize: the host-side digest
# ---------------------------------------------------------------------------

def test_summarize_matches_result():
    inst = _inst()
    res, tr = api.solve(inst, mode="pd", config=CFG, trace=True)
    s = summarize(tr)
    assert s["rounds"] == int(tr.rounds) == len(s["per_round"])
    assert s["objective"]["final"] == pytest.approx(float(res.objective))
    assert s["lower_bound"]["best"] <= s["objective"]["best"] + 1e-4
    assert s["gap"] == pytest.approx(
        s["objective"]["final"] - s["lower_bound"]["best"])
    assert s["total_contracted"] == int(np.sum(
        np.asarray(tr.n_contracted)[:s["rounds"]]))
    # unsharded traces carry no shard_balance section
    assert "shard_balance" not in s
    assert "shard_edges" not in s["per_round"][0]


def test_summarize_handles_padding_and_empty():
    empty = init_trace(4, shards=2)
    assert summarize(empty) == {"rounds": 0, "per_round": []}
    tr = trace_set_round(empty, 0, lower_bound=-3.0, objective=5.0,
                         n_cycles=7, n_contracted=2, n_clusters=9,
                         shard_edges=[6, 2], shard_topk=[4, 4],
                         shard_halo=[0, 0])
    s = summarize(tr)
    assert s["rounds"] == 1
    assert s["per_round"][0]["lower_bound"] == -3.0
    assert s["per_round"][0]["shard_edges"] == [6, 2]
    assert s["gap"] == pytest.approx(8.0)
    bal = s["shard_balance"]
    assert bal["edges"]["max_imbalance"] == pytest.approx(6 / 4)
    assert bal["topk"]["max_imbalance"] == pytest.approx(1.0)
    assert bal["halo"]["max_imbalance"] == pytest.approx(1.0)  # 0 total


def test_trace_set_round_bumps_rounds_monotonically():
    tr = init_trace(4)
    tr = trace_set_round(tr, 2, lower_bound=1.0)
    assert int(tr.rounds) == 3
    tr = trace_set_round(tr, 0, lower_bound=2.0)   # earlier row: no shrink
    assert int(tr.rounds) == 3
    assert float(tr.lower_bound[0]) == 2.0
    assert math.isinf(float(tr.lower_bound[1]))


# ---------------------------------------------------------------------------
# sharded solves: per-shard telemetry, bit identity across shard counts
# ---------------------------------------------------------------------------

def _run(code: str, devices: int = 4) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_traced_sharded_solve_bitwise_across_shard_counts():
    """On 4 virtual devices: for S ∈ {1, 2, 4} the traced sharded solve
    returns bitwise-identical results to the untraced one, the trace
    carries (R, S) shard leaves whose edge counts sum to the same total
    on every S, and summarize reports shard balance for S > 1."""
    stdout = _run("""
        import dataclasses
        import numpy as np
        import jax
        from repro import api
        from repro.core.solver import SolverConfig
        from repro.core.graph import random_instance
        from repro.obs import summarize

        assert jax.device_count() == 4
        inst = random_instance(60, 0.15, seed=3, pad_edges=1024,
                               pad_nodes=64)
        base = SolverConfig(graph_impl="sparse", first_round_cycles45=False)
        totals = {}
        for S in (1, 2, 4):
            cfg = dataclasses.replace(base, state_shards=S)
            ref = api.solve(inst, mode="pd", config=cfg)
            res, tr = api.solve(inst, mode="pd", config=cfg, trace=True)
            for x, y in zip(jax.tree_util.tree_leaves(res),
                            jax.tree_util.tree_leaves(ref)):
                assert np.asarray(x).tobytes() == np.asarray(y).tobytes(), S
            R = int(tr.rounds)
            assert R >= 1 and tr.shard_edges.shape[1] == S, S
            # the part counters are not threaded through the sharded
            # solve: padding on every row
            for name in ("cc_iters", "forest_chosen", "forest_ran",
                         "chords_added", "chords_dropped", "long_rows"):
                assert not np.any(np.asarray(getattr(tr, name))), name
            totals[S] = np.asarray(tr.shard_edges)[:R].sum(axis=1)
            s = summarize(tr)
            if S > 1:
                assert s["state_shards"] == S
                assert s["shard_balance"]["edges"]["max_imbalance"] >= 1.0
                assert len(s["per_round"][0]["shard_edges"]) == S
            else:
                assert "shard_balance" not in s
        # live-edge totals are a partition: identical across shard counts
        for S in (2, 4):
            assert np.array_equal(totals[1], totals[S]), (S, totals)
        print("traced-sharded-ok")
        """)
    assert "traced-sharded-ok" in stdout


# ---------------------------------------------------------------------------
# contraction and separation counters
# ---------------------------------------------------------------------------

# 12 x 12 grid: 537 edges; padded to 1024 it has 487 free slots for chords,
# unpadded none. Sparse with a short cap of 4, so the long bucket has rows.
GRID_CFG = SolverConfig(graph_impl="sparse", sparse_row_cap=16,
                        sparse_row_cap_short=4)
SEP_COUNTERS = ("chords_added", "chords_dropped", "long_rows")
CON_COUNTERS = ("cc_iters", "forest_chosen", "forest_ran")


def _grid(pad):
    return grid_instance(12, 12, seed=1, pad_edges=pad)


@functools.lru_cache(maxsize=None)
def _grid_solve(pad, mode, impl="sparse"):
    """(untraced result, traced result, trace) of the 12 x 12 grid."""
    cfg = dataclasses.replace(GRID_CFG, graph_impl=impl)
    inst = _grid(pad)
    ref = api.solve(inst, mode=mode, config=cfg)
    res, tr = api.solve(inst, mode=mode, config=cfg, trace=True)
    return ref, res, tr


def _live(tr, name):
    return np.asarray(getattr(tr, name))[:int(tr.rounds)]


@pytest.mark.parametrize("pad", [None, 1024], ids=["unpadded", "padded"])
@pytest.mark.parametrize("mode", ["pd", "d"])
def test_traced_grid_solve_is_bitwise_identical_with_counters(mode, pad):
    ref, res, tr = _grid_solve(pad, mode)
    _leaves_bit_eq(res, ref)
    # the counters repeat exactly: a second traced solve gives the same
    # trace, new leaves included
    cfg = GRID_CFG
    _, tr2 = api.solve(_grid(pad), mode=mode, config=cfg, trace=True)
    _leaves_bit_eq(tr2, tr)
    assert _live(tr, "long_rows").sum() > 0


def _np_components(u, v, mask, n):
    """NumPy replay of connected_components: (labels, loop trips)."""
    labels, trips = np.arange(n), 0
    while True:
        trips += 1
        lu, lv = labels[u], labels[v]
        m = np.minimum(lu, lv)
        new = labels.copy()
        np.minimum.at(new, u, np.where(mask, m, lu))
        np.minimum.at(new, v, np.where(mask, m, lv))
        new = new[new]
        new = new[new]
        if np.array_equal(new, labels):
            return new, trips
        labels = new


def _np_forest(u, v, cost, ev, n, rounds):
    """NumPy replay of spanning_forest_contraction: (S, summed trips)."""
    E = len(u)
    neg = ev & (cost < 0)
    S, labels, trips = np.zeros(E, bool), np.arange(n), 0
    for _ in range(rounds):
        cl_u, cl_v = labels[u], labels[v]
        active = ev & (cost > 0) & (cl_u != cl_v)
        best = {}          # component -> concat index of its best edge
        for k, (seg, e) in enumerate(zip(np.concatenate([cl_u, cl_v]),
                                         np.tile(np.arange(E), 2))):
            if active[e] and (seg not in best
                              or cost[e] > cost[best[seg] % E]):
                best[seg] = k
        cand = np.zeros(E, bool)
        cand[[k % E for k in best.values()]] = True
        lt, t1 = _np_components(u, v, S | cand, n)
        conflict = neg & (lt[u] == lt[v]) & (labels[u] != labels[v])
        frozen = np.zeros(n, bool)
        frozen[lt[u][conflict]] = True
        S = S | (cand & ~frozen[lt[u]] & ~frozen[lt[v]])
        labels, t2 = _np_components(u, v, S, n)
        trips += t1 + t2
    return S, trips


@pytest.mark.parametrize("density", [0.0, 0.3, 0.7, 1.0])
def test_components_trips_match_numpy_replay(density):
    inst = grid_instance(16, 16, seed=2)
    mask = np.random.default_rng(3).random(inst.num_edges) < density
    mask &= np.asarray(inst.edge_valid)
    labels, trips = jax.jit(
        lambda m: connected_components(inst.u, inst.v, m, inst.num_nodes,
                                       with_aux=True))(mask)
    want_labels, want_trips = _np_components(
        np.asarray(inst.u), np.asarray(inst.v), mask, inst.num_nodes)
    assert np.array_equal(np.asarray(labels), want_labels)
    assert int(trips) == want_trips


def _check_round0(tr, inst, cfg):
    """Replay round 0's contraction counters in NumPy; returns whether the
    matching sufficed (``enough``)."""
    u, v = np.asarray(inst.u), np.asarray(inst.v)
    cost, ev = np.asarray(inst.cost), np.asarray(inst.edge_valid)
    S_forest, forest_trips = _np_forest(u, v, cost, ev, inst.num_nodes,
                                        cfg.forest_rounds)
    assert np.array_equal(
        S_forest, np.asarray(spanning_forest_contraction(
            inst, rounds=cfg.forest_rounds)))
    S_match = np.asarray(maximum_matching(inst,
                                          rounds=cfg.matching_rounds))
    n_nodes = int(np.sum(np.asarray(inst.node_valid)))
    enough = S_match.sum() >= cfg.switch_frac * n_nodes
    use_match = enough | (S_forest.sum() < S_match.sum())
    S = S_match if use_match else S_forest
    _, contract_trips = _np_components(u, v, S & ev, inst.num_nodes)
    assert int(tr.cc_iters[0]) == \
        (0 if enough else forest_trips) + contract_trips
    assert int(tr.forest_chosen[0]) == int(not use_match)
    assert int(tr.forest_ran[0]) == int(not enough)
    for name in ("forest_chosen", "forest_ran"):
        assert set(_live(tr, name).tolist()) <= {0, 1}
    assert np.all(_live(tr, "forest_chosen") <= _live(tr, "forest_ran"))
    assert np.all(_live(tr, "cc_iters") >= 1)
    return bool(enough)


def test_round_counters_match_numpy_replay():
    """Round 0 of a p solve contracts the original instance: its
    ``cc_iters`` is the contraction's components loop, plus the forest's
    8 loops if the matching fell short, replayed in NumPy; ``forest_ran``
    is that test and ``forest_chosen`` the matching/forest switch,
    recomputed from the two candidate sets."""
    _, _, tr = _grid_solve(1024, "p")
    _check_round0(tr, _grid(1024), GRID_CFG)


@pytest.mark.parametrize("switch_frac,forest_ran", [(0.1, 0), (0.9, 1)],
                         ids=["matching_suffices", "forest_runs"])
def test_forest_ran_matches_numpy_replay(switch_frac, forest_ran):
    """Round 0 of the grid's p solve runs the forest only where the
    matching falls short of ``switch_frac`` of the nodes: both cases."""
    inst = _grid(1024)
    cfg = dataclasses.replace(GRID_CFG, switch_frac=switch_frac)
    if switch_frac == GRID_CFG.switch_frac:
        _, _, tr = _grid_solve(1024, "p")
    else:
        _, tr = api.solve(inst, mode="p", config=cfg, trace=True)
    assert _check_round0(tr, inst, cfg) == (not forest_ran)
    assert int(tr.forest_ran[0]) == forest_ran
    assert summarize(tr)["total_forest_ran"] == \
        int(_live(tr, "forest_ran").sum())


@pytest.mark.parametrize("pad", [None, 1024], ids=["unpadded", "padded"])
def test_chord_counters(pad):
    """With no free edge slot every fresh chord is dropped and no 4- or
    5-cycle is separated; with 487 free slots none is, and the chords
    added are the growth of the valid-edge count."""
    inst = _grid(pad)
    _, _, tr = _grid_solve(pad, "d")
    added, dropped = _live(tr, "chords_added"), _live(tr, "chords_dropped")
    sep = jax.jit(lambda i: separate(
        i, max_neg=GRID_CFG.max_neg,
        max_tri_per_edge=GRID_CFG.max_tri_per_edge, nbr_k=GRID_CFG.nbr_k,
        graph_impl="sparse", sparse_row_cap=GRID_CFG.sparse_row_cap,
        sparse_row_cap_short=GRID_CFG.sparse_row_cap_short,
        with_aux=True))(inst)
    grown = int(np.sum(np.asarray(sep.instance.edge_valid))) \
        - int(np.sum(np.asarray(inst.edge_valid)))
    assert int(sep.counts.chords_added) == grown == int(added[0])
    assert int(sep.counts.chords_dropped) == int(dropped[0])
    if pad is None:
        assert dropped.sum() > 0 and added.sum() == 0
    else:
        assert dropped.sum() == 0 and added.sum() > 0
        _, final, tr_d = jax.jit(lambda i: _solve_d_device(
            i, GRID_CFG, trace=True))(inst)
        assert int(np.sum(np.asarray(final.edge_valid))) \
            - int(np.sum(np.asarray(inst.edge_valid))) == added.sum()
        _leaves_bit_eq(tr_d, tr)


@pytest.mark.parametrize("mode,impl,padding", [
    ("p", "sparse", SEP_COUNTERS),
    ("d", "sparse", CON_COUNTERS),
    ("pd", "dense", ("long_rows",)),
])
def test_counters_are_padding_where_not_run(mode, impl, padding):
    _, _, tr = _grid_solve(1024, mode, impl)
    for name in padding:
        assert not np.any(np.asarray(getattr(tr, name))), name
    ran = set(SEP_COUNTERS + CON_COUNTERS) - set(padding) \
        - {"chords_dropped"}
    for name in ran:
        assert _live(tr, name).sum() > 0, name


def test_summarize_reports_counter_totals():
    _, _, tr = _grid_solve(1024, "pd")
    s = summarize(tr)
    for name in SEP_COUNTERS + CON_COUNTERS:
        assert s[f"total_{name}"] == int(_live(tr, name).sum())
        assert s["per_round"][0][name] == int(getattr(tr, name)[0])
