import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import solver
from repro.core.contraction import (
    adjacency_dense, choose_contraction_set, connected_components, contract,
    contract_dense, maximum_matching, spanning_forest_contraction,
)
from repro.core.graph import (
    grid_instance, make_instance, random_instance, to_host_edges,
)


def _nx_components(u, v, n):
    import networkx as nx
    g = nx.Graph()
    g.add_nodes_from(range(n))
    g.add_edges_from(zip(u.tolist(), v.tolist()))
    lab = np.empty(n, dtype=np.int64)
    for comp in nx.connected_components(g):
        m = min(comp)
        for x in comp:
            lab[x] = m
    return lab


@pytest.mark.parametrize("seed", range(5))
def test_connected_components_vs_networkx(seed):
    rng = np.random.default_rng(seed)
    n, e = 40, 40
    u = rng.integers(0, n, e).astype(np.int32)
    v = rng.integers(0, n, e).astype(np.int32)
    mask = rng.random(e) < 0.7
    labels = connected_components(jnp.asarray(u), jnp.asarray(v),
                                  jnp.asarray(mask), n)
    want = _nx_components(u[mask], v[mask], n)
    np.testing.assert_array_equal(np.asarray(labels), want)


@pytest.mark.parametrize("seed", range(5))
def test_matching_is_matching(seed):
    """Handshaking output must be a matching on attractive edges."""
    inst = random_instance(30, 0.3, seed=seed, pad_edges=256, pad_nodes=32)
    S = maximum_matching(inst)
    S = np.asarray(S)
    u, v = np.asarray(inst.u), np.asarray(inst.v)
    c = np.asarray(inst.cost)
    assert (c[S] > 0).all(), "matched a non-attractive edge"
    deg = np.zeros(inst.num_nodes)
    np.add.at(deg, u[S], 1)
    np.add.at(deg, v[S], 1)
    assert deg.max() <= 1, "node matched twice"


def test_matching_takes_global_max():
    """The globally heaviest attractive edge is always mutual-best."""
    inst = make_instance([0, 1, 2], [1, 2, 3], [1.0, 5.0, 2.0], 4,
                         pad_edges=8, pad_nodes=4)
    S = np.asarray(maximum_matching(inst))
    u, v, c = to_host_edges(inst)
    heavy = np.where((np.asarray(inst.cost) == 5.0))[0][0]
    assert S[heavy]


@pytest.mark.parametrize("seed", range(3))
def test_forest_no_internal_repulsive(seed):
    """Component freezing: contraction must never merge the endpoints of a
    repulsive edge (the invariant the paper's path-repair maintains)."""
    inst = random_instance(30, 0.4, seed=seed, pad_edges=256, pad_nodes=32)
    S = spanning_forest_contraction(inst)
    labels = connected_components(inst.u, inst.v, S & inst.edge_valid,
                                  inst.num_nodes)
    labels = np.asarray(labels)
    u, v, c = np.asarray(inst.u), np.asarray(inst.v), np.asarray(inst.cost)
    ev = np.asarray(inst.edge_valid)
    neg = ev & (c < 0)
    assert not (labels[u[neg]] == labels[v[neg]]).any()


@pytest.mark.parametrize("seed", range(4))
def test_contract_matches_dense_lemma4(seed):
    """Sparse contraction == dense KᵀAK − diag (Lemma 4a) on the live part."""
    inst = random_instance(20, 0.4, seed=seed, pad_edges=256, pad_nodes=20)
    S = maximum_matching(inst)
    res = contract(inst, S)
    n_new = int(res.n_new)
    A = adjacency_dense(inst)
    Ad = contract_dense(A, res.mapping, n_new)
    # rebuild dense adjacency from contracted sparse instance
    out = res.instance
    B = np.zeros((n_new, n_new), np.float32)
    u, v, c = np.asarray(out.u), np.asarray(out.v), np.asarray(out.cost)
    ev = np.asarray(out.edge_valid)
    nv_count = int(np.asarray(out.node_valid).sum())
    assert nv_count == n_new
    for a, b, w in zip(u[ev], v[ev], c[ev]):
        B[a, b] += w
        B[b, a] += w
    np.testing.assert_allclose(B, np.asarray(Ad)[:n_new, :n_new], atol=1e-4)


@pytest.mark.parametrize("seed", range(4))
def test_contract_objective_consistency(seed):
    """Objective of any labeling of the contracted graph + self-loop gain ==
    objective of the lifted labeling on the original graph (Lemma 1b/4b)."""
    inst = random_instance(20, 0.4, seed=seed, pad_edges=256, pad_nodes=20)
    S = choose_contraction_set(inst)
    res = contract(inst, S)
    n_new = int(res.n_new)
    rng = np.random.default_rng(seed)
    lab_new = jnp.asarray(rng.integers(0, 3, res.instance.num_nodes),
                          jnp.int32)
    lifted = lab_new[res.mapping]
    obj_orig = float(inst.objective(lifted))
    obj_new = float(res.instance.objective(lab_new))
    # cost inside merged clusters never appears in the contracted objective
    assert obj_orig == pytest.approx(obj_new, abs=1e-3)


def test_contract_gain_positive_for_matching():
    """Matching only contracts attractive edges, so the absorbed self-loop
    mass (Lemma 4b) must be positive — the join decreases the objective."""
    inst = random_instance(30, 0.4, seed=7, pad_edges=256, pad_nodes=32)
    S = maximum_matching(inst)
    if not bool(S.any()):
        pytest.skip("no matching found")
    res = contract(inst, S)
    assert float(res.self_loop_gain) > 0


def test_choose_contraction_never_empty_while_positive():
    """Regression: forest fallback returning fewer edges than matching must
    not lose the matching (premature solver termination)."""
    inst = random_instance(12, 0.5, seed=11, pad_edges=64, pad_nodes=16)
    c = np.asarray(inst.cost)
    if not (c[np.asarray(inst.edge_valid)] > 0).any():
        pytest.skip("instance has no positive edges")
    S = choose_contraction_set(inst)
    assert int(jnp.sum(S)) >= 1


# ---------------------------------------------------------------------------
# the forest runs only where the matching falls short
# ---------------------------------------------------------------------------

def _select_both(inst, matching_rounds=3, forest_rounds=4, switch_frac=0.1,
                 contract_frac=0.0, with_aux=False):
    """The chooser as it was before the forest went under a ``lax.cond``:
    both candidate sets computed, one kept with ``where``."""
    min_cost = 0.0
    if contract_frac > 0.0:
        cmax = jnp.max(jnp.where(inst.edge_valid, inst.cost, 0.0))
        min_cost = contract_frac * jnp.maximum(cmax, 0.0)
    S_match = maximum_matching(inst, rounds=matching_rounds,
                               min_cost=min_cost)
    enough = jnp.sum(S_match) >= switch_frac * jnp.sum(inst.node_valid)
    S_forest = spanning_forest_contraction(inst, rounds=forest_rounds,
                                           min_cost=min_cost)
    use_match = enough | (jnp.sum(S_forest) < jnp.sum(S_match))
    S = jnp.where(use_match, S_match, S_forest)
    if not with_aux:
        return S
    return S, (~use_match).astype(jnp.int32), (~enough).astype(jnp.int32)


def _chooser_case(case):
    """An instance for each way the chooser can go."""
    if case == "matching_suffices":
        return random_instance(12, 0.3, seed=0, pad_edges=128, pad_nodes=16)
    if case == "forest_kept":
        # the matching takes 1 of 12 nodes' edges: short of 10 %
        return random_instance(12, 0.1, seed=14, pad_edges=128, pad_nodes=16)
    # a path a-b-c with a repulsive chord a-c among 17 isolated nodes: the
    # matching takes a-b; the forest's one Borůvka round would close the
    # chord inside a component, freezes it, and keeps nothing
    return make_instance([0, 1, 0], [1, 2, 2], [3.0, 2.0, -10.0], 20,
                         pad_edges=8)


@pytest.mark.parametrize("case", ["matching_suffices", "forest_kept",
                                  "forest_loses"])
def test_chooser_matches_both_branch_select(case):
    inst = _chooser_case(case)
    S, trips, chosen, ran = jax.jit(
        lambda i: choose_contraction_set(i, with_aux=True))(inst)
    S_old, chosen_old, ran_old = _select_both(inst, with_aux=True)
    assert np.array_equal(np.asarray(S), np.asarray(S_old))
    assert int(chosen) == int(chosen_old)
    assert int(ran) == int(ran_old)
    assert np.array_equal(np.asarray(jax.jit(choose_contraction_set)(inst)),
                          np.asarray(S_old))
    S_match = np.asarray(maximum_matching(inst))
    S_forest = np.asarray(spanning_forest_contraction(inst))
    # the case occurs as named
    assert int(ran) == (case != "matching_suffices")
    assert int(chosen) == (case == "forest_kept")
    if case == "forest_loses":
        assert S_forest.sum() < S_match.sum()
    # trips count only loops that ran
    assert (int(trips) > 0) == bool(ran)


@pytest.mark.parametrize("mode", ["p", "pd"])
def test_solve_matches_both_branch_select(mode, monkeypatch):
    """A jitted solve of a padded grid, whose rounds take both sides of
    the matching test, is bitwise the solve under the old select."""
    inst = grid_instance(12, 12, seed=1, pad_edges=1024)
    cfg = solver.SolverConfig(graph_impl="sparse")
    res, tr = jax.jit(lambda i: solver.solve_device(
        i, mode, cfg, trace=True))(inst)
    ran = np.asarray(tr.forest_ran)[:int(tr.rounds)]
    assert 0 < ran.sum() < len(ran)
    new = jax.jit(lambda i: solver.solve_device(i, mode, cfg))(inst)
    monkeypatch.setattr(solver, "choose_contraction_set", _select_both)
    old = jax.jit(lambda i: solver.solve_device(i, mode, cfg))(inst)
    for x, y, z in zip(jax.tree_util.tree_leaves(new),
                       jax.tree_util.tree_leaves(old),
                       jax.tree_util.tree_leaves(res)):
        assert np.asarray(x).tobytes() == np.asarray(y).tobytes()
        assert np.asarray(x).tobytes() == np.asarray(z).tobytes()


def test_solve_batch_matches_both_branch_select(monkeypatch):
    """Under ``vmap`` the cond's predicate is batched and both branches
    run: ``api.solve_batch`` keeps the old select's answers bit for bit."""
    from repro import api
    insts = [grid_instance(12, 12, seed=s, pad_edges=1024) for s in (1, 2)]
    batch = api.stack_instances(insts)
    cfg = solver.SolverConfig(graph_impl="sparse")
    new = api.solve_batch(batch, mode="pd", config=cfg, backend="reference")
    monkeypatch.setattr(solver, "choose_contraction_set", _select_both)
    old = jax.jit(jax.vmap(lambda i: solver.solve_device(i, "pd", cfg)))(
        batch)
    for x, y in zip(jax.tree_util.tree_leaves(new),
                    jax.tree_util.tree_leaves(old)):
        assert np.asarray(x).tobytes() == np.asarray(y).tobytes()
