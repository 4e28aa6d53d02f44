"""The configurations' generators: one instance per seed, fixed padded
shapes across seeds, the grid bit-for-bit the program's grid_instance,
and the planted labelling's cost as the reference counts it."""
import json

import numpy as np
import pytest

from bench import harness, reference
from bench.host import HostInstance, solve_order
from bench.tests.tiny import REPO, tiny_config
from repro.core.graph import grid_instance

CONFIGS = REPO / "bench" / "configs"


def generator(name):
    return harness.load_module(CONFIGS / f"{name}.py", f"gen_{name}").generate


def config(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def small_rag():
    cfg = config("connectomics_rag50k")
    cfg.update(supervoxels=600, volume=[12, 24, 24], density_cells=[2, 3, 3],
               objects=12, nodes=600, edges_padded=6000)
    return cfg


def same(a: HostInstance, b: HostInstance) -> bool:
    return all(np.array_equal(getattr(a, f), getattr(b, f))
               for f in ("u", "v", "cost", "edge_valid", "node_valid",
                         "planted"))


@pytest.mark.parametrize("name,cfg", [
    ("cityscapes_grid256", tiny_config()),
    ("connectomics_rag50k", small_rag()),
])
def test_generator_is_deterministic_with_fixed_shapes(name, cfg):
    gen = generator(name)
    a, b, c = gen(cfg, 7), gen(cfg, 7), gen(cfg, 2**31 + 11)
    assert same(a, b)
    assert not np.array_equal(a.cost, c.cost)
    for inst in (a, c):
        assert inst.num_edges == cfg["edges_padded"]
        assert inst.num_nodes == cfg["nodes"]
        assert inst.cost.dtype == np.float32
        u, v, _ = inst.valid_edges()
        assert np.all(u < v)
        assert len(np.unique(u.astype(np.int64) * cfg["nodes"] + v)) == len(u)


@pytest.mark.parametrize("name", ["cityscapes_grid256", "connectomics_rag50k"])
def test_full_size_shapes(name):
    cfg = config(name)
    inst = generator(name)(cfg, 3)
    assert (inst.num_nodes, inst.num_edges) == (cfg["nodes"],
                                                cfg["edges_padded"])


@pytest.mark.parametrize("h,w,seed,pad", [(12, 12, 0, None),
                                           (20, 33, 5, 3000),
                                           (256, 256, 2**31 + 3, 326656)])
def test_grid_copy_is_the_programs_grid(h, w, seed, pad):
    cfg = tiny_config()
    ref = grid_instance(h, w, seed=seed, pad_edges=pad)
    cfg.update(rows=h, cols=w, edges_padded=ref.num_edges)
    inst = generator("cityscapes_grid256")(cfg, seed)
    for f in ("u", "v", "cost", "edge_valid", "node_valid"):
        assert np.array_equal(getattr(inst, f), np.asarray(getattr(ref, f)))
    assert inst.cost.tobytes() == np.asarray(ref.cost).tobytes()


def test_planted_objective_by_hand():
    inst = HostInstance(
        u=np.array([0, 0, 1, 2, 0], np.int32),
        v=np.array([1, 2, 2, 3, 0], np.int32),
        cost=np.array([2.0, -1.5, 0.25, -4.0, 0.0], np.float32),
        edge_valid=np.array([True, True, True, True, False]),
        node_valid=np.ones(4, bool),
        planted=np.array([0, 0, 1, 1]))
    # cut edges: (0,2) and (1,2); (2,3) and (0,1) stay inside
    assert reference.planted_objective(inst) == -1.25
    assert reference.objective(inst, np.arange(4)) == -3.25
    assert reference.trivial_bound(inst) == -5.5


def test_grid_planted_objective_counts_edges_across_segments():
    inst = generator("cityscapes_grid256")(tiny_config(), 4)
    u, v, c = inst.valid_edges()
    across = inst.planted[u] != inst.planted[v]
    assert reference.planted_objective(inst) == pytest.approx(
        float(c[across].sum()), rel=1e-6)
    assert 0 < across.sum() < len(u)


def test_solve_order_is_a_permutation_from_any_whole_number():
    orders = {tuple(solve_order(seed, 3)) for seed in
              (0, 5, 2**31 + 9, 2**40 + 1, -3, 77, 78, 79)}
    assert all(sorted(o) == [0, 1, 2] for o in orders)
    assert len(orders) > 1
    assert solve_order(2**31 + 9, 3) == solve_order(2**31 + 9, 3)
