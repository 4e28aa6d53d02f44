"""Unsupervised image segmentation via multicut (the paper's Cityscapes
use-case, CPU scale).

    PYTHONPATH=src python examples/image_segmentation.py

A synthetic image with planted segments is converted to a grid multicut
instance (4-connectivity + long-range edges, affinity costs), solved with
PD, and rendered as ASCII next to GAEC's segmentation for comparison.

Two paths, mirroring how a deployment would use the API:

* whole image — ONE instance: the plain single-solve path
  (``api.solve``; nothing to batch);
* tiled image — MANY small instances: routed through
  :class:`repro.serve.SolveEngine`, which buckets and micro-batches the
  tiles into a single vmapped dispatch (see examples/serve_tiles.py for
  the full streaming version)."""
import sys

sys.path.insert(0, "src")

import numpy as np

from repro import api
from repro.compile_cache import enable_compile_cache
from repro.core.baselines import gaec, objective
from repro.core.graph import grid_instance

H = W = 24
GLYPHS = "·#o+x%@*=~^"


def render(labels, h, w):
    lab = np.asarray(labels)[: h * w].reshape(h, w)
    # relabel by frequency so glyphs are stable
    uniq, counts = np.unique(lab, return_counts=True)
    order = {u: i for i, u in enumerate(uniq[np.argsort(-counts)])}
    return "\n".join(
        "".join(GLYPHS[order[lab[y, x]] % len(GLYPHS)] for x in range(w))
        for y in range(h))


def main():
    enable_compile_cache()
    inst = grid_instance(H, W, seed=3, n_segments=5)
    cfg = api.SolverConfig(max_neg=4096, max_tri_per_edge=8, nbr_k=8,
                           mp_iters=10, contract_frac=0.5, max_rounds=40)
    res = api.solve(inst, mode="pd", config=cfg)   # single-solve path
    lab_gaec = gaec(inst)

    print(f"PD:   objective {res.objective:9.2f}  LB {res.lower_bound:9.2f}"
          f"  clusters {len(set(res.labels.tolist()))}")
    print(f"GAEC: objective {objective(inst, lab_gaec):9.2f}"
          f"  clusters {len(np.unique(lab_gaec))}")
    left = render(res.labels, H, W).splitlines()
    right = render(lab_gaec, H, W).splitlines()
    print(f"\n{'PD segmentation':<{W + 4}}GAEC segmentation")
    for l, r in zip(left, right):
        print(f"{l}    {r}")

    # tiled variant: four independent quadrant instances are a batch job —
    # serve them through the engine (one bucketed, vmapped dispatch)
    from repro.serve import SolveEngine

    t = H // 2
    quads = [grid_instance(t, t, seed=3 * 10 + q, n_segments=3,
                           pad_edges=4 * t * t) for q in range(4)]
    engine = SolveEngine(batch_cap=4, flush_timeout_s=None)
    tile_res = engine.solve_stream(quads)
    counts = [len(set(r.labels.tolist())) for r in tile_res]
    print(f"\ntiled ({t}x{t} quadrants via SolveEngine, "
          f"{engine.stats.n_dispatches} dispatch): "
          f"clusters per tile {counts}")


if __name__ == "__main__":
    main()
