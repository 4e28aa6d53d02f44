"""Segmentation-tile serving: a stream of per-tile multicut instances
through the bucketed serving engine.

    PYTHONPATH=src python examples/serve_tiles.py

This is the deployment shape the RAMA paper motivates (per-image
segmentation multicuts solved at GPU throughput, as in "Next Generation
Multicuts"): many *independent*, mixed-size instances arriving as a
stream. A synthetic scene is cut into tiles of mixed sizes (finer tiles
where the planted segmentation is busy, coarse ones elsewhere — like a
detector emitting regions of interest); every tile becomes a grid
multicut instance and the whole stream is served by
:class:`repro.serve.SolveEngine`:

* tiles are routed by size (small -> dense separation, large -> sparse
  CSR) and padded onto geometric shape buckets,
* same-bucket tiles ride one vmapped dispatch (micro-batching),
* the engine compiles at most (buckets x routes) executables for the
  whole stream, however many tiles arrive.
"""
import sys
import time

sys.path.insert(0, "src")

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.graph import grid_instance
from repro.serve import BucketPolicy, SolveEngine, default_router

SCENE = 48          # scene is SCENE x SCENE pixels
COARSE = 16         # coarse tile edge; busy regions split into 8x8 tiles


def make_tiles(seed: int = 0):
    """Mixed-size tiling: coarse tiles, except the four centre tiles which
    are split 4-way (stand-in for a saliency-driven tiler)."""
    rng = np.random.default_rng(seed)
    tiles = []
    for ty in range(0, SCENE, COARSE):
        for tx in range(0, SCENE, COARSE):
            centre = (SCENE // 3 <= ty < 2 * SCENE // 3
                      and SCENE // 3 <= tx < 2 * SCENE // 3)
            step = COARSE // 2 if centre else COARSE
            for y in range(ty, ty + COARSE, step):
                for x in range(tx, tx + COARSE, step):
                    tiles.append((y, x, step,
                                  grid_instance(step, step,
                                                seed=int(rng.integers(1e6)),
                                                n_segments=3,
                                                pad_edges=5 * step * step)))
    return tiles


def main():
    enable_compile_cache()
    tiles = make_tiles()
    insts = [t[3] for t in tiles]
    print(f"== serving {len(insts)} segmentation tiles "
          f"({sorted({t[2] for t in tiles})}-px edges) ==")

    engine = SolveEngine(router=default_router(),
                         policy=BucketPolicy(node_floor=64, edge_floor=256),
                         batch_cap=8, flush_timeout_s=None)
    engine.warmup([(i.num_nodes, i.num_edges) for i in insts])
    print(f"warmup: {engine.stats.compiles} executables compiled "
          f"(buckets x routes)")

    t0 = time.perf_counter()
    results = engine.solve_stream(insts)
    wall = time.perf_counter() - t0

    lat = engine.stats.latency_hist
    n_clusters = sum(len(set(r.labels.tolist())) for r in results)
    total_obj = sum(float(r.objective) for r in results)
    print(f"served {len(results)} tiles in {wall:.2f}s "
          f"({len(results) / wall:.1f} tiles/s)")
    print(f"latency p50 {lat.percentile(50):.3f}s  "
          f"p99 {lat.percentile(99):.3f}s")
    print(f"dispatches {engine.stats.n_dispatches}  "
          f"occupancy {engine.stats.occupancy:.0%}  "
          f"compiles {engine.stats.compiles}")
    print(f"total objective {total_obj:.1f} over {n_clusters} clusters")

    # per-tile summary map (clusters found per tile, coarse grid)
    print("\nclusters per tile (scene layout, finer tiles in the centre):")
    by_pos = {(t[0], t[1]): len(set(r.labels.tolist()))
              for t, r in zip(tiles, results)}
    rows = sorted({y for y, _ in by_pos})
    for y in rows:
        cells = [f"{by_pos[(y, x)]:3d}"
                 for x in sorted(x for yy, x in by_pos if yy == y)]
        print("  " + " ".join(cells))


if __name__ == "__main__":
    main()
