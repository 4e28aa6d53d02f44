"""Generator of ``cityscapes_grid256``: a Cityscapes-like pixel grid.

A copy of ``repro.core.graph.grid_instance`` kept with the benchmark, so
that a change to the program cannot move the instances. It makes bit for
bit the instance ``grid_instance(rows, cols, seed,
pad_edges=edges_padded)`` makes, and returns the planted Voronoi
segmentation as well."""
from __future__ import annotations

import numpy as np

from bench.host import HostInstance, padded


def generate(cfg: dict, seed: int) -> HostInstance:
    h, w = cfg["rows"], cfg["cols"]
    noise, k = cfg["noise"], cfg["segments"]
    rng = np.random.default_rng(seed)
    cy = rng.uniform(0, h, k)
    cx = rng.uniform(0, w, k)
    yy, xx = np.mgrid[0:h, 0:w]
    seg = (((yy[..., None] - cy) ** 2 + (xx[..., None] - cx) ** 2)
           .argmin(-1).ravel())
    idx = np.arange(h * w).reshape(h, w)
    us, vs = [], []
    for dy, dx in cfg["offsets"]:
        if h > dy and w > dx:
            us.append(idx[: h - dy, : w - dx].ravel())
            vs.append(idx[dy:, dx:].ravel())
    u = np.concatenate(us)
    v = np.concatenate(vs)
    base = np.where(seg[u] == seg[v], 1.0, -1.0)
    cost = (base + rng.normal(0, noise * 2, size=len(u))
            .astype(np.float32)).astype(np.float32)
    return padded(u, v, cost, h * w, cfg["edges_padded"], seg)
