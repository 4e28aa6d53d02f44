"""Generator of ``connectomics_rag50k``: a supervoxel region-adjacency
graph of an electron-microscopy volume.

Supervoxels are the 3-D Voronoi cells of ``supervoxels`` seeds in a
``volume`` of voxels; the seed density follows a log-normal field over
``density_cells`` blocks, so cells and degrees vary as in an
over-segmentation of real tissue. Two supervoxels are adjacent where
their voxels share a face. A planted labelling groups the supervoxels
into ``objects`` neurites (the Voronoi cells of as many object centres,
taken over the supervoxel seeds). An edge's cost is +1 inside an object
and -1 across, plus N(0, ``noise``), times log(1 + shared face voxels)."""
from __future__ import annotations

import numpy as np
from scipy.spatial import cKDTree

from bench.host import HostInstance, padded

QUERY_THREADS = 4       # host threads of the voxel-owner lookup


def generate(cfg: dict, seed: int) -> HostInstance:
    rng = np.random.default_rng(seed)
    vol = np.asarray(cfg["volume"])
    cells = tuple(cfg["density_cells"])
    n = cfg["supervoxels"]
    density = np.exp(rng.normal(0.0, cfg["density_sigma"], size=cells))
    cell = rng.choice(density.size, size=n,
                      p=(density / density.sum()).ravel())
    corner = np.stack(np.unravel_index(cell, cells), axis=1)
    seeds = (corner + rng.random((n, 3))) * (vol / np.asarray(cells))
    grid = np.meshgrid(*(np.arange(s) + 0.5 for s in vol), indexing="ij")
    voxels = np.stack([g.ravel() for g in grid], axis=1)
    _, owner = cKDTree(seeds).query(voxels, workers=QUERY_THREADS)
    owner = owner.reshape(tuple(vol)).astype(np.int64)
    keys = []
    for ax in range(3):
        a = np.moveaxis(owner, ax, 0)
        p, q = a[:-1].ravel(), a[1:].ravel()
        face = p != q
        keys.append(np.minimum(p[face], q[face]) * n
                    + np.maximum(p[face], q[face]))
    pair, faces = np.unique(np.concatenate(keys), return_counts=True)
    u = (pair // n).astype(np.int32)
    v = (pair % n).astype(np.int32)
    centres = rng.random((cfg["objects"], 3)) * vol
    _, obj = cKDTree(centres).query(seeds)
    base = np.where(obj[u] == obj[v], 1.0, -1.0)
    cost = ((base + rng.normal(0.0, cfg["noise"], size=len(u)))
            * np.log1p(faces)).astype(np.float32)
    return padded(u, v, cost, n, cfg["edges_padded"], obj)
