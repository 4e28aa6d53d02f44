"""Plain reference of a multicut answer, on the host in float64.

It imports nothing of the program and takes nothing the program made but
the answer it judges: the labels and the two numbers a solve reports."""
from __future__ import annotations

import numpy as np

from bench.host import HostInstance


def objective(inst: HostInstance, labels: np.ndarray) -> float:
    """Summed cost of the valid edges whose endpoints ``labels`` puts in
    different clusters."""
    u, v, c = inst.valid_edges()
    labels = np.asarray(labels)
    return float(c[labels[u] != labels[v]].astype(np.float64).sum())


def trivial_bound(inst: HostInstance) -> float:
    """Sum of min(0, cost): the lower bound of the zero dual."""
    _, _, c = inst.valid_edges()
    return float(np.minimum(c.astype(np.float64), 0.0).sum())


def planted_objective(inst: HostInstance) -> float:
    return objective(inst, inst.planted)
