"""Host-side instances: padded edge arrays made from a seed, with the
labelling the generator planted."""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class HostInstance:
    """A multicut instance as numpy arrays, padded to fixed shapes.

    Padding edges are zero-cost self-loops on node 0 with
    ``edge_valid=False``; padding nodes have ``node_valid=False``.
    ``planted`` is the generator's ground-truth labelling of the nodes."""
    u: np.ndarray           # (E,) int32, u < v on valid edges
    v: np.ndarray           # (E,) int32
    cost: np.ndarray        # (E,) float32
    edge_valid: np.ndarray  # (E,) bool
    node_valid: np.ndarray  # (N,) bool
    planted: np.ndarray     # (N,) int64

    @property
    def num_nodes(self) -> int:
        return self.node_valid.shape[0]

    @property
    def num_edges(self) -> int:
        return self.edge_valid.shape[0]

    def valid_edges(self):
        """(u, v, cost) of the valid edges."""
        m = self.edge_valid
        return self.u[m], self.v[m], self.cost[m]


def padded(u, v, cost, num_nodes: int, pad_edges: int,
           planted) -> HostInstance:
    """Pad a simple graph (u < v, no repeated pair) to ``pad_edges``."""
    E = len(u)
    if E > pad_edges:
        raise ValueError(f"{E} edges do not fit the padded count "
                         f"{pad_edges}")
    uu = np.zeros(pad_edges, np.int32)
    vv = np.zeros(pad_edges, np.int32)
    cc = np.zeros(pad_edges, np.float32)
    ev = np.zeros(pad_edges, bool)
    uu[:E], vv[:E], cc[:E], ev[:E] = u, v, cost, True
    return HostInstance(u=uu, v=vv, cost=cc, edge_valid=ev,
                        node_valid=np.ones(num_nodes, bool),
                        planted=np.asarray(planted, np.int64))


def solve_order(seed: int, count: int) -> list[int]:
    """The order in which a run solves its ``count`` instances: a
    permutation drawn from the run's seed (any whole number). Every seed
    gets the same instances, so runs differ in order alone."""
    rng = np.random.default_rng(seed % 2**63)
    return [int(k) for k in rng.permutation(count)]
