"""The least work of a solver phase in one round, counted from the
instance's padded shapes and the solver settings the traffic file pins.

Nothing here reads the program: the counts say what the phase has to
touch, whatever implements it, so a roofline share built on them moves
only when the phase's device time does. Bytes are those the phase must
read or write at least once; operations are the arithmetic of the
algorithm. Both phases are bound by bytes on a v5e by a wide margin."""
from __future__ import annotations

F32 = I32 = 4
BOOL = 1
EDGE_BYTES = 2 * I32 + F32 + BOOL   # u, v, cost, validity of one edge


def triangles(solver: dict, cycles45: bool) -> int:
    """Triangle subproblems of one round: up to ``max_tri_per_edge``
    3-cycles per separated repulsive edge, plus the three triangles of
    one triangulated 4- or 5-cycle per repulsive edge in a round that
    separates those."""
    t = solver["max_neg"] * solver["max_tri_per_edge"]
    return t + (3 * solver["max_neg"] if cycles45 else 0)


def message_passing(num_edges: int, num_nodes: int, solver: dict,
                    cycles45: bool) -> tuple[float, float]:
    """(operations, bytes) of one round of message passing.

    Per iteration and triangle: read its 3 edge ids and the 3 edge costs
    they point at, read and write its 3 costs, and add 3 contributions
    back to the edges; about 45 operations (six min-marginal updates and
    the three shares). Once per round: the bound's edge term reads every
    edge's cost and validity and takes min(0, c) and a sum."""
    T = triangles(solver, cycles45)
    iters = solver["mp_iters"]
    per_tri = 3 * I32 + 3 * F32 + 2 * 3 * F32 + 3 * F32
    nbytes = iters * T * per_tri + num_edges * (F32 + BOOL)
    flops = iters * T * 45 + 2 * num_edges
    return float(flops), float(nbytes)


def contraction(num_edges: int, num_nodes: int,
                solver: dict) -> tuple[float, float]:
    """(operations, bytes) of one round of contraction.

    Each matching and forest round reads the edge list once and reads
    and writes a label per node; relabelling the endpoints and merging
    parallel edges reads and writes the edge list once more, and writes
    the new labels. About one comparison per edge and pass."""
    passes = solver["matching_rounds"] + solver["forest_rounds"]
    nbytes = (passes * (num_edges * EDGE_BYTES + 2 * I32 * num_nodes)
              + 2 * num_edges * EDGE_BYTES + I32 * num_nodes)
    flops = (passes + 1) * num_edges
    return float(flops), float(nbytes)
