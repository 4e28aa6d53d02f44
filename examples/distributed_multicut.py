"""End-to-end distributed multicut: the paper's stated future work
("multi-GPU ... decomposition methods") realised on a device mesh.

    PYTHONPATH=src python examples/distributed_multicut.py

Pipeline (exactly the production path, on 8 faked host devices):
  1. host partitioner splits a 4000-node instance into per-device blocks;
  2. every device runs interior RAMA PD rounds under shard_map
     (separation → message passing → contraction, all device-local);
  3. block LBs are psum'd with the boundary relaxation into a VALID global
     lower bound;
  4. the contracted blocks + boundary edges form a quotient instance,
     solved on one device;
  5. the composed labeling is scored on the original instance.
"""
import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8").strip()

import sys

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro import api
from repro.compile_cache import enable_compile_cache
from repro.core.dist import (
    make_dist_pd_round, merge_blocks_quotient, partition_instance,
)
from repro.core.graph import random_instance
from repro.launch.mesh import make_debug_mesh

N_NODES = 4000
BLK_NODES = 512
BLK_EDGES = 8192


def main():
    enable_compile_cache()
    mesh = make_debug_mesh(4, 2)
    n_blocks = mesh.size
    print(f"mesh: {dict(mesh.shape)} ({n_blocks} devices)")

    inst = random_instance(N_NODES, 0.004, seed=0, pad_edges=65536,
                           pad_nodes=n_blocks * BLK_NODES)
    parts = partition_instance(inst, n_blocks, BLK_NODES, BLK_EDGES)
    n_boundary = len(parts["boundary_cost"])
    print(f"instance: {N_NODES} nodes, partitioned into {n_blocks} blocks, "
          f"{n_boundary} boundary edges")

    rnd = make_dist_pd_round(mesh, mp_iters=5, max_neg=256)
    args = [jnp.asarray(parts[k]) for k in
            ("u", "v", "cost", "edge_valid", "node_valid", "boundary_cost")]
    u, v, c, ev, nv, mapping, lb = rnd(*args)
    print(f"distributed round done; valid global LB = {float(lb[0]):.2f}")

    # merge: quotient graph over contracted block clusters + boundary edges
    q, global_labels = merge_blocks_quotient(
        np.asarray(mapping), parts["boundary_u"], parts["boundary_v"],
        parts["boundary_cost"], BLK_NODES, pad_edges=65536)
    nq = int(np.asarray(q.node_valid).sum())
    print(f"quotient instance: {nq} super-nodes")
    res_q = api.solve(q, mode="pd",
                      config=api.SolverConfig(max_neg=1024, mp_iters=8))

    # compose: original node -> block cluster -> quotient cluster
    final = np.asarray(res_q.labels)[global_labels][:N_NODES]
    obj = float(inst.objective(jnp.asarray(
        np.concatenate([final, np.zeros(inst.num_nodes - N_NODES,
                                        np.int32)]))))
    # single-device reference
    ref = api.solve(inst, mode="pd",
                    config=api.SolverConfig(max_neg=1024, mp_iters=8))
    print(f"distributed objective {obj:.2f}   "
          f"single-device PD {float(ref.objective):.2f}   "
          f"LB {float(lb[0]):.2f}")
    assert float(lb[0]) <= obj + 1e-3, "LB must bound any feasible solution"
    print("OK: LB <= distributed objective (certificate holds)")


if __name__ == "__main__":
    main()
