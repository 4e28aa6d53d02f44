import os
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (architecture × input shape)
cell on the production meshes and record memory/cost analysis.

This is how the distribution config is proven coherent without hardware:
``jax.jit(step).lower(*ShapeDtypeStructs).compile()`` runs the full XLA SPMD
partitioner — sharding mismatches, compile-time OOM and unsupported
collectives all fail here.

Usage:
    python -m repro.launch.dryrun                      # all cells, both meshes
    python -m repro.launch.dryrun --arch granite-34b   # one arch
    python -m repro.launch.dryrun --arch granite-34b --shape train_4k \
        --mesh single                                  # one cell
    python -m repro.launch.dryrun --out results.json   # dump records

The FIRST two lines above set XLA_FLAGS before any jax import — jax locks
the device count at first init. Do not import this module from tests.
"""
import argparse
import json
import sys
import time
import traceback

import jax

from repro.configs.base import REGISTRY, get_arch, all_arch_ids
from repro.launch.mesh import make_production_mesh


def _collect_state(arch, shape):
    """(state trees, state shardings) for the cell's step signature."""
    kind = shape.kind
    states, shardings = [], []
    ss = arch.state_shardings if hasattr(arch, "state_shardings") else None
    if arch.family == "lm":
        states.append(arch.abstract_params())
        if kind == "train":
            states.append(arch.abstract_opt())
        if kind == "decode":
            states.append(arch.abstract_cache(shape))
    elif arch.family == "gnn":
        states.append(arch.abstract_params(shape))
        states.append(arch.abstract_opt(shape))
    elif arch.family == "recsys":
        if kind != "retrieval":
            states.append(arch.abstract_params())
            if kind == "train":
                states.append(arch.abstract_opt())
    return states


def _state_shardings(arch, mesh, shape):
    out = arch.state_shardings(mesh, shape)
    kind = shape.kind
    ordered = []
    if arch.family == "lm":
        ordered.append(out["params"])
        if kind == "train":
            ordered.append(out["opt"])
        if kind == "decode":
            ordered.append(out["cache"])
    elif arch.family == "gnn":
        ordered.append(out["params"])
        ordered.append(out["opt"])
    elif arch.family == "recsys":
        if kind != "retrieval":
            ordered.append(out["params"])
            if kind == "train":
                ordered.append(out["opt"])
    return ordered


def input_specs(arch_id: str, shape_name: str):
    """ShapeDtypeStruct stand-ins for every model input of a cell —
    weak-type-correct, shardable, no device allocation."""
    arch = get_arch(arch_id)
    shape = arch.shapes[shape_name]
    return arch.abstract_inputs(shape)


def dryrun_cell(arch_id: str, shape_name: str, mesh, *, verbose=True):
    """Lower + compile one (arch × shape) cell on ``mesh``. Returns a record
    with memory and cost analysis."""
    arch = get_arch(arch_id)
    if hasattr(arch, "for_mesh"):
        arch = arch.for_mesh(mesh)
    shape = arch.shapes[shape_name]
    t0 = time.time()
    if arch.family == "multicut" and shape.kind == "dist":
        step = arch.step_fn(shape, mesh=mesh)
        ins = arch.dist_inputs(mesh, shape)
        in_shardings = arch.input_shardings(mesh, shape)
        args = list(ins.values())
        in_sh = tuple(in_shardings[k] for k in ins)
        jitted = jax.jit(step, in_shardings=in_sh)
        lowered = jitted.lower(*args)
    else:
        step = arch.step_fn(shape)
        states = _collect_state(arch, shape)
        state_sh = _state_shardings(arch, mesh, shape)
        ins = arch.abstract_inputs(shape)
        in_sh_map = arch.input_shardings(mesh, shape)
        args = states + [ins[k] for k in ins]
        in_sh = tuple(state_sh) + tuple(in_sh_map[k] for k in ins)
        # serving donates the KV cache (in-place update); training donates
        # params + optimizer state. Without donation the dry-run double
        # counts these buffers, which is not how the step runs in prod.
        if shape.kind == "decode":
            donate = (1,)
        elif shape.kind == "train" and len(states) == 2:
            donate = (0, 1)
        else:
            donate = ()
        with jax.sharding.set_mesh(mesh):
            jitted = jax.jit(step, in_shardings=in_sh,
                             donate_argnums=donate)
            lowered = jitted.lower(*args)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    t1 = time.time()
    n_dev = mesh.size
    rec = {
        "arch": arch_id,
        "shape": shape_name,
        "mesh": "x".join(str(s) for s in mesh.shape.values()),
        "devices": n_dev,
        "compile_s": round(t1 - t0, 1),
        "flops": float(cost.get("flops", -1.0)),
        "bytes_accessed": float(cost.get("bytes accessed", -1.0)),
        "argument_size_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
        "output_size_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
        "temp_size_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
        "peak_bytes_per_device": int(
            getattr(mem, "argument_size_in_bytes", 0)
            + getattr(mem, "output_size_in_bytes", 0)
            + getattr(mem, "temp_size_in_bytes", 0)),
    }
    if verbose:
        print(f"  [{rec['mesh']}] {arch_id}/{shape_name}: "
              f"compile {rec['compile_s']}s, "
              f"{rec['flops']:.3e} flops, "
              f"args {rec['argument_size_bytes'] / 2**30:.2f} GiB, "
              f"temp {rec['temp_size_bytes'] / 2**30:.2f} GiB "
              f"(per device)")
    return rec, lowered, compiled


def iter_cells(arch_ids=None, shape_names=None):
    for aid in (arch_ids or all_arch_ids()):
        arch = get_arch(aid)
        for sname in (shape_names or list(arch.shapes)):
            if sname in arch.shapes:
                yield aid, sname


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default=None, help="write records JSON here")
    args = ap.parse_args(argv)

    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single-pod 16x16", make_production_mesh()))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi-pod 2x16x16",
                       make_production_mesh(multi_pod=True)))

    arch_ids = [args.arch] if args.arch else None
    shapes = [args.shape] if args.shape else None
    records, failures = [], []
    for mesh_name, mesh in meshes:
        print(f"=== {mesh_name} ({mesh.size} devices) ===")
        for aid, sname in iter_cells(arch_ids, shapes):
            try:
                rec, _, _ = dryrun_cell(aid, sname, mesh)
                records.append(rec)
            except Exception as e:  # noqa: BLE001 — report every cell
                failures.append((mesh_name, aid, sname, repr(e)))
                print(f"  FAIL {aid}/{sname}: {e}")
                traceback.print_exc(limit=3)
    print(f"\n{len(records)} cells compiled, {len(failures)} failures")
    for f in failures:
        print("  FAILED:", *f[:3])
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(records, fh, indent=1)
        print("wrote", args.out)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
