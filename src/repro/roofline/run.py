import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Roofline runner: per (arch × shape) cell on the single-pod mesh, derive
the three roofline terms from compiled artifacts with loop-trip-count
correction, and write results/roofline_single.json + a markdown table.

Loop correction (see analysis.py): XLA counts while-loop bodies once, so
for the LM family we compile depth-1 and depth-2 layer-stack variants
(attention/CE chunk maps unrolled, grad_accum=1) and extrapolate
    X(L) ≈ X(1) + (L−1)·ΔX          for X ∈ {flops, bytes, collective_bytes}
GNN/recsys models are Python-loop structured — no correction needed.
RAMA's message-passing scan gets the same two-point treatment over
mp_iters. Usage:

    PYTHONPATH=src python -m repro.roofline.run [--arch A] [--shape S]
"""
import argparse
import dataclasses
import json
import sys
import time

import jax
import jax.numpy as jnp

from repro.configs.base import REGISTRY, get_arch, all_arch_ids
from repro.launch.mesh import make_production_mesh
from repro.roofline.analysis import (
    collective_bytes, dominant_term, hardware, roofline_fraction,
    roofline_terms, step_time_estimate,
)

# the chip the production meshes describe; the dry run compiles on faked
# host devices, so the target is named, not read from jax.devices()
HW = hardware("TPU v5 lite")

ASSIGNED = [
    "granite-34b", "gemma2-9b", "phi3-mini-3.8b", "llama4-scout-17b-a16e",
    "grok-1-314b", "dimenet", "egnn", "mace", "graphcast", "wide-deep",
]


def _measure(arch, shape_name, mesh):
    """Compile one variant, return (flops, bytes, coll_bytes_dict)."""
    from repro.launch.dryrun import dryrun_cell
    import repro.configs.base as base
    base.REGISTRY["__tmp__"] = arch
    try:
        rec, lowered, compiled = dryrun_cell("__tmp__", shape_name, mesh,
                                             verbose=False)
    finally:
        del base.REGISTRY["__tmp__"]
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    return rec["flops"], rec["bytes_accessed"], coll, rec


def _lm_variant(arch, n_layers):
    # unrolled layers + unrolled attention/CE chunk maps: every loop body
    # appears in the HLO, so HloCostAnalysis counts it (scan bodies are
    # counted once regardless of trip count)
    cfg = dataclasses.replace(
        arch.cfg, n_layers=n_layers, attn_unroll=True, scan_layers=False)
    return dataclasses.replace(arch, cfg=cfg, grad_accum=1)


def measure_cell(arch_id: str, shape_name: str, mesh):
    arch = get_arch(arch_id)
    shape = arch.shapes[shape_name]
    t0 = time.time()
    if arch.family == "lm":
        # depth delta: gemma2 scans layer PAIRS, so depths are 2/4 there
        step_depths = (2, 4) if arch.cfg.local_global_alternate else (1, 2)
        f1, b1, c1, _ = _measure(_lm_variant(arch, step_depths[0]),
                                 shape_name, mesh)
        f2, b2, c2, _ = _measure(_lm_variant(arch, step_depths[1]),
                                 shape_name, mesh)
        per = step_depths[1] - step_depths[0]
        L = arch.cfg.n_layers
        df, db = (f2 - f1) / per, (b2 - b1) / per
        dc = {k: (c2[k] - c1[k]) / per for k in c1}
        n0 = step_depths[0]
        flops = f1 + (L - n0) * df
        bytes_ = b1 + (L - n0) * db
        coll = {k: c1[k] + (L - n0) * dc[k] for k in c1}
        # microbatch scaling: the depth variants run grad_accum=1 over the
        # full batch, which equals the summed microbatch work (linear in
        # tokens); the optimizer update is counted once in both — correct.
    elif arch.family == "multicut":
        a = dataclasses.replace(arch, unroll=True)
        flops, bytes_, coll, _ = _measure(a, shape_name, mesh)
    else:
        flops, bytes_, coll, _ = _measure(arch, shape_name, mesh)

    n_chips = mesh.size
    terms = roofline_terms(flops, bytes_, coll["total"], HW)
    # CPU-backend dtype correction: XLA:CPU upcasts bf16 dot operands to
    # f32, so activation/weight collectives and HBM traffic are measured at
    # 2x their TPU size for bf16-compute archs (verified: param dtype
    # doesn't change the totals — the converts sit in front of every dot).
    # DP gradient reductions are f32 in production too but are <1% of the
    # totals here (one param-sized reduce vs per-layer activation traffic).
    dtype_bf16 = getattr(getattr(arch, "cfg", None), "dtype", None) == \
        jnp.bfloat16 or getattr(arch, "compute_dtype", None) == jnp.bfloat16
    if dtype_bf16:
        corr = roofline_terms(flops, bytes_ / 2, coll["total"] / 2, HW)
        terms_corr = {f"{k}_corr": round(v, 6) for k, v in corr.items()}
    else:
        terms_corr = {f"{k}_corr": round(terms[k], 6) for k in terms}
    # RAMA solver/mp cells run REPLICATED (single-device programs inside
    # the mesh); their per-chip HLO flops are whole-problem flops, so
    # MODEL_FLOPS is not divided by the chip count for them.
    if arch.family == "multicut" and shape.kind != "dist":
        model_flops = arch.model_flops(shape)
    else:
        model_flops = arch.model_flops(shape) / n_chips
    rec = {
        "arch": arch_id, "shape": shape_name, "chips": n_chips,
        "hlo_flops_per_chip": flops,
        "hlo_bytes_per_chip": bytes_,
        "collective_bytes_per_chip": coll["total"],
        "collective_breakdown": {k: v for k, v in coll.items()
                                 if k != "total" and v > 0},
        **{k: round(v, 6) for k, v in terms.items()},
        **terms_corr,
        "dominant": dominant_term(terms),
        "model_flops_per_chip": model_flops,
        "useful_flop_ratio": round(model_flops / flops, 4) if flops else 0,
        "roofline_fraction": round(roofline_fraction(model_flops, terms, HW),
                                    4),
        "roofline_fraction_corr": round(roofline_fraction(
            model_flops,
            {k.replace("_corr", ""): v for k, v in terms_corr.items()},
            HW), 4),
        "step_time_est_s": round(step_time_estimate(terms), 6),
        "analysis_s": round(time.time() - t0, 1),
    }
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--out", default="results/roofline_single.json")
    args = ap.parse_args(argv)

    mesh = make_production_mesh()    # roofline table is single-pod only
    arch_ids = [args.arch] if args.arch else ASSIGNED + ["rama-multicut"]
    records, failures = [], []
    for aid in arch_ids:
        arch = get_arch(aid)
        shapes = [args.shape] if args.shape else list(arch.shapes)
        for sname in shapes:
            try:
                rec = measure_cell(aid, sname, mesh)
                records.append(rec)
                print(f"{aid}/{sname}: dom={rec['dominant']} "
                      f"c={rec['compute_s']:.4f}s m={rec['memory_s']:.4f}s "
                      f"x={rec['collective_s']:.4f}s "
                      f"useful={rec['useful_flop_ratio']:.2f} "
                      f"roofline={rec['roofline_fraction']:.2%}")
            except Exception as e:  # noqa: BLE001
                failures.append((aid, sname, repr(e)[:200]))
                print(f"FAIL {aid}/{sname}: {e}")
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump(records, fh, indent=1)
    print(f"\n{len(records)} cells analysed, {len(failures)} failures "
          f"-> {args.out}")
    for f in failures:
        print("  FAILED:", *f)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
