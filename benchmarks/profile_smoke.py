"""Roofline profile smoke: per-phase flops/bytes/wall attribution of one
solver round on the smoke instance, for BOTH separation data paths.

    PYTHONPATH=src python -m benchmarks.run --profile

Writes ``BENCH_profile.json`` — the measured counterpart to the static
roofline model in :mod:`repro.roofline.analysis`. CI uploads it as an
artifact (report-only, never gated): the per-phase walls localise a perf
regression to separation / message passing / contraction before anyone
has to bisect, and the flops/bytes columns say whether a phase moved
because the work changed or because the machine did.

Message-passing numbers are loop-corrected to ``mp_iters`` (XLA counts a
scan body once; see :func:`repro.roofline.solver.loop_corrected`).
Roofline fields are filled only on a TPU, from the peaks of its
``device_kind`` (:data:`repro.roofline.analysis.PEAKS`); elsewhere they are
null, "not measured".
"""
from __future__ import annotations

import dataclasses
import json
import platform

import jax

from repro.roofline.analysis import hardware
from repro.roofline.solver import profile_solve_round

from benchmarks.solver_smoke import GRAPH_IMPLS, SMOKE_CFG, smoke_instance

PHASE_METRICS = ("wall_s", "flops", "bytes_accessed", "peak_temp_bytes",
                 "roofline_s", "dominant")


def run_profile(out_path: str = "BENCH_profile.json", csv=None) -> dict:
    inst = smoke_instance()
    dev = jax.devices()[0]
    hw = hardware(dev.device_kind) if dev.platform == "tpu" else None
    report = {
        "bench": "profile_smoke",
        "backend": jax.default_backend(),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": jax.device_count()},
        "platform": platform.platform(),
        "impls": {},
    }
    for impl in GRAPH_IMPLS:
        cfg = dataclasses.replace(SMOKE_CFG, graph_impl=impl)
        prof = profile_solve_round(inst, cfg, hw=hw)
        report["impls"][impl] = prof
        if csv is not None:
            csv.add("profile", f"round/{impl}", "wall_s",
                    round(prof["round_wall_s"], 4))
            for phase, rec in prof["phases"].items():
                for metric in PHASE_METRICS:
                    v = rec.get(metric)
                    if isinstance(v, float):
                        v = round(v, 6)
                    csv.add("profile", f"{phase}/{impl}", metric, v)
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(f"wrote {out_path}")
    return report
