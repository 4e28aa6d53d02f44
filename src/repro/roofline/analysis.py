"""Roofline terms from compiled dry-run artifacts.

    compute term    = HLO_FLOPs_per_device / peak_FLOP/s
    memory term     = HLO_bytes_per_device / HBM_bw
    collective term = collective_bytes_per_device / ICI_bw

``cost_analysis()`` on an SPMD-partitioned executable reports PER-DEVICE
numbers (the module is the per-device program), so we divide by per-chip
peaks — algebraically identical to global/(chips × peak).

collective_bytes is not in cost_analysis: we parse the optimized HLO text
and sum the tensor sizes flowing through every all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute.

KNOWN ACCOUNTING CAVEAT (and how the runner fixes it): XLA's HloCostAnalysis
counts while-loop bodies ONCE, not × trip-count. Every lax.scan (layer
stacks, MP iterations) would therefore under-report. The runner compiles
depth-1 and depth-2 variants and extrapolates linearly:
    flops(L) ≈ flops(1) + (L − 1) · (flops(2) − flops(1))
which also captures remat recompute inside the loop body. Inner chunk maps
(flash attention / chunked CE) are compiled UNROLLED for the same reason.
"""
from __future__ import annotations

import dataclasses
import re


@dataclasses.dataclass(frozen=True)
class Hardware:
    name: str
    peak_flops: float       # bf16 FLOP/s per chip
    hbm_bw: float           # bytes/s per chip
    ici_bw: float           # bytes/s per ICI link
    hbm_bytes: float        # capacity per chip


# Published per-chip peaks, keyed by jax's ``device_kind``. TPU v5e: Google
# Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 16 GB of HBM at
# 819 GB/s, 1,600 Gbit/s of interconnect (four links, ~50 GB/s each).
PEAKS = {
    "TPU v5 lite": Hardware(name="TPU v5e", peak_flops=197e12,
                            hbm_bw=819e9, ici_bw=50e9,
                            hbm_bytes=16 * 2**30),
}


def hardware(device_kind: str) -> Hardware:
    """The peaks of ``device_kind``; a device missing from :data:`PEAKS`
    is an error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1, "pred": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(text: str) -> int:
    """Total bytes of every dtype[dims] shape literal in ``text``."""
    total = 0
    for m in _SHAPE_RE.finditer(text):
        dt = m.group(1)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        dims = m.group(2)
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-collective-kind byte totals from optimized HLO text.

    For each collective instruction we count the RESULT shape's bytes (the
    tensor that traverses the interconnect once per op under a bandwidth-
    optimal ring; all-reduce moves ~2x that — accounted via the factor
    below)."""
    out = {k: 0 for k in _COLLECTIVES}
    out["total"] = 0
    for line in hlo_text.splitlines():
        stripped = line.strip()
        for kind in _COLLECTIVES:
            # match the op use, e.g. "%x = f32[...] all-gather(" — results
            # are on the lhs of " = "
            if f" {kind}(" not in stripped and \
                    f" {kind}-start(" not in stripped:
                continue
            rhs = stripped.split(" = ")[1] if " = " in stripped else stripped
            # result may be a tuple: "(bf16[..], s32[..]) all-to-all(...)"
            op_pos = rhs.find(f" {kind}")
            shape_part = rhs[:op_pos] if op_pos >= 0 else rhs.split("(")[0]
            b = _shape_bytes(shape_part)
            factor = 2.0 if kind == "all-reduce" else 1.0
            out[kind] += int(b * factor)
            out["total"] += int(b * factor)
            break
    return out


def roofline_terms(flops: float, bytes_accessed: float, coll_bytes: float,
                   hw: Hardware) -> dict:
    compute_s = flops / hw.peak_flops
    memory_s = bytes_accessed / hw.hbm_bw
    collective_s = coll_bytes / hw.ici_bw
    return {"compute_s": compute_s, "memory_s": memory_s,
            "collective_s": collective_s}


def dominant_term(terms: dict) -> str:
    keys = ("compute_s", "memory_s", "collective_s")
    return max(keys, key=lambda k: terms[k]).replace("_s", "")


def step_time_estimate(terms: dict, overlap: bool = True) -> float:
    """Roofline step-time: max of the three terms when compute/memory/
    collectives overlap (TPU async DMA + XLA latency hiding), their sum
    when they serialise."""
    vals = (terms["compute_s"], terms["memory_s"], terms["collective_s"])
    return max(vals) if overlap else sum(vals)


def roofline_fraction(model_flops_per_device: float, terms: dict,
                      hw: Hardware) -> float:
    """Fraction of peak the step achieves under the roofline estimate:
    useful-FLOPs-time / estimated step time."""
    t = step_time_estimate(terms)
    if t <= 0:
        return 0.0
    return (model_flops_per_device / hw.peak_flops) / t
