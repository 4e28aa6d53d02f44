"""Published per-chip peaks, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16,
16 GB of HBM at 819 GB/s. (Copied from ``repro.roofline.analysis.PEAKS``
so that the benchmark's yardstick stays where later changes to the
program cannot move it.) A device missing from the table is an error."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def least_seconds(flops: float, nbytes: float, device_kind: str) -> float:
    """The least time the chip can take for the work: the larger of the
    compute bound and the bytes bound."""
    p = peaks(device_kind)
    return max(flops / p["flops_per_s"], nbytes / p["hbm_bytes_per_s"])
