"""Published per-chip peaks, keyed by JAX's ``Device.device_kind``.

One TPU v5e (Google Cloud documentation, "TPU v5e"): 197 TFLOP/s in bf16,
16 GB of HBM at 819 GB/s.  A kind missing from the table is an error: no
peak is ever assumed.  No metric of the benchmark reads a roofline yet;
the table is here so that the one that will is measured against the
benchmark's copy, not the program's.
"""

from __future__ import annotations

PEAKS: dict[str, dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(kind: str) -> dict[str, float]:
    try:
        return PEAKS[kind]
    except KeyError:
        raise ValueError(f"no peaks for device kind {kind!r}; known: "
                         f"{sorted(PEAKS)}") from None
