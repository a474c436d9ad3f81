"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
inter-chip interconnect per chip. A device that is not in the table is an
error, not a default.
"""

from __future__ import annotations

V5E = {
    "bf16_flops": 197e12,
    "int8_ops": 393e12,
    "hbm_bytes_per_s": 819e9,
    "hbm_bytes": 16e9,
    "ici_bits_per_s": 1.6e12,
}

PEAKS = {"TPU v5 lite": V5E, "TPU v5e": V5E}


def for_kind(kind: str) -> dict:
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r}")
    return PEAKS[kind]
