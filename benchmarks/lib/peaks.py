"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` JAX reports.  A device that is not in the table is an
error, never a default: a utilization against a guessed peak is worse than
none.

Source, TPU v5e: Google Cloud documentation, "TPU v5e" system
architecture page (per chip: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e
at 819 GB/s, 1,600 Gbit/s inter-chip interconnect).
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9,
        "source": "Google Cloud documentation, 'TPU v5e' (per chip)",
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peak table of ``device_kind``; an unknown device raises."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add a row "
            f"to benchmarks/lib/peaks.py with its source (known: "
            f"{sorted(PEAKS)})") from None
