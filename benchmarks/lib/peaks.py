"""Published peaks of one chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 819 GB/s of HBM bandwidth and 16 GB of HBM per chip.
A device that is not in the table is an error, never a default: a share
of a peak that was guessed is worse than none.
"""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peak(device_kind: str, what: str) -> float:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} in "
            f"benchmarks/lib/peaks.py (known: {sorted(PEAKS)}); add the "
            f"row with its source before reporting a share of a peak")
    return PEAKS[device_kind][what]
