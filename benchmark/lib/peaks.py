"""Published peaks per chip, keyed by ``jax.devices()[0].device_kind``.

Source: Google Cloud documentation, "TPU v5e" system architecture page
(197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip).  A device that is
not in the table is an error, not a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peak(device_kind: str, what: str) -> float:
    try:
        return PEAKS[device_kind][what]
    except KeyError:
        raise KeyError(
            f"no published {what!r} for device kind {device_kind!r}: add it "
            "to benchmark/lib/peaks.py with its source") from None
