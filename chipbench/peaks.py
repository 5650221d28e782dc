"""Published peaks of each chip, keyed by ``jax.Device.device_kind``.

A kind that is not in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
                  "16 GB HBM2 at 819 GB/s per chip",
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks recorded for device kind {device_kind!r}; "
            f"known kinds: {sorted(PEAKS)}"
        ) from None
