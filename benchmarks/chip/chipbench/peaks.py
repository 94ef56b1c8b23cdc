"""Peak figures of one chip, keyed by ``jax.devices()[0].device_kind``.

A kind that is not listed is an error: a share of another chip's peak is
wrong, not rough.
"""
from __future__ import annotations

CHIP_PEAKS = {
    "TPU v5 lite": {
        # Google Cloud TPU documentation, "TPU v5e": 819 GB/s of HBM2
        "hbm_bytes_per_s": 819e9,
    },
}


def chip_peaks(device_kind: str) -> dict:
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak figures for device kind {device_kind!r}; known: {sorted(CHIP_PEAKS)}"
        ) from None
