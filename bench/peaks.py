"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

A kind that is not in the table is an error, never a default: a share
of a peak read against the wrong chip's peak means nothing.
"""

from __future__ import annotations

#: device_kind -> peaks of one chip
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bytes_per_s": 1600e9 / 8,
        "ici_links": 4,
        "source": 'Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
                  '16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI per chip over '
                  '4 links',
    },
}


def peaks_for(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; raises on an unknown kind."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(DEVICE_PEAKS)}") from None
