"""Published peaks per chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture): per
chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.  No published
peak covers the vector unit, so work done there (compares, selects, adds)
has no operations bound.  A kind that is not in the table is an error.
"""

from __future__ import annotations

SOURCE = "Google Cloud documentation, TPU v5e"

_V5E = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_s": 819e9,
        "hbm_bytes": 16e9}

PEAKS = {
    "TPU v5 lite": _V5E,      # what JAX reports for a v5e chip
    "TPU v5e": _V5E,
}


def peaks(device_kind: str) -> dict:
    """The peaks of one chip of this kind; KeyError for an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
