"""Published peaks per chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s).  JAX reports a v5e chip
as "TPU v5 lite".  A kind that is not in the table is an error, never a
default.
"""

from __future__ import annotations

_V5E = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12, "hbm_bytes": 16e9}

PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
