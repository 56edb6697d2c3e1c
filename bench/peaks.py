"""Published peak rates of one chip, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s).  The FLOP peak is the
matrix unit's bf16 rate.  The cost-evaluation work measured here is
float32 elementwise arithmetic on the vector unit, for which no peak is
published and none is assumed, so its roofline shares are small and are
read for their changes.  A device kind missing from the table is an
error, not a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "source": "Google Cloud TPU v5e documentation"},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise LookupError(f"no published peaks for device kind "
                          f"{device_kind!r}; known: {sorted(PEAKS)}") from None
