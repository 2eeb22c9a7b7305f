"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

"TPU v5 lite" is TPU v5e.  Source: Google Cloud documentation, "TPU
v5e": 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s,
1,600 Gbit/s of chip-to-chip interconnect.  A kind that is not here is
an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16 * 10 ** 9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
