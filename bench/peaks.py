"""Published peaks of one chip, keyed by ``jax.devices()[i].device_kind``.
A kind missing from the table is an error, never a default.

"TPU v5 lite" is the TPU v5e. Source: Google Cloud documentation, "TPU v5e":
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
inter-chip interconnect per chip over 4 links (50 GB/s per link).
"""
from __future__ import annotations

PEAKS: dict[str, dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes": 16e9, "hbm_bw": 819e9, "link_bw": 50e9},
}


def peaks(device_kind: str) -> dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind {device_kind!r}"
                         f" (known: {sorted(PEAKS)})") from None
