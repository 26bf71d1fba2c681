"""Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``.

Copied from the program's ``repro.launch.roofline.PEAKS`` so that no later
change to the program can move the yardstick. Source: Google Cloud
documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at
819 GB/s, 1,600 Gbit/s chip-to-chip interconnect (50 GB/s per link, 4 links).
A device kind that is not in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "ici_bytes_per_s": 50e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def least_time_s(flops: float, bytes_moved: float, row: dict) -> float:
    """Roofline floor: the larger of compute time and memory time."""
    return max(flops / row["bf16_flops"], bytes_moved / row["hbm_bytes_per_s"])
