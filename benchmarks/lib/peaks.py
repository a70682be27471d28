"""Published peaks of one chip, keyed by ``jax.devices()[0].device_kind``.

Source: Google Cloud documentation, "TPU v5e" system architecture page:
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s
of chip-to-chip interconnect. Copied from ``bench.DEVICE_PEAKS`` (PR 22)
with the int8 peak added. A device that is not in the table is an
error, never a default: a share of the wrong chip's peak is a wrong
number under a right-looking name.
"""

from __future__ import annotations

DEVICE_PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
    },
}


def peaks_for(kind: str) -> dict:
    if kind not in DEVICE_PEAKS:
        raise KeyError(
            f"no published peaks for device kind {kind!r}; known: "
            f"{sorted(DEVICE_PEAKS)}"
        )
    return DEVICE_PEAKS[kind]
