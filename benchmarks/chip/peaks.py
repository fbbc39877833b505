"""Published peaks of the chips the benchmark runs on, keyed by JAX's
`device_kind`. A device that is not in the table is an error, not a default.
"""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
    # 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


class UnknownDevice(RuntimeError):
    pass


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise UnknownDevice(
            f"no published peaks for device_kind {device_kind!r}; known: {sorted(PEAKS)}"
        )
    return PEAKS[device_kind]
