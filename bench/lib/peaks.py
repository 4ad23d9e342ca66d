"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip.
A kind that is not in the table is an error, never a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "ops_int8": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks(device_kind: str) -> dict:
    """The peak table entry of ``device_kind``; KeyError if unknown."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
