"""Peak rates of each accelerator, keyed by JAX's ``device_kind``.

Source of the TPU v5e numbers: Google Cloud documentation, "TPU v5e"
(197 TFLOP/s bf16, 819 GB/s HBM per chip). A kind that is not in the table
is an error, never a default."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
