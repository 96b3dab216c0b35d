"""Published peaks per chip, keyed by JAX's `device_kind`.

TPU v5e ("TPU v5 lite"): 197 TFLOP/s in bfloat16, 819 GB/s of HBM
bandwidth, 16 GB of HBM (Google Cloud documentation, "TPU v5e").  The
FLOP peak is the bfloat16 one: the program's float32 matrix products run at
the default precision, which on a TPU is one bfloat16 pass with float32
accumulation.  A kind that is not in the table is an error.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return PEAKS[device_kind]
