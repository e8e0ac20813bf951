"""Peak rates of the chips the benchmark knows, keyed by JAX's
``device_kind``.  A kind missing here is an error, never a default.

TPU v5e: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/
v5e): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.  The compute
roof is the bf16 peak also for the float32 served here: a float32 matmul at
default precision runs as one bf16 pass on the MXU.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(f"no peaks for device_kind {device_kind!r}; "
                            f"known: {sorted(PEAKS)}") from None
