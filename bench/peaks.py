"""Published peaks of the chips the benchmark runs on, keyed by
``device_kind`` as JAX reports it.  A kind that is not here is an error:
a roofline against another chip's peaks would be a wrong number.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s per chip.
The v5e publishes no peak for integer vector (VPU) work, so the bitmap
kernels' rooflines are bounded by bytes alone.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float       # FLOP/s
    int8_ops: float         # OP/s
    hbm_bytes: float        # bytes of HBM
    hbm_bytes_per_s: float  # HBM bandwidth, bytes/s


PEAKS = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, int8_ops=393e12,
                         hbm_bytes=16e9, hbm_bytes_per_s=819e9),
}


def peaks(device_kind: str) -> Peaks:
    """The peaks of ``device_kind``; raises KeyError for a kind that has
    none in the table."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} (have {sorted(PEAKS)})") from None
