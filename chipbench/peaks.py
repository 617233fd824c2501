"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.  A kind that is not here is an error, not a default."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float        # dense bf16 FLOP/s
    hbm_bytes: float    # HBM bytes/s
    source: str


PEAKS = {
    # 197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s
    "TPU v5 lite": Peaks(197e12, 819e9,
                         'Google Cloud documentation, "TPU v5e"'),
}


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r} in chipbench/peaks.py") from None
