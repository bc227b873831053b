"""Published peaks of each chip the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A kind that is not here is an error.

TPU v5e: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    name: str
    bf16_flops: float        # FLOP/s
    hbm_bytes_per_s: float   # B/s
    source: str


TPU_V5E = Peaks("TPU v5e", bf16_flops=197e12, hbm_bytes_per_s=819e9,
                source='Google Cloud documentation, "TPU v5e"')

BY_KIND = {"TPU v5 lite": TPU_V5E}


def for_kind(kind: str) -> Peaks:
    try:
        return BY_KIND[kind]
    except KeyError:
        raise ValueError(f"no published peaks for device_kind {kind!r}; "
                         f"known: {sorted(BY_KIND)}") from None
