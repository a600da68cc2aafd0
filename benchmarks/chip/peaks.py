"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` string JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
one v5e chip has 197 TFLOP/s of bf16 compute, 393 TOP/s of int8, and
16 GB of HBM2 at 819 GB/s.  JAX names that chip "TPU v5 lite".

A device that is not in the table is an error: a metric that divides by
a peak must not fall back to a guess.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
    bf16_flops: float        # FLOP/s, dense bf16 matrix multiply
    hbm_bytes: float         # bytes/s, HBM bandwidth
    hbm_capacity: int        # bytes of HBM
    source: str


PEAKS = {
    "TPU v5 lite": Peak(bf16_flops=197e12, hbm_bytes=819e9,
                        hbm_capacity=16 * 10**9,
                        source="Google Cloud documentation, 'TPU v5e'"),
}


def peak(device_kind: str) -> Peak:
    """The peaks of ``device_kind``; raises KeyError for an unknown chip."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
