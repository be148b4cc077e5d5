"""Published peaks of each chip, keyed by ``device_kind`` as JAX reports it.

TPU v5e: Google Cloud documentation, "TPU v5e" (system architecture): 197
TFLOP/s bf16, 393 TOP/s int8, 16 GiB HBM at 819 GB/s. The same numbers are
in ``repro.launch.roofline`` (``PEAK_FLOPS``, ``HBM_BW``) and were copied
here so that no later change to the program moves the yardstick.

A kind that is not in the table is an error: a roofline share against a
guessed peak is no measurement.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peak:
    bf16_flops: float      # FLOP/s
    hbm_bytes: float       # bytes/s
    hbm_capacity: int      # bytes
    source: str


PEAKS = {
    "TPU v5 lite": Peak(197e12, 819e9, 16 * 2**30,
                        "Google Cloud documentation, TPU v5e"),
}


def peak(device_kind: str) -> Peak:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peak for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
