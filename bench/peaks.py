"""Published peaks of one accelerator chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
197 TFLOP/s in bf16, 394 TOP/s in int8, 16 GB of HBM at 819 GB/s, 1,600
Gbit/s of chip-to-chip interconnect.  The row is a copy of
``repro.core.costmodel.DEVICE_PEAKS`` kept with the benchmark, so that no
change to the program can move the yardstick.  A kind that is not in the
table is an error, never a default.  Only the peaks that a metric reads are
kept: a reader of another peak adds its field with its published value.
"""
from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    hbm_bytes_per_s: float   # HBM bandwidth


PEAKS = {
    "TPU v5 lite": Peaks(hbm_bytes_per_s=819e9),
}


def peaks(device_kind: str) -> Peaks:
    """The published peaks of ``device_kind``; raises on an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; add them to bench/peaks.py") from None
