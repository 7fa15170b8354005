"""Published peaks of the chips the benchmark runs on, keyed by
``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bfloat16,
393 TOP/s in int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
interconnect.  A device that is not in the table is an error: no share of
a peak is computed against a guess.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float        # bfloat16 FLOP/s per chip
    hbm_bw: float       # HBM bytes/s per chip


V5E = Peaks(flops=197e12, hbm_bw=819e9)
PEAKS = {"TPU v5 lite": V5E, "TPU v5e": V5E}


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
