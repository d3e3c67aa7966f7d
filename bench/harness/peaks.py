"""Published peaks of the chips the benchmark runs on, keyed by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (per chip): 197 TFLOP/s bf16,
394 TOP/s int8, 16 GB HBM at 819 GB/s.  A device that is not in the table
is an error, never a default.
"""

from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    bf16_flops: float        # FLOP/s
    int8_ops: float          # OP/s
    hbm_bytes: float         # bytes/s
    hbm_capacity: float      # bytes
    source: str


_V5E = Peaks(197e12, 394e12, 819e9, 16e9, "Google Cloud, TPU v5e")

PEAKS = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
