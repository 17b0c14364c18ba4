"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture
table): 197 TFLOP/s bf16, 394 TOP/s int8, 16 GB of HBM at 819 GB/s,
1,600 Gbit/s of inter-chip interconnect per chip.  A kind that is not
in the table is an error, never a default.
"""
from __future__ import annotations

from typing import Dict, NamedTuple


class Peaks(NamedTuple):
    flops: float        # bf16 FLOP/s
    hbm_bw: float       # HBM bytes/s
    hbm_bytes: float    # HBM capacity, bytes
    ici_bw: float       # inter-chip bytes/s, all links of one chip


PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, hbm_bytes=16e9,
                         ici_bw=1600e9 / 8),
}


def peaks_for(kind: str) -> Peaks:
    try:
        return PEAKS[kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {kind!r}; known kinds: "
            f"{', '.join(sorted(PEAKS))}") from None
