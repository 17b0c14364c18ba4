"""Roofline terms from a compiled dry-run artifact.

Nothing here is measured: all three terms are derived from XLA's
compiled-artifact cost table (``hlo_cost.xla_cost_analysis``) plus an
HLO-text parse that sums the operand bytes of every collective.  XLA
reports the cost of the *per-device* SPMD module (verified in
``tests/test_roofline.py``: a jit over N devices reports ~1/N of the
global matmul FLOPs), so each term divides by the per-chip peaks of
the device kind the program targets:

    compute_s    = flops_per_device / peaks.flops
    memory_s     = bytes_per_device / peaks.hbm_bw
    collective_s = collective_bytes_per_device / peaks.ici_bw

Collective bytes come from the trip-count-aware HLO analyzer in
``hlo_cost.py`` (operand bytes summed per collective kind).
"""
from __future__ import annotations

from typing import Dict, NamedTuple


class Peaks(NamedTuple):
    """Published per-chip peaks of one device kind."""

    flops: float        # bf16 FLOP/s
    hbm_bw: float       # HBM bytes/s
    hbm_bytes: float    # HBM capacity, bytes
    ici_bw: float       # inter-chip bytes/s per link


# Keyed by ``jax.Device.device_kind``.  Source: Google Cloud
# documentation, "TPU v5e" (system architecture table): 197 TFLOP/s
# bf16, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of inter-chip
# interconnect, here split over the 4 links of a chip in the 2-D torus.
PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, hbm_bytes=16e9,
                         ici_bw=1600e9 / 8 / 4),
}


def peaks_for(kind: str) -> Peaks:
    """The peaks of device kind ``kind``; a kind not in the table is
    an error, never a default."""
    try:
        return PEAKS[kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {kind!r}; known kinds: "
            f"{', '.join(sorted(PEAKS))}") from None


def model_flops(cfg, shape, n_chips: int) -> float:
    """Useful FLOPs per device: 6·N_active·D train, 2·N_active·D fwd."""
    from ..models.config import param_count
    total, active = param_count(cfg)
    tokens = shape.global_batch * (
        shape.seq_len if shape.kind in ("train", "prefill") else 1)
    mult = 6 if shape.kind == "train" else 2
    return mult * active * tokens / n_chips


def roofline_terms(rec: Dict, cfg, shape, kind: str) -> Dict:
    """The three terms (s), the bottleneck, and the useful-FLOP ratio.

    The memory term uses ``bytes_hbm`` (TPU-fusion materialization
    model + entry args/outputs — see hlo_cost._MATERIALIZE) when the
    record carries it; ``bytes_accessed`` (every top-level op at
    CPU-fusion granularity) is kept in the record as the upper bound.
    """
    peaks = peaks_for(kind)
    comp = rec["flops"] / peaks.flops
    mem = rec.get("bytes_hbm", rec["bytes_accessed"]) / peaks.hbm_bw
    coll = rec["collective_bytes"]["total"] / peaks.ici_bw
    dom = max(("compute", comp), ("memory", mem),
              ("collective", coll), key=lambda kv: kv[1])[0]
    mf = model_flops(cfg, shape, rec["n_chips"])
    bound = max(comp, mem, coll)
    return {
        "compute_s": comp,
        "memory_s": mem,
        "collective_s": coll,
        "dominant": dom,
        "model_flops": mf,
        "useful_flop_ratio": (mf / rec["flops"]) if rec["flops"] else 0.0,
        # fraction of roofline-bound time the chip would spend at peak
        # on *useful* math — the headline perf score
        "roofline_fraction": (mf / peaks.flops) / bound if bound else 0.0,
    }
