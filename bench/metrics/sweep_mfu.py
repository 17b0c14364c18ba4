"""Useful FLOP of a sweep per chip over (the traced seconds per sweep x
the chip's bf16 peak).  The sweep's matmuls are float32 at default
precision, one bf16 pass on the v5e MXU, so the bf16 peak divides."""
from benchkit.peaks import peaks_for


def read(run):
    n = run.readings.get("traced_sweeps")
    if not n:
        return None
    peak = peaks_for(run.devices[0].device_kind).flops
    per_sweep = run.reduced.window_s / n
    return 100.0 * run.readings["sweep_flops_per_chip"] / (per_sweep * peak)
