"""Useful FLOP of the requests answered in the window over (the window
x the chip's bf16 peak)."""
from benchkit.peaks import peaks_for


def read(run):
    r = run.readings
    if not r.get("useful_flops"):
        return None
    peak = peaks_for(run.devices[0].device_kind).flops
    return 100.0 * r["useful_flops"] / (run.window_s * peak)
