"""The Gram stage's least time over its device time, per sweep and chip.

Operations and bytes are counted at the stage's interface
(``benchkit.counts.gram_flops``/``gram_bytes``) from the real
observations of this chip's rows, the same whatever implements it."""
from benchkit import counts
from benchkit.peaks import peaks_for


def read(run):
    red, n = run.reduced, run.readings.get("traced_sweeps")
    per = [s.get("gram", 0.0) for s in red.stage_s.values()]
    if not n or not any(per):
        return None
    cfg, chips = run.config, run.chips
    nnz = run.readings["observations"] / chips
    least = counts.least_time(
        counts.gram_flops(nnz, cfg["num_latent"]),
        counts.gram_bytes(nnz, (cfg["n_rows"] + cfg["n_cols"]) / chips,
                          cfg["n_rows"] + cfg["n_cols"], cfg["num_latent"]),
        peaks_for(run.devices[0].device_kind))
    stage = sum(per) / len(per) / n
    return 100.0 * least / stage
