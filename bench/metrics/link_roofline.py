"""The link stage's least time over its device time, per sweep and chip.

Operations and bytes are counted at the stage's interface
(``benchkit.link_counts``) from the compound rows, the side width and
the latent width, the same whatever implements it."""
from benchkit import counts
from benchkit.link_counts import link_bytes, link_flops
from benchkit.peaks import peaks_for


def read(run):
    red, n = run.reduced, run.readings.get("traced_sweeps")
    per = [s.get("link", 0.0) for s in red.stage_s.values()]
    if not n or not any(per):
        return None
    cfg = run.config
    rows = cfg["n_rows"] / run.chips
    d, k = cfg["num_features"], cfg["num_latent"]
    peaks = peaks_for(run.devices[0].device_kind)
    flops, nbytes = link_flops(rows, d, k), link_bytes(rows, d, k)
    least = counts.least_time(flops, nbytes, peaks)
    bound = ("memory" if nbytes / peaks.hbm_bw >= flops / peaks.flops
             else "compute")
    stage = sum(per) / len(per) / n
    print(f"link_roofline: least {1e3 * least} ms ({bound} bound), stage "
          f"{1e3 * stage} ms per sweep")
    return 100.0 * least / stage
