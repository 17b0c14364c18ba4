"""The scoring/top-K stage's least time over its device time, over the
traced window: least time summed over the service steps (each step's
batch from the server's ``serve/step`` spans; operations and bytes
from ``benchkit.counts.topk_flops``/``topk_bytes``), device time of
the programs the ``topk`` stage claims."""
from benchkit import counts
from benchkit.peaks import peaks_for


def read(run):
    red = run.reduced
    stage = sum(s.get("topk", 0.0) for s in red.stage_s.values())
    batches = run.readings.get("batches")
    if not stage or not batches:
        return None
    cfg = run.config
    peaks = peaks_for(run.devices[0].device_kind)
    args = (cfg["samples"], cfg["n_cols"], cfg["num_latent"])
    least = sum(counts.least_time(counts.topk_flops(b, *args),
                                  counts.topk_bytes(b, *args, cfg["top_k"]),
                                  peaks) for b in batches)
    return 100.0 * least / stage
