"""Time per sweep that the loop thread spends handing a sample to the
writer: its ``ckpt/wait`` (the previous write) and ``ckpt/host_copy``
(the sample's copy to the host) spans."""
from benchkit import spans


def read(run):
    n = run.readings.get("traced_sweeps")
    prog = spans.of(run)
    if not n or not prog.count("ckpt/host_copy"):
        return None
    for name in ("ckpt/wait", "ckpt/host_copy"):
        print(f"save_stall_ms {name}: "
              f"{1e3 * prog.duration((name,)) / n} ms per sweep")
    return 1e3 * prog.duration(("ckpt/wait", "ckpt/host_copy")) / n
