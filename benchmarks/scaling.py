"""Paper Fig. 3 x-axis analogue: scaling with worker count.

The paper scales OpenMP threads across 36 cores (and GASPI to 2048
cores).  Offline analogue: shard the Gibbs sweep over N XLA host-
platform devices with the production ``shard_map``/pjit path
(``core/distributed.py``) and measure one sweep at N = 1, 2, 4, 8.
Device count is locked at jax init, so every N runs in a fresh
subprocess.  Strong scaling on a fixed CPU is bounded by the shared
physical cores — the figure of merit is that the *distributed step
itself* (the code path the 512-chip dry-run proves) runs and stays
flat-ish rather than degrading with partitioning overhead.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from .common import emit

_CHILD = r"""
import os, sys, json, time
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=%d")
import jax, numpy as np
sys.path.insert(0, "src")
from repro.core import FixedGaussian, TrainSession, init_state
from repro.core.distributed import make_distributed_step
from repro.data.synthetic import chembl_like

n_dev = %d
mat, test, _ = chembl_like(0, 4096, 256)
s = TrainSession(num_latent=16, burnin=0, nsamples=1, seed=0)
s.add_train_and_test(mat, test=test, noise=FixedGaussian(5.0))
model, data = s._build()
state = init_state(model, data, 0)
mesh = jax.make_mesh((n_dev,), ("data",))
step, ds, ss = make_distributed_step(model, mesh, data, state)
out = step(data, state)
jax.block_until_ready(out)
ts = []
for _ in range(3):
    t0 = time.perf_counter()
    out = step(data, out[0])
    jax.block_until_ready(out)
    ts.append(time.perf_counter() - t0)
print(json.dumps({"n_dev": n_dev, "t": sorted(ts)[1]}))
"""


def run(device_counts=(1, 2, 4, 8)):
    results = {}
    for n in device_counts:
        proc = subprocess.run(
            [sys.executable, "-c", _CHILD % (n, n)],
            capture_output=True, text=True, cwd=os.getcwd(),
            # simulated host devices: the children never ask for an
            # accelerator, so they cannot compete for the chip
            env={**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"},
            timeout=600)
        if proc.returncode != 0:
            emit("scaling", f"devices_{n}", "ERROR", "s/sweep",
                 proc.stderr.strip().splitlines()[-1][:100]
                 if proc.stderr.strip() else "no stderr")
            continue
        rec = json.loads(proc.stdout.strip().splitlines()[-1])
        results[n] = rec["t"]
        base = results.get(device_counts[0], rec["t"])
        emit("scaling", f"devices_{n}", f"{rec['t']:.4f}", "s/sweep",
             f"t1/tN = {base / rec['t']:.2f} (shared phys cores)")
    return results
