"""A tiny copy of the benchmark, for running its cells on the CPU.

``tiny_root(tmp)`` lays out a checkout under ``tmp``: the benchmark's
files with every configuration cut to a few hundred rows, and the
program's ``src`` linked in.  ``run_cell`` runs one cell through the
harness in this process, past the look for a chip.
"""
from __future__ import annotations

import contextlib
import io
import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)

if os.path.join(REPO, "src") not in sys.path:
    sys.path.insert(0, os.path.join(REPO, "src"))

TINY = {"n_rows": 256, "n_cols": 64, "num_latent": 8, "nnz_per_row": 8,
        "n_test_per_row": 2, "samples": 4, "top_k": 5, "slots": 4}


def tiny_root(tmp: str, **overrides) -> str:
    root = os.path.join(tmp, "checkout")
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns(".out", "__pycache__",
                                                  "testdata"))
    os.symlink(os.path.join(REPO, "src"), os.path.join(root, "src"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bm = json.load(f)
    for c in bm["configs"]:
        path = os.path.join(root, c["file"])
        with open(path) as f:
            cfg = json.load(f)
        cfg.update(TINY, **overrides)
        if cfg.get("mesh"):
            cfg["n_rows"] *= 4
        with open(path, "w") as f:
            json.dump(cfg, f)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    return root


def run_cell(root: str, cell: str, seed: int = 7, seconds: float = 1.0,
             trace: int = 0):
    """(exit code, result line, stdout) of one run of ``cell``."""
    sys.path.insert(0, os.path.join(root, "bench"))
    try:
        from benchkit import harness, peaks
        # the readers divide by a chip's peaks; the CPU borrows the v5e's
        # so that they run (what they print is no device number)
        peaks.PEAKS.setdefault("cpu", peaks.PEAKS["TPU v5 lite"])
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            rc = harness.main(["--workload", cell, "--seed", str(seed),
                               "--seconds", str(seconds), "--trace",
                               str(trace)], root=root, require_chip=False)
    finally:
        sys.path.remove(os.path.join(root, "bench"))
    text = out.getvalue()
    last = text.strip().splitlines()[-1] if text.strip() else ""
    return rc, (json.loads(last) if rc == 0 else None), text
