"""Seeded binary fingerprints for the compounds of a side-information
cell, generated on the host.

Each compound sets ``bits_per_row`` of ``n_features`` bits, like a folded
ECFP4 of a drug-like molecule (a few dozen bits of 2,048).  The bits
are drawn so that the link carries signal: a compound's bit scores are
its planted factor row (``benchkit.data.fixed_degree``'s ``U``, drawn
again from the same seed) times a fixed random projection, plus Gumbel
noise, and its highest-scoring bits are set.  Similar planted rows then
share bits, as similar molecules do.
"""
from __future__ import annotations

import numpy as np


def planted_rows(seed: int, n_rows: int, rank: int = 8) -> np.ndarray:
    """The planted factor rows of ``fixed_degree(seed, n_rows, ...)``:
    its first draw from the seed's stream."""
    rng = np.random.default_rng(seed)
    scale = np.float32(rank ** -0.25)
    return rng.standard_normal((n_rows, rank), np.float32) * scale


def ecfp_like(seed: int, n_rows: int, n_features: int, bits_per_row: int,
              rank: int = 8, block: int = 4096) -> np.ndarray:
    """(n_rows, n_features) float32 0/1 fingerprints, ``bits_per_row``
    bits set in every row."""
    if not 0 < bits_per_row <= n_features:
        raise ValueError(f"bits_per_row={bits_per_row} must lie in "
                         f"[1, n_features={n_features}]")
    U = planted_rows(seed, n_rows, rank)
    rng = np.random.default_rng([seed, 1])
    proj = rng.standard_normal((rank, n_features), np.float32)
    out = np.zeros((n_rows, n_features), np.float32)
    for r0 in range(0, n_rows, block):
        u = U[r0:r0 + block]
        score = u @ proj + rng.gumbel(size=(len(u), n_features)).astype(
            np.float32)
        top = np.argpartition(-score, bits_per_row - 1,
                              axis=1)[:, :bits_per_row]
        np.put_along_axis(out[r0:r0 + block], top, 1.0, axis=1)
    return out
