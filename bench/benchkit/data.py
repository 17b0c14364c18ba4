"""Seeded data for the BMF cells, generated on the host.

A copy of the program's ``data.synthetic.fixed_degree`` generator (the
same random stream, so the same seed gives the same matrix), returning
plain COO arrays: the benchmark owns its inputs, and the reference
reads them without the program's sparse layout.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np


class Problem(NamedTuple):
    """Observed entries, held-out test entries and observed columns."""

    i: np.ndarray        # (E,) row id of each observation
    j: np.ndarray        # (E,) column id
    v: np.ndarray        # (E,) float32 value
    ti: np.ndarray       # (T,) test rows
    tj: np.ndarray       # (T,) test columns
    tv: np.ndarray       # (T,) float32 test values
    cols: np.ndarray     # (n_rows, nnz_per_row) observed columns per row
    shape: tuple


def fixed_degree(seed: int, n_rows: int, n_cols: int, nnz_per_row: int,
                 n_test_per_row: int = 8, rank: int = 8,
                 noise: float = 0.3) -> Problem:
    """Planted low-rank data with exactly ``nnz_per_row`` observations
    per row.

    The columns split into ``nnz_per_row`` equal stripes and each row
    observes one random column of every stripe, so column degrees are
    binomial around ``n_rows * nnz_per_row / n_cols``.  Each row holds
    out ``n_test_per_row`` entries, in distinct stripes, at columns it
    does not observe.  Values are a rank-``rank`` product of unit
    variance plus Gaussian noise of std ``noise``.
    """
    if n_cols % nnz_per_row:
        raise ValueError(f"n_cols={n_cols} is not a multiple of "
                         f"nnz_per_row={nnz_per_row}")
    stripe = n_cols // nnz_per_row
    if n_test_per_row > nnz_per_row or (n_test_per_row and stripe < 2):
        raise ValueError(
            f"no room for {n_test_per_row} held-out entries per row "
            f"with {nnz_per_row} stripes of {stripe} columns")
    rng = np.random.default_rng(seed)
    scale = np.float32(rank ** -0.25)
    U = rng.standard_normal((n_rows, rank), np.float32) * scale
    V = rng.standard_normal((n_cols, rank), np.float32) * scale
    offs = rng.integers(0, stripe, (n_rows, nnz_per_row))
    cols = np.arange(nnz_per_row) * stripe + offs
    held = np.argsort(rng.random((n_rows, nnz_per_row)),
                      axis=1)[:, :n_test_per_row]
    held_off = (np.take_along_axis(offs, held, axis=1)
                + rng.integers(1, max(stripe, 2), held.shape)) % stripe
    test_cols = held * stripe + held_off

    def observe(i, j):
        return (np.einsum("ek,ek->e", U[i], V[j]) + noise
                * rng.standard_normal(len(i), np.float32))

    i = np.repeat(np.arange(n_rows), nnz_per_row)
    j = cols.ravel()
    v = observe(i, j).astype(np.float32)
    ti = np.repeat(np.arange(n_rows), n_test_per_row)
    tj = test_cols.ravel()
    tv = observe(ti, tj).astype(np.float32)
    return Problem(i, j, v, ti, tj, tv, cols, (n_rows, n_cols))
