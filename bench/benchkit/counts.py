"""Operations and bytes the algorithm needs, from shapes and real counts.

Every count takes the number of REAL observations (``observations``
counts a mask, never the padded width), so padding a layout cannot
raise a roofline share.  FLOP count a multiply and an add as two.

Per Gibbs sweep of a two-entity BMF block with K latent dimensions,
``nnz`` observations, ``n_test`` test entries and ``n_rows`` +
``n_cols`` factor rows:

* Gram and RHS, each orientation: 2K^2 per observation (the K x K
  outer product accumulated) + 2K (the RHS), so 2 (2K^2 + 2K) nnz.
* Conditional draw, each row: Cholesky K^3/3, two triangular solves
  for the mean and one for the noise, K^2 each: K^3/3 + 3K^2.
* Hyper-parameters, each entity: the moment F^T F, 2 N K^2.
* Noise: the SDDMM prediction at every observation, 2K per
  observation; the test accumulation, 2K per test entry.
"""
from __future__ import annotations

import numpy as np

F32 = 4
I32 = 4


def observations(mask) -> int:
    """Real observations in a (possibly padded) mask."""
    return int(np.count_nonzero(np.asarray(mask)))


def gram_flops(nnz: int, k: int) -> float:
    """Both orientations' Gram + RHS."""
    return 2.0 * nnz * (2.0 * k * k + 2.0 * k)


def gram_bytes(nnz: int, rows_updated: int, rows_fixed: int,
               k: int) -> float:
    """Bytes at the Gram stage's interface, both orientations: each
    observation's id, value and mask read once per orientation, the
    fixed factors' ``rows_fixed`` rows read once, and the (R, K, K)
    Gram and (R, K) RHS written once for each of the ``rows_updated``
    rows.  On one chip both row counts are n_rows + n_cols; a chip of
    a row-sharded mesh updates its share and reads whole fixed
    factors."""
    obs = 2.0 * nnz * (I32 + F32 + F32)
    fixed = rows_fixed * k * F32
    out = rows_updated * (k * k + k) * F32
    return obs + fixed + out


def solve_flops(n_rows: int, n_cols: int, k: int) -> float:
    return (n_rows + n_cols) * (k ** 3 / 3.0 + 3.0 * k * k)


def sweep_flops(n_rows: int, n_cols: int, k: int, nnz: int,
                n_test: int) -> float:
    """Useful FLOP of one sweep, as the module docstring counts them."""
    hyper = 2.0 * (n_rows + n_cols) * k * k
    noise = 2.0 * k * nnz
    test = 2.0 * k * n_test
    return (gram_flops(nnz, k) + solve_flops(n_rows, n_cols, k)
            + hyper + noise + test)


def topk_flops(batch: int, samples: int, items: int, k: int) -> float:
    """Scoring B users against S x N items: the K-long dot (2K), then
    the mean and second-moment sums and the exclusion (3)."""
    return float(batch) * samples * items * (2.0 * k + 3.0)


def topk_bytes(batch: int, samples: int, items: int, k: int,
               top: int) -> float:
    """The item stack read once per call, the users' sampled rows, the
    exclusion mask, and the answers (id, mean, std per slot)."""
    stack = float(samples) * items * k * F32
    users = float(batch) * samples * k * F32
    mask = float(batch) * items * F32
    answers = float(batch) * top * (I32 + F32 + F32)
    return stack + users + mask + answers


def least_time(flops: float, nbytes: float, peaks) -> float:
    """The roofline's least time: the larger of the compute and the
    memory bound."""
    return max(flops / peaks.flops, nbytes / peaks.hbm_bw)
