"""Data pipeline: synthetic ChEMBL-like MF data + LM token streams.

The paper benchmarks on a ChEMBL IC50 extraction (compounds x proteins,
~1M x thousands, very sparse, ECFP fingerprints as side info).  Offline
we generate a statistically similar planted-low-rank matrix: power-law
row occupancy (compounds tested against few targets), binary sparse
fingerprints correlated with the latent factors so the Macau lift is
actually measurable.

The LM side is an infinite deterministic token stream (seeded,
restartable from any step index — checkpoint/resume does not need to
save data-pipeline state, just the step).
"""
from __future__ import annotations

from typing import Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.sparse import SparseMatrix, from_coo


def chembl_like(seed: int, n_compounds: int = 2000, n_proteins: int = 200,
                density: float = 0.02, rank: int = 16,
                noise: float = 0.4, n_features: int = 128,
                feature_noise: float = 0.5,
                ) -> Tuple[SparseMatrix, Tuple, np.ndarray]:
    """Synthetic compound-activity data.

    Returns (train SparseMatrix, (i,j,v) test triplets, fingerprints F).
    Row occupancy is power-law (like real assay data); fingerprints are
    binarized projections of the true compound factors.
    """
    rng = np.random.default_rng(seed)
    U = rng.normal(size=(n_compounds, rank)).astype(np.float32)
    V = rng.normal(size=(n_proteins, rank)).astype(np.float32)

    # power-law tests-per-compound
    w = (1.0 / np.arange(1, n_compounds + 1) ** 0.7)
    w = w[rng.permutation(n_compounds)]
    p_row = w / w.sum()
    nnz = int(density * n_compounds * n_proteins)
    i = rng.choice(n_compounds, size=3 * nnz, p=p_row)
    j = rng.integers(0, n_proteins, size=3 * nnz)
    ij = np.unique(np.stack([i, j], 1), axis=0)
    ij = ij[rng.permutation(len(ij))[:nnz]]
    i, j = ij[:, 0], ij[:, 1]
    v = np.einsum("ek,ek->e", U[i], V[j]) + noise * rng.normal(
        size=len(i)).astype(np.float32)

    # ECFP-like binary fingerprints correlated with the latent factors
    proj = rng.normal(size=(rank, n_features)).astype(np.float32)
    F = (U @ proj + feature_noise * rng.normal(
        size=(n_compounds, n_features)) > 0).astype(np.float32)

    n_test = max(1, nnz // 10)
    test = (i[:n_test], j[:n_test], v[:n_test].astype(np.float32))
    tr = slice(n_test, None)
    mat = from_coo(i[tr], j[tr], v[tr].astype(np.float32),
                   (n_compounds, n_proteins))
    return mat, test, F


def fixed_degree(seed: int, n_rows: int, n_cols: int, nnz_per_row: int,
                 n_test_per_row: int = 8, rank: int = 8,
                 noise: float = 0.3
                 ) -> Tuple[SparseMatrix, Tuple, np.ndarray]:
    """Planted low-rank data with exactly ``nnz_per_row`` observations
    per row.

    The columns split into ``nnz_per_row`` equal stripes and each row
    observes one random column of every stripe.  Column degrees are
    then binomial around ``n_rows * nnz_per_row / n_cols``, so neither
    padded orientation is much wider than the mean degree.  (The
    power-law rows of :func:`chembl_like` give the busiest row nearly
    every column at scale, and padded CSR pads every row to it.)  Each
    row also holds out ``n_test_per_row`` entries, in distinct stripes,
    at columns it does not observe.  Values are a rank-``rank`` product
    of unit variance plus Gaussian noise of std ``noise``.

    Returns (train SparseMatrix, (i, j, v) test triplets,
    (n_rows, nnz_per_row) observed column ids per row).
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
    mat = from_coo(i, j, observe(i, j), (n_rows, n_cols))
    ti = np.repeat(np.arange(n_rows), n_test_per_row)
    tj = test_cols.ravel()
    return mat, (ti, tj, observe(ti, tj)), cols


class TokenStream:
    """Deterministic, seekable synthetic token stream for LM training.

    Markov-chain-ish tokens so the loss actually decreases (the model
    can learn bigram structure) — a pure-uniform stream would give a
    flat loss and hide training bugs.
    """

    def __init__(self, vocab_size: int, seed: int = 0,
                 n_states: int = 64):
        self.vocab = vocab_size
        self.seed = seed
        rng = np.random.default_rng(seed)
        # sparse-ish bigram transition structure over a state space
        self._succ = rng.integers(0, vocab_size,
                                  size=(n_states, 8)).astype(np.int32)
        self.n_states = n_states

    def batch(self, step: int, batch: int, seq: int) -> np.ndarray:
        rng = np.random.default_rng((self.seed, step))
        state = rng.integers(0, self.n_states, size=(batch,))
        out = np.empty((batch, seq + 1), np.int32)
        for t in range(seq + 1):
            choice = rng.integers(0, 8, size=(batch,))
            tok = self._succ[state, choice]
            out[:, t] = tok
            state = tok % self.n_states
        return out


def make_lm_batch(stream: TokenStream, step: int, batch: int, seq: int,
                  frontend_tokens: int = 0, d_model: int = 0,
                  enc_frames: int = 0) -> Dict[str, jnp.ndarray]:
    """One training batch: tokens/labels (+ stub modality embeddings)."""
    toks = stream.batch(step, batch, seq)
    out: Dict[str, jnp.ndarray] = {
        "tokens": jnp.asarray(toks[:, :-1]),
        "labels": jnp.asarray(toks[:, 1:]),
    }
    if frontend_tokens:
        rng = np.random.default_rng((stream.seed, step, 7))
        out["frontend"] = jnp.asarray(
            rng.normal(size=(batch, frontend_tokens, d_model))
            .astype(np.float32))
    if enc_frames:
        rng = np.random.default_rng((stream.seed, step, 11))
        out["enc_frames"] = jnp.asarray(
            rng.normal(size=(batch, enc_frames, d_model))
            .astype(np.float32))
    return out


def lm_batches(stream: TokenStream, start_step: int, batch: int,
               seq: int, **kw) -> Iterator[Dict[str, jnp.ndarray]]:
    step = start_step
    while True:
        yield make_lm_batch(stream, step, batch, seq, **kw)
        step += 1
