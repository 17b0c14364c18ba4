"""Fused posterior-scoring + top-K Pallas-TPU kernel.

The serving hot path (ROADMAP "recommendations as a service"; the
compound-activity prediction-at-scale story of arXiv:1904.02514 §1)
scores one user row against ALL items across ALL retained posterior
samples and keeps only the K best:

    score[s, n] = u_s . V_s[n]            per sample s, item n
    mean[n]     = 1/S sum_s score[s, n]   posterior mean
    ex2[n]      = 1/S sum_s score[s, n]^2
    std[n]      = sqrt(max(ex2 - mean^2, 0))   posterior uncertainty

A naive implementation materializes the (S, n_items) score matrix per
request — at catalogue scale (millions of items, ~100 samples) that is
hundreds of MB of HBM traffic per user.  This kernel tiles the item
axis and fuses the three stages, so only a (S, BN) score *tile* ever
exists in VMEM:

  grid = (B users, n_items / BN); the item axis is the minor (fastest
  varying) dimension so each user's running top-K state stays resident
  in VMEM while item tiles stream through (revisiting pattern).  Per
  tile the VPU computes the scores and their sample moments with
  ``ref.score_moments`` — ordered f32 multiply-adds, the float program
  the reference runs, since XLA and the TPU kernel compiler accumulate
  a dot in different orders — and a k-step selection loop merges the
  tile's means into the running top-K (ids, mean, ex2, masked ranking
  score).  ``ops.topk_score`` converts the selected ex2 to the
  posterior std AFTER the kernel, with the same (B, k)-shaped float
  program the reference path uses — shape-dependent FMA fusion of
  ``ex2 - mean*mean`` is what broke bitwise equality when each path
  finalized its own std (measured: 1-ulp drift).

Tie-breaking contract: equal posterior means rank by LOWEST item id —
the selection takes the lowest position among equal maxima and the
running top-K stores candidates in (rank desc, id asc) order, so the
merge reproduces the stable ``jnp.argsort`` reference
(``ref.topk_score_ref``) bitwise in fp32, asserted in
tests/test_kernels.py and, on the chip, by ``chip_smoke.py``.

Excluded items (already-observed entries a request does not want
re-recommended) enter the ranking at -inf but keep their true
mean/ex2; slots beyond the number of rankable items are masked at the
``ops.topk_score`` level, identically for kernel and reference.

Contract-checked: the item-axis revisit-accumulate discipline (all
four outputs init under ``@pl.when(t == 0)`` before any merge read),
bounds over the shared ``ops.pad_to_blocks`` padding, fp32/i32 state
dtypes, and the VMEM budget of the serving envelope are statically
verified over the ``ops.KERNELS`` probes by
``repro.analysis.kernelcheck``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import score_moments


def _topk_kernel(us_ref, v_ref, excl_ref, ids_ref, mean_ref, ex2_ref,
                 rank_ref, *, k: int, block_items: int,
                 n_samples: int):
    t = pl.program_id(1)
    kp = ids_ref.shape[-1]

    @pl.when(t == 0)
    def _init():
        ids_ref[...] = jnp.full((1, 1, kp), -1, jnp.int32)
        mean_ref[...] = jnp.zeros((1, 1, kp), jnp.float32)
        rank_ref[...] = jnp.full((1, 1, kp), -jnp.inf, jnp.float32)
        ex2_ref[...] = jnp.zeros((1, 1, kp), jnp.float32)

    us = us_ref[0]                         # (S, K)
    v = v_ref[...]                         # (S, BN, K)
    excl = excl_ref[0]                     # (1, BN)
    mean_t, ex2_t = score_moments(us, v, n_samples)   # (1, BN) each
    rank_t = jnp.where(excl > 0, -jnp.inf, mean_t)

    # merge the tile into the running top-K.  Every vector stays 2-D
    # with items on the lane axis (the layout the TPU compiler tiles);
    # the running state comes FIRST so the lowest-position tie-break
    # keeps the lowest item id (state entries always carry lower ids
    # than this tile's, and are themselves in (rank desc, id asc)
    # order).
    state_rank = rank_ref[0]
    state_mean = mean_ref[0]
    state_ex2 = ex2_ref[0]
    state_ids = ids_ref[0]
    tile_ids = t * block_items + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_items), 1)
    cand_rank = jnp.concatenate([state_rank, rank_t], axis=1)
    cand_mean = jnp.concatenate([state_mean, mean_t], axis=1)
    cand_ex2 = jnp.concatenate([state_ex2, ex2_t], axis=1)
    cand_ids = jnp.concatenate([state_ids, tile_ids], axis=1)
    n_cand = kp + block_items
    pos_iota = jax.lax.broadcasted_iota(jnp.int32, (1, n_cand), 1)
    slot_iota = jax.lax.broadcasted_iota(jnp.int32, (1, kp), 1)

    def select(i, carry):
        cand, o_rank, o_mean, o_ex2, o_ids = carry
        best = jnp.max(cand)
        pos = jnp.min(jnp.where(cand == best, pos_iota, n_cand))
        hot = pos_iota == pos
        at = slot_iota == i
        o_rank = jnp.where(at, best, o_rank)
        o_mean = jnp.where(
            at, jnp.max(jnp.where(hot, cand_mean, -jnp.inf)), o_mean)
        o_ex2 = jnp.where(
            at, jnp.max(jnp.where(hot, cand_ex2, -jnp.inf)), o_ex2)
        o_ids = jnp.where(at, jnp.max(jnp.where(hot, cand_ids, -1)),
                          o_ids)
        return (jnp.where(hot, -jnp.inf, cand), o_rank, o_mean, o_ex2,
                o_ids)

    _, sel_rank, sel_mean, sel_ex2, sel_ids = jax.lax.fori_loop(
        0, k, select,
        (cand_rank, state_rank, state_mean, state_ex2, state_ids))
    rank_ref[0] = sel_rank
    mean_ref[0] = sel_mean
    ex2_ref[0] = sel_ex2
    ids_ref[0] = sel_ids


@functools.partial(
    jax.jit, static_argnames=("k", "block_items", "interpret"))
def topk_score_pallas(us: jnp.ndarray, v: jnp.ndarray,
                      excl: jnp.ndarray, *, k: int,
                      block_items: int = 256,
                      interpret: bool = False):
    """Fused scoring + top-K: see module docstring.

    us (B, S, K) user latent rows per sample, v (S, N, K) item factor
    stack, excl (B, N) 1.0 = excluded from ranking  ->
    ids (B, k) i32, mean (B, k) f32, ex2 (B, k) f32, rank (B, k) f32
    (the masked selection scores; callers discard them).  N must be
    divisible by ``block_items`` (callers pad; padded items carry
    excl 1.0).

    Per-user vectors travel as (B, 1, n) so each block's last two dims
    are (1, n): a unit sublane dim equal to the array's, and a lane dim
    that is the full width or a multiple of 128, as the TPU compiler
    requires.  The running state is ``k`` rounded up to 128 lanes.
    """
    B, S, K = us.shape
    S2, N, K2 = v.shape
    if (S, K) != (S2, K2):
        raise ValueError(f"us {us.shape} vs v {v.shape} mismatch")
    bn = min(block_items, N)
    if N % bn:
        raise ValueError(f"n_items {N} not divisible by tile {bn}")
    n_tiles = N // bn
    kp = -(-k // 128) * 128
    kern = functools.partial(_topk_kernel, k=k, block_items=bn,
                             n_samples=S)
    state = pl.BlockSpec((1, 1, kp), lambda b, t: (b, 0, 0))

    outs = pl.pallas_call(
        kern,
        grid=(B, n_tiles),
        in_specs=[
            pl.BlockSpec((1, S, K), lambda b, t: (b, 0, 0)),
            pl.BlockSpec((S, bn, K), lambda b, t: (0, t, 0)),
            pl.BlockSpec((1, 1, bn), lambda b, t: (b, 0, t)),
        ],
        out_specs=[state, state, state, state],
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, kp), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, kp), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, kp), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, kp), jnp.float32),
        ],
        interpret=interpret,
    )(us, v, excl[:, None, :])
    return tuple(o[:, 0, :k] for o in outs)
