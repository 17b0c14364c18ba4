"""Fused masked batched Gram Pallas-TPU kernel.

The compute hot-spot of SMURFF (paper section 3) is the per-row Gibbs
update: for every row of the factor being updated, accumulate

    gram[r] = sum_t mask[r,t] * v[r,t,:] v[r,t,:]^T      (K x K)
    rhs[r]  = sum_t mask[r,t] * val[r,t] * v[r,t,:]      (K,)

over that row's nonzeros.  The CPU original does this with an irregular
OpenMP loop + Eigen rank-1 updates.  On TPU we pad rows to a common
``max_nnz`` (see ``core/sparse.py``) and compute *both* reductions in a
single fused pass, tiled so VMEM only ever holds a
``(row_block, nnz_block, K)`` slab of gathered vectors:

  grid = (rows / BR, nnz / BT); the nnz axis is the *minor* (fastest
  varying) grid dimension so the output block for a given row tile stays
  resident in VMEM while we accumulate over nnz tiles (revisiting
  pattern), giving fp32 accumulation without HBM round-trips.

The MXU does the heavy lifting: the (BR, BT, K) x (BR, BT, K) batched
outer-product reduction lowers to a dot_general with K x K output per
row, which is MXU-shaped when K is a multiple of 128.

Contract-checked: the ``@pl.when(t == 0)`` init / ``t != 0``
accumulate discipline below, the block bounds, fp32 accumulation, and
the VMEM budget are statically verified over the ``ops.KERNELS``
probe envelope by ``repro.analysis.kernelcheck`` (CI ``--kernels``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _gram_kernel(vg_ref, val_ref, mask_ref, gram_ref, rhs_ref):
    t = pl.program_id(1)

    vg = vg_ref[...].astype(jnp.float32)      # (BR, BT, K)
    m = mask_ref[...].astype(jnp.float32)     # (BR, BT)
    w = val_ref[...].astype(jnp.float32) * m  # (BR, BT)

    vm = vg * m[..., None]
    # batched rank-BT update: (BR, K, BT) @ (BR, BT, K) -> (BR, K, K)
    g = jax.lax.dot_general(
        vm, vg,
        dimension_numbers=(((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    # (BR, 1, BT) @ (BR, BT, K) -> (BR, K).  Mosaic lowers a batched
    # matmul with a unit M dim; the (BR, BT) x (BR, BT, K) contraction
    # over a non-minor rhs dim is refused by the TPU compiler.
    b = jax.lax.dot_general(
        w[:, None, :], vg,
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)[:, 0, :]

    @pl.when(t == 0)
    def _init():
        gram_ref[...] = g
        rhs_ref[...] = b

    @pl.when(t != 0)
    def _acc():
        gram_ref[...] += g
        rhs_ref[...] += b


@functools.partial(
    jax.jit, static_argnames=("block_rows", "block_nnz", "interpret"))
def gram_pallas(vg: jnp.ndarray, val: jnp.ndarray, mask: jnp.ndarray,
                *, block_rows: int = 8, block_nnz: int = 128,
                interpret: bool = False):
    """Fused masked Gram: see module docstring.

    vg (R, T, K), val (R, T), mask (R, T)  ->  gram (R, K, K), rhs (R, K).
    R must be divisible by block_rows and T by block_nnz (callers pad;
    padded entries carry mask 0 so they are exact no-ops).
    """
    R, T, K = vg.shape
    br = min(block_rows, R)
    bt = min(block_nnz, T)
    if R % br or T % bt:
        raise ValueError(f"({R},{T}) not divisible by blocks ({br},{bt})")
    grid = (R // br, T // bt)

    return pl.pallas_call(
        _gram_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, bt, K), lambda r, t: (r, t, 0)),
            pl.BlockSpec((br, bt), lambda r, t: (r, t)),
            pl.BlockSpec((br, bt), lambda r, t: (r, t)),
        ],
        out_specs=[
            pl.BlockSpec((br, K, K), lambda r, t: (r, 0, 0)),
            pl.BlockSpec((br, K), lambda r, t: (r, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, K, K), jnp.float32),
            jax.ShapeDtypeStruct((R, K), jnp.float32),
        ],
        interpret=interpret,
    )(vg, val, mask)
