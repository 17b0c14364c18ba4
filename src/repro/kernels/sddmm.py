"""Gathered-operand SDDMM Pallas-TPU kernel.

pred[e] = ug[e] . vg[e] over the observed/test entries — used by the
RMSE evaluation, the adaptive-noise residual, and the probit latent
augmentation (paper Algorithm 1 "for all test points").

The gather U[i[e]], V[j[e]] happens outside the kernel (XLA gather is
efficient and Pallas-TPU dynamic gathers are not); the kernel fuses the
elementwise product + K-reduction with explicit VMEM tiling so the
(E, K) operand slabs stream through VMEM once.

Contract-checked: the K-axis revisit-accumulate discipline, bounds,
fp32 accumulation, and VMEM budget are statically verified over the
``ops.KERNELS`` probe envelope by ``repro.analysis.kernelcheck``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _sddmm_kernel(ug_ref, vg_ref, out_ref):
    k = pl.program_id(1)
    u = ug_ref[...].astype(jnp.float32)   # (BE, BK)
    v = vg_ref[...].astype(jnp.float32)   # (BE, BK)
    part = jnp.sum(u * v, axis=-1)        # (BE,)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = part

    @pl.when(k != 0)
    def _acc():
        out_ref[...] += part


@functools.partial(
    jax.jit, static_argnames=("block_e", "block_k", "interpret"))
def sddmm_pallas(ug: jnp.ndarray, vg: jnp.ndarray, *,
                 block_e: int = 1024, block_k: int = 128,
                 interpret: bool = False) -> jnp.ndarray:
    """pred (E,) = rowwise dot of ug (E, K) and vg (E, K).

    The 1-D output block must match XLA's TPU layout of a 1-D f32
    array, which tiles it 1024 elements at a time: a 512-element block
    is refused by the TPU compiler.
    """
    E, K = ug.shape
    be = min(block_e, E)
    bk = min(block_k, K)
    if E % be or K % bk:
        raise ValueError(f"({E},{K}) not divisible by blocks ({be},{bk})")
    grid = (E // be, K // bk)

    return pl.pallas_call(
        _sddmm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((be, bk), lambda e, k: (e, k)),
            pl.BlockSpec((be, bk), lambda e, k: (e, k)),
        ],
        out_specs=pl.BlockSpec((be,), lambda e, k: (e,)),
        out_shape=jax.ShapeDtypeStruct((E,), jnp.float32),
        interpret=interpret,
    )(ug, vg)
