"""Lane-batched Cholesky draw Pallas-TPU kernel.

The conditional draw of a factor's rows (paper Algorithm 1, "update
model") is, per row n with precision Lambda_n and right-hand side b_n,

    Lambda_n = R_n^T R_n                       (Cholesky, R upper)
    u_n      = Lambda_n^{-1} b_n + R_n^{-1} z_n,   z_n ~ N(0, I)

XLA's TPU Cholesky and triangular inverse handle a batch of (K, K)
matrices one matrix at a time.  This kernel puts the batch on the
lanes instead: a block of 128 rows is held in VMEM as
``prec[i, j, n]`` (K, K, 128), so each (8, 128) vreg holds eight
entries of one matrix row for 128 matrices, and every step of the
column loop is dense elementwise VPU work with sublane broadcasts — no
cross-lane reduction, no MXU, all in float32.

  grid = (N / 128, K / W) with W = min(K, 128) Gram columns a step; per
  block of 128 rows:

  0. relayout: the Gram rows arrive as they lie in HBM, row i of all
     128 matrices one sublane-strided load apart; each (128, W) slice
     plus lam's row goes batch-last through one transpose.  Wider K
     takes its columns over the minor grid axis;
  1. at the last column step, a right-looking Cholesky in place: for
     column k, row k of R is ``prec[k] / sqrt(prec[k, k])`` and the
     trailing rows i > k take the rank-1 update
     ``prec[i, j] -= R[k, i] R[k, j]`` on their upper (8-sublane)
     chunks ``j >= 8 (i // 8)``, eight rows at a time;
  2. the forward solve R^T y = b rides along column by column;
  3. one back solve R u = y + z gives mean + noise together
     (R^{-1} R^{-T} b + R^{-1} z).

Only u reaches HBM: the precisions, the factor and every intermediate
solve stay in VMEM.  A non-positive-definite matrix gives NaN in its
own lane (the square root of a negative pivot) and nothing is clamped,
as ``lax.linalg.cholesky`` gives NaN.

Contract-checked: the column axis's first-visit relayout before the
last visit's reads, block bounds over the shared ``ops.pad_to_blocks``
padding and the VMEM budget are statically verified over the
``ops.KERNELS`` probes by ``repro.analysis.kernelcheck``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# matrices per block: the lane width
LANES = 128


def _chol_draw_kernel(gram_ref, lam_ref, b_ref, z_ref, u_ref, prec_ref):
    """gram_ref (L * Kp, W): columns [c W, (c + 1) W) of the block's
    Gram rows, row n * Kp + i holding gram_n[i, :]; lam_ref (Kp, W) the
    same columns of lam, added to each; b_ref, z_ref, u_ref (Kp, L);
    prec_ref (Kp, Kp, L) scratch: the precisions batch-last, then R's
    rows (zero below the diagonal)."""
    kp, lanes = b_ref.shape
    width = gram_ref.shape[1]
    c = pl.program_id(1)
    last = pl.num_programs(1) - 1

    def batch_last(i):
        """This step's columns of row i of all L precisions, as (W, L)."""
        rows = gram_ref[pl.ds(i, lanes, stride=kp), :]
        return (rows + lam_ref[pl.ds(i, 1), :]).T

    @pl.when(c == 0)
    def _first_columns():
        def row(i, carry):
            prec_ref[i, pl.ds(0, width), :] = batch_last(i)
            return carry

        jax.lax.fori_loop(0, kp, row, 0)

    if kp > width:
        @pl.when(c != 0)
        def _more_columns():
            col0 = pl.multiple_of(c * width, width)

            def row(i, carry):
                prec_ref[i, pl.ds(col0, width), :] = batch_last(i)
                return carry

            jax.lax.fori_loop(0, kp, row, 0)

    @pl.when(c == last)
    def _draw():
        n_chunks = kp // 8
        sub = jax.lax.broadcasted_iota(jnp.int32, (kp, lanes), 0)
        sub8 = jax.lax.broadcasted_iota(jnp.int32, (8, lanes), 0)

        def pick(x, k):
            """Row k of a (Kp, L) slab as (1, L)."""
            return jnp.sum(jnp.where(sub == k, x, 0.0), axis=0,
                           keepdims=True)

        def column(k, y):
            row = prec_ref[k]
            inv = 1.0 / jnp.sqrt(pick(row, k))
            r = row * inv
            prec_ref[k] = jnp.where(sub >= k, r, 0.0)
            yk = pick(y, k) * inv
            y = jnp.where(sub == k, yk,
                          y - jnp.where(sub > k, r, 0.0) * yk)

            def right_of_pivot(off):
                """R[k, off:off + 8], zero at and left of the pivot."""
                x = prec_ref[k, pl.ds(off, 8), :]
                return jnp.where(sub8 + off > k, x, 0.0)

            # rows i <= k of the first block (row k among them) take
            # R[k, i] = 0: exact no-ops
            def rows8(blk, carry):
                base = pl.multiple_of(blk * 8, 8)
                rb = right_of_pivot(base)
                ri = jnp.stack([jnp.broadcast_to(rb[s:s + 1, :],
                                                 (8, lanes))
                                for s in range(8)])

                def chunk(ch, carry):
                    off = pl.multiple_of(ch * 8, 8)
                    prec_ref[pl.ds(base, 8), pl.ds(off, 8), :] -= \
                        ri * right_of_pivot(off)
                    return carry

                return jax.lax.fori_loop(blk, n_chunks, chunk, carry)

            jax.lax.fori_loop((k + 1) // 8, n_chunks, rows8, 0)
            return y

        y = jax.lax.fori_loop(0, kp, column, b_ref[...])
        rhs = y + z_ref[...]

        def back(t, u):
            k = kp - 1 - t
            row = prec_ref[k]
            acc = jnp.sum(row * u, axis=0, keepdims=True)
            uk = (pick(rhs, k) - acc) / pick(row, k)
            return jnp.where(sub == k, uk, u)

        u_ref[...] = jax.lax.fori_loop(0, kp, back, jnp.zeros_like(rhs))


def chol_draw_pallas(gram: jnp.ndarray, lam: jnp.ndarray, b: jnp.ndarray,
                     z: jnp.ndarray, *, interpret: bool = False
                     ) -> jnp.ndarray:
    """gram (N * Kp, Kp) Gram rows, row n * Kp + i holding gram_n[i, :]
    (a free reshape of (N, Kp, Kp) when Kp is a multiple of 8), lam
    (Kp, Kp) added to every matrix, b and z (Kp, N) -> u (Kp, N).  Kp
    must be a multiple of 8 up to 128 and of 128 above, N a multiple of
    128 (callers pad: zero Gram rows over a positive definite lam,
    identity in lam's padded diagonal, zero b and z are exact
    no-ops)."""
    kp, n = b.shape
    width = min(kp, LANES)
    if kp % 8 or kp % width or n % LANES:
        raise ValueError(f"({kp}, {n}) not divisible by "
                         f"({8 if kp <= LANES else LANES}, {LANES})")
    f32 = 4
    # the Gram and lam columns double-buffered, the precisions, b, z, u
    vmem = (2 * (LANES + 1) * kp * width + kp * kp * LANES
            + 6 * kp * LANES) * f32 + (4 << 20)
    return pl.pallas_call(
        _chol_draw_kernel,
        grid=(n // LANES, kp // width),
        in_specs=[
            pl.BlockSpec((LANES * kp, width), lambda i, c: (i, c)),
            pl.BlockSpec((kp, width), lambda i, c: (0, c)),
            pl.BlockSpec((kp, LANES), lambda i, c: (0, i)),
            pl.BlockSpec((kp, LANES), lambda i, c: (0, i)),
        ],
        out_specs=pl.BlockSpec((kp, LANES), lambda i, c: (0, i)),
        out_shape=jax.ShapeDtypeStruct((kp, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((kp, kp, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=interpret,
        name="chol_draw",
    )(gram, lam, b, z)
