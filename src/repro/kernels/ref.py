"""Pure-jnp oracles for the Pallas kernels.

These are the ground truth the kernels are validated against
(``tests/test_kernels.py`` sweeps shapes/dtypes in interpret mode) and
the paper-faithful baseline implementation used when
``use_pallas=False`` (the XLA path — analogous to SMURFF's plain
Eigen/MKL GEMM path).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.lax.linalg import cholesky, triangular_solve


def gram_ref(vg: jnp.ndarray, val: jnp.ndarray, mask: jnp.ndarray):
    """Masked batched Gram + rhs — the SMURFF per-row hot loop.

    For each row r (paper Algorithm 1 inner loop):
        gram[r] = sum_t mask[r,t] * vg[r,t,:] vg[r,t,:]^T     (K x K)
        rhs[r]  = sum_t mask[r,t] * val[r,t] * vg[r,t,:]      (K,)

    Args:
      vg:   (R, T, K) gathered latent vectors of the *fixed* factor.
      val:  (R, T) observed ratings (0 where padded).
      mask: (R, T) 1.0 for real entries, 0.0 for padding.

    Returns:
      gram (R, K, K) f32, rhs (R, K) f32.
    """
    if vg.dtype == jnp.bfloat16:
        # bf16 gathered operands (ModelDef.bf16_gather): keep every
        # pre-contraction op in bf16 — an f32 upcast here would let
        # XLA's simplifier fold it into the pre-gather cast and move
        # the (all-)gather back to f32 (measured).  The MXU/dot
        # accumulates in f32 via preferred_element_type.
        m = mask.astype(jnp.bfloat16)
        w = (val * mask).astype(jnp.bfloat16)
        gram = jnp.einsum("rtk,rtl->rkl", vg * m[..., None], vg,
                          preferred_element_type=jnp.float32)
        rhs = jnp.einsum("rtk,rt->rk", vg, w,
                         preferred_element_type=jnp.float32)
        return gram, rhs
    vg = vg.astype(jnp.float32)
    w = (val * mask).astype(jnp.float32)
    m = mask.astype(jnp.float32)
    gram = jnp.einsum("rtk,rtl->rkl", vg * m[..., None], vg)
    rhs = jnp.einsum("rtk,rt->rk", vg, w)
    return gram, rhs


def chol_draw_ref(gram_rows: jnp.ndarray, lam: jnp.ndarray,
                  b: jnp.ndarray, z: jnp.ndarray) -> jnp.ndarray:
    """The conditional draw of a block of factor rows, through
    ``lax.linalg``: the oracle of ``kernels/chol_draw.py`` and the path
    every backend but the TPU takes.

    gram_rows (N, K, K), lam (K, K) added to every row, b and z (N, K)
    -> u (N, K): u_n = Lambda_n^{-1} b_n + L_n^{-T} z_n with
    Lambda_n = gram_rows_n + lam = L_n L_n^T.
    """
    L = cholesky(gram_rows + lam[None, :, :])                # (N,K,K)
    y = triangular_solve(L, b[..., None], left_side=True, lower=True)
    mean = triangular_solve(L, y, left_side=True, lower=True,
                            transpose_a=True)[..., 0]
    dz = triangular_solve(L, z[..., None], left_side=True, lower=True,
                          transpose_a=True)[..., 0]
    return mean + dz


def score_moments(u: jnp.ndarray, v: jnp.ndarray, n_samples: int):
    """Posterior mean and E[score^2] of items against one user.

    u (S, K) user rows, v (S, N, K) items -> mean (1, N), ex2 (1, N).
    Every sum runs as explicit float32 multiplies and adds in a fixed
    order (over K, then over samples), never as a dot or a reduction
    whose order a compiler picks: XLA and the TPU kernel compiler
    accumulate dots differently, and this is what lets the
    ``topk_score`` kernel, which calls this on each item tile, agree
    with the reference bitwise on every backend.  bf16 operands are
    widened first, so their products are exact.
    """
    u = u.astype(jnp.float32)
    vt = jnp.swapaxes(v.astype(jnp.float32), 1, 2)      # (S, K, N)
    scores = u[:, 0:1] * vt[:, 0, :]
    for k in range(1, u.shape[1]):
        scores = scores + u[:, k:k + 1] * vt[:, k, :]   # (S, N)
    first = scores[0:1]
    mean, ex2 = first, first * first
    for s in range(1, n_samples):
        row = scores[s:s + 1]
        mean = mean + row
        ex2 = ex2 + row * row
    inv_s = jnp.float32(1.0) / jnp.float32(n_samples)
    return mean * inv_s, ex2 * inv_s


def topk_score_ref(us: jnp.ndarray, v: jnp.ndarray,
                   excl: jnp.ndarray, k: int):
    """Posterior scoring + stable top-K — the serving oracle.

    For each user b (scored against every item across every retained
    posterior sample):
        score[s, n] = us[b, s] . v[s, n]
        mean[n]     = 1/S sum_s score[s, n]
        std[n]      = sqrt(max(E[score^2] - mean^2, 0))
    ranked by mean with excluded items at -inf; ties broken by LOWEST
    item id (stable argsort).

    Users are scored through ``lax.map`` — one identical float program
    per user regardless of batch size — so a batched call is bitwise
    equal to B single-user calls.  This is the contract that lets
    ``RecommendServer`` batch concurrent requests without changing any
    individual answer (asserted in tests/test_serving.py); the Pallas
    kernel preserves it by scoring each user in its own grid row.

    Args:
      us:   (B, S, K) user latent rows, one per posterior sample.
      v:    (S, N, K) item factor stack.
      excl: (B, N) 1.0 = excluded from the ranking.
      k:    static top-K (callers clamp to k <= N).

    Returns:
      ids (B, k) i32, mean (B, k) f32, ex2 (B, k) f32.  The std is
      finalized by ``ops.topk_score`` from (mean, ex2) with one shared
      (B, k) float program for both paths (see kernels/topk_score.py
      on why per-path finalization broke bitwise equality).
    """
    S = v.shape[0]

    def one_user(args):
        u, ex = args                              # (S, K), (N,)
        mean, ex2 = score_moments(u, v, S)
        mean, ex2 = mean[0], ex2[0]
        rank = jnp.where(ex > 0, -jnp.inf, mean)
        order = jnp.argsort(-rank)[:k].astype(jnp.int32)  # stable
        return order, mean[order], ex2[order]

    return jax.lax.map(one_user, (us, excl))


def attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                  causal: bool = True, window: int = 0,
                  q_offset: int = 0) -> jnp.ndarray:
    """Plain-softmax attention oracle for ``flash_fwd_pallas``.

    Materializes the full (Sq, Sk) score matrix in f32 — exactly what
    the flash kernel exists to avoid — and applies the same
    position-based masking: query position ``q_offset + row``, causal
    ``kpos <= qpos``, optional sliding window ``kpos > qpos - window``.
    GQA (H a multiple of KVH) repeats each kv head over its G query
    heads.  Rows with every key masked out return 0, matching the
    kernel's ``l == 0`` guard.

    Args:
      q: (B, Sq, H, hd); k/v: (B, Sk, KVH, hd).

    Returns:
      (B, Sq, H, hd) in q's dtype.
    """
    B, Sq, H, hd = q.shape
    Sk, KVH = k.shape[1], k.shape[2]
    G = H // KVH
    qf = q.astype(jnp.float32)
    kf = jnp.repeat(k.astype(jnp.float32), G, axis=2)
    vf = jnp.repeat(v.astype(jnp.float32), G, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) / jnp.sqrt(
        jnp.float32(hd))
    if causal:
        qpos = q_offset + jnp.arange(Sq)[:, None]
        kpos = jnp.arange(Sk)[None, :]
        ok = kpos <= qpos
        if window > 0:
            ok &= kpos > qpos - window
        s = jnp.where(ok[None, None], s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0))
    l = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.where(l == 0.0, 1.0, l)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, vf)
    return out.astype(q.dtype)


def sddmm_ref(ug: jnp.ndarray, vg: jnp.ndarray) -> jnp.ndarray:
    """Gathered-operand SDDMM: pred[e] = ug[e] . vg[e].

    Args:
      ug: (E, K) U rows gathered at the observed entries.
      vg: (E, K) V rows gathered at the observed entries.

    Returns:
      (E,) f32 predictions.
    """
    if ug.dtype == jnp.bfloat16 and vg.dtype == jnp.bfloat16:
        return jnp.einsum("ek,ek->e", ug, vg,
                          preferred_element_type=jnp.float32)
    return jnp.einsum(
        "ek,ek->e", ug.astype(jnp.float32), vg.astype(jnp.float32))
