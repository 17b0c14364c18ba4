"""Plain reference of Macau: BMF whose compound rows have a prior mean
moved by side information through a link matrix beta.

Written from the model (Simm et al., arXiv:1509.04610; SMURFF,
arXiv:1904.02514, Table 1 col. 2), in float32 at the highest matmul
precision, importing nothing of the program.  It loads ``bmf.py`` beside
it by path for the pieces the two models share (the Normal-Wishart draw,
the protein factor's draw, the noise, the observations' layout) and
adds the link:

    u_i ~ N(mu + beta^T f_i, Lambda^{-1}),
    beta | U ~ MN(A^{-1} F^T (U - 1 mu^T), A^{-1}, Lambda^{-1}),
    A = F^T F + lambda_beta I,
    lambda_beta ~ Gamma(1 + D K / 2, 1 + tr(beta Lambda beta^T) / 2).

The key schedule is part of the chain's definition: each entity's key
splits into (hyper, factor, block) keys as in ``bmf.sweep_keys``; the
compound's hyper key splits into (Normal-Wishart, beta, lambda_beta)
keys, the first then split by the Normal-Wishart draw itself; factor
rows draw ``fold_in(key, row)`` normals.  Each stage is computed from
the program's own output of the stage before it: the Normal-Wishart
draw on ``U - F beta_old``, beta from the program's new mu and Lambda,
lambda_beta from the program's new beta, the compound rows from the
program's new mu, Lambda and beta.

``lower=True`` computes the same in bfloat16 (every stored value
rounded, lambda_beta's draw included), ``lower="link"`` rounds only the
link: the side products (``F^T F``, ``F^T U``, ``F beta``), A's Cholesky
factor and the link's triangular solves.  Both are controls that the
comparison has to fail.
"""
from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.lax.linalg import cholesky, triangular_solve


def _load_bmf():
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "bmf.py")
    spec = importlib.util.spec_from_file_location(
        "bench_reference_bmf_of_macau", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


bmf = _load_bmf()
HIGHEST = bmf.HIGHEST
Observations = bmf.Observations
sweep_keys = bmf.sweep_keys
_rnd, _mm, _tri = bmf._rnd, bmf._mm, bmf._tri

# the link's prior on its precision: Gamma(1, 1), as the program's
A_BETA, B_BETA = 1.0, 1.0


@partial(jax.jit, static_argnames=("lower",))
def centred(side, U, beta, *, lower=False):
    """U - F beta: the factor the Normal-Wishart draw sees."""
    return U - _mm(side, beta, lower)


@partial(jax.jit, static_argnames=("lower",))
def link(key, side, U, mu, Lam, beta_prec, *, lower=False):
    """beta ~ MN(A^{-1} F^T (U - 1 mu^T), A^{-1}, Lambda^{-1}); returns
    beta, A's Cholesky factor and Lambda's."""
    D, K = side.shape[1], U.shape[1]
    FtF = _mm(side.T, side, lower)
    A = FtF + beta_prec * jnp.eye(D, dtype=jnp.float32)
    La = _rnd(cholesky(A), lower)
    FtU = _mm(side.T, U, lower) - jnp.outer(jnp.sum(side, axis=0), mu)
    mean = _tri(La, _tri(La, FtU, lower), lower, transpose=True)
    Z = jax.random.normal(key, (D, K), dtype=jnp.float32)
    Zr = _tri(La, Z, lower, transpose=True)
    Llam = cholesky(Lam)
    noise = triangular_solve(Llam, Zr.T, left_side=True, lower=True,
                             transpose_a=True).T
    return mean + noise, La, Llam


@partial(jax.jit, static_argnames=("lower",))
def link_precision(key, beta, Lam, *, lower=False):
    """lambda_beta ~ Gamma(A + D K / 2, B + tr(beta Lambda beta^T) / 2)."""
    D, K = beta.shape
    beta = _rnd(beta, lower)
    sse = _rnd(jnp.sum(_mm(beta, _rnd(Lam, lower), lower) * beta), lower)
    return _rnd(jax.random.gamma(key, A_BETA + 0.5 * D * K)
                / (B_BETA + 0.5 * sse), lower).astype(jnp.float32)


@partial(jax.jit, static_argnames=("lower",))
def prior_means(side, mu, beta, Lam, *, lower=False):
    """Each compound row's Lambda (mu + beta^T f_i), (N, K)."""
    m = mu[None, :] + _mm(side, beta, lower)
    return _mm(m, Lam, lower)


@jax.jit
def link_gap(beta_prog, beta, La, Llam):
    """The gap in beta in units of its conditional's std: La^T gap Llam,
    whose entries are standard normal draws' gaps (largest, rms)."""
    white = jnp.matmul(jnp.matmul(La.T, beta_prog - beta,
                                  precision=HIGHEST), Llam,
                       precision=HIGHEST)
    return jnp.max(jnp.abs(white)), jnp.sqrt(jnp.mean(white * white))


def _draw(key, row0, fixed, idx, val, mask, alpha, Lam, bp, lower):
    """Rows ``row0 + [0, R)`` from their conditional, with a prior mean
    (``bp``, (R, K), already multiplied by Lambda) of their own."""
    R, K = idx.shape[0], fixed.shape[1]
    vg = _rnd(fixed, lower)[idx] * mask[..., None]           # (R, T, K)
    gram = _rnd(jnp.einsum("rtk,rtl->rkl", vg, vg, precision=HIGHEST),
                lower)
    rhs = _rnd(jnp.einsum("rtk,rt->rk", vg, val * mask,
                          precision=HIGHEST), lower)
    prec = _rnd(alpha * gram + _rnd(Lam, lower)[None], lower)
    b = _rnd(alpha * rhs + bp, lower)
    rows = row0 + jnp.arange(R)
    z = jax.vmap(lambda r: jax.random.normal(
        jax.random.fold_in(key, r), (K,), jnp.float32))(rows)
    L = _rnd(cholesky(prec), lower)
    mean = _tri(L, _tri(L, b[..., None], lower), lower,
                transpose=True)[..., 0]
    u = _rnd(mean + _tri(L, z[..., None], lower, transpose=True)[..., 0],
             lower)
    return u, L


_draw_jit = jax.jit(_draw, static_argnames=("lower",))


@partial(jax.jit, static_argnames=("lower",))
def factor_rows(key, row0, fixed, idx, val, mask, alpha, Lam, bp, u_prog,
                bp_c, *, lower=False):
    """As ``bmf.factor_rows``, with per-row prior means; with ``lower``
    the control's own draw, from its own prior means ``bp_c``, stands in
    the program's place."""
    u, L = _draw(key, row0, fixed, idx, val, mask, alpha, Lam, bp, False)
    if lower:
        u_prog, _ = _draw(key, row0, fixed, idx, val, mask, alpha, Lam,
                          bp_c, lower is True)
    gap = u_prog - u
    white = jnp.einsum("rkl,rk->rl", L, gap, precision=HIGHEST)
    return (jnp.max(jnp.abs(white), axis=1), jnp.sum(white * white, axis=1),
            jnp.linalg.norm(gap, axis=1), jnp.linalg.norm(u, axis=1))


def _pad(x, n):
    x = np.asarray(x)
    return np.concatenate([x, np.zeros((n - x.shape[0],) + x.shape[1:],
                                       x.dtype)]) if n > x.shape[0] else x


def _compound_gaps(key, fixed, obs, alpha, Lam, bp, u_prog, bp_c, lower):
    idx, val, mask, block = obs.padded(True, obs.v)
    n = obs.shape[0]
    u_prog, bp, bp_c = (_pad(x, idx.shape[0]) for x in (u_prog, bp, bp_c))
    fixed = jnp.asarray(fixed)
    parts = []
    for r0 in range(0, idx.shape[0], block):
        sl = slice(r0, r0 + block)
        parts.append([np.asarray(x) for x in factor_rows(
            key, r0, fixed, jnp.asarray(idx[sl]), jnp.asarray(val[sl]),
            jnp.asarray(mask[sl]), alpha, Lam, jnp.asarray(bp[sl]),
            jnp.asarray(u_prog[sl]), jnp.asarray(bp_c[sl]), lower=lower)])
    white, sq, gap, norm = (np.concatenate(p)[:n] for p in zip(*parts))
    rms = np.sqrt(np.sum(sq, dtype=np.float64) / sq.size / u_prog.shape[1])
    return (float(white.max()), float(rms),
            float(gap.max() / np.median(norm)))


def draw_compounds(key, fixed, obs, alpha, Lam, bp) -> np.ndarray:
    """The reference's draw of every compound row from the prior means
    ``bp`` (N, K), to plant a fault in the program's place."""
    idx, val, mask, block = obs.padded(True, obs.v)
    bp = _pad(bp, idx.shape[0])
    fixed = jnp.asarray(fixed)
    out = []
    with jax.default_matmul_precision("highest"):
        for r0 in range(0, idx.shape[0], block):
            sl = slice(r0, r0 + block)
            u, _ = _draw_jit(key, r0, fixed, jnp.asarray(idx[sl]),
                             jnp.asarray(val[sl]), jnp.asarray(mask[sl]),
                             alpha, Lam, jnp.asarray(bp[sl]), lower=False)
            out.append(np.asarray(u))
    return np.concatenate(out)[:obs.shape[0]]


def check_transition(prev: dict, nxt: dict, obs: Observations, side,
                     lower=False, factors: bool = True) -> dict:
    """Readings of one sweep ``prev`` -> ``nxt`` of the program against
    the reference.  States are dicts of key, U, V, mu0, Lam0, beta,
    beta_prec, mu1, Lam1, alpha; ``side`` is the (N, D) side matrix.
    With ``lower`` the control's own values are measured in the
    program's place; ``factors=False`` leaves out the factor draws."""
    full = lower is True
    side = jnp.asarray(side)
    with jax.default_matmul_precision("highest"):
        key, ents, nkey = sweep_keys(jnp.asarray(prev["key"]))
        out = {"key_differs": float(not np.array_equal(
            np.asarray(key), np.asarray(nxt["key"])))}
        k_hyp, k_fac, _ = ents[0]
        k_nw, k_b, k_prec = jax.random.split(k_hyp, 3)
        U0, beta0 = jnp.asarray(prev["U"]), jnp.asarray(prev["beta"])

        # compound hyper-parameters: Normal-Wishart on U - F beta_old
        mu, Lam = bmf.sample_hyper(k_nw, centred(side, U0, beta0))
        if full:
            mu_c, Lam_c = bmf.sample_hyper(
                k_nw, centred(side, U0, beta0, lower=True), lower=True)
        elif lower:
            mu_c, Lam_c = mu, Lam
        else:
            mu_c, Lam_c = nxt["mu0"], nxt["Lam0"]
        mu_white = [bmf._whiten_mu(jnp.asarray(mu_c) - mu, Lam)]
        lam_rel = [_rel(Lam_c, Lam)]

        # the link, from the program's new mu and Lambda
        mu_p, Lam_p = jnp.asarray(nxt["mu0"]), jnp.asarray(nxt["Lam0"])
        bprec0 = jnp.asarray(prev["beta_prec"])
        beta, La, Llam = link(k_b, side, U0, mu_p, Lam_p, bprec0)
        if lower:
            beta_c, _, _ = link(k_b, side, U0, mu_p, Lam_p, bprec0,
                                lower=full or "link")
        else:
            beta_c = jnp.asarray(nxt["beta"])
        bw, bw_rms = link_gap(beta_c, beta, La, Llam)
        out["beta_white"], out["beta_white_rms"] = float(bw), float(bw_rms)

        # lambda_beta, from the program's new beta
        beta_p = beta_c if lower else jnp.asarray(nxt["beta"])
        prec = float(link_precision(k_prec, beta_p, Lam_p))
        if full:
            prec_c = float(link_precision(k_prec, beta_p, Lam_p, lower=True))
        elif lower:
            prec_c = prec
        else:
            prec_c = float(nxt["beta_prec"])
        out["beta_prec_rel"] = abs(prec_c - prec) / prec

        # protein hyper-parameters, as in BMF
        k_hyp1, k_fac1, _ = ents[1]
        V0 = jnp.asarray(prev["V"])
        mu1, Lam1 = bmf.sample_hyper(k_hyp1, V0)
        if full:
            mu1_c, Lam1_c = bmf.sample_hyper(k_hyp1, V0, lower=True)
        elif lower:
            mu1_c, Lam1_c = mu1, Lam1
        else:
            mu1_c, Lam1_c = nxt["mu1"], nxt["Lam1"]
        mu_white.append(bmf._whiten_mu(jnp.asarray(mu1_c) - mu1, Lam1))
        lam_rel.append(_rel(Lam1_c, Lam1))

        if factors:
            alpha = jnp.asarray(prev["alpha"])
            bp = prior_means(side, mu_p, jnp.asarray(nxt["beta"]), Lam_p)
            bp_c = (prior_means(side, mu_p, beta_c, Lam_p,
                                lower=full or "link") if lower else bp)
            w0, r0, g0 = _compound_gaps(k_fac, prev["V"], obs, alpha, Lam_p,
                                        bp, nxt["U"], bp_c, lower)
            if lower and not full:
                # the link control draws the proteins as the reference
                w1 = r1 = g1 = 0.0
            else:
                w1, r1, g1 = bmf._factor_gaps(
                    k_fac1, nxt["U"], obs, False, alpha,
                    jnp.asarray(nxt["mu1"]), jnp.asarray(nxt["Lam1"]),
                    nxt["V"], full)
            out["factor_white"] = max(w0, w1)
            out["factor_white_rms"] = max(r0, r1)
            out["factor_rel"] = max(g0, g1)

        args = (nkey, jnp.asarray(nxt["U"]), jnp.asarray(nxt["V"]),
                jnp.asarray(obs.i), jnp.asarray(obs.j), jnp.asarray(obs.v))
        a = float(bmf.noise_alpha(*args))
        if full:
            a_c = float(bmf.noise_alpha(*args, lower=True))
        elif lower:
            a_c = a
        else:
            a_c = float(nxt["alpha"])
        out["alpha_rel"] = abs(a_c - a) / a
        out["mu_white"] = max(mu_white)
        out["lambda_rel"] = max(lam_rel)
    return out


def _rel(a, b) -> float:
    a, b = np.asarray(a), np.asarray(b)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))
