"""Plain reference of BMF with Normal priors and adaptive Gaussian noise.

Written from the model (BPMF, Salakhutdinov & Mnih 2008; SMURFF,
arXiv:1904.02514, Algorithm 1), in float32 at the highest matmul
precision, importing nothing of the program.  Two parts:

* ``Transition``: one Gibbs sweep from a given state.  The chain is
  stochastic, so the reference draws the same random numbers: the key
  schedule below is part of the chain's definition (per-entity keys,
  per-row counter-based normals ``fold_in(key, row)``).  Each stage is
  computed from the program's own output of the stage before it
  (hyper-parameters from the state before the sweep, the first factor
  from those, the second factor from the program's new first factor,
  the noise from both new factors), so one stage's rounding does not
  spill into the next stage's comparison.
* ``scores``: posterior mean and standard deviation of every item's
  score for one user over the retained samples.

``lower=True`` computes the same in bfloat16 (every stored value
rounded to bfloat16): the control that the comparison has to fail.
``lower="solve"`` rounds only a factor draw's Gram, right-hand side,
Cholesky factor and triangular solves, the change that would tempt a
faster solve; hyper-parameters, noise and the drawn rows stay float32.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.lax.linalg import cholesky, triangular_solve

HIGHEST = jax.lax.Precision.HIGHEST

# hyper-prior and noise constants of the configuration
B0, MU0 = 2.0, 0.0           # Normal-Wishart: b0, mu0, W0 = I, df0 = K
A0, BETA0 = 0.5, 0.5         # Gamma prior on the noise precision
ALPHA_MIN, ALPHA_MAX = 1e-6, 1e4


def _rnd(x, lower: bool):
    """Round to bfloat16 in the control, identity otherwise."""
    return x.astype(jnp.bfloat16).astype(jnp.float32) if lower else x


def _mm(a, b, lower):
    return _rnd(jnp.matmul(a, b, precision=HIGHEST), lower)


def _tri(L, b, lower, transpose=False):
    return _rnd(triangular_solve(L, b, left_side=True, lower=True,
                                 transpose_a=transpose), lower)


@partial(jax.jit, static_argnames=("lower",))
def sample_hyper(key, F, *, lower=False):
    """(mu, Lambda) ~ Normal-Wishart posterior given factor rows F."""
    K = F.shape[1]
    F = _rnd(F, lower)
    N = jnp.asarray(F.shape[0], jnp.float32)
    s = _rnd(jnp.sum(F, axis=0), lower)
    C = _mm(F.T, F, lower)
    fbar = s / N
    SS = C - N * jnp.outer(fbar, fbar)
    mu0 = jnp.full((K,), MU0, jnp.float32)
    b_star = B0 + N
    df_star = K + N
    mu_star = (B0 * mu0 + N * fbar) / b_star
    dv = fbar - mu0
    eye = jnp.eye(K, dtype=jnp.float32)
    Winv = _rnd(eye + SS + (B0 * N / b_star) * jnp.outer(dv, dv), lower)
    Lw = _rnd(cholesky(Winv), lower)
    W = _tri(Lw, _tri(Lw, eye, lower), lower, transpose=True)
    Ls = _rnd(cholesky((W + W.T) / 2.0), lower)
    k1, k2 = jax.random.split(key)
    # Wishart(Ls Ls^T, df_star) by the Bartlett decomposition
    kn, kg = jax.random.split(k1)
    i = jnp.arange(K, dtype=jnp.float32)
    c = jnp.sqrt(2.0 * jax.random.gamma(kg, (df_star - i) / 2.0,
                                        dtype=jnp.float32))
    n = jax.random.normal(kn, (K, K), dtype=jnp.float32)
    A = jnp.tril(n, -1) + jnp.diag(c)
    LA = _mm(Ls, A, lower)
    Lam = _mm(LA, LA.T, lower)
    Llam = _rnd(cholesky(Lam * b_star), lower)
    z = jax.random.normal(k2, (K,), dtype=jnp.float32)
    mu = mu_star + _tri(Llam, z[:, None], lower, transpose=True)[:, 0]
    return _rnd(mu, lower), Lam


def _draw(key, row0, fixed, idx, val, mask, alpha, mu, Lam, lower):
    R, K = idx.shape[0], fixed.shape[1]
    full, lower = lower is True, bool(lower)
    vg = _rnd(fixed, full)[idx] * mask[..., None]            # (R, T, K)
    gram = _rnd(jnp.einsum("rtk,rtl->rkl", vg, vg, precision=HIGHEST),
                lower)
    rhs = _rnd(jnp.einsum("rtk,rt->rk", vg, val * mask,
                          precision=HIGHEST), lower)
    Lam_p = _rnd(Lam, lower)
    b_p = _rnd(jnp.matmul(Lam, mu, precision=HIGHEST), lower)
    prec = _rnd(alpha * gram + Lam_p[None], lower)
    b = _rnd(alpha * rhs + b_p[None], lower)
    rows = row0 + jnp.arange(R)
    z = jax.vmap(lambda r: jax.random.normal(
        jax.random.fold_in(key, r), (K,), jnp.float32))(rows)
    L = _rnd(cholesky(prec), lower)
    mean = _tri(L, _tri(L, b[..., None], lower), lower,
                transpose=True)[..., 0]
    u = _rnd(mean + _tri(L, z[..., None], lower, transpose=True)[..., 0],
             full)
    return u, L


@partial(jax.jit, static_argnames=("lower",))
def factor_rows(key, row0, fixed, idx, val, mask, alpha, mu, Lam,
                u_prog, *, lower=False):
    """Draw rows ``row0 + [0, R)`` of a factor from their conditional
    and measure a candidate draw against it: the program's ``u_prog``,
    or with ``lower`` the bfloat16 control's own draw.

    ``idx``/``val``/``mask`` (R, T) list each row's observations of the
    ``fixed`` factor.  Returns, per row, the largest gap in units of the
    conditional's standard deviation (the gap whitened by the
    reference's Cholesky factor), the sum of the squared whitened gaps,
    the gap's norm and the reference row's norm.
    """
    u, L = _draw(key, row0, fixed, idx, val, mask, alpha, mu, Lam, False)
    if lower:
        u_prog, _ = _draw(key, row0, fixed, idx, val, mask, alpha, mu,
                          Lam, lower)
    gap = u_prog - u
    white = jnp.einsum("rkl,rk->rl", L, gap, precision=HIGHEST)
    return (jnp.max(jnp.abs(white), axis=1), jnp.sum(white * white, axis=1),
            jnp.linalg.norm(gap, axis=1), jnp.linalg.norm(u, axis=1))


@partial(jax.jit, static_argnames=("lower",))
def noise_alpha(key, U, V, i, j, v, *, lower=False):
    """alpha ~ Gamma(a0 + nnz/2, b0 + SSE/2) at the observed entries."""
    pred = jnp.sum(_rnd(U, lower)[i] * _rnd(V, lower)[j], axis=1)
    r = v - _rnd(pred, lower)
    sse = _rnd(jnp.sum(r * r), lower)
    nnz = jnp.asarray(i.shape[0], jnp.float32)
    a = jax.random.gamma(key, A0 + 0.5 * nnz) / (BETA0 + 0.5 * sse)
    return jnp.clip(a, ALPHA_MIN, ALPHA_MAX).astype(jnp.float32)


def sweep_keys(key):
    """The sweep's key schedule: (next key, entity keys, noise key);
    each entity key splits into (hyper, factor, block) keys."""
    key, e0, e1, nkey = jax.random.split(key, 4)
    ents = [jax.random.split(k, 3) for k in (e0, e1)]
    return key, ents, jax.random.split(nkey, 1)[0]


@partial(jax.jit, static_argnames=("lower",))
def scores(u, v, *, lower=False):
    """Mean and std over samples of u[s] . v[s, n]: u (S, K), v (S, N, K)
    -> (N,), (N,)."""
    s = _rnd(jnp.einsum("sk,snk->sn", _rnd(u, lower), _rnd(v, lower),
                        precision=HIGHEST), lower)
    mean = jnp.mean(s, axis=0)
    std = jnp.sqrt(jnp.maximum(jnp.mean(s * s, axis=0) - mean * mean, 0.0))
    return mean, std


def _whiten_mu(mu_gap, Lam):
    """A gap in mu in units of its conditional's std: mu ~ N(., (b Lam)^-1)
    with b of order N, so the gap is whitened by chol(Lam) alone and
    reads as a share of one prior std."""
    L = cholesky(Lam)
    return float(jnp.max(jnp.abs(L.T @ mu_gap)))


class Observations:
    """Each row's and each column's observations, padded per orientation
    with a mask, in blocks of fixed shape."""

    def __init__(self, i, j, v, shape, block_rows=2048, block_cols=512):
        self.shape = shape
        self.i, self.j, self.v = (np.asarray(i, np.int32),
                                  np.asarray(j, np.int32),
                                  np.asarray(v, np.float32))
        self.rows = self._padded(self.i, self.j, shape[0], block_rows)
        self.cols = self._padded(self.j, self.i, shape[1], block_cols)

    @staticmethod
    def _padded(owner, other, n, block):
        order = np.argsort(owner, kind="stable")
        deg = np.bincount(owner, minlength=n)
        width = max(int(deg.max()), 1)
        start = np.concatenate([[0], np.cumsum(deg)[:-1]])
        slot = np.arange(len(owner)) - np.repeat(start, deg)
        block = min(block, n)
        n_pad = -(-n // block) * block
        idx = np.zeros((n_pad, width), np.int32)
        val = np.zeros((n_pad, width), np.float32)
        mask = np.zeros((n_pad, width), np.float32)
        own = owner[order]
        idx[own, slot] = other[order]
        mask[own, slot] = 1.0
        return idx, val, mask, order, own, slot, block

    def padded(self, as_rows: bool, v: np.ndarray):
        idx, val, mask, order, own, slot, block = (
            self.rows if as_rows else self.cols)
        val = np.zeros_like(val)
        val[own, slot] = v[order]
        return idx, val, mask, block


def _factor_gaps(key, fixed, obs, as_rows, alpha, mu, Lam, u_prog, lower):
    idx, val, mask, block = obs.padded(as_rows, obs.v)
    n = obs.shape[0 if as_rows else 1]
    u_prog = np.asarray(u_prog)
    pad = idx.shape[0] - n
    if pad:
        u_prog = np.concatenate([u_prog, np.zeros((pad, u_prog.shape[1]),
                                                  np.float32)])
    fixed = jnp.asarray(fixed)
    parts = []
    for r0 in range(0, idx.shape[0], block):
        sl = slice(r0, r0 + block)
        parts.append([np.asarray(x) for x in factor_rows(
            key, r0, fixed, jnp.asarray(idx[sl]), jnp.asarray(val[sl]),
            jnp.asarray(mask[sl]), alpha, mu, Lam,
            jnp.asarray(u_prog[sl]), lower=lower)])
    white, sq, gap, norm = (np.concatenate(p)[:n] for p in zip(*parts))
    rms = np.sqrt(np.sum(sq, dtype=np.float64) / sq.size / u_prog.shape[1])
    return (float(white.max()), float(rms),
            float(gap.max() / np.median(norm)))


def check_transition(prev: dict, nxt: dict, obs: Observations,
                     lower=False, factors: bool = True) -> dict:
    """Readings of one sweep ``prev`` -> ``nxt`` of the program against
    the reference.  States are dicts of key, U, V, mu0, Lam0, mu1,
    Lam1, alpha.  With ``lower`` the control's own values are measured
    in the program's place; ``factors=False`` leaves out the factor
    draws (the costly part) and reads the rest."""
    with jax.default_matmul_precision("highest"):
        key, ents, nkey = sweep_keys(jnp.asarray(prev["key"]))
        out = {"key_differs": float(not np.array_equal(
            np.asarray(key), np.asarray(nxt["key"])))}
        white, rms, rel, lam_rel, mu_white = [], [], [], [], []
        for e, name in enumerate(("U", "V")):
            k_hyp, k_fac, _ = ents[e]
            mu, Lam = sample_hyper(k_hyp, jnp.asarray(prev[name]))
            if lower is True:
                mu_c, Lam_c = sample_hyper(k_hyp, jnp.asarray(prev[name]),
                                           lower=True)
            elif lower:
                mu_c, Lam_c = mu, Lam
            else:
                mu_c, Lam_c = nxt[f"mu{e}"], nxt[f"Lam{e}"]
            Lam_c = np.asarray(Lam_c)
            lam_rel.append(float(np.linalg.norm(Lam_c - np.asarray(Lam))
                                 / np.linalg.norm(np.asarray(Lam))))
            mu_white.append(_whiten_mu(jnp.asarray(mu_c) - mu, Lam))
            if not factors:
                continue
            # the factor from the program's own hyper-parameters and,
            # for the second entity, the program's new first factor
            fixed = nxt["U"] if e == 1 else prev["V"]
            w, r, g = _factor_gaps(k_fac, fixed, obs, e == 0,
                                   jnp.asarray(prev["alpha"]),
                                   jnp.asarray(nxt[f"mu{e}"]),
                                   jnp.asarray(nxt[f"Lam{e}"]), nxt[name],
                                   lower)
            white.append(w)
            rms.append(r)
            rel.append(g)
        args = (nkey, jnp.asarray(nxt["U"]), jnp.asarray(nxt["V"]),
                jnp.asarray(obs.i), jnp.asarray(obs.j), jnp.asarray(obs.v))
        a = float(noise_alpha(*args))
        if lower is True:
            a_c = float(noise_alpha(*args, lower=True))
        elif lower:
            a_c = a
        else:
            a_c = float(nxt["alpha"])
        out["alpha_rel"] = abs(a_c - a) / a
        out["mu_white"] = max(mu_white)
        out["lambda_rel"] = max(lam_rel)
        if factors:
            out["factor_white"] = max(white)
            out["factor_white_rms"] = max(rms)
            out["factor_rel"] = max(rel)
    return out


_draw_jit = jax.jit(_draw, static_argnames=("lower",))


def draw_factor(key, fixed, obs: Observations, as_rows: bool, alpha, mu,
                Lam, keep=None) -> np.ndarray:
    """The reference's draw of a whole factor, in row blocks; ``keep``
    (a function of (owner ids, other ids) -> bool) drops observations,
    to plant a fault in the reference's place."""
    idx, val, mask, block = obs.padded(as_rows, obs.v)
    n = obs.shape[0 if as_rows else 1]
    if keep is not None:
        own = np.arange(idx.shape[0])[:, None] + np.zeros_like(idx)
        mask = mask * keep(own, idx).astype(np.float32)
    fixed = jnp.asarray(fixed)
    out = []
    with jax.default_matmul_precision("highest"):
        for r0 in range(0, idx.shape[0], block):
            sl = slice(r0, r0 + block)
            u, _ = _draw_jit(key, r0, fixed, jnp.asarray(idx[sl]),
                             jnp.asarray(val[sl]), jnp.asarray(mask[sl]),
                             alpha, mu, Lam, lower=False)
            out.append(np.asarray(u))
    return np.concatenate(out)[:n]
