"""Prior distributions over the factor matrices (paper Table 1, col 2).

Choices, exactly as in SMURFF:

* ``NormalPrior``       — multivariate Normal with a Normal-Wishart
                          hyperprior (BPMF, Salakhutdinov & Mnih 2008).
* ``MacauPrior``        — NormalPrior + side information F through a
                          link matrix beta (Simm et al. 2017).
* ``SpikeAndSlabPrior`` — per-(row, component) spike-and-slab for
                          group-sparse factors (GFA, Virtanen 2012).

Each prior exposes:

* ``init(key, n_rows)``                  -> hyper-state pytree
* ``sample_hyper(key, F, hyper, ...)``   -> new hyper-state given the
                                            current factor matrix
* ``precision_term(hyper)``              -> Lambda_p (K, K)
* ``mean_term(hyper, n_rows)``           -> b_p (n_rows, K) or (K,)
                                            (Lambda_p @ prior_mean rows)

All sampling is counter-based ``jax.random`` — reproducible regardless
of how the row axis is sharded.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.lax.linalg import cholesky, triangular_solve

from ..obs import count_trace


# ---------------------------------------------------------------------------
# shared linear-algebra helpers
# ---------------------------------------------------------------------------

def chol_solve(L: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Solve (L L^T) x = b for batched lower-triangular L.

    L (..., K, K), b (..., K)  ->  x (..., K)
    """
    b = b[..., None]
    y = triangular_solve(L, b, left_side=True, lower=True)
    x = triangular_solve(L, y, left_side=True, lower=True, transpose_a=True)
    return x[..., 0]


def sample_mvn_from_precision(key, L_prec: jnp.ndarray,
                              mean: jnp.ndarray) -> jnp.ndarray:
    """x ~ N(mean, Lambda^{-1}) given L_prec = chol(Lambda), batched."""
    z = jax.random.normal(key, mean.shape, dtype=mean.dtype)
    dz = triangular_solve(L_prec, z[..., None], left_side=True, lower=True,
                          transpose_a=True)[..., 0]
    return mean + dz


def sample_wishart(key, L_scale: jnp.ndarray, df: float) -> jnp.ndarray:
    """Draw Lambda ~ Wishart(scale, df) via the Bartlett decomposition.

    L_scale = chol(scale matrix), K x K.  Returns a K x K precision
    sample Lambda = (L A)(L A)^T where A is the Bartlett factor.
    """
    K = L_scale.shape[-1]
    kn, kg = jax.random.split(key)
    # chi2(df - i) = 2 * gamma((df - i) / 2)
    i = jnp.arange(K, dtype=jnp.float32)
    c = jnp.sqrt(2.0 * jax.random.gamma(kg, (df - i) / 2.0,
                                        dtype=jnp.float32))
    n = jax.random.normal(kn, (K, K), dtype=jnp.float32)
    A = jnp.tril(n, -1) + jnp.diag(c)
    LA = L_scale @ A
    return LA @ LA.T


# ---------------------------------------------------------------------------
# Normal prior with Normal-Wishart hyperprior (BPMF)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class NormalPrior:
    """mu, Lambda ~ Normal-Wishart(mu0, b0, W0 = I, df = K)."""

    num_latent: int
    b0: float = 2.0
    mu0: float = 0.0

    def init(self, key, n_rows: int):
        K = self.num_latent
        return {"mu": jnp.zeros((K,), jnp.float32),
                "Lambda": jnp.eye(K, dtype=jnp.float32)}

    def sample_hyper(self, key, F: jnp.ndarray, hyper,
                     F_sum: Optional[jnp.ndarray] = None,
                     F_cov: Optional[jnp.ndarray] = None,
                     n_rows: Optional[jnp.ndarray] = None):
        """Conditional NW update given the factor matrix F (N, K).

        ``F_sum``/``F_cov``/``n_rows`` override the locally computed
        moments — the distributed path psums them across shards first.
        """
        s = F.sum(axis=0) if F_sum is None else F_sum
        C = F.T @ F if F_cov is None else F_cov
        N = F.shape[0] if n_rows is None else n_rows
        return self.sample_hyper_moments(key, hyper, F_sum=s, F_cov=C,
                                         n_rows=N)

    def sample_hyper_moments(self, key, hyper, *, F_sum: jnp.ndarray,
                             F_cov: jnp.ndarray, n_rows):
        """NW update from sufficient statistics only.

        ``F_sum`` (K,) and ``F_cov`` = F^T F (K, K) are the moments of
        the factor matrix; the distributed sweep computes them as a
        K/K^2-sized ``psum`` over row shards, so the hyper-sample is an
        identical replicated computation on every device.
        """
        K = self.num_latent
        N = jnp.asarray(n_rows, jnp.float32)
        fbar = F_sum / N
        # scatter matrix sum_i (f_i - fbar)(f_i - fbar)^T
        SS = F_cov - N * jnp.outer(fbar, fbar)

        mu0 = jnp.full((K,), self.mu0, jnp.float32)
        b_star = self.b0 + N
        df_star = K + N
        mu_star = (self.b0 * mu0 + N * fbar) / b_star
        dv = fbar - mu0
        Winv = (jnp.eye(K, dtype=jnp.float32) + SS
                + (self.b0 * N / b_star) * jnp.outer(dv, dv))
        # scale = Winv^{-1}: invert through the Cholesky of Winv
        Lw = cholesky(Winv)
        eye = jnp.eye(K, dtype=jnp.float32)
        y = triangular_solve(Lw, eye, left_side=True, lower=True)
        W = triangular_solve(Lw, y, left_side=True, lower=True,
                             transpose_a=True)
        Ls = cholesky((W + W.T) / 2.0)

        k1, k2 = jax.random.split(key)
        Lam = sample_wishart(k1, Ls, df_star)
        Llam = cholesky(Lam * b_star)
        mu = sample_mvn_from_precision(k2, Llam, mu_star)
        return {"mu": mu, "Lambda": Lam}

    def precision_term(self, hyper) -> jnp.ndarray:
        return hyper["Lambda"]

    def mean_term(self, hyper, n_rows: int) -> jnp.ndarray:
        """Lambda_p @ prior-mean, shared by all rows -> (K,)."""
        return hyper["Lambda"] @ hyper["mu"]


@dataclasses.dataclass(frozen=True)
class FixedNormalPrior:
    """Fixed z_i ~ N(0, I) — no hyper-sampling.

    This is GFA's prior on the shared sample factor Z (Virtanen 2012):
    pinning Z's scale/rotation is what lets the spike-and-slab prior on
    the loading matrices actually kill unused components.  (A
    Normal-Wishart prior on Z would re-absorb any rescaling and keep
    every component alive.)
    """

    num_latent: int

    def init(self, key, n_rows: int):
        return {}

    def sample_hyper(self, key, F, hyper, **_):
        return hyper

    def precision_term(self, hyper) -> jnp.ndarray:
        return jnp.eye(self.num_latent, dtype=jnp.float32)

    def mean_term(self, hyper, n_rows: int) -> jnp.ndarray:
        return jnp.zeros((self.num_latent,), jnp.float32)


# ---------------------------------------------------------------------------
# Macau prior: Normal + side information through a link matrix
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MacauPrior:
    """NormalPrior whose per-row mean is shifted by beta^T f_i.

    u_i ~ N(mu + beta^T f_i, Lambda^{-1}),
    beta ~ MatrixNormal(0, (beta_precision)^{-1} I_D, Lambda^{-1}).

    ``side`` F is (N, D) and is considered static data (closed over at
    jit time via the model definition).
    """

    num_latent: int
    num_features: int
    b0: float = 2.0
    mu0: float = 0.0
    beta_precision: float = 5.0
    sample_beta_precision: bool = True

    @property
    def _normal(self) -> NormalPrior:
        return NormalPrior(self.num_latent, self.b0, self.mu0)

    def init(self, key, n_rows: int):
        K, D = self.num_latent, self.num_features
        h = self._normal.init(key, n_rows)
        h["beta"] = jnp.zeros((D, K), jnp.float32)
        h["beta_prec"] = jnp.asarray(self.beta_precision, jnp.float32)
        return h

    def sample_hyper(self, key, F, hyper, side, stats):
        """NW update on (U - F beta), then the beta conditional.

        side (N, D): feature matrix.  ``stats``: its static statistics
        ``(side^T side (D, D), column sums (D,))``, computed once when
        the data is built (``gibbs.side_statistics``).
        """
        FtF, s_side = stats
        with jax.named_scope("link"):
            U_centered = F - side @ hyper["beta"]
            StF = side.T @ F
        return self.sample_hyper_moments(
            key, hyper, F_sum=U_centered.sum(axis=0),
            F_cov=U_centered.T @ U_centered, n_rows=F.shape[0], StF=StF,
            s_side=s_side, FtF=FtF)

    def sample_hyper_moments(self, key, hyper, *, F_sum, F_cov, n_rows,
                             StF, s_side, FtF):
        """Macau hyper-sample from sufficient statistics only.

        ``F_sum``/``F_cov`` are the moments of the *centered* factor
        U - side @ beta; ``StF`` = side^T U (D, K), ``s_side`` =
        column sums of side (D,), ``FtF`` = side^T side (D, D).  The
        distributed sweep psums the first three over row shards (the
        last two are static, ``gibbs.side_statistics``); the rest of the
        update is replicated K/D-sized linear algebra.
        """
        k_nw, k_b, k_prec = jax.random.split(key, 3)
        h = self._normal.sample_hyper_moments(k_nw, hyper, F_sum=F_sum,
                                              F_cov=F_cov, n_rows=n_rows)

        # the direct link solve: one (D, D) Cholesky per traced program
        count_trace("link.direct_solves")
        with jax.named_scope("link"):
            # beta | U, Lambda  ~ MN(mean, A^{-1}, Lambda^{-1}),
            # A = side^T side + beta_prec * I
            D, K = self.num_features, self.num_latent
            A = FtF + hyper["beta_prec"] * jnp.eye(D, dtype=jnp.float32)
            La = cholesky(A)
            # side^T (U - mu 1^T) decomposed so shards only contribute sums
            FtU = StF - jnp.outer(s_side, h["mu"])  # (D, K)
            y = triangular_solve(La, FtU, left_side=True, lower=True)
            mean_b = triangular_solve(La, y, left_side=True, lower=True,
                                      transpose_a=True)
            # sample: mean + La^{-T} Z Llam^{-1}
            Z = jax.random.normal(k_b, (D, K), dtype=jnp.float32)
            Zr = triangular_solve(La, Z, left_side=True, lower=True,
                                  transpose_a=True)
            Llam = cholesky(h["Lambda"])
            beta = mean_b + _mn_col_mix(Zr, Llam)

            # lambda_beta ~ Gamma conditional (Macau eq. for the link
            # precision): beta's D*K entries weighted by Lambda across
            # components, tr(beta Lambda beta^T) without the (D, D) product
            if self.sample_beta_precision:
                sse = jnp.sum((beta @ h["Lambda"]) * beta)
                a_post = 0.5 * (D * K) + 1.0
                b_post = 0.5 * sse + 1.0
                prec = jax.random.gamma(k_prec, a_post) / b_post
                h["beta_prec"] = prec.astype(jnp.float32)
            else:
                h["beta_prec"] = hyper["beta_prec"]
        h["beta"] = beta
        return h

    def precision_term(self, hyper) -> jnp.ndarray:
        return hyper["Lambda"]

    def mean_term(self, hyper, n_rows: int, side=None) -> jnp.ndarray:
        """(N, K): Lambda @ (mu + beta^T f_i) per row."""
        assert side is not None
        with jax.named_scope("link"):
            m = hyper["mu"][None, :] + side @ hyper["beta"]
            return m @ hyper["Lambda"].T

    def predict_factor(self, hyper, F_new) -> jnp.ndarray:
        """Latent rows for UNSEEN entities through the sampled link.

        The Macau conditional mean of a row with feature vector f is
        ``mu + beta^T f``; ``beta``/``mu`` here are the posterior
        SAMPLES carried in ``hyper`` (``beta`` is resampled every
        sweep by ``sample_hyper_moments`` and saved with the chain
        state).  ``PredictSession`` averages this per retained sample
        for out-of-matrix prediction — whole rows never present in the
        training matrix, the compound-activity cold-start workflow
        (Simm et al. 2017; arXiv:1904.02514).

        F_new (M, D) -> (M, K).
        """
        F_new = jnp.asarray(F_new, jnp.float32)
        return hyper["mu"][None, :] + F_new @ hyper["beta"]


def _mn_col_mix(Zr: jnp.ndarray, Llam: jnp.ndarray) -> jnp.ndarray:
    """Right-multiply row-mixed noise by Llam^{-T}: Zr @ Llam^{-1}...

    For MN(0, A^{-1}, Lambda^{-1}) we need Zr @ L_c^T with
    L_c = chol(Lambda^{-1}) = Llam^{-T}; i.e. Zr @ Llam^{-1}.
    Solve X Llam = Zr  =>  Llam^T X^T = Zr^T.
    """
    Xt = triangular_solve(Llam, Zr.T, left_side=True, lower=True,
                          transpose_a=True)
    return Xt.T


# ---------------------------------------------------------------------------
# Spike-and-slab prior (GFA-style group sparsity)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SpikeAndSlabPrior:
    """v_ik ~ (1 - rho_k) delta_0 + rho_k N(0, 1 / tau_k).

    Per-component inclusion probability rho_k ~ Beta(a, b) and slab
    precision tau_k ~ Gamma(c, d) are resampled each sweep.  The factor
    update itself is the coordinate-wise conditional (handled in
    ``gibbs.py::_sample_sns_factor`` because it needs the residuals);
    this class owns the hyper-state.
    """

    num_latent: int
    rho_a: float = 1.0
    rho_b: float = 1.0
    tau_c: float = 1.0
    tau_d: float = 1.0

    def init(self, key, n_rows: int):
        K = self.num_latent
        return {"rho": jnp.full((K,), 0.5, jnp.float32),
                "tau": jnp.ones((K,), jnp.float32)}

    def sample_hyper(self, key, F, hyper, n_incl=None, sumsq=None,
                     n_rows=None, **_):
        """F is the factor matrix (N, K); zeros mark excluded entries.

        ``n_incl``/``sumsq``/``n_rows`` override the locally computed
        per-component moments — the distributed sweep psums them over
        row shards first (two K-sized collectives).
        """
        s = (jnp.abs(F) > 0).astype(jnp.float32)     # inclusion indicators
        return self.sample_hyper_moments(
            key, hyper,
            n_incl=s.sum(axis=0) if n_incl is None else n_incl,
            sumsq=(F * F).sum(axis=0) if sumsq is None else sumsq,
            n_rows=F.shape[0] if n_rows is None else n_rows)

    def sample_hyper_moments(self, key, hyper, *, n_incl, sumsq, n_rows):
        """SnS hyper-sample from sufficient statistics only.

        ``n_incl`` (K,) counts the included (nonzero) entries per
        component and ``sumsq`` (K,) their sum of squares; the
        distributed sweep psums both over row shards — the ONLY
        collectives the spike-and-slab composition adds to a sweep —
        so the hyper-sample is an identical replicated computation on
        every device, mirroring ``NormalPrior.sample_hyper_moments``.
        """
        N = jnp.asarray(n_rows, jnp.float32)
        kr, kt1, kt2 = jax.random.split(key, 3)
        # rho_k ~ Beta(a + n_incl, b + N - n_incl)
        g1 = jax.random.gamma(kr, self.rho_a + n_incl)
        g2 = jax.random.gamma(kt1, self.rho_b + N - n_incl)
        rho = g1 / (g1 + g2)
        # tau_k ~ Gamma(c + n_incl/2, d + sum v^2 / 2)
        tau = (jax.random.gamma(kt2, self.tau_c + 0.5 * n_incl)
               / (self.tau_d + 0.5 * sumsq))
        return {"rho": jnp.clip(rho, 1e-4, 1.0 - 1e-4), "tau": tau}

    def precision_term(self, hyper) -> jnp.ndarray:
        return jnp.diag(hyper["tau"])

    def mean_term(self, hyper, n_rows: int) -> jnp.ndarray:
        return jnp.zeros((self.num_latent,), jnp.float32)
