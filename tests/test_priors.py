"""Prior conditionals: statistical sanity of the Gibbs building blocks."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core.gibbs import side_statistics
from repro.core.priors import (MacauPrior, NormalPrior,
                               SpikeAndSlabPrior, chol_solve,
                               sample_mvn_from_precision, sample_wishart)


def test_chol_solve_batched():
    rng = np.random.default_rng(0)
    A = rng.normal(size=(5, 4, 4)).astype(np.float32)
    spd = A @ np.swapaxes(A, -1, -2) + 4 * np.eye(4, dtype=np.float32)
    b = rng.normal(size=(5, 4)).astype(np.float32)
    L = np.linalg.cholesky(spd)
    x = chol_solve(jnp.asarray(L), jnp.asarray(b))
    expect = np.linalg.solve(spd, b[..., None])[..., 0]
    np.testing.assert_allclose(x, expect, rtol=1e-4, atol=1e-4)


def test_wishart_mean():
    """E[Wishart(V, df)] = df * V."""
    key = jax.random.PRNGKey(0)
    K, df, n = 3, 10.0, 4000
    V = np.array([[1.0, 0.3, 0.0], [0.3, 1.0, 0.2], [0.0, 0.2, 0.5]],
                 np.float32)
    L = jnp.asarray(np.linalg.cholesky(V))
    draws = jax.vmap(lambda k: sample_wishart(k, L, df))(
        jax.random.split(key, n))
    mean = np.asarray(draws).mean(axis=0)
    np.testing.assert_allclose(mean, df * V, rtol=0.08, atol=0.05)


def test_mvn_from_precision_moments():
    key = jax.random.PRNGKey(1)
    K, n = 3, 20000
    Lam = np.array([[2.0, 0.5, 0.0], [0.5, 1.5, 0.3], [0.0, 0.3, 1.0]],
                   np.float32)
    L = jnp.asarray(np.linalg.cholesky(Lam))
    mean = jnp.asarray([1.0, -2.0, 0.5])
    draws = jax.vmap(
        lambda k: sample_mvn_from_precision(
            k, L, mean))(jax.random.split(key, n))
    d = np.asarray(draws)
    np.testing.assert_allclose(d.mean(axis=0), mean, atol=0.05)
    np.testing.assert_allclose(np.cov(d.T), np.linalg.inv(Lam),
                               rtol=0.1, atol=0.05)


def test_normal_prior_hyper_tracks_factor():
    """With many rows the NW posterior concentrates near the sample
    moments of the factor matrix."""
    rng = np.random.default_rng(2)
    N, K = 5000, 4
    true_mu = np.array([1.0, -1.0, 0.5, 0.0], np.float32)
    F = (true_mu + 0.5 * rng.normal(size=(N, K))).astype(np.float32)
    prior = NormalPrior(K)
    h = prior.init(jax.random.PRNGKey(0), N)
    h = prior.sample_hyper(jax.random.PRNGKey(1), jnp.asarray(F), h)
    np.testing.assert_allclose(np.asarray(h["mu"]), true_mu, atol=0.1)
    # Lambda ~ inverse of sample covariance = 1/0.25 * I
    lam = np.asarray(h["Lambda"])
    np.testing.assert_allclose(lam, 4.0 * np.eye(K), rtol=0.25, atol=0.4)


def test_normal_prior_distributed_moments_match():
    """Passing psummed moments equals the local computation."""
    rng = np.random.default_rng(3)
    F = jnp.asarray(rng.normal(size=(100, 4)).astype(np.float32))
    prior = NormalPrior(4)
    h0 = prior.init(jax.random.PRNGKey(0), 100)
    key = jax.random.PRNGKey(42)
    a = prior.sample_hyper(key, F, h0)
    b = prior.sample_hyper(key, F, h0, F_sum=F.sum(axis=0),
                           F_cov=F.T @ F, n_rows=100)
    np.testing.assert_allclose(np.asarray(a["mu"]), np.asarray(b["mu"]),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(a["Lambda"]),
                               np.asarray(b["Lambda"]),
                               rtol=1e-4, atol=1e-4)


def test_macau_beta_recovers_planted_link():
    """U = F beta* + noise: the beta conditional should find beta*."""
    rng = np.random.default_rng(4)
    N, D, K = 2000, 8, 3
    F = rng.normal(size=(N, D)).astype(np.float32)
    beta_true = rng.normal(size=(D, K)).astype(np.float32)
    U = (F @ beta_true + 0.1 * rng.normal(size=(N, K))).astype(np.float32)
    prior = MacauPrior(K, D, sample_beta_precision=False,
                       beta_precision=1.0)
    h = prior.init(jax.random.PRNGKey(0), N)
    (stats,) = side_statistics((jnp.asarray(F),))
    for it in range(5):
        h = prior.sample_hyper(jax.random.PRNGKey(it), jnp.asarray(U), h,
                               jnp.asarray(F), stats)
    np.testing.assert_allclose(np.asarray(h["beta"]), beta_true,
                               rtol=0.15, atol=0.15)


def test_sns_hyper_estimates_sparsity():
    """rho_k tracks the per-component inclusion rate."""
    rng = np.random.default_rng(5)
    N, K = 4000, 4
    incl = np.array([0.9, 0.5, 0.1, 1.0])
    s = rng.random((N, K)) < incl
    F = (s * rng.normal(size=(N, K))).astype(np.float32)
    prior = SpikeAndSlabPrior(K)
    h = prior.sample_hyper(jax.random.PRNGKey(0), jnp.asarray(F),
                           prior.init(jax.random.PRNGKey(0), N))
    np.testing.assert_allclose(np.asarray(h["rho"]), incl, atol=0.05)
    # tau ~ 1 (unit slab variance); the rarely-included component has
    # few samples, so its posterior draw is noisy
    np.testing.assert_allclose(np.asarray(h["tau"]), 1.0, rtol=0.35)


def test_sns_distributed_moments_match():
    """Passing psummed per-component moments equals the local
    computation — the SnS sibling of the NormalPrior moments test,
    backing the two K-sized psums the distributed sweep issues."""
    rng = np.random.default_rng(6)
    N, K = 200, 4
    s = rng.random((N, K)) < 0.6
    F = jnp.asarray((s * rng.normal(size=(N, K))).astype(np.float32))
    prior = SpikeAndSlabPrior(K)
    h0 = prior.init(jax.random.PRNGKey(0), N)
    key = jax.random.PRNGKey(42)
    a = prior.sample_hyper(key, F, h0)
    incl = (jnp.abs(F) > 0).astype(jnp.float32)
    b = prior.sample_hyper_moments(key, h0, n_incl=incl.sum(axis=0),
                                   sumsq=(F * F).sum(axis=0), n_rows=N)
    for hk in ("rho", "tau"):
        np.testing.assert_allclose(np.asarray(a[hk]), np.asarray(b[hk]),
                                   rtol=1e-5, atol=1e-6)
