"""Noise models (paper Table 1, col 3).

* ``FixedGaussian``    — fixed precision alpha.
* ``AdaptiveGaussian`` — alpha ~ Gamma conditional on the residual SSE
                         (SMURFF's "adaptive" noise).
* ``ProbitNoise``      — binary data via truncated-normal latent
                         augmentation (unit precision on the latents).

Each noise model owns a tiny state pytree and two hooks used by the
Gibbs sweep:

* ``sample_state(key, state, pred, vals, mask)`` — resample the noise
  state from residuals at the observed entries.
* ``augment(key, state, pred, vals, mask)`` — return the effective
  (values, precision) the factor update should regress on.  For
  Gaussian noise this is identity; for probit it draws the truncated-
  normal latents.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.scipy.special import ndtri

_SQRT2 = 1.4142135623730951


@dataclasses.dataclass(frozen=True)
class FixedGaussian:
    precision: float = 5.0

    def init(self):
        return {"alpha": jnp.asarray(self.precision, jnp.float32)}

    def sample_state(self, key, state, pred, vals, mask,
                     sse=None, nnz=None):
        return state

    def augment(self, key, state, pred, vals, mask, row_offset=0):
        return vals, state["alpha"]


@dataclasses.dataclass(frozen=True)
class AdaptiveGaussian:
    """alpha ~ Gamma(a0 + nnz/2, b0 + SSE/2), resampled every sweep.

    ``sn_init`` seeds alpha; ``sn_max`` caps it (SMURFF exposes the same
    knobs as signal-to-noise ratios; we keep them as direct precisions).
    """

    sn_init: float = 1.0
    sn_max: float = 1e4
    a0: float = 0.5
    b0: float = 0.5

    def init(self):
        return {"alpha": jnp.asarray(self.sn_init, jnp.float32)}

    def sample_state(self, key, state, pred, vals, mask,
                     sse=None, nnz=None):
        """``sse``/``nnz`` override the local residual sums — the
        distributed sweep psums them over shards first, so every shard
        draws the same alpha from the same (replicated) key."""
        if sse is None:
            resid = (vals - pred) * mask
            sse = jnp.sum(resid * resid)
        if nnz is None:
            nnz = jnp.sum(mask)
        a_post = self.a0 + 0.5 * nnz
        b_post = self.b0 + 0.5 * sse
        alpha = jax.random.gamma(key, a_post) / b_post
        alpha = jnp.clip(alpha, 1e-6, self.sn_max).astype(jnp.float32)
        # an all-masked block (or fully padded shard view) has no
        # residuals to learn from: keep the previous alpha instead of
        # drawing from the data-free (degenerate) Gamma conditional
        return {"alpha": jnp.where(nnz > 0, alpha, state["alpha"])}

    def augment(self, key, state, pred, vals, mask, row_offset=0):
        return vals, state["alpha"]


_EPS = 1e-7
_TINY = float(jnp.finfo(jnp.float32).tiny)


def _truncnorm_from_u(u, mean, lower_tail: jnp.ndarray):
    """Inverse-CDF truncated-normal transform of uniforms ``u``.

    z ~ N(mean, 1) truncated to z>0 where lower_tail else z<0, with
    u in the open interval (0, 1).  Elementwise, so a row slice of
    (u, mean, lower_tail) yields exactly the matching slice of z —
    which is what lets the distributed sweep draw per-shard.

    Each side inverts its own tail: with m the probability mass of the
    allowed side (an erfc, accurate however small it is),
    z = mean - ndtri(m u) on the positive side and mean + ndtri(m u)
    on the negative one.  No probability is formed as one minus a
    number near one: inverting ``p0 + u (1 - p0)`` through erfinv lost
    the tail in float32, and one ulp of ``mean`` could move z by units
    (measured: 2.7 at mean -4.9), which turned reduction-order ULPs in
    ``mean`` into visible chain differences.
    """
    pos = lower_tail > 0
    mass = 0.5 * jax.lax.erfc(jnp.where(pos, -mean, mean) / _SQRT2)
    step = ndtri(jnp.maximum(mass * u, _TINY))
    # the clamp only acts where the mass underflows (|mean| > ~13)
    return jnp.where(pos, jnp.maximum(mean - step, 0.0),
                     jnp.minimum(mean + step, 0.0))


def _truncnorm(key, mean, lower_tail: jnp.ndarray):
    """z ~ N(mean, 1) truncated to z>0 where lower_tail else z<0.

    Inverse-CDF sampling of the allowed tail (``_truncnorm_from_u``).
    One batch-shaped draw — the Gibbs sweep instead goes through
    ``ProbitNoise.augment`` whose uniforms are per-row counter-based.
    """
    u = jax.random.uniform(key, mean.shape, dtype=jnp.float32,
                           minval=_EPS, maxval=1.0 - _EPS)
    return _truncnorm_from_u(u, mean, lower_tail)


@dataclasses.dataclass(frozen=True)
class ProbitNoise:
    """Binary matrices: P(r=1) = Phi(u.v); Albert-Chib augmentation.

    ``augment`` replaces each observed binary value with a latent
    z ~ TruncNormal(pred, 1) whose sign matches the observation, and
    fixes the regression precision at 1.

    The uniforms behind the truncated-normal draws are per-row
    counter-based (``gibbs.row_uniforms``): row i of a (R, T) operand
    draws from ``fold_in(key, row_offset + i)``, a pure function of
    the sweep key and the row's GLOBAL index.  A shard holding rows
    [off, off + n) of the padded view therefore consumes exactly the
    uniforms the single-device sweep consumes for those rows — the
    same contract as ``gibbs.row_normals`` — which is what admits
    probit models into the explicit distributed sweep.
    """

    threshold: float = 0.5  # vals > threshold count as positive

    def init(self):
        return {"alpha": jnp.asarray(1.0, jnp.float32)}

    def sample_state(self, key, state, pred, vals, mask,
                     sse=None, nnz=None):
        return state

    def augment(self, key, state, pred, vals, mask, row_offset=0):
        # deferred import: gibbs imports this module at load time
        from .gibbs import row_uniforms
        pos = (vals > self.threshold).astype(jnp.float32)
        u = row_uniforms(key, vals.shape[0], vals.shape[1], row_offset,
                         minval=_EPS, maxval=1.0 - _EPS)
        z = _truncnorm_from_u(u, pred, pos)
        return z * mask, state["alpha"]
