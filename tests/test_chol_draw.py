"""The lane-batched Cholesky draw kernel against the lax.linalg draw.

The kernel runs in interpret mode on the CPU; ``_sample_normal_factor``
is driven once through the kernel's branch (``ops._draw_elsewhere``
pointed at ``ops.chol_draw``) and once through ``lax.linalg``, with
the same key, so both draw the same b and z.
"""
import functools

import jax
import numpy as np
import pytest

from repro.core.gibbs import _sample_normal_factor
from repro.kernels import ops, ref
from repro.obs import trace_counts

_kernel_branch = functools.partial(ops.chol_draw, interpret=True)


def _problem(seed, n, k, rows_mean, shared):
    """Gram rows of 2K random observations each, a Wishart-like prior
    precision, and the prior mean term as (K,) or (N, K)."""
    rng = np.random.default_rng(seed)
    v = rng.standard_normal((n, 2 * k, k)).astype(np.float32) / np.sqrt(k)
    gram_rows = np.swapaxes(v, 1, 2) @ v
    w = rng.standard_normal((k, k)).astype(np.float32) / np.sqrt(k)
    lam_p = w @ w.T + np.eye(k, dtype=np.float32)
    gram_shared = None
    if shared:
        s = rng.standard_normal((3 * k, k)).astype(np.float32) / k
        gram_shared = s.T @ s
    rhs = rng.standard_normal((n, k)).astype(np.float32)
    b_p = rng.standard_normal((n, k) if rows_mean else (k,)).astype(
        np.float32)
    return gram_shared, gram_rows, rhs, lam_p, b_p


def _draw(monkeypatch, kernel, args):
    monkeypatch.setattr(ops, "_draw_elsewhere",
                        _kernel_branch if kernel else ref.chol_draw_ref)
    key = jax.random.PRNGKey(7)
    fn = jax.jit(lambda *a: _sample_normal_factor(key, *a))
    return np.asarray(fn(*args))


def _assert_rows_agree(u, u_ref, rtol=1e-5):
    """Every entry within ``rtol`` of its row's largest magnitude."""
    gap = np.abs(u - u_ref) / np.abs(u_ref).max(axis=1, keepdims=True)
    assert gap.max() < rtol, f"largest gap {gap.max():.3g} of its row"


_SIZES = [(k, n) for k in (4, 13, 128) for n in (1, 300, 1024)]
# (b_p per row, gram_shared present): every combination over the sizes
_VARIANTS = [(False, False), (True, False), (False, True), (True, True)]
CASES = [(k, n) + _VARIANTS[i % 4] for i, (k, n) in enumerate(_SIZES)]
CASES += [(13, 300) + v for v in _VARIANTS if (13, 300) + v not in CASES]
# K past one lane width: two column steps per block of rows
CASES += [(200, 130, True, True)]


@pytest.mark.parametrize("k,n,rows_mean,shared", CASES,
                         ids=lambda x: str(x))
def test_kernel_draw_matches_lax_draw(monkeypatch, k, n, rows_mean,
                                      shared):
    args = _problem(k * n, n, k, rows_mean, shared)
    u_kernel = _draw(monkeypatch, True, args)
    u_lax = _draw(monkeypatch, False, args)
    assert u_kernel.shape == u_lax.shape == (n, k)
    assert np.isfinite(u_kernel).all()
    _assert_rows_agree(u_kernel, u_lax)


def test_kernel_draw_non_pd_rows_give_nan_in_both(monkeypatch):
    """A row whose precision is not positive definite draws NaN on both
    paths; no value is clamped and the other rows are untouched."""
    k, n = 13, 300
    gram_shared, gram_rows, rhs, lam_p, b_p = _problem(1, n, k, True,
                                                       False)
    bad = np.array([0, 129, 299])
    gram_rows[bad] -= 50.0 * np.eye(k, dtype=np.float32)
    args = (gram_shared, gram_rows, rhs, lam_p, b_p)
    u_kernel = _draw(monkeypatch, True, args)
    u_lax = _draw(monkeypatch, False, args)
    for u in (u_kernel, u_lax):
        assert np.isnan(u[bad]).any(axis=1).all()
        assert np.isfinite(np.delete(u, bad, axis=0)).all()
    good = np.delete(np.arange(n), bad)
    _assert_rows_agree(u_kernel[good], u_lax[good])


def test_kernel_padding_solves_to_zeros():
    """The wrapper pads K and N with identity matrices and b, z with
    zeros.  A block-aligned problem (K=16, N=128) bordered by an
    identity to K=19 with two zero rows (N=130, so the wrapper pads to
    24 x 256) draws the same real rows to float32 rounding, and exact
    zeros everywhere in the border."""
    k, n = 16, 128
    _, gram_rows, rhs, lam_p, _ = _problem(3, n, k, False, False)
    z = np.random.default_rng(4).standard_normal((n, k)).astype(np.float32)
    u = np.asarray(ops.chol_draw(gram_rows, lam_p, rhs, z,
                                 interpret=True))
    kb, nb = 19, 130
    g = np.zeros((nb, kb, kb), np.float32)
    g[:n, :k, :k] = gram_rows
    lam = np.eye(kb, dtype=np.float32)
    lam[:k, :k] = lam_p
    g[n:] = np.eye(kb) - lam
    b = np.zeros((nb, kb), np.float32)
    zb = np.zeros((nb, kb), np.float32)
    b[:n, :k], zb[:n, :k] = rhs, z
    ub = np.asarray(ops.chol_draw(g, lam, b, zb, interpret=True))
    np.testing.assert_allclose(ub[:n, :k], u, rtol=0, atol=1e-6 *
                               np.abs(u).max())
    assert not ub[n:].any() and not ub[:, k:].any()


def test_draw_counts_follow_the_shape_decision():
    """Each traced batched draw counts once, by the shape decision: K up
    to CHOL_DRAW_MAX_K takes the kernel on a TPU, a larger K lax.linalg
    on every platform."""
    def decide(k):
        before = trace_counts()
        branches = ops.draw_branches(k)
        after = trace_counts()
        return branches, {
            name: after.get(name, 0) - before.get(name, 0)
            for name in ("solve.kernel_draws", "solve.lax_draws")}

    (tpu, default), counts = decide(ops.CHOL_DRAW_MAX_K)
    assert counts == {"solve.kernel_draws": 1, "solve.lax_draws": 0}
    assert tpu is ops._draw_on_tpu and default is ops._draw_elsewhere
    (tpu, default), counts = decide(ops.CHOL_DRAW_MAX_K + 1)
    assert counts == {"solve.kernel_draws": 0, "solve.lax_draws": 1}
    assert tpu is default is ref.chol_draw_ref
