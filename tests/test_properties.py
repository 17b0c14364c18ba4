"""Hypothesis property tests on system invariants.

Invariants covered:
  * COO -> padded-bucket -> COO is lossless, both orientations agree
    with a dense reconstruction (the TPU-native CSR is exact);
  * transpose is an involution on every observed entry;
  * the batched gram/rhs equals the per-row loop for ARBITRARY sparse
    patterns (not just the fixed seeds of test_gibbs_reference);
  * the bf16 gather path (ModelDef.bf16_gather) stays within bf16
    tolerance of the f32 gram;
  * one gibbs_step preserves every invariant of the sampler state
    (shapes, finiteness, PSD-able precision, positive noise alpha)
    for arbitrary planted data;
  * with_coo_values rebuilds both orientations consistently;
  * the probit truncated-normal machinery: _truncnorm draws carry the
    observation's sign and stay finite for |mean| up to 8, and the
    counter-based row_uniforms (the distributed probit contract) give
    bitwise shard-slice parity for every divisor split;
  * the counter-based row_bernoulli (the spike-and-slab inclusion
    contract) gives the same bitwise shard-slice parity and tracks
    its probability argument;
  * the ring pipeline's chunk-accumulated dense Gram/RHS moments
    (``_dense_chunk_contrib``, folded per ppermute hop in
    ``distributed._ring_accumulate``) equal the monolithic
    ``_dense_contrib`` moments for arbitrary chunk counts, UNEVEN
    chunk widths, masked payloads, and the all-ones-mask
    ``fully=True`` shared-Gram fast path — and the ring's
    ``dynamic_update_slice`` view reassembly is bitwise the gathered
    array for every rotation of the chunk order.
"""
import jax
import jax.numpy as jnp
import numpy as np
try:
    from hypothesis import given, settings, strategies as st
except ImportError:   # container without dev deps — see requirements-dev.txt
    from _hypothesis_fallback import given, settings, strategies as st

from repro.core import (AdaptiveGaussian, BlockDef, EntityDef,
                        FixedGaussian, MFData, ModelDef, NormalPrior,
                        ProbitNoise, dense_block, from_coo, gibbs_step,
                        init_state)
from repro.core.gibbs import (_dense_chunk_contrib, _dense_contrib,
                              _sparse_contrib, row_bernoulli,
                              row_uniforms)
from repro.core.noise import _truncnorm
from repro.kernels import ref


@st.composite
def sparse_problem(draw, max_n=24, max_m=16):
    n = draw(st.integers(2, max_n))
    m = draw(st.integers(2, max_m))
    nnz = draw(st.integers(1, min(60, n * m)))
    seed = draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    flat = rng.choice(n * m, size=nnz, replace=False)
    i, j = np.divmod(flat, m)
    v = rng.normal(size=nnz).astype(np.float32)
    # hypothesis shouldn't shrink through the rng — keep data derived
    return n, m, i.astype(np.int64), j.astype(np.int64), v


@settings(max_examples=25, deadline=None)
@given(sparse_problem())
def test_padded_roundtrip_lossless(prob):
    n, m, i, j, v = prob
    mat = from_coo(i, j, v, (n, m))
    dense = np.zeros((n, m), np.float32)
    dense[i, j] = v

    # rows orientation reconstructs the dense matrix
    rows = mat.rows
    rec = np.zeros((n, m), np.float32)
    idx = np.asarray(rows.idx)
    val = np.asarray(rows.val)
    msk = np.asarray(rows.mask)
    for r in range(n):
        for t in range(rows.max_nnz):
            if msk[r, t] > 0:
                rec[r, idx[r, t]] += val[r, t]
    np.testing.assert_allclose(rec, dense, atol=0)

    # cols orientation reconstructs the transpose
    cols = mat.cols
    recT = np.zeros((m, n), np.float32)
    idx = np.asarray(cols.idx)
    val = np.asarray(cols.val)
    msk = np.asarray(cols.mask)
    for c in range(m):
        for t in range(cols.max_nnz):
            if msk[c, t] > 0:
                recT[c, idx[c, t]] += val[c, t]
    np.testing.assert_allclose(recT, dense.T, atol=0)

    # nnz preserved, COO mask exact
    assert int(np.asarray(mat.nnz)) == len(v)


@settings(max_examples=20, deadline=None)
@given(sparse_problem())
def test_transpose_involution(prob):
    n, m, i, j, v = prob
    mat = from_coo(i, j, v, (n, m))
    tt = mat.transpose().transpose()
    assert tt.shape == mat.shape
    np.testing.assert_array_equal(np.asarray(tt.rows.idx),
                                  np.asarray(mat.rows.idx))
    np.testing.assert_array_equal(np.asarray(tt.coo_v),
                                  np.asarray(mat.coo_v))


@settings(max_examples=20, deadline=None)
@given(sparse_problem(), st.integers(2, 6))
def test_gram_matches_loop_any_pattern(prob, K):
    n, m, i, j, v = prob
    mat = from_coo(i, j, v, (n, m))
    rng = np.random.default_rng(K)
    V = rng.normal(size=(m, K)).astype(np.float32)
    alpha = 3.0
    noise = FixedGaussian(alpha)
    model = ModelDef((EntityDef("r", n, NormalPrior(K)),
                      EntityDef("c", m, NormalPrior(K))),
                     (BlockDef(0, 1, noise, sparse=True),), K, False)
    gram, rhs = _sparse_contrib(model, mat, True, jnp.asarray(V),
                                jnp.zeros((n, K)), noise, noise.init(),
                                jax.random.PRNGKey(0))
    for r in range(n):
        sel = i == r
        vs = V[j[sel]]
        np.testing.assert_allclose(np.asarray(gram[r]),
                                   alpha * (vs.T @ vs),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(rhs[r]),
                                   alpha * (v[sel] @ vs),
                                   rtol=2e-4, atol=2e-4)


@settings(max_examples=15, deadline=None)
@given(sparse_problem(), st.integers(2, 5))
def test_bf16_gather_gram_close_to_f32(prob, K):
    n, m, i, j, v = prob
    mat = from_coo(i, j, v, (n, m))
    rng = np.random.default_rng(K + 1)
    V = rng.normal(size=(m, K)).astype(np.float32)
    vg32 = jnp.asarray(V)[mat.rows.idx]
    vg16 = jnp.asarray(V).astype(jnp.bfloat16)[mat.rows.idx]
    g32, b32 = ref.gram_ref(vg32, mat.rows.val, mat.rows.mask)
    g16, b16 = ref.gram_ref(vg16, mat.rows.val, mat.rows.mask)
    # bf16 mantissa ~ 8 bits -> ~1e-2 relative
    scale = float(jnp.max(jnp.abs(g32))) + 1e-6
    assert float(jnp.max(jnp.abs(g16 - g32))) < 0.05 * scale


@settings(max_examples=10, deadline=None)
@given(sparse_problem(), st.booleans())
def test_gibbs_step_preserves_state_invariants(prob, bf16):
    n, m, i, j, v = prob
    K = 3
    mat = from_coo(i, j, v, (n, m))
    model = ModelDef((EntityDef("r", n, NormalPrior(K)),
                      EntityDef("c", m, NormalPrior(K))),
                     (BlockDef(0, 1, AdaptiveGaussian(), sparse=True),),
                     K, False, bf16_gather=bf16)
    data = MFData((mat,), (None, None))
    state = init_state(model, data, 7)
    st1, metrics = gibbs_step(model, data, state)

    assert st1.step == state.step + 1
    for e, f in enumerate(st1.factors):
        assert f.shape == state.factors[e].shape
        assert bool(jnp.all(jnp.isfinite(f)))
    for h in st1.hypers:
        lam = h["Lambda"]
        # precision sample must be symmetric positive definite
        assert bool(jnp.all(jnp.isfinite(lam)))
        evals = np.linalg.eigvalsh(np.asarray(lam))
        assert evals.min() > 0
    assert float(st1.noises[0]["alpha"]) > 0
    assert np.isfinite(float(metrics["rmse_train_0"]))


@st.composite
def truncnorm_problem(draw, max_n=64):
    n = draw(st.integers(1, max_n))
    seed = draw(st.integers(0, 2**31 - 1))
    scale_tenths = draw(st.integers(0, 80))     # |mean| up to 8.0
    rng = np.random.default_rng(seed)
    mean = rng.uniform(-1.0, 1.0, size=n).astype(np.float32) \
        * (scale_tenths / 10.0)
    obs = (rng.random(n) < 0.5).astype(np.float32)
    return seed, mean, obs


@settings(max_examples=30, deadline=None)
@given(truncnorm_problem())
def test_truncnorm_sign_agreement_and_finite(prob):
    """The latent draw stays finite out to |mean| = 8, and lands on
    the observation's side of 0 wherever the f32 inverse-CDF can
    resolve the tail (|mean| <= 4; beyond ~5 the 1e-7 CDF clip trades
    sign for finiteness, which the clip-to-[mean-8, mean+8] bounds)."""
    seed, mean, obs = prob
    z = np.asarray(_truncnorm(jax.random.PRNGKey(seed),
                              jnp.asarray(mean), jnp.asarray(obs)))
    assert np.isfinite(z).all(), (mean, z)
    resolvable = np.abs(mean) <= 4.0
    agree = (z > 0) == (obs > 0)
    assert agree[resolvable].all(), (mean, obs, z)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 8), st.integers(1, 24), st.integers(0, 2**31 - 1))
def test_row_uniforms_shard_slices_bitwise(n_shards, width, seed):
    """Counter-based uniforms: a shard holding rows [off, off+n) draws
    EXACTLY the bits of the full draw's slice — the probit analogue of
    the row_normals contract the distributed sweep is built on."""
    key = jax.random.PRNGKey(seed)
    rows_per = 6
    n_rows = n_shards * rows_per
    full = np.asarray(row_uniforms(key, n_rows, width))
    assert ((0.0 <= full) & (full < 1.0)).all()
    for s in range(n_shards):
        part = np.asarray(row_uniforms(key, rows_per, width,
                                       row_offset=rows_per * s))
        np.testing.assert_array_equal(part,
                                      full[rows_per * s:
                                           rows_per * (s + 1)])


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 8), st.integers(0, 2**31 - 1), st.booleans())
def test_row_bernoulli_shard_slices_bitwise(n_shards, seed, wide):
    """Counter-based Bernoulli (the SnS inclusion indicators): a shard
    holding rows [off, off+n) draws EXACTLY the bits of the full
    draw's slice, for (n_rows,) and (n_rows, W) probability shapes —
    the sibling of the row_normals/row_uniforms contracts that admits
    spike-and-slab into the distributed sweep."""
    rng = np.random.default_rng(seed)
    rows_per = 6
    n_rows = n_shards * rows_per
    shape = (n_rows, 3) if wide else (n_rows,)
    p = jnp.asarray(rng.random(shape), jnp.float32)
    key = jax.random.PRNGKey(seed)
    full = np.asarray(row_bernoulli(key, p))
    assert full.dtype == bool and full.shape == shape
    for s in range(n_shards):
        sl = slice(rows_per * s, rows_per * (s + 1))
        part = np.asarray(row_bernoulli(key, p[sl],
                                        row_offset=rows_per * s))
        np.testing.assert_array_equal(part, full[sl])


def test_row_bernoulli_tracks_probability():
    """Statistical sanity: the inclusion rate follows p."""
    key = jax.random.PRNGKey(0)
    for p in (0.1, 0.5, 0.9):
        draws = np.asarray(row_bernoulli(
            key, jnp.full((20000,), p, jnp.float32)))
        assert abs(draws.mean() - p) < 0.02, (p, draws.mean())


@settings(max_examples=10, deadline=None)
@given(st.integers(2, 4), st.integers(0, 2**31 - 1))
def test_probit_augment_shard_slices_bitwise(n_shards, seed):
    """ProbitNoise.augment(row_offset=...) on a row slice reproduces
    the matching slice of the full augmentation bit for bit (given the
    same pred slice) — what admits probit into the sharded sweep."""
    rng = np.random.default_rng(seed)
    rows_per, width = 5, 7
    n_rows = n_shards * rows_per
    pred = jnp.asarray(rng.normal(size=(n_rows, width)), jnp.float32)
    vals = jnp.asarray((rng.random((n_rows, width)) < 0.5), jnp.float32)
    mask = jnp.asarray((rng.random((n_rows, width)) < 0.8), jnp.float32)
    noise = ProbitNoise()
    state = noise.init()
    key = jax.random.PRNGKey(seed)
    z_full, _ = noise.augment(key, state, pred, vals, mask)
    for s in range(n_shards):
        sl = slice(rows_per * s, rows_per * (s + 1))
        z_part, _ = noise.augment(key, state, pred[sl], vals[sl],
                                  mask[sl], row_offset=rows_per * s)
        np.testing.assert_array_equal(np.asarray(z_part),
                                      np.asarray(z_full)[sl])


@st.composite
def chunked_dense_problem(draw, max_r=10, max_c=32, max_k=5):
    """A dense block, a fixed factor, and an UNEVEN partition of the
    fixed-factor rows into chunks (the ring exchange delivers equal
    chunks, but the chunk math must not depend on that)."""
    R = draw(st.integers(2, max_r))
    C = draw(st.integers(2, max_c))
    K = draw(st.integers(2, max_k))
    n_chunks = draw(st.integers(1, min(6, C)))
    fully = draw(st.booleans())
    seed = draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    cuts = np.sort(rng.choice(np.arange(1, C), size=n_chunks - 1,
                              replace=False)) if n_chunks > 1 else \
        np.array([], np.int64)
    bounds = [0] + [int(c) for c in cuts] + [C]
    X = rng.normal(size=(R, C)).astype(np.float32)
    mask = np.ones((R, C), np.float32) if fully else \
        (rng.random((R, C)) < 0.7).astype(np.float32)
    if not fully:
        # an all-ones mask IS the fully-observed block (dense_block
        # detects it), so a masked problem keeps one unobserved cell
        mask[rng.integers(R), rng.integers(C)] = 0.0
    F = rng.normal(size=(C, K)).astype(np.float32)
    return X, mask, F, bounds, fully


@settings(max_examples=20, deadline=None)
@given(chunked_dense_problem(), st.floats(0.5, 4.0))
def test_dense_chunk_moments_match_monolithic(prob, alpha):
    """Chunk-accumulated dense Gram/RHS (the ring pipeline's per-hop
    fold, ``_dense_chunk_contrib``) equals the monolithic
    ``_dense_contrib`` moments over any partition of the fixed-factor
    rows — uneven widths, masked payloads, and the all-ones-mask
    ``fully=True`` shared-Gram fast path — up to f32 summation order."""
    X, mask, F, bounds, fully = prob
    payload = dense_block(X, None if fully else mask)
    assert payload.fully == fully
    noise = FixedGaussian(alpha)
    u = jnp.zeros((X.shape[0], F.shape[1]), jnp.float32)
    gs_m, gr_m, rhs_m = _dense_contrib(payload, True, jnp.asarray(F), u,
                                       noise, noise.init(),
                                       jax.random.PRNGKey(0))
    gs = gr = None
    rhs = jnp.zeros_like(rhs_m)
    vals, msk = payload.oriented(True)
    for c0, c1 in zip(bounds, bounds[1:]):
        dgs, dgr, drh = _dense_chunk_contrib(vals, msk, fully,
                                             jnp.asarray(F[c0:c1]),
                                             jnp.asarray(c0))
        if dgs is not None:
            gs = dgs if gs is None else gs + dgs
        if dgr is not None:
            gr = dgr if gr is None else gr + dgr
        rhs = rhs + drh
    scale = float(jnp.max(jnp.abs(gs_m if gr_m is None else gr_m))) + 1.0
    if fully:
        assert gr_m is None and gr is None
        np.testing.assert_allclose(np.asarray(alpha * gs),
                                   np.asarray(gs_m), atol=1e-4 * scale)
    else:
        assert gs_m is None and gs is None
        np.testing.assert_allclose(np.asarray(alpha * gr),
                                   np.asarray(gr_m), atol=1e-4 * scale)
    np.testing.assert_allclose(np.asarray(alpha * rhs),
                               np.asarray(rhs_m), rtol=1e-4, atol=1e-4)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 8), st.integers(0, 7), st.integers(2, 6),
       st.integers(0, 2**31 - 1))
def test_ring_view_reassembly_bitwise(n_shards, start, width, seed):
    """The ring's view reassembly (``dynamic_update_slice`` of equal
    chunks, visited in the shard-dependent rotation ``(s + t) % S``)
    rebuilds EXACTLY the gathered array — pure data movement, no
    arithmetic — for every shard's rotation of the chunk order.  This
    is what makes the ring chain bitwise the eager chain on every
    gather-indexed (sparse/SnS/probit/metrics) path."""
    rows_per = 5
    rng = np.random.default_rng(seed)
    full = rng.normal(size=(n_shards * rows_per, width)) \
        .astype(np.float32)
    s0 = start % n_shards
    out = jnp.zeros_like(full)
    for t in range(n_shards):
        owner = (s0 + t) % n_shards
        chunk = jnp.asarray(full[owner * rows_per:
                                 (owner + 1) * rows_per])
        out = jax.lax.dynamic_update_slice(
            out, chunk, (jnp.asarray(owner * rows_per), 0))
    np.testing.assert_array_equal(np.asarray(out), full)


@settings(max_examples=15, deadline=None)
@given(sparse_problem())
def test_with_coo_values_consistent(prob):
    n, m, i, j, v = prob
    mat = from_coo(i, j, v, (n, m))
    # COO view is padded: provide one value per padded slot
    new_v = (jnp.arange(1, mat.coo_v.shape[0] + 1, dtype=jnp.float32)
             * mat.coo_mask)
    mat2 = mat.with_coo_values(new_v)
    # both orientations must carry exactly the new values
    assert float(jnp.sum(mat2.rows.val * mat2.rows.mask)) == \
        float(jnp.sum(new_v * mat.coo_mask))
    assert float(jnp.sum(mat2.cols.val * mat2.cols.mask)) == \
        float(jnp.sum(new_v * mat.coo_mask))
