"""The Gibbs sweep (paper Algorithm 1) as pure, jit-able JAX.

One ``gibbs_step`` performs, per entity in order:

  1. resample the entity's prior hyper-parameters from its current
     factor matrix ("sample hyper-parameters ... based on U/V"),
  2. resample the whole factor matrix from its conditional
     ("for all movies/users: update model") — one *batched* pass:
     masked Gram + rhs (Pallas kernel or jnp oracle), batched Cholesky,
     batched triangular solves, one fused N(0,1) draw,

then resamples every block's noise state from the residuals and reports
train-RMSE metrics.

The CPU original loops rows with OpenMP; here the full half-sweep is a
handful of large dense ops, which is what the TPU (and the distributed
layer in ``distributed.py``) wants.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.lax.linalg import cholesky, triangular_solve

from .. import compat
from ..kernels import ops
from .blocks import DenseBlock, ModelDef
from .noise import ProbitNoise
from .priors import MacauPrior, SpikeAndSlabPrior, chol_solve
from .sparse import SparseMatrix


class MFState(NamedTuple):
    """Full sampler state — everything needed to restart the chain."""

    key: jax.Array                      # PRNG key (counter-based)
    factors: Tuple[jnp.ndarray, ...]    # per entity (N_e, K)
    hypers: Tuple[Any, ...]             # per entity prior hyper pytree
    noises: Tuple[Any, ...]             # per block noise state pytree
    step: jnp.ndarray                   # int32 sweep counter


class MFData(NamedTuple):
    """Observed data — static across the chain."""

    blocks: Tuple[Any, ...]             # SparseMatrix | DenseBlock
    sides: Tuple[Optional[jnp.ndarray], ...]   # per entity side info


def init_state(model: ModelDef, data: MFData, seed: int = 0,
               init_scale: float = 1.0,
               key: Optional[jax.Array] = None) -> MFState:
    """Fresh chain state from the STATIC graph alone — ``data`` is
    accepted for signature symmetry but never read.  That contract is
    load-bearing: ``modelspec.state_template`` rebuilds checkpoint
    templates from a ``model.json`` spec with no data payloads, so any
    future data-dependent initialization must stay out of the state
    *structure*.

    ``key`` overrides the ``PRNGKey(seed)`` derivation — the multi-chain
    layer passes ``chain_keys(seed, C)[c]`` here so chain ``c`` of a
    C-chain run is exactly the single-chain run seeded with that key.
    """
    if key is None:
        key = jax.random.PRNGKey(seed)
    keys = jax.random.split(key, len(model.entities) + 1)
    factors = []
    hypers = []
    for e, ent in enumerate(model.entities):
        factors.append(init_scale * jax.random.normal(
            keys[e], (ent.n_rows, model.num_latent), jnp.float32))
        hypers.append(ent.prior.init(keys[e], ent.n_rows))
    noises = tuple(b.noise.init() for b in model.blocks)
    return MFState(keys[-1], tuple(factors), tuple(hypers), noises,
                   jnp.asarray(0, jnp.int32))


# ---------------------------------------------------------------------------
# multi-chain helpers
# ---------------------------------------------------------------------------

def chain_keys(seed: int, chains: int):
    """Per-chain root PRNG keys.

    Chain 0 is ``PRNGKey(seed)`` — NOT folded — so chain 0 of any
    C-chain run is bitwise the existing single-chain golden chain.
    Chains 1..C-1 fold the chain index into the base key.
    """
    base = jax.random.PRNGKey(seed)
    return [base if c == 0 else jax.random.fold_in(base, c)
            for c in range(chains)]


def init_chain_states(model: ModelDef, data: MFData, seed: int,
                      chains: int, init_scale: float = 1.0):
    """List of C independent fresh states (one per chain key)."""
    return [init_state(model, data, seed, init_scale, key=k)
            for k in chain_keys(seed, chains)]


def stack_states(states) -> MFState:
    """Stack per-chain states along a new leading chain axis."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)


def unstack_state(stacked: MFState, c: int) -> MFState:
    """Slice chain ``c`` out of a stacked multi-chain state."""
    return jax.tree_util.tree_map(lambda x: x[c], stacked)


def multi_chain_step(model: ModelDef, data: MFData, stacked: MFState
                     ) -> Tuple[MFState, Dict[str, jnp.ndarray]]:
    """One Gibbs sweep of every chain in a stacked state.

    Maps ``gibbs_step`` over the leading chain axis with ``lax.map``
    rather than ``vmap``: vmap batches the per-chain ops into wider
    kernels whose reductions tile differently, drifting ~1e-6 from the
    single-chain program, while ``lax.map`` keeps each chain's subgraph
    identical to ``gibbs_step`` — measured bitwise-equal to C
    independent seeded runs.  Metrics come back stacked with a leading
    ``(C,)`` axis.
    """
    return jax.lax.map(lambda st: gibbs_step(model, data, st), stacked)


@partial(jax.jit, static_argnums=0)
def multi_chain_step_jit(model: ModelDef, data: MFData, stacked: MFState):
    """Jitted ``multi_chain_step`` (single-device multi-chain path)."""
    return multi_chain_step(model, data, stacked)


# ---------------------------------------------------------------------------
# per-block contributions to an entity's conditional
# ---------------------------------------------------------------------------

def _sparse_contrib(model: ModelDef, mat: SparseMatrix, as_row: bool,
                    fixed: jnp.ndarray, u_cur: jnp.ndarray,
                    noise, nstate, key, row_offset=0):
    """alpha-weighted (gram, rhs) of one sparse block for one entity.

    ``row_offset`` is the global index of the operand's row 0 — nonzero
    on row shards of the distributed sweep, where it keeps the probit
    augmentation draws bitwise slices of the single-device draws.
    """
    padded = mat.rows if as_row else mat.cols
    with jax.named_scope("gather"):
        vg = fixed[padded.idx]                  # (R, T, K)
    if isinstance(noise, ProbitNoise):
        pred = jnp.einsum("rtk,rk->rt", vg, u_cur)
        vals, alpha = noise.augment(key, nstate, pred, padded.val,
                                    padded.mask, row_offset=row_offset)
    else:
        vals, alpha = noise.augment(key, nstate, None, padded.val,
                                    padded.mask, row_offset=row_offset)
    with jax.named_scope("gram"):
        gram, rhs = ops.gram_and_rhs(vg, vals, padded.mask,
                                     use_pallas=model.use_pallas)
        return alpha * gram, alpha * rhs        # (R,K,K), (R,K)


def _dense_contrib(payload: DenseBlock, as_row: bool, fixed: jnp.ndarray,
                   u_cur: jnp.ndarray, noise, nstate, key, row_offset=0):
    """Contributions of a dense block.

    Returns (gram_shared | None, gram_rows | None, rhs).  Reads the
    stored orientation (``X`` or ``XT``) rather than transposing, so
    inside the distributed sweep a shard's slice of either orientation
    is self-contained (see ``DenseBlock``); ``row_offset`` as in
    ``_sparse_contrib``.
    """
    X, m = payload.oriented(as_row)             # (R, C)
    if isinstance(noise, ProbitNoise):
        pred = u_cur @ fixed.T
        vals, alpha = noise.augment(key, nstate, pred, X, m,
                                    row_offset=row_offset)
    else:
        vals, alpha = noise.augment(key, nstate, None, X, m,
                                    row_offset=row_offset)
    with jax.named_scope("gram"):
        if payload.fully:
            gram_shared = alpha * (fixed.T @ fixed)         # (K, K)
            rhs = alpha * (vals @ fixed)                    # (R, K)
            return gram_shared, None, rhs
        gram_rows = alpha * jnp.einsum("rc,ck,cl->rkl", m, fixed, fixed)
        rhs = alpha * ((vals * m) @ fixed)
        return None, gram_rows, rhs


def _dense_chunk_contrib(vals: jnp.ndarray, m: jnp.ndarray, fully: bool,
                         chunk: jnp.ndarray, c0):
    """Chunk-accumulating form of ``_dense_contrib``'s moment math.

    ``chunk`` holds rows ``[c0, c0 + Cc)`` of the fixed factor (one
    ring-exchange hop's worth); ``vals``/``m`` are the full oriented
    (R, C) payload, already noise-augmented.  Returns this chunk's
    additive contribution ``(gram_shared | None, gram_rows | None,
    rhs)``.  Summed over any partition of ``[0, C)`` the contributions
    equal the monolithic moments up to f32 summation order — the
    per-chunk compute the ring pipeline overlaps with the next hop's
    ``ppermute`` (property-tested against the monolithic forms in
    ``tests/test_properties.py``, including the ``fully=True`` shared-
    Gram fast path and uneven chunk widths).  The alpha weight is
    applied by the caller AFTER accumulation, not per chunk.
    """
    Cc = chunk.shape[0]
    vs = jax.lax.dynamic_slice_in_dim(vals, c0, Cc, axis=1)
    if fully:
        return chunk.T @ chunk, None, vs @ chunk
    ms = jax.lax.dynamic_slice_in_dim(m, c0, Cc, axis=1)
    gram_rows = jnp.einsum("rc,ck,cl->rkl", ms, chunk, chunk)
    return None, gram_rows, (vs * ms) @ chunk


# ---------------------------------------------------------------------------
# factor conditionals
# ---------------------------------------------------------------------------

def row_normals(key, n_rows: int, num_latent: int, row_offset=0):
    """(n_rows, K) standard normals drawn row-by-row, counter-based.

    Row i's draw comes from ``fold_in(key, row_offset + i)`` — a pure
    function of the sweep key and the row's GLOBAL index, never of the
    batch shape.  A shard holding rows [off, off + n) therefore draws
    exactly the bits the single-device sweep draws for those rows,
    which is what makes the distributed chain bit-compatible with the
    reference chain (and elastic re-meshes safe).

    Probit's truncated-normal augmentation obeys the same contract
    through :func:`row_uniforms` below — every stochastic per-row
    quantity in the sweep is a counter-based function of the global
    row index, so the whole model zoo (Gaussian AND probit, sparse AND
    dense) re-meshes without perturbing the chain.
    """
    rows = row_offset + jnp.arange(n_rows)
    keys = jax.vmap(lambda r: jax.random.fold_in(key, r))(rows)
    return jax.vmap(
        lambda k: jax.random.normal(k, (num_latent,), jnp.float32))(keys)


def row_uniforms(key, n_rows: int, width: int, row_offset=0, *,
                 minval=0.0, maxval=1.0):
    """(n_rows, width) uniforms drawn row-by-row, counter-based.

    The uniform sibling of :func:`row_normals`, with the identical
    contract: row i's ``width`` draws come from
    ``fold_in(key, row_offset + i)`` — a pure function of the sweep
    key and the row's GLOBAL index, never of the batch shape.  This is
    what ``ProbitNoise.augment`` consumes for its truncated-normal
    latents, so probit shard draws are bitwise slices of the
    single-device chain exactly like the factor draws above.
    """
    rows = row_offset + jnp.arange(n_rows)
    keys = jax.vmap(lambda r: jax.random.fold_in(key, r))(rows)
    return jax.vmap(
        lambda k: jax.random.uniform(k, (width,), jnp.float32,
                                     minval, maxval))(keys)


def row_bernoulli(key, p, row_offset=0):
    """Bernoulli(p) draws, counter-based row-by-row.

    ``p`` is (n_rows,) or (n_rows, W); row i's draw(s) consume the
    uniforms of ``fold_in(key, row_offset + i)`` via
    :func:`row_uniforms` — the same contract as ``row_normals``: a
    pure function of the sweep key and the row's GLOBAL index, never
    of the batch shape.  This is what the spike-and-slab inclusion
    indicators consume (folded per component), so SnS shard draws are
    bitwise slices of the single-device chain and the GFA composition
    can run the explicit distributed sweep.
    """
    n_rows = p.shape[0]
    width = 1 if p.ndim == 1 else p.shape[1]
    u = row_uniforms(key, n_rows, width, row_offset)
    if p.ndim == 1:
        u = u[:, 0]
    return u < p


def _sample_normal_factor(key, gram_shared, gram_rows, rhs, Lam_p, b_p,
                          row_offset=0):
    """u_i ~ N(Lam_i^{-1} b_i, Lam_i^{-1}) batched over rows.

    gram_shared (K,K) and/or gram_rows (N,K,K); rhs (N,K); Lam_p (K,K);
    b_p (K,) or (N,K).  ``row_offset`` is the global index of row 0 —
    nonzero on row shards of the distributed sweep.
    """
    b = rhs + b_p if b_p.ndim == 2 else rhs + b_p[None, :]
    z = row_normals(key, b.shape[0], b.shape[1], row_offset)
    if gram_rows is None:
        # one shared precision -> one Cholesky, matrix solves
        Lam = gram_shared + Lam_p                            # (K,K)
        L = cholesky(Lam)
        y = triangular_solve(L, b.T, left_side=True, lower=True)
        mean = triangular_solve(L, y, left_side=True, lower=True,
                                transpose_a=True).T          # (N,K)
        dz = triangular_solve(L, z.T, left_side=True, lower=True,
                              transpose_a=True).T
        return mean + dz
    Lam = gram_rows + (gram_shared + Lam_p)[None, :, :] \
        if gram_shared is not None else gram_rows + Lam_p[None, :, :]
    L = cholesky(Lam)                                        # (N,K,K)
    mean = chol_solve(L, b)
    dz = triangular_solve(L, z[..., None], left_side=True, lower=True,
                          transpose_a=True)[..., 0]
    return mean + dz


def _sample_sns_factor(model: ModelDef, data: MFData, key,
                       e: int, u: jnp.ndarray, hyper,
                       fixed_for, noises, row_offset=0) -> jnp.ndarray:
    """Coordinate-wise spike-and-slab update for entity ``e``.

    For each latent component k (sequentially — the conditionals are
    coupled through the residual), vectorized over rows:

        q_ik = tau_k + sum_b alpha_b sum_t m f_k^2
        l_ik = sum_b alpha_b sum_t m (r - pred_{-k}) f_k
        odds = rho/(1-rho) * sqrt(tau_k/q) * exp(l^2 / 2q)
        s ~ Bern(odds/(1+odds));  u_ik = s * N(l/q, 1/q)

    ``fixed_for(o)`` returns the dense (pre-gathered) fixed factor of
    entity ``o``; ``u`` and the block payload rows may be a row shard,
    with ``row_offset`` the global index of row 0.  Both q and l are
    row-local, and every stochastic quantity — the Bernoulli inclusion
    indicator (``row_bernoulli``) and the slab normal (``row_normals``),
    each folded per component — is a counter-based function of the
    GLOBAL row index, so this body runs unchanged inside
    ``distributed._sharded_sweep`` and shard draws are bitwise slices
    of the single-device chain.
    """
    K = model.num_latent
    touching = model.blocks_touching(e)

    # gather per-block views once
    views = []
    for bi, as_row in touching:
        blk = model.blocks[bi]
        payload = data.blocks[bi]
        fixed = fixed_for(blk.other(e))
        alpha = noises[bi]["alpha"]
        if blk.sparse:
            padded = payload.rows if as_row else payload.cols
            vg = fixed[padded.idx]                     # (R,T,K)
            pred = jnp.einsum("rtk,rk->rt", vg, u)
            views.append(("sp", vg, padded.val, padded.mask, pred, alpha))
        else:
            X, m = payload.oriented(as_row)
            pred = u @ fixed.T
            kind = "df" if payload.fully else "dn"
            views.append((kind, fixed, X, m, pred, alpha))

    rho, tau = hyper["rho"], hyper["tau"]
    k_incl, k_slab = jax.random.split(key)

    # The K coordinate updates are a lax.scan, not a Python loop, so
    # large-K GFA compiles one body instead of K copies (flat compile
    # time; carried over from PR 3's TODO).  Kinds and the per-view
    # constants (Fv, val, m, alpha) are loop-invariant closures; the
    # carry is (u, per-view residual predictions).  Every indexed read
    # (Fv[..., k], tau[k], rho[k]) and the per-component ``fold_in``
    # take the traced k, which lowers to gathers/dynamic-slices with
    # the same values as the unrolled loop — the golden GFA chains pin
    # this bitwise.
    kinds = tuple(v[0] for v in views)
    consts = tuple((Fv, val, m, alpha)
                   for _, Fv, val, m, _, alpha in views)
    preds0 = tuple(v[4] for v in views)

    def body(carry, k):
        u, preds = carry
        q = tau[k]
        l = jnp.zeros((u.shape[0],), jnp.float32)
        new_preds = []
        for kind, (Fv, val, m, alpha), pred in zip(kinds, consts, preds):
            if kind == "sp":
                fk = Fv[:, :, k]                        # (R,T)
                pred_mk = pred - u[:, k][:, None] * fk
                q = q + alpha * jnp.sum(fk * fk * m, axis=-1)
                l = l + alpha * jnp.sum((val - pred_mk) * m * fk, axis=-1)
            elif kind == "df":
                fk = Fv[:, k]                           # (C,)
                pred_mk = pred - jnp.outer(u[:, k], fk)
                # fully observed: every row shares the one scalar
                # sum_c fk_c^2 and the mask multiply drops — the GFA
                # production views take this branch, saving an
                # O(rows x cols) matvec per component per view
                q = q + alpha * jnp.sum(fk * fk)
                l = l + alpha * ((val - pred_mk) @ fk)
            else:
                fk = Fv[:, k]                           # (C,)
                pred_mk = pred - jnp.outer(u[:, k], fk)
                # masked: sum_c m_rc fk_c^2  (per row)
                q = q + alpha * (m @ (fk * fk))
                l = l + alpha * (((val - pred_mk) * m) @ fk)
            new_preds.append(pred_mk)

        mu = l / q
        log_odds = (jnp.log(rho[k]) - jnp.log1p(-rho[k])
                    + 0.5 * (jnp.log(tau[k]) - jnp.log(q))
                    + 0.5 * mu * l)
        p_incl = jax.nn.sigmoid(log_odds)
        s = row_bernoulli(jax.random.fold_in(k_incl, k), p_incl,
                          row_offset).astype(jnp.float32)
        eps = row_normals(jax.random.fold_in(k_slab, k), u.shape[0], 1,
                          row_offset)[:, 0]
        u_k = s * (mu + eps / jnp.sqrt(q))
        u = u.at[:, k].set(u_k)

        # restore preds with the new component folded back in
        restored = tuple(
            pred_mk + (u_k[:, None] * Fv[:, :, k] if kind == "sp"
                       else jnp.outer(u_k, Fv[:, k]))
            for kind, (Fv, _, _, _), pred_mk in
            zip(kinds, consts, new_preds))
        return (u, restored), None

    (u, _), _ = jax.lax.scan(body, (u, preds0), jnp.arange(K))
    return u


# ---------------------------------------------------------------------------
# the full sweep
# ---------------------------------------------------------------------------

def _gather_view(model: ModelDef, factors):
    """The factor views used as gather/contraction operands.

    With ``bf16_gather`` every consumer (half-sweep gathers, SDDMM
    metrics) shares ONE bf16 copy, so the sharded all-gather moves
    half the bytes and is CSE'd across uses — casting inside each
    consumer instead makes XLA materialize both precisions (measured:
    2x the collective bytes, not 0.5x).
    """
    if not model.bf16_gather:
        return factors

    mesh = compat.get_abstract_mesh()
    axes = () if mesh is None else tuple(
        a for a in ("pod", "data", "model") if a in mesh.axis_names)
    n = 1
    for a in axes:
        n *= mesh.shape[a]

    def cast(f):
        if not axes or f.shape[0] % n != 0:
            return f.astype(jnp.bfloat16)
        # EXPLICIT bf16 all-gather.  Leaving this to the partitioner
        # does not work: XLA's algebraic simplifier sinks the bf16
        # convert past any volume-reducing gather, so the implicit
        # all-gather moves f32 again (measured: 2x wire bytes).  An
        # explicit collective on the bf16 shard cannot be rewritten.
        def body(x):
            return jax.lax.all_gather(x.astype(jnp.bfloat16), axes,
                                      axis=0, tiled=True)

        with jax.named_scope("exchange"):
            return compat.shard_map(
                body, mesh=mesh,
                in_specs=jax.sharding.PartitionSpec(axes),
                out_specs=jax.sharding.PartitionSpec(),
                check=False)(f)

    return tuple(cast(f) for f in factors)


def _entity_update(model: ModelDef, data: MFData, key, e: int,
                   factors, hypers, noises):
    """Hyper-sample + factor-sample for one entity; returns updates."""
    ent = model.entities[e]
    prior = ent.prior
    side = data.sides[e]
    k_hyp, k_fac, k_blk = jax.random.split(key, 3)
    u = factors[e]

    # 1. hyper-parameters from the current factor (Algorithm 1 line 2/5)
    with jax.named_scope("hyper"):
        if isinstance(prior, MacauPrior):
            hyper = prior.sample_hyper(k_hyp, u, hypers[e], side=side)
        else:
            hyper = prior.sample_hyper(k_hyp, u, hypers[e])

    # 2. factor matrix from its conditional
    gview = _gather_view(model, factors)
    if isinstance(prior, SpikeAndSlabPrior):
        u_new = _sample_sns_factor(model, data, k_fac, e, u, hyper,
                                   lambda o: gview[o], noises)
        return u_new, hyper

    with jax.named_scope("hyper"):
        Lam_p = prior.precision_term(hyper)
        if isinstance(prior, MacauPrior):
            b_p = prior.mean_term(hyper, ent.n_rows, side=side)
        else:
            b_p = prior.mean_term(hyper, ent.n_rows)

    gram_shared = None
    gram_rows = None
    rhs_acc = jnp.zeros((ent.n_rows, model.num_latent), jnp.float32)
    bkeys = jax.random.split(k_blk, max(1, len(model.blocks)))
    for bi, as_row in model.blocks_touching(e):
        blk = model.blocks[bi]
        fixed = gview[blk.other(e)]
        if blk.sparse:
            g, r = _sparse_contrib(model, data.blocks[bi], as_row, fixed,
                                   u, blk.noise, noises[bi], bkeys[bi])
            gram_rows = g if gram_rows is None else gram_rows + g
            rhs_acc = rhs_acc + r
        else:
            gs, gr, r = _dense_contrib(data.blocks[bi], as_row, fixed,
                                       u, blk.noise, noises[bi], bkeys[bi])
            if gs is not None:
                gram_shared = gs if gram_shared is None else gram_shared + gs
            if gr is not None:
                gram_rows = gr if gram_rows is None else gram_rows + gr
            rhs_acc = rhs_acc + r

    if gram_shared is None and gram_rows is None:
        gram_shared = jnp.zeros((model.num_latent, model.num_latent),
                                jnp.float32)
    with jax.named_scope("solve"):
        u_new = _sample_normal_factor(k_fac, gram_shared, gram_rows,
                                      rhs_acc, Lam_p, b_p)
    return u_new, hyper


def _block_pred_observed(model: ModelDef, data: MFData, bi: int, factors):
    """Predictions + (vals, mask) at a block's observed entries."""
    blk = model.blocks[bi]
    U = factors[blk.row_entity]
    V = factors[blk.col_entity]
    payload = data.blocks[bi]
    if blk.sparse:
        pred = ops.sddmm(U[payload.coo_i], V[payload.coo_j],
                         use_pallas=model.use_pallas)
        return pred, payload.coo_v, payload.coo_mask
    pred = U @ V.T
    return pred, payload.X, payload.mask


@partial(jax.jit, static_argnums=0)
def gibbs_step(model: ModelDef, data: MFData, state: MFState
               ) -> Tuple[MFState, Dict[str, jnp.ndarray]]:
    """One full Gibbs sweep over all entities + noise states."""
    key, *ekeys = jax.random.split(state.key, len(model.entities) + 2)
    nkey = ekeys[-1]
    factors = list(state.factors)
    hypers = list(state.hypers)
    noises = list(state.noises)

    for e in range(len(model.entities)):
        u_new, hyper = _entity_update(model, data, ekeys[e], e,
                                      tuple(factors), tuple(hypers),
                                      tuple(noises))
        factors[e] = u_new
        hypers[e] = hyper

    metrics = {}
    nkeys = jax.random.split(nkey, max(1, len(model.blocks)))
    gview = _gather_view(model, tuple(factors))
    for bi, blk in enumerate(model.blocks):
        with jax.named_scope("residuals"):
            pred, vals, mask = _block_pred_observed(model, data, bi, gview)
        with jax.named_scope("noise"):
            noises[bi] = blk.noise.sample_state(nkeys[bi], noises[bi],
                                                pred, vals, mask)
        with jax.named_scope("metrics"):
            se = jnp.sum(((vals - pred) * mask) ** 2)
            # all-masked blocks (padded shard views) have nnz == 0:
            # report rmse 0 instead of 0/0 -> NaN poisoning the trace
            metrics[f"rmse_train_{bi}"] = jnp.sqrt(
                se / jnp.maximum(jnp.sum(mask), 1.0))
        metrics[f"alpha_{bi}"] = noises[bi]["alpha"]

    new_state = MFState(key, tuple(factors), tuple(hypers), tuple(noises),
                        state.step + 1)
    return new_state, metrics


@partial(jax.jit, static_argnums=(0, 3))
def run_sweeps(model: ModelDef, data: MFData, state: MFState, n: int):
    """``lax.scan`` over n sweeps; returns final state + stacked metrics.

    Used by benchmarks to amortize dispatch overhead; the session layer
    uses single ``gibbs_step`` calls to collect posterior samples.
    """

    def body(st, _):
        st, m = gibbs_step(model, data, st)
        return st, m

    return jax.lax.scan(body, state, None, length=n)
