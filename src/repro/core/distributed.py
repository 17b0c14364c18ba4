"""Distributed Gibbs sweep — the paper's §7 future work, realized.

SMURFF was single-node OpenMP; the GASPI multi-node port was a separate
code base.  Here the sweep distributes through an EXPLICIT ``shard_map``
over the production mesh (``compat.shard_map`` — version-portable):

* rows of every factor (and the corresponding padded-CSR block rows)
  are sharded over all mesh axes flattened — the MF analogue of the
  paper's parallel-for over users/movies, but across chips;
* the *fixed* factor of each half-sweep is needed (in full) by every
  chip.  HOW it travels is the ``pipeline`` knob of
  ``make_distributed_step`` (default from the ``REPRO_PIPELINE``
  environment variable, else ``"eager"``):

  - ``"eager"``: exactly ONE explicit ``all_gather`` per half-sweep
    (bf16 when ``ModelDef.bf16_gather`` — cast BEFORE the collective,
    halving the wire bytes), matching the GASPI implementation's
    communication pattern (Vander Aa et al. 2017); the gather of the
    final factor is reused for the residual metrics, so a sweep over
    E entities moves exactly E gathers;
  - ``"ring"``: the same bytes travel as ``n_shards - 1``
    ``lax.ppermute`` hops around the flattened mesh ring
    (``_ring_accumulate``) — ZERO all-gathers in the program, and the
    hop for chunk t+1 is issued before chunk t is consumed, so the
    wire transfer overlaps the local math (the asynchronous /
    limited-communication BMF exchange of arXiv:1705.10633 and
    arXiv:2004.02561).  Dense non-probit blocks of the earlier
    half-sweep consume the circulating chunks directly through
    chunk-accumulated Gram/RHS moments (``gibbs._dense_chunk_contrib``)
    and never materialize the dense fixed view at all; every other
    consumer (padded-CSR gathers, probit's pred-dependent
    augmentation, the SnS coordinate loop, end-of-sweep metrics)
    reassembles the view from the chunks by ``dynamic_update_slice``
    — bitwise the all-gathered array, so those chains are
    draw-for-draw the eager chains.  Ring-vs-eager parity and the
    collective-permute/no-all-gather HLO contract are pinned in
    ``tests/test_distributed.py``; the overlap-aware exchange term is
    modeled in ``launch/mf_dryrun.py`` (eager stays the default until
    that term wins on the target).
* the Normal-Wishart hyper-sample needs global factor moments: those
  reduce over the row shards with K- and K^2-sized ``psum`` payloads
  (D-sized for the Macau link terms) and are then resampled as an
  identical replicated computation on every shard;
* dense blocks shard the same way: both stored orientations
  (``DenseBlock.X``/``XT``) are row-sharded along their leading axis,
  and each shard's Gram/RHS contribution contracts its slice against
  the gathered fixed factor — fully-observed blocks additionally share
  ONE replicated (K, K) Gram across all rows;
* probit noise rides through the same machinery because its
  truncated-normal augmentation is per-row counter-based
  (``gibbs.row_uniforms`` threaded through ``ProbitNoise.augment`` via
  ``row_offset``) — the compound-activity classification workload of
  the paper runs the explicit sweep, not the pjit fallback;
* the Macau side-Gramian ``FtF = side^T side`` is STATIC data: it is
  computed once at ``make_distributed_step`` placement time and passed
  in replicated, so the per-sweep hyper path carries no (D, D) psum;
* spike-and-slab priors (the GFA composition, paper Table 1
  "Normal + SnS") run the same schedule: the coordinate-wise q/l
  moments are row-local given the gathered fixed factor, so the
  per-component loop adds ZERO collectives, and the hyper update
  reduces exactly two K-sized psums (inclusion counts + per-component
  sum of squares, ``SpikeAndSlabPrior.sample_hyper_moments``); the
  inclusion indicators and slab normals are counter-based per row
  (``gibbs.row_bernoulli``/``row_normals``, folded per component);
* counter-based per-row RNG (``gibbs.row_normals`` for the factor
  draws, ``gibbs.row_uniforms`` for the probit latents,
  ``gibbs.row_bernoulli`` for the SnS inclusions) means each
  shard draws exactly the bits the single-device sweep draws for its
  rows (asserted bitwise in tests), so the sampled chain agrees with
  the single-device chain up to reduction-order ULPs — psum grouping
  of the K/K^2 moments and XLA's batch-size-dependent tiling of the
  per-row solves; measured ~1e-5 after 3 sweeps, asserted at 2e-4 —
  which is what makes elastic restart onto a different mesh safe.
  Verified against the single-device chain on 8 simulated CPU devices
  in ``tests/test_distributed.py`` (Gaussian, probit, dense-block, and
  spike-and-slab/GFA models) and through an on-disk checkpoint +
  shrunk-mesh restore in ``tests/test_elastic.py``.

Models outside the sharded subset (self-blocks, row counts that do not
divide the mesh) fall back to auto-sharded pjit over the same
shardings — slower collectives, same results.  Every prior in the
paper's Table 1 now runs the explicit sweep.

``FACTOR_AXES`` flattens ("pod", "data", "model") — MF has no tensor
axis worth model-parallelism (K is tiny), so every chip takes a row
slice.  This gives perfect load balance by construction (padded rows).
"""
from __future__ import annotations

import os
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import compat
from .blocks import DenseBlock, ModelDef
from .gibbs import (MFData, MFState, _dense_chunk_contrib, _dense_contrib,
                    _sample_normal_factor, _sample_sns_factor,
                    _sparse_contrib, gibbs_step)
from .noise import AdaptiveGaussian, FixedGaussian, ProbitNoise
from .priors import (FixedNormalPrior, MacauPrior, NormalPrior,
                     SpikeAndSlabPrior)

FACTOR_AXES = ("pod", "data", "model")

PIPELINES = ("eager", "ring")

# below this shard count the ring loop is unrolled (tests pin one
# collective-permute per hop on the HLO); above it a lax.scan keeps the
# program size flat (production meshes: one while loop, trip S - 1,
# which launch/hlo_cost.py multiplies back out)
RING_UNROLL_MAX = 32


def resolve_pipeline(pipeline: Optional[str] = None) -> str:
    """Validate the exchange-pipeline knob, defaulting from the
    ``REPRO_PIPELINE`` environment variable (CI runs a ring leg that
    way), else ``"eager"``."""
    if pipeline is None:
        pipeline = os.environ.get("REPRO_PIPELINE", "eager")
    if pipeline not in PIPELINES:
        raise ValueError(
            f"unknown pipeline {pipeline!r}; valid pipelines: "
            f"{', '.join(PIPELINES)} (the REPRO_PIPELINE environment "
            "variable sets the default)")
    return pipeline


def _axes_in(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in FACTOR_AXES if a in mesh.axis_names)


def row_sharding(mesh: Mesh) -> NamedSharding:
    """Shard axis 0 over every mesh axis; replicate the rest."""
    return NamedSharding(mesh, P(_axes_in(mesh)))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def _n_shards(mesh: Mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in _axes_in(mesh)]))


def _fit_rows_spec(mesh: Mesh, x) -> P:
    """Row-shard when the leading dim divides the mesh, else replicate
    (elastic re-meshes may not divide the COO padding width)."""
    if hasattr(x, "ndim") and x.ndim >= 1 \
            and x.shape[0] % _n_shards(mesh) == 0:
        return P(_axes_in(mesh))
    return P()


def state_specs(model: ModelDef, mesh: Mesh, state: MFState) -> MFState:
    """PartitionSpec pytree matching an MFState: factors row-sharded,
    hyper/noise state replicated (they are K-sized)."""
    factors = tuple(_fit_rows_spec(mesh, f) for f in state.factors)
    hypers = jax.tree.map(lambda x: P(), state.hypers)
    noises = jax.tree.map(lambda x: P(), state.noises)
    return MFState(P(), factors, hypers, noises, P())


def stacked_state_specs(model: ModelDef, mesh: Mesh, stacked: MFState,
                        chain_axis: Optional[str] = None) -> MFState:
    """PartitionSpec pytree for a chain-stacked ``(C, ...)`` MFState.

    The leading chain dim shards over ``chain_axis`` when given (chains
    x shards fills the mesh) and replicates otherwise; factor ROWS (now
    axis 1) shard over the FACTOR_AXES exactly as in ``state_specs``.
    """
    ca = chain_axis

    def fit_rows(x):
        if hasattr(x, "ndim") and x.ndim >= 2 \
                and x.shape[1] % _n_shards(mesh) == 0:
            return P(ca, _axes_in(mesh))
        return P(ca)

    factors = tuple(fit_rows(f) for f in stacked.factors)
    hypers = jax.tree.map(lambda x: P(ca), stacked.hypers)
    noises = jax.tree.map(lambda x: P(ca), stacked.noises)
    return MFState(P(ca), factors, hypers, noises, P(ca))


def data_specs(model: ModelDef, mesh: Mesh, data: MFData) -> MFData:
    """Both padded orientations row-sharded; COO and sides likewise.

    Any leaf whose leading dim does not divide the shard count falls
    back to replication — the fit rule that keeps elastic re-meshes
    onto awkward survivor counts legal.  (The COO view only drives
    test-point prediction and adaptive noise.)
    """

    def for_block(blk):
        return jax.tree.map(lambda x: _fit_rows_spec(mesh, x), blk)

    blocks = tuple(for_block(b) for b in data.blocks)
    sides = tuple(None if s is None else _fit_rows_spec(mesh, s)
                  for s in data.sides)
    return MFData(blocks, sides)


def _with_mesh(mesh: Mesh, tree):
    return jax.tree.map(lambda spec: NamedSharding(mesh, spec), tree,
                        is_leaf=lambda x: isinstance(x, P))


def state_shardings(model: ModelDef, mesh: Mesh,
                    state: MFState) -> MFState:
    """NamedSharding pytree for device_put, mirroring ``state_specs``."""
    return _with_mesh(mesh, state_specs(model, mesh, state))


def data_shardings(model: ModelDef, mesh: Mesh, data: MFData) -> MFData:
    """NamedSharding pytree for device_put, mirroring ``data_specs``."""
    return _with_mesh(mesh, data_specs(model, mesh, data))


def distributed_unsupported_reason(model: ModelDef, mesh: Mesh,
                                   data: Optional[MFData] = None
                                   ) -> Optional[str]:
    """Why this model falls off the explicit sweep — None when it fits.

    The predicate behind :func:`distributed_supported`, kept separate
    so the session layer's pjit-fallback warning can NAME the reason
    (an arbitrary builder-composed graph has many more ways to miss
    the subset than the old two hardcoded session shapes did).
    """
    S = _n_shards(mesh)
    for e, ent in enumerate(model.entities):
        if ent.n_rows % S != 0:
            return (f"entity {ent.name!r} has {ent.n_rows} rows, not "
                    f"divisible by the {S}-shard mesh")
        if not isinstance(ent.prior,
                          (NormalPrior, MacauPrior, FixedNormalPrior,
                           SpikeAndSlabPrior)):
            return (f"entity {ent.name!r} prior "
                    f"{type(ent.prior).__name__} has no sharded moment "
                    "algebra")
        if isinstance(ent.prior, MacauPrior) and (
                data is None or data.sides[e] is None):
            return (f"entity {ent.name!r} has a Macau prior but no "
                    "side-information matrix in the data")
    for bi, blk in enumerate(model.blocks):
        if blk.row_entity == blk.col_entity:
            return (f"block {bi} relates entity {blk.row_entity} to "
                    "itself (self-blocks are not sharded)")
        if not isinstance(blk.noise,
                          (FixedGaussian, AdaptiveGaussian, ProbitNoise)):
            return (f"block {bi} noise {type(blk.noise).__name__} has "
                    "no sharded residual reduction")
        if not blk.sparse and data is not None:
            payload = data.blocks[bi]
            # both orientations must be stored for per-shard reads
            if not isinstance(payload, DenseBlock) \
                    or getattr(payload, "XT", None) is None:
                return (f"block {bi} dense payload lacks the stored "
                        "transposed orientation (use dense_block())")
    return None


def distributed_supported(model: ModelDef, mesh: Mesh,
                          data: Optional[MFData] = None) -> bool:
    """True when the explicit shard_map sweep covers this model.

    Whitelist, not blacklist: only prior/noise types whose sharded
    moment algebra ``_sharded_sweep`` implements are admitted — a new
    prior whose ``sample_hyper`` reads the factor matrix would
    otherwise silently sample per-shard-divergent hypers (out_specs
    P() with check off never validates replication).  The subset now
    spans sparse AND dense blocks under Gaussian, adaptive-Gaussian,
    and probit noise (probit's truncated-normal draws are per-row
    counter-based, so shard draws slice the single-device chain), and
    every Table-1 prior including spike-and-slab (counter-based
    ``row_bernoulli``/``row_normals`` coordinate updates + two K-sized
    hyper psums) — the GFA composition runs the explicit sweep, and so
    does any multi-relation graph ``ModelBuilder`` composes from the
    admitted pieces.  Outside it (self-blocks, non-dividing row
    counts, dense payloads without the stored transposed orientation)
    ``make_distributed_step`` falls back to pjit;
    :func:`distributed_unsupported_reason` names the offending piece.
    """
    return distributed_unsupported_reason(model, mesh, data) is None


# ---------------------------------------------------------------------------
# the explicit shard_map sweep
# ---------------------------------------------------------------------------

def _shard_index(axes: Tuple[str, ...], sizes: Tuple[int, ...]):
    """Flattened row-shard index of this device (major-to-minor = axes
    order, matching both NamedSharding(P(axes)) layout and tiled
    all_gather concatenation order)."""
    idx = jnp.asarray(0, jnp.int32)
    for a, sz in zip(axes, sizes):
        idx = idx * sz + jax.lax.axis_index(a)
    return idx


def _ring_accumulate(axes: Tuple[str, ...], sizes: Tuple[int, ...],
                     shard, f_shard, init, chunk_fn):
    """Circulate this device's shard of a fixed factor around the ring.

    Device ``s`` starts from its own shard and receives the remaining
    ``S - 1`` chunks via ``lax.ppermute`` over the flattened mesh axes
    (exactly ``S - 1`` hops; no all-gather anywhere).  The hop moving
    chunk ``t + 1`` is issued BEFORE chunk ``t`` is consumed, so on
    targets with async collectives the wire transfer overlaps
    ``chunk_fn``'s compute — the double-buffered exchange of the
    asynchronous-communication BMF (arXiv:1705.10633).

    ``chunk_fn(acc, chunk, c0) -> acc`` must be pure; ``c0`` is the
    global row index of the chunk's first row (traced — device ``s``
    sees chunk ``(s + t) % S`` at step ``t``).  Unrolled below
    ``RING_UNROLL_MAX`` shards, ``lax.scan``-rolled above it.
    """
    S = int(np.prod(sizes))
    rows_per = f_shard.shape[0]
    perm = [((j + 1) % S, j) for j in range(S)]

    def c0_at(t):
        return ((shard + t) % S) * rows_per

    if S <= RING_UNROLL_MAX:
        acc, chunk = init, f_shard
        for t in range(S):
            nxt = jax.lax.ppermute(chunk, axes, perm) if t < S - 1 \
                else None
            acc = chunk_fn(acc, chunk, c0_at(t))
            chunk = nxt
        return acc

    def body(carry, t):
        chunk, acc = carry
        nxt = jax.lax.ppermute(chunk, axes, perm)
        return (nxt, chunk_fn(acc, chunk, c0_at(t))), None

    (chunk, acc), _ = jax.lax.scan(body, (f_shard, init),
                                   jnp.arange(S - 1))
    return chunk_fn(acc, chunk, c0_at(S - 1))


def _streamable(model: ModelDef, bi: int, e: int) -> bool:
    """True when block ``bi``'s contribution to entity ``e``'s update
    can consume the ring exchange chunk-by-chunk, never materializing
    the dense fixed view: dense payload, pred-free augmentation (non-
    probit), and ``e`` is the EARLIER-updated side (the later side's
    view is the one the end-of-sweep metrics reuse, so that half-sweep
    reassembles it instead)."""
    blk = model.blocks[bi]
    return (not blk.sparse
            and not isinstance(blk.noise, ProbitNoise)
            and max(blk.row_entity, blk.col_entity) != e)


def _psum_hyper(model: ModelDef, e: int, key, u, hyper, side, axes,
                ftf=None):
    """Hyper-sample from psummed moments — replicated-identical output.

    The collective payloads are K (factor sum), K^2 (factor Gramian)
    and, for Macau link terms, D/DxK — negligible next to the factor
    all-gathers.  The Macau (D, D) side-Gramian ``ftf`` is NOT psummed
    here: it is static data, computed once at placement time in
    ``make_distributed_step`` and passed in replicated.
    """
    prior = model.entities[e].prior
    N = model.entities[e].n_rows
    psum = partial(jax.lax.psum, axis_name=axes)
    if isinstance(prior, MacauPrior):
        Uc = u - side @ hyper["beta"]
        return prior.sample_hyper_moments(
            key, hyper,
            F_sum=psum(Uc.sum(axis=0)), F_cov=psum(Uc.T @ Uc), n_rows=N,
            StF=psum(side.T @ u), s_side=psum(side.sum(axis=0)),
            FtF=ftf)
    if isinstance(prior, NormalPrior):
        return prior.sample_hyper_moments(
            key, hyper, F_sum=psum(u.sum(axis=0)), F_cov=psum(u.T @ u),
            n_rows=N)
    if isinstance(prior, SpikeAndSlabPrior):
        # two K-sized payloads: per-component inclusion counts and
        # sum of squares — the ONLY collectives SnS adds to a sweep
        s = (jnp.abs(u) > 0).astype(jnp.float32)
        return prior.sample_hyper_moments(
            key, hyper, n_incl=psum(s.sum(axis=0)),
            sumsq=psum((u * u).sum(axis=0)), n_rows=N)
    # moment-free priors (FixedNormalPrior): identical on every shard
    return prior.sample_hyper(key, u, hyper)


def _sharded_sweep(model: ModelDef, axes: Tuple[str, ...],
                   sizes: Tuple[int, ...], pipeline: str, ftf,
                   data: MFData, state: MFState):
    """One full Gibbs sweep, executed per-shard inside shard_map.

    Mirrors ``gibbs.gibbs_step`` exactly — same key-splitting sequence,
    same per-row draws (offset by the shard's global row origin), same
    per-block contributions (sparse padded-CSR or dense, Gaussian or
    probit-augmented) — with the three global couplings made explicit:
    one fixed-factor exchange per half-sweep (a blocking ``all_gather``
    in the ``"eager"`` pipeline, ``S - 1`` double-buffered ``ppermute``
    hops in ``"ring"`` — see the module docstring), K/K^2 psums for the
    hyper moments, scalar psums for residual SSE/nnz.  ``ftf`` holds
    the per-entity Macau side-Gramians, precomputed and replicated
    (None for non-Macau entities).
    """
    S = int(np.prod(sizes))
    shard = _shard_index(axes, sizes)
    ring = pipeline == "ring"
    key, *ekeys = jax.random.split(state.key, len(model.entities) + 2)
    nkey = ekeys[-1]
    factors = list(state.factors)          # row shards (N_e / S, K)
    hypers = list(state.hypers)
    noises = list(state.noises)

    gathered = {}   # entity -> full exchange-view factor on this shard

    def _wire_cast(f):
        return f.astype(jnp.bfloat16) if model.bf16_gather else f

    def fixed_view(o: int):
        """The dense fixed factor of entity ``o`` on this shard.

        Eager: ONE tiled all-gather, bf16 when the model flags it
        (cast before the collective — half the bytes).  Ring: the same
        bytes arrive as ``S - 1`` ppermute hops and are reassembled by
        ``dynamic_update_slice`` — bitwise the all-gathered array (pure
        data movement, no arithmetic), with zero all-gathers in the
        program.
        """
        if o not in gathered:
            f = _wire_cast(factors[o])
            with jax.named_scope("exchange"):
                if ring:
                    full0 = jnp.zeros(
                        (model.entities[o].n_rows, f.shape[1]), f.dtype)
                    ag = _ring_accumulate(
                        axes, sizes, shard, f, full0,
                        lambda acc, chunk, c0:
                            jax.lax.dynamic_update_slice(acc, chunk,
                                                         (c0, 0)))
                else:
                    ag = jax.lax.all_gather(f, axes, axis=0, tiled=True)
            if model.bf16_gather:
                # Keep the gathered value bf16 in the optimized graph:
                # without the barrier the algebraic simplifier may hoist
                # the consumers' bf16->f32 upcast through the collective
                # and move f32 on the wire.  (XLA:CPU additionally
                # normalizes bf16 collectives to convert-gather-convert
                # — backend detail; the dry-run test asserts the bf16
                # exchange on the lowered StableHLO, pre-backend.)
                ag = jax.lax.optimization_barrier(ag)
            gathered[o] = ag
        return gathered[o]

    for e in range(len(model.entities)):
        ent = model.entities[e]
        side = data.sides[e]
        k_hyp, k_fac, k_blk = jax.random.split(ekeys[e], 3)
        u = factors[e]
        row_offset = shard * (ent.n_rows // S)

        # 1. hyper-parameters from psummed global moments
        with jax.named_scope("hyper"):
            hyper = _psum_hyper(model, e, k_hyp, u, hypers[e], side, axes,
                                ftf=ftf[e])

        # 2. this shard's factor rows from their conditional
        prior = ent.prior
        if isinstance(prior, SpikeAndSlabPrior):
            # coordinate-wise SnS update: q/l moments are row-local
            # given the gathered fixed factor, and the inclusion/slab
            # draws are counter-based on the global row index — the
            # body is the single-device one, offset to this shard.
            # Zero per-component collectives.
            factors[e] = _sample_sns_factor(model, data, k_fac, e, u,
                                            hyper, fixed_view, noises,
                                            row_offset=row_offset)
            hypers[e] = hyper
            gathered.pop(e, None)
            continue
        with jax.named_scope("hyper"):
            Lam_p = prior.precision_term(hyper)
            if isinstance(prior, MacauPrior):
                b_p = prior.mean_term(hyper, ent.n_rows, side=side)
            else:
                b_p = prior.mean_term(hyper, ent.n_rows)

        gram_shared = None
        gram_rows = None
        rhs_acc = jnp.zeros((ent.n_rows // S, model.num_latent),
                            jnp.float32)
        bkeys = jax.random.split(k_blk, max(1, len(model.blocks)))
        touching = model.blocks_touching(e)
        streamed = set()
        if ring:
            # Chunk-accumulated circulations: group the touching blocks
            # by their fixed entity; a group streams (per-chunk Gram/RHS
            # folded into the ring, the dense fixed view NEVER
            # materialized) when every consumer qualifies — see
            # ``_streamable``.  Non-streamed groups fall through to the
            # reassembled ``fixed_view`` below.
            by_fixed = {}
            for bi, as_row in touching:
                by_fixed.setdefault(model.blocks[bi].other(e),
                                    []).append((bi, as_row))
            for o, group in by_fixed.items():
                if o in gathered or not all(_streamable(model, bi, e)
                                            for bi, _ in group):
                    continue
                streamed.update(bi for bi, _ in group)
                # augment once per block up front (pred-free for the
                # non-probit noises this path admits); one circulation
                # then folds every block's moment contributions chunk
                # by chunk, overlapping the next hop's wire transfer
                prep = []
                for bi, as_row in group:
                    blk = model.blocks[bi]
                    X, msk = data.blocks[bi].oriented(as_row)
                    vals, alpha = blk.noise.augment(
                        bkeys[bi], noises[bi], None, X, msk,
                        row_offset=row_offset)
                    prep.append((data.blocks[bi].fully, vals, msk, alpha))
                K = model.num_latent
                R = ent.n_rows // S
                init = tuple(
                    (jnp.zeros((K, K), jnp.float32) if fully else None,
                     None if fully else jnp.zeros((R, K, K), jnp.float32),
                     jnp.zeros((R, K), jnp.float32))
                    for fully, _, _, _ in prep)

                def chunk_fn(acc, chunk, c0, prep=prep):
                    if model.bf16_gather:
                        # same guard as fixed_view's reassembled view:
                        # without the barrier the algebraic simplifier
                        # may hoist the moment math's bf16->f32 upcast
                        # through the ppermute chain and move f32 on
                        # the wire
                        chunk = jax.lax.optimization_barrier(chunk)
                    out = []
                    for (fully, vals, msk, _), (gs, gr, rh) in zip(prep,
                                                                   acc):
                        dgs, dgr, drh = _dense_chunk_contrib(
                            vals, msk, fully, chunk, c0)
                        out.append((
                            None if gs is None else gs + dgs,
                            None if gr is None else gr + dgr,
                            rh + drh))
                    return tuple(out)

                accs = _ring_accumulate(axes, sizes, shard,
                                        _wire_cast(factors[o]), init,
                                        chunk_fn)
                for (fully, _, _, alpha), (gs, gr, rh) in zip(prep, accs):
                    if gs is not None:
                        gram_shared = alpha * gs if gram_shared is None \
                            else gram_shared + alpha * gs
                    if gr is not None:
                        gram_rows = alpha * gr if gram_rows is None \
                            else gram_rows + alpha * gr
                    rhs_acc = rhs_acc + alpha * rh
        for bi, as_row in touching:
            if bi in streamed:
                continue
            blk = model.blocks[bi]
            fixed = fixed_view(blk.other(e))
            if blk.sparse:
                g, r = _sparse_contrib(model, data.blocks[bi], as_row,
                                       fixed, u, blk.noise, noises[bi],
                                       bkeys[bi], row_offset=row_offset)
                gram_rows = g if gram_rows is None else gram_rows + g
            else:
                gs, g, r = _dense_contrib(data.blocks[bi], as_row,
                                          fixed, u, blk.noise,
                                          noises[bi], bkeys[bi],
                                          row_offset=row_offset)
                if gs is not None:
                    # fully-observed: ONE (K, K) Gram shared by every
                    # row, built from the gathered (replicated) fixed
                    # factor — identical on all shards by construction
                    gram_shared = gs if gram_shared is None \
                        else gram_shared + gs
                if g is not None:
                    gram_rows = g if gram_rows is None else gram_rows + g
            rhs_acc = rhs_acc + r

        if gram_shared is None and gram_rows is None:
            gram_shared = jnp.zeros(   # entity with no observed blocks
                (model.num_latent, model.num_latent), jnp.float32)
        with jax.named_scope("solve"):
            factors[e] = _sample_normal_factor(
                k_fac, gram_shared, gram_rows, rhs_acc, Lam_p, b_p,
                row_offset=row_offset)
        hypers[e] = hyper
        gathered.pop(e, None)   # any cached view of e is now stale

    # 3. noise states + metrics from the residuals, re-using the last
    # half-sweep's gather: orient each block along its later-updated
    # entity, whose fixed factor (the earlier-updated one) is already
    # dense on every shard.
    metrics = {}
    nkeys = jax.random.split(nkey, max(1, len(model.blocks)))
    psum = partial(jax.lax.psum, axis_name=axes)
    for bi, blk in enumerate(model.blocks):
        e_last = max(blk.row_entity, blk.col_entity)
        payload = data.blocks[bi]
        fixed = gathered[blk.other(e_last)]
        v = factors[e_last]
        if model.bf16_gather:
            v = v.astype(jnp.bfloat16)
        with jax.named_scope("residuals"):
            if blk.sparse:
                padded = payload.rows if blk.row_entity == e_last \
                    else payload.cols
                vals, msk = padded.val, padded.mask
                pred = jnp.einsum("rtk,rk->rt", fixed[padded.idx], v)
            else:
                vals, msk = payload.oriented(blk.row_entity == e_last)
                pred = v @ fixed.T
            resid = (vals - pred) * msk
            se = psum(jnp.sum(resid * resid))
            nnz = psum(jnp.sum(msk))
        with jax.named_scope("noise"):
            noises[bi] = blk.noise.sample_state(nkeys[bi], noises[bi],
                                                pred, vals, msk, sse=se,
                                                nnz=nnz)
        with jax.named_scope("metrics"):
            metrics[f"rmse_train_{bi}"] = jnp.sqrt(
                se / jnp.maximum(nnz, 1.0))
        metrics[f"alpha_{bi}"] = noises[bi]["alpha"]

    new_state = MFState(key, tuple(factors), tuple(hypers), tuple(noises),
                        state.step + 1)
    return new_state, metrics


def _macau_ftf(model: ModelDef, data: MFData):
    """Per-entity Macau side-Gramians ``side^T side`` — STATIC data.

    Computed ONCE here (placement time) so the per-sweep loop carries
    no (D, D) psum; asserted on the HLO in tests/test_distributed.py.
    Abstract (ShapeDtypeStruct) sides — the dry-run path, which only
    lowers — produce abstract Gramians.
    """
    out = []
    for e, ent in enumerate(model.entities):
        side = data.sides[e]
        if not isinstance(ent.prior, MacauPrior) or side is None:
            out.append(None)
        elif isinstance(side, jax.ShapeDtypeStruct):
            D = side.shape[1]
            out.append(jax.ShapeDtypeStruct((D, D), jnp.float32))
        else:
            side = jnp.asarray(side, jnp.float32)
            out.append(side.T @ side)
    return tuple(out)


def _validate_chain_axis(mesh: Mesh, chains: int,
                         chain_axis: Optional[str]) -> None:
    if chain_axis is None:
        return
    if chain_axis in FACTOR_AXES:
        raise ValueError(
            f"chain_axis {chain_axis!r} collides with the row-sharding "
            f"axes {FACTOR_AXES}; name the chain mesh axis something "
            "else (conventionally 'chain')")
    if chain_axis not in mesh.axis_names:
        raise ValueError(
            f"chain_axis {chain_axis!r} is not a mesh axis; this mesh "
            f"has {tuple(mesh.axis_names)}")
    size = mesh.shape[chain_axis]
    if chains % size != 0:
        raise ValueError(
            f"chains={chains} does not divide over chain_axis "
            f"{chain_axis!r} of size {size}")


def make_multi_chain_step(model: ModelDef, mesh: Mesh, data: MFData,
                          stacked: MFState,
                          pipeline: Optional[str] = None,
                          chains: int = 1,
                          chain_axis: Optional[str] = None):
    """The distributed sweep over a chain-stacked ``(C, ...)`` state.

    Chains map over the leading axis with ``lax.map`` INSIDE the
    shard_map body — each chain runs the identical ``_sharded_sweep``
    subgraph, so chain c of the multi-chain program is bitwise the
    single-chain distributed run keyed with ``chain_keys(seed, C)[c]``
    (vmap would batch the per-chain reductions and drift ~1e-6).

    With ``chain_axis`` the stacked state shards its chain dim over
    that mesh axis and rows over the remaining FACTOR_AXES — chains x
    shards fills the pod, each device sweeps ``C / mesh.shape[chain_
    axis]`` local chains, and the per-sweep collective census equals
    the single-chain census on the smaller per-chain shard group
    (``contract_for(..., chains=C, chain_axis_size=...)`` derives it).
    Without ``chain_axis`` every shard sweeps all C chains serially and
    the census scales by C.

    Returns (step_fn, placed_data_shardings, stacked_state_shardings);
    metrics come back stacked ``(C,)`` per quantity.
    """
    pipeline = resolve_pipeline(pipeline)
    _validate_chain_axis(mesh, chains, chain_axis)
    sss = stacked_state_specs(model, mesh, stacked, chain_axis)
    ss = _with_mesh(mesh, sss)
    ds = data_shardings(model, mesh, data)
    mspec = P(chain_axis)
    if distributed_supported(model, mesh, data):
        axes = _axes_in(mesh)
        sizes = compat.mesh_axis_sizes(mesh, axes)
        ftf = _macau_ftf(model, data)
        ftf_specs = jax.tree.map(lambda x: P(), ftf)

        def sweep_chains(ftf_, data_, stacked_):
            return jax.lax.map(
                lambda st: _sharded_sweep(model, axes, sizes, pipeline,
                                          ftf_, data_, st),
                stacked_)

        body = compat.shard_map(
            sweep_chains,
            mesh=mesh,
            in_specs=(ftf_specs,
                      data_specs(model, mesh, data),
                      sss),
            out_specs=(sss, mspec),
            check=False)
        jfn = jax.jit(body,
                      in_shardings=(_with_mesh(mesh, ftf_specs), ds, ss),
                      out_shardings=(ss, NamedSharding(mesh, mspec)))

        def fn(data, state):
            return jfn(ftf, data, state)

        fn.lower = lambda data, state: jfn.lower(ftf, data, state)
    else:
        fn = jax.jit(
            lambda data_, stacked_: jax.lax.map(
                lambda st: gibbs_step(model, data_, st), stacked_),
            in_shardings=(ds, ss),
            out_shardings=(ss, NamedSharding(mesh, mspec)),
        )
    return fn, ds, ss


def make_distributed_step(model: ModelDef, mesh: Mesh, data: MFData,
                          state: MFState, pipeline: Optional[str] = None):
    """The distributed sweep jitted on ``mesh``.

    Returns (step_fn, placed_data, placed_state) — on real hardware the
    placement transfers; in the dry-run we only ``.lower().compile()``.
    Uses the explicit shard_map sweep when the model is in the sharded
    subset (see ``distributed_supported``); otherwise jits the
    single-device ``gibbs_step`` with the same in/out shardings and
    lets the partitioner place the collectives.

    ``pipeline`` selects the fixed-factor exchange: ``"eager"`` (one
    blocking all-gather per half-sweep) or ``"ring"`` (``S - 1``
    double-buffered ppermute hops overlapping the local solves); None
    defers to the ``REPRO_PIPELINE`` environment variable (see
    ``resolve_pipeline``).  The knob only changes HOW the exchange
    travels — the sampled chain is pinned to the eager one by the
    ring-vs-eager parity and golden-chain tests.

    ``step_fn(data, state)`` closes over the precomputed Macau
    side-Gramians (replicated) and exposes ``.lower(data, state)``
    exactly like a bare ``jax.jit`` result.
    """
    pipeline = resolve_pipeline(pipeline)
    ss = state_shardings(model, mesh, state)
    ds = data_shardings(model, mesh, data)
    if distributed_supported(model, mesh, data):
        axes = _axes_in(mesh)
        sizes = compat.mesh_axis_sizes(mesh, axes)
        ftf = _macau_ftf(model, data)
        ftf_specs = jax.tree.map(lambda x: P(), ftf)
        body = compat.shard_map(
            partial(_sharded_sweep, model, axes, sizes, pipeline),
            mesh=mesh,
            in_specs=(ftf_specs,
                      data_specs(model, mesh, data),
                      state_specs(model, mesh, state)),
            out_specs=(state_specs(model, mesh, state), P()),
            check=False)
        jfn = jax.jit(body,
                      in_shardings=(_with_mesh(mesh, ftf_specs), ds, ss),
                      out_shardings=(ss, replicated(mesh)))

        def fn(data, state):
            return jfn(ftf, data, state)

        fn.lower = lambda data, state: jfn.lower(ftf, data, state)
    else:
        fn = jax.jit(
            partial(gibbs_step, model),
            in_shardings=(ds, ss),
            out_shardings=(ss, replicated(mesh)),
        )
    return fn, ds, ss


def pad_rows_to(n: int, devices: int) -> int:
    """Round a row count up so every shard is equal (elastic re-bucket)."""
    return int(-(-n // devices) * devices)
