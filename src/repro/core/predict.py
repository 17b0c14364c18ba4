"""Posterior-predictive evaluation: RMSE / AUC over collected samples.

SMURFF's predict step (Algorithm 1 "for all test points") evaluated per
sweep; predictions for the final report average U_s V_s^T over the
collected posterior samples, which is what makes BMF robust against
overfitting (paper section 1).

:class:`PredictSession` is the from-disk counterpart: it reloads the
posterior samples a session streamed out (``save_freq``/``save_dir``)
and serves averaged predictions without the training data — at
arbitrary cells of any block, as whole dense blocks, and for rows
never present in training through the sampled Macau link matrices
(out-of-matrix prediction, the compound-activity cold-start workflow
of arXiv:1904.02514).

Serving many requests is where the original lazy design fell over:
every ``predict``/``predict_all``/``predict_new`` call re-read the
ENTIRE sample store from disk, so R requests cost R x S checkpoint
loads.  The structural fix is the **resident posterior cache**
(:class:`PosteriorCache`): the first request loads the factor stack
once into ``(S, N, K)`` device arrays (plus the stacked Macau hyper
draws for cold-start rows), bounded by a byte budget
(``cache_bytes``, env ``REPRO_PREDICT_CACHE_BYTES``); every later
request performs ZERO checkpoint loads (asserted via the
``load_count`` counter in tests/test_serving.py).  Stores above the
budget keep the lazy streaming path.  ``recommend``/``recommend_rows``
serve batched top-K item recommendations with posterior mean AND
uncertainty through the fused ``kernels.topk_score`` scorer — the
online serving layer ``launch.serve.RecommendServer`` batches
concurrent requests onto.
"""
from __future__ import annotations

import os
from collections import OrderedDict
from typing import (Any, Dict, Iterator, List, NamedTuple, Optional,
                    Sequence, Tuple, Union)

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops


class TestSet(NamedTuple):
    i: jnp.ndarray   # (E,) int32 row ids
    j: jnp.ndarray   # (E,) int32 col ids
    v: jnp.ndarray   # (E,) f32 true values


def make_test_set(i, j, v) -> TestSet:
    return TestSet(jnp.asarray(i, jnp.int32), jnp.asarray(j, jnp.int32),
                   jnp.asarray(v, jnp.float32))


@jax.jit
def predict_one(U: jnp.ndarray, V: jnp.ndarray, test: TestSet
                ) -> jnp.ndarray:
    """Single-sample prediction at the test entries."""
    return ops.sddmm(U[test.i], V[test.j])


def rmse(pred: jnp.ndarray, truth: jnp.ndarray) -> jnp.ndarray:
    return jnp.sqrt(jnp.mean((pred - truth) ** 2))


def auc(pred: np.ndarray, truth: np.ndarray, threshold: float = 0.5
        ) -> float:
    """Rank-based AUC (Mann-Whitney); truth binarized at threshold.

    Tied predictions get MIDRANKS (the average of the ranks they
    span), the standard tie-corrected Mann-Whitney statistic: each
    tied positive/negative pair then contributes 1/2, matching the
    trapezoidal ROC area.  Raw ``argsort`` ranks instead assign tied
    groups an arbitrary input-order permutation, biasing the AUC on
    discrete/probit outputs where ties are the common case.
    """
    pred = np.asarray(pred)
    pos = np.asarray(truth) > threshold
    n_pos = int(pos.sum())
    n_neg = pos.size - n_pos
    if n_pos == 0 or n_neg == 0:
        return float("nan")
    _, inv, counts = np.unique(pred, return_inverse=True,
                               return_counts=True)
    # group g spans ranks (end - count, end]; its midrank is their mean
    end = np.cumsum(counts)
    ranks = (end - (counts - 1) / 2.0)[inv]
    s = ranks[pos].sum()
    return float((s - n_pos * (n_pos + 1) / 2) / (n_pos * n_neg))


class PredictAccumulator:
    """Streaming average of per-sample predictions (posterior mean)."""

    def __init__(self, test: TestSet):
        self.test = test
        self._sum = jnp.zeros_like(test.v)
        self._sum2 = jnp.zeros_like(test.v)
        self.n = 0

    def update(self, U: jnp.ndarray, V: jnp.ndarray):
        p = predict_one(U, V, self.test)
        self._sum = self._sum + p
        self._sum2 = self._sum2 + p * p
        self.n += 1
        return p

    @property
    def mean(self) -> jnp.ndarray:
        return self._sum / max(self.n, 1)

    @property
    def var(self) -> jnp.ndarray:
        """Population variance OVER THE POSTERIOR SAMPLES of the
        per-sample predictions: ``E[p^2] - E[p]^2`` with both moments
        averaged over the ``n`` accumulated samples (pinned against a
        hand-rolled oracle in tests/test_predict.py).  This is the
        posterior-predictive spread of ``u_s . v_s`` — the Bayesian
        uncertainty of the score — NOT an error bar on the mean
        estimator (which would shrink with 1/n)."""
        m = self.mean
        return jnp.maximum(self._sum2 / max(self.n, 1) - m * m, 0.0)

    @property
    def std(self) -> jnp.ndarray:
        """Posterior standard deviation per prediction: sqrt(var).
        The uncertainty field the serving layer reports next to every
        recommended score."""
        return jnp.sqrt(self.var)

    def rmse(self) -> float:
        return float(rmse(self.mean, self.test.v))

    def auc(self, threshold: float = 0.5) -> float:
        return auc(np.asarray(self.mean), np.asarray(self.test.v),
                   threshold)


# ---------------------------------------------------------------------------
# from-disk prediction over saved posterior samples
# ---------------------------------------------------------------------------

# model.json specs keyed by realpath -> (mtime, spec): every
# PredictSession pointed at the same store shares one parsed spec
# instead of re-reading the JSON per instance (a store is written once
# by the training session; mtime invalidates the entry if it IS
# rewritten, e.g. by a resumed chain).  Bounded LRU: a long-lived
# server cycling through many stores (mtime-keyed entries used to
# accumulate FOREVER) now evicts least-recently-used specs past
# _SPEC_CACHE_MAX.
_SPEC_CACHE: "OrderedDict[str, Tuple[float, dict]]" = OrderedDict()
_SPEC_CACHE_MAX = 64
_SPEC_CACHE_STATS = {"hits": 0, "misses": 0, "evictions": 0}

DEFAULT_CACHE_BYTES = 1 << 30    # 1 GiB of stacked posterior samples


def spec_cache_stats() -> dict:
    """Counters + occupancy of the module-level model.json spec cache
    (part of ``PredictSession.cache_stats()``)."""
    out = dict(_SPEC_CACHE_STATS)
    out["size"] = len(_SPEC_CACHE)
    out["max_size"] = _SPEC_CACHE_MAX
    return out


def _load_spec_cached(path: str) -> dict:
    from .modelspec import load_model_spec
    try:
        key = os.path.realpath(path)
        mtime = os.path.getmtime(path)
    except OSError:
        # missing file: fall through for the helpful error message
        return load_model_spec(path)
    hit = _SPEC_CACHE.get(key)
    if hit is not None and hit[0] == mtime:
        _SPEC_CACHE_STATS["hits"] += 1
        _SPEC_CACHE.move_to_end(key)
        return hit[1]
    _SPEC_CACHE_STATS["misses"] += 1
    spec = load_model_spec(path)
    _SPEC_CACHE[key] = (mtime, spec)
    _SPEC_CACHE.move_to_end(key)
    while len(_SPEC_CACHE) > _SPEC_CACHE_MAX:
        _SPEC_CACHE.popitem(last=False)
        _SPEC_CACHE_STATS["evictions"] += 1
    return spec


def _resolve_cache_bytes(cache_bytes: Optional[int]) -> int:
    if cache_bytes is not None:
        return int(cache_bytes)
    env = os.environ.get("REPRO_PREDICT_CACHE_BYTES")
    return int(env) if env else DEFAULT_CACHE_BYTES


class PosteriorCache(NamedTuple):
    """The whole sample store, resident: one device array per leaf.

    ``factors[e]`` stacks entity ``e``'s sampled factor over the
    retained chain — shape ``(S, N_e, K)``, the operand layout the
    fused ``kernels.topk_score`` scorer consumes directly.
    ``hypers[e]`` stacks the prior hyper pytree the same way (leading
    ``S`` axis per leaf), which is what out-of-matrix prediction needs
    (the sampled Macau ``mu_s``/``beta_s`` per retained draw).
    """

    factors: Tuple[jnp.ndarray, ...]
    hypers: Tuple[Any, ...]
    n_samples: int

    def hyper_at(self, entity: int, s: int):
        """Entity ``entity``'s hyper pytree of retained sample ``s``."""
        return jax.tree.map(lambda x: x[s], self.hypers[entity])

    def nbytes(self) -> int:
        """Actual resident bytes of the stacked cache (all leaves)."""
        return sum(int(np.prod(x.shape)) * x.dtype.itemsize
                   for x in jax.tree.leaves((self.factors, self.hypers)))


class RecResult(NamedTuple):
    """Batched top-K recommendations with posterior uncertainty.

    ``ids[b, r]`` is the r-th ranked item for query ``b`` (-1 past the
    number of rankable items), ``mean``/``std`` the posterior mean and
    standard deviation of its score over the retained samples (NaN on
    -1 slots).
    """

    ids: np.ndarray     # (B, k) int32
    mean: np.ndarray    # (B, k) float32
    std: np.ndarray     # (B, k) float32


class PredictSession:
    """Serve averaged predictions from a saved posterior-sample store.

    ``save_dir`` is a directory written by a session with
    ``save_freq > 0``: a ``model.json`` spec (the static entity/block
    graph — see ``core/modelspec.py``) plus ``samples/step_<sweep>``
    checkpoints, each holding one full sampled ``MFState``.  No
    training data is needed: prediction only reads the sampled factors
    and, for out-of-matrix rows, the sampled Macau link matrices in
    the hyper state.

    * ``predict(i, j, block=...)`` — posterior-mean prediction at
      arbitrary cells of a block, the same streaming average the
      in-session accumulator computes (same kernel, same summation
      order — a reload reproduces the in-session ``rmse_test`` to
      float32 tolerance, asserted in tests/test_predict_session.py).
    * ``predict_all(block=...)`` — the whole dense block's posterior
      mean (rows x cols).
    * ``predict_new(entity, F_new, block=...)`` — OUT-of-matrix: rows
      never present in training, mapped into latent space per sample
      through the sampled link (``MacauPrior.predict_factor``:
      ``mu_s + beta_s^T f``) and contracted against that sample's
      other-entity factor.
    * ``recommend(user=..., k=...)`` / ``recommend_rows`` — batched
      top-K item recommendation with posterior mean AND std per score
      through the fused ``kernels.topk_score`` scorer (the serving
      path; ``launch.serve.RecommendServer`` batches onto it).
    * ``restore_latest()`` — (step, MFState) of the newest sample, for
      continuing an interrupted chain (``Session.run(resume=True)``
      uses the same store).

    The first prediction loads the store ONCE into the resident
    :class:`PosteriorCache` (bounded by ``cache_bytes``); every later
    request touches only device memory — ``load_count`` counts
    checkpoint loads and stays flat across repeat requests.  Stores
    bigger than the budget keep the original lazy one-sample-at-a-time
    streaming (the store can be much bigger than memory), trading
    per-request reloads for residency.

    **Multi-chain stores + the convergence gate.**  A session run with
    ``chains=C > 1`` writes one single-chain store per chain under
    ``save_dir/chain_<c>/``; this class detects the layout and POOLS
    the samples of every chain (step-major, chain-minor — the exact
    summation order of the in-session accumulator, so a reload still
    reproduces the in-session ``rmse_test``).  ``num_samples`` counts
    pooled samples; ``load_sample(step, chain=...)`` addresses one.
    The training run also records split-R-hat / bulk-ESS per monitored
    quantity in ``save_dir/diagnostics.json`` (``core.diagnostics``);
    ``require_converged=True`` REFUSES to serve a store whose recorded
    R-hat exceeds ``rhat_threshold`` (or that has no recorded
    diagnostics at all), naming the offending quantities —
    ``require_converged="warn"`` warns instead of raising.  Production
    Bayesian serving should gate: averaging the samples of unmixed
    chains silently serves the wrong posterior.
    """

    def __init__(self, save_dir: str,
                 cache_bytes: Optional[int] = None,
                 require_converged: Union[bool, str] = False,
                 rhat_threshold: Optional[float] = None,
                 recorder: Any = None):
        from ..obs import resolve_recorder
        from ..checkpoint.ckpt import list_steps
        from .diagnostics import load_diagnostics
        from .modelspec import (MODEL_SPEC_FILE, SAMPLES_SUBDIR,
                                chain_count_on_disk, chain_subdir,
                                spec_to_model, state_template)
        self.dir = save_dir
        self.spec = _load_spec_cached(os.path.join(save_dir,
                                                   MODEL_SPEC_FILE))
        self.model = spec_to_model(self.spec)
        self._template = state_template(self.model)
        chains_on_disk = chain_count_on_disk(save_dir)
        self.n_chains = max(1, chains_on_disk)
        if chains_on_disk == 0:
            self._sample_dirs = [os.path.join(save_dir, SAMPLES_SUBDIR)]
        else:
            self._sample_dirs = [
                os.path.join(save_dir, chain_subdir(c), SAMPLES_SUBDIR)
                for c in range(chains_on_disk)]
        self._samples_dir = self._sample_dirs[0]
        per_chain = [list_steps(d) for d in self._sample_dirs]
        # pooled (step, chain) ids, step-major chain-minor — the
        # in-session accumulation order
        self.chain_steps: List[Tuple[int, int]] = sorted(
            (s, c) for c, steps in enumerate(per_chain) for s in steps)
        self.steps: List[int] = sorted({s for s, _ in self.chain_steps})
        if not self.chain_steps:
            raise ValueError(
                f"no complete samples under {self._samples_dir}; run "
                "the session with save_freq > 0 (and let at least one "
                "post-burnin sweep finish)")
        self._step_sets = [frozenset(s) for s in per_chain]
        self._step_set = frozenset(self.steps)   # O(1) membership
        self.cache_bytes = _resolve_cache_bytes(cache_bytes)
        self.load_count = 0          # checkpoint loads, ever
        self._cache: Optional[PosteriorCache] = None
        # obs: request-level hit/miss on the resident cache (a hit =
        # warm_cache found the store already resident; a miss = a load
        # or an over-budget refusal that fell back to streaming)
        self.obs = resolve_recorder(recorder)
        self._cache_hits = 0
        self._cache_misses = 0
        self._cache_over_budget = 0
        self.diagnostics = load_diagnostics(save_dir)
        if require_converged:
            self._check_converged(require_converged, rhat_threshold)

    def _check_converged(self, mode: Union[bool, str],
                         rhat_threshold: Optional[float]) -> None:
        from .diagnostics import DEFAULT_RHAT_THRESHOLD
        threshold = (DEFAULT_RHAT_THRESHOLD if rhat_threshold is None
                     else float(rhat_threshold))
        if self.diagnostics is None:
            msg = (
                f"require_converged: store {self.dir!r} records no "
                "diagnostics.json — it predates convergence recording "
                "or the training run died before finishing; rerun the "
                "session (ideally chains>=2) to record split-R-hat/"
                "bulk-ESS, or serve explicitly ungated with "
                "require_converged=False")
        else:
            failing = self.diagnostics.failing(threshold)
            if not failing:
                return
            worst = ", ".join(f"{k}={v:.4g}"
                              for k, v in sorted(failing.items()))
            msg = (
                f"require_converged: store {self.dir!r} has NOT "
                f"converged — split-R-hat over "
                f"{self.diagnostics.n_chains} chain(s) x "
                f"{self.diagnostics.n_draws} draws exceeds "
                f"{threshold:g} for: {worst}. Run more sweeps/chains, "
                "raise rhat_threshold deliberately, or serve "
                "explicitly ungated with require_converged=False")
        if mode == "warn":
            import warnings
            warnings.warn(msg, stacklevel=3)
        else:
            raise ValueError(msg)

    # -- sample access -----------------------------------------------------

    @property
    def num_samples(self) -> int:
        """Pooled sample count — across ALL chains for a multi-chain
        store."""
        return len(self.chain_steps)

    def load_sample(self, step: int, chain: int = 0):
        """The full sampled ``MFState`` saved at global sweep ``step``
        (of ``chain``, for a multi-chain store)."""
        from ..checkpoint.ckpt import load_pytree
        if not 0 <= chain < self.n_chains:
            raise ValueError(
                f"no chain {chain}; this store holds "
                f"{self.n_chains} chain(s)")
        if step not in self._step_sets[chain]:
            saved = ", ".join(map(str, sorted(self._step_sets[chain])))
            raise ValueError(
                f"no sample at step {step}"
                + (f" for chain {chain}" if self.n_chains > 1 else "")
                + f"; saved steps: {saved}")
        self.load_count += 1
        return load_pytree(self._template,
                           os.path.join(self._sample_dirs[chain],
                                        f"step_{step}"))

    def samples(self) -> Iterator:
        """Lazily yield every sampled state — in chain order, and for
        multi-chain stores pooled step-major chain-minor (the
        in-session accumulation order)."""
        for s, c in self.chain_steps:
            yield self.load_sample(s, c)

    def restore_latest(self) -> Tuple[int, object]:
        """(step, MFState) of the newest sample — the resume point.
        For a multi-chain store this is CHAIN 0's newest sample
        (``Session.run(resume=True)`` restores every chain itself)."""
        last = max(self._step_sets[0])
        return last, self.load_sample(last, 0)

    # -- resident posterior cache ------------------------------------------

    def store_nbytes(self) -> int:
        """Resident size of the FULL stacked store, estimated from the
        state template (factor + hyper + noise leaves x num_samples) —
        what the cache would occupy, computed without loading it."""
        per_sample = sum(
            int(np.prod(np.shape(leaf))) * np.dtype(
                getattr(leaf, "dtype", np.float32)).itemsize
            for leaf in jax.tree.leaves(self._template))
        return per_sample * self.num_samples

    @property
    def cache_resident(self) -> bool:
        return self._cache is not None

    def warm_cache(self) -> Optional[PosteriorCache]:
        """Load the store once into the resident cache (idempotent).

        Returns the cache, or None when the store exceeds
        ``cache_bytes`` — callers then stream samples lazily.  This is
        the ONLY place serving paths are allowed to touch the
        checkpoint loader (enforced structurally by the
        ``checkpoint-load-in-serving-request-path`` invariant rule on
        ``launch/serve.py``).
        """
        if self._cache is not None:
            self._cache_hits += 1
            self.obs.add("predict.cache_hit")
            return self._cache
        self._cache_misses += 1
        self.obs.add("predict.cache_miss")
        if self.store_nbytes() > self.cache_bytes:
            # the cache's only "eviction": an all-or-nothing refusal
            # to go resident (there is no partial LRU over samples)
            self._cache_over_budget += 1
            self.obs.add("predict.cache_over_budget")
            return None
        n_ent = len(self.model.entities)
        with self.obs.span("predict/warm_cache", cat="predict",
                           samples=self.num_samples):
            fac: List[List[np.ndarray]] = [[] for _ in range(n_ent)]
            hyp: List[List[Any]] = [[] for _ in range(n_ent)]
            for st in self.samples():
                for e in range(n_ent):
                    fac[e].append(np.asarray(st.factors[e]))
                    hyp[e].append(st.hypers[e])
            factors = tuple(jnp.asarray(np.stack(f)) for f in fac)
            hypers = tuple(
                jax.tree.map(
                    lambda *xs: jnp.asarray(np.stack(
                        [np.asarray(x) for x in xs])), *h)
                for h in hyp)
            self._cache = PosteriorCache(factors, hypers,
                                         self.num_samples)
        self.obs.gauge("predict.cache_resident_bytes",
                       self._cache.nbytes())
        return self._cache

    def cache_stats(self) -> dict:
        """Counters for the resident posterior cache + the module
        spec cache (PR 10 satellite — observability for serving).

        ``hits``/``misses`` count ``warm_cache()`` calls (every
        request path goes through it): a miss is the initial load OR
        an over-budget refusal that fell back to streaming.
        """
        return {
            "hits": self._cache_hits,
            "misses": self._cache_misses,
            "over_budget": self._cache_over_budget,
            "resident": self._cache is not None,
            "resident_bytes": (self._cache.nbytes()
                               if self._cache is not None else 0),
            "budget_bytes": self.cache_bytes,
            "load_count": self.load_count,
            "spec_cache": spec_cache_stats(),
        }

    def _factor_iter(self, entity: int) -> Iterator[jnp.ndarray]:
        """Entity factors per retained sample — from the cache when
        resident (zero loads), streamed from disk otherwise."""
        cache = self.warm_cache()
        if cache is not None:
            for s in range(cache.n_samples):
                yield cache.factors[entity][s]
        else:
            for st in self.samples():
                yield jnp.asarray(st.factors[entity])

    def _factor_pair_iter(self, ent_a: int, ent_b: int):
        cache = self.warm_cache()
        if cache is not None:
            for s in range(cache.n_samples):
                yield (cache.factors[ent_a][s],
                       cache.factors[ent_b][s])
        else:
            for st in self.samples():
                yield (jnp.asarray(st.factors[ent_a]),
                       jnp.asarray(st.factors[ent_b]))

    def _hyper_factor_iter(self, entity: int, other: int):
        """(hyper_s of ``entity``, factor_s of ``other``) per sample."""
        cache = self.warm_cache()
        if cache is not None:
            for s in range(cache.n_samples):
                yield (cache.hyper_at(entity, s),
                       cache.factors[other][s])
        else:
            for st in self.samples():
                yield st.hypers[entity], jnp.asarray(st.factors[other])

    # -- block/entity resolution -------------------------------------------

    def _resolve_block(self, block: Union[int, Tuple[str, str]]
                       ) -> Tuple[int, bool]:
        """(block_index, flipped): ``flipped`` means the caller named
        the pair in the OPPOSITE order to the block's stored
        orientation — their (i, j) address (col, row) cells and their
        result axes are transposed.  An integer block always addresses
        the stored orientation."""
        model = self.model
        if isinstance(block, tuple):
            a = model.entity_index(block[0])
            b = model.entity_index(block[1])
            for bi, blk in enumerate(model.blocks):
                if (blk.row_entity, blk.col_entity) == (a, b):
                    return bi, False
                if (blk.row_entity, blk.col_entity) == (b, a):
                    return bi, True
            names = model.entity_names
            pairs = ", ".join(
                f"({names[blk.row_entity]}, {names[blk.col_entity]})"
                for blk in model.blocks)
            raise ValueError(
                f"no block relates {block!r}; blocks in this model: "
                f"{pairs}")
        bi = int(block)
        if not 0 <= bi < len(model.blocks):
            raise ValueError(
                f"block index {bi} out of range; this model has "
                f"{len(model.blocks)} blocks")
        return bi, False

    # -- prediction --------------------------------------------------------

    def predict(self, i, j, block: Union[int, Tuple[str, str]] = 0,
                return_var: bool = False):
        """Posterior-mean prediction at cells (i[e], j[e]) of a block.

        The identical streaming average the in-session accumulator
        runs — one ``predict_one`` per sample, summed in chain order —
        so a reload reproduces the in-session posterior mean at the
        same cells to float32 tolerance.  A tuple ``block`` addresses
        (i, j) in the order the tuple names the entities, whichever
        orientation the block was declared in.

        Routed through the resident cache: repeat calls perform zero
        checkpoint loads (the accumulator runs over the cached device
        arrays — the same float program, so cached and lazy answers
        are bitwise equal).
        """
        bi, flipped = self._resolve_block(block)
        blk = self.model.blocks[bi]
        if flipped:
            i, j = j, i
        i = np.asarray(i)
        test = make_test_set(i, j, np.zeros(i.shape[0], np.float32))
        acc = PredictAccumulator(test)
        for u, v in self._factor_pair_iter(blk.row_entity,
                                           blk.col_entity):
            acc.update(u, v)
        if return_var:
            return np.asarray(acc.mean), np.asarray(acc.var)
        return np.asarray(acc.mean)

    def predict_all(self, block: Union[int, Tuple[str, str]] = 0
                    ) -> np.ndarray:
        """The whole block's posterior-mean prediction.

        Axes follow the order the caller named the entities in a tuple
        ``block`` (an integer block uses the stored orientation).
        """
        bi, flipped = self._resolve_block(block)
        blk = self.model.blocks[bi]
        s = None
        for u, v in self._factor_pair_iter(blk.row_entity,
                                           blk.col_entity):
            p = u @ v.T
            s = p if s is None else s + p
        out = np.asarray(s / self.num_samples)
        return out.T if flipped else out

    def predict_new(self, entity: Union[int, str], F_new,
                    block: Optional[Union[int, Tuple[str, str]]] = None
                    ) -> np.ndarray:
        """Out-of-matrix prediction for UNSEEN rows of ``entity``.

        ``F_new`` (M, D) holds the new rows' side-information features;
        each retained sample maps them into latent space through ITS
        link matrix draw (``mu_s + beta_s^T f``, exposed as
        ``MacauPrior.predict_factor``) and contracts against ITS
        other-entity factor — averaging after the nonlinearity, the
        correct posterior-predictive mean.  Returns (M, n_other)
        predictions against ``block``'s other entity (``block`` may be
        omitted when only one block touches the entity).
        """
        from .priors import MacauPrior
        model = self.model
        e = model.entity_index(entity)
        ent = model.entities[e]
        if not isinstance(ent.prior, MacauPrior):
            raise ValueError(
                f"entity {ent.name!r} has {type(ent.prior).__name__}; "
                "out-of-matrix prediction needs the Macau "
                "side-information prior (its sampled beta link maps "
                "new feature rows to latents) — add_entity(..., "
                "side_info=F)")
        touching = model.blocks_touching(e)
        if block is None:
            if len(touching) != 1:
                names = model.entity_names
                opts = ", ".join(
                    f"({names[model.blocks[bi].row_entity]}, "
                    f"{names[model.blocks[bi].col_entity]})"
                    for bi, _ in touching)
                raise ValueError(
                    f"entity {ent.name!r} touches {len(touching)} "
                    f"blocks ({opts}); pass block= to pick one")
            bi = touching[0][0]
        else:
            bi, _ = self._resolve_block(block)
            if bi not in [b for b, _ in touching]:
                names = model.entity_names
                opts = ", ".join(
                    f"({names[model.blocks[b].row_entity]}, "
                    f"{names[model.blocks[b].col_entity]})"
                    for b, _ in touching)
                raise ValueError(
                    f"block {block!r} does not touch entity "
                    f"{ent.name!r}; touching blocks: {opts}")
        other = model.blocks[bi].other(e)
        F_new = np.atleast_2d(np.asarray(F_new, np.float32))
        if F_new.shape[1] != ent.prior.num_features:
            raise ValueError(
                f"F_new has {F_new.shape[1]} features; entity "
                f"{ent.name!r} was trained with "
                f"{ent.prior.num_features}")
        s = None
        for hyper, v in self._hyper_factor_iter(e, other):
            u = ent.prior.predict_factor(hyper, F_new)
            p = u @ v.T
            s = p if s is None else s + p
        return np.asarray(s / self.num_samples)

    # -- batched top-K recommendation (the serving path) -------------------

    def _block_entities(self, block: Union[int, Tuple[str, str]]
                        ) -> Tuple[int, int]:
        """(user_entity, item_entity) of ``block`` — a tuple block
        names (users, items) in that order; an integer block ranks the
        column entity's rows as items."""
        bi, flipped = self._resolve_block(block)
        blk = self.model.blocks[bi]
        if flipped:
            return blk.col_entity, blk.row_entity
        return blk.row_entity, blk.col_entity

    def user_rows(self, users: Sequence[int],
                  block: Union[int, Tuple[str, str]] = 0
                  ) -> jnp.ndarray:
        """Sampled latent rows of warm users: (B, S, K).

        Gathered from the resident cache when it fits the budget
        (zero loads); streamed from disk once otherwise.
        """
        ue, _ = self._block_entities(block)
        users = np.asarray(users, np.int32)
        n_rows = self.model.entities[ue].n_rows
        bad = users[(users < 0) | (users >= n_rows)]
        if bad.size:
            raise ValueError(
                f"user row(s) {bad.tolist()} out of range for entity "
                f"{self.model.entities[ue].name!r} with {n_rows} rows;"
                " unseen rows are served via features= (cold start)")
        cache = self.warm_cache()
        if cache is not None:
            # (S, B, K) -> (B, S, K)
            return jnp.swapaxes(cache.factors[ue][:, users, :], 0, 1)
        rows = [np.asarray(f)[users] for f in self._factor_iter(ue)]
        return jnp.swapaxes(jnp.asarray(np.stack(rows)), 0, 1)

    def cold_rows(self, F_new,
                  block: Union[int, Tuple[str, str]] = 0
                  ) -> jnp.ndarray:
        """Sampled latent rows for UNSEEN users via the Macau link:
        (M, S, K), one ``mu_s + beta_s^T f`` draw per retained sample
        (same per-sample mapping as ``predict_new``, kept per sample
        so top-K scoring sees the full posterior spread)."""
        from .priors import MacauPrior
        ue, _ = self._block_entities(block)
        ent = self.model.entities[ue]
        if not isinstance(ent.prior, MacauPrior):
            raise ValueError(
                f"entity {ent.name!r} has {type(ent.prior).__name__};"
                " cold-start recommendation needs the Macau "
                "side-information prior — add_entity(..., "
                "side_info=F)")
        F_new = np.atleast_2d(np.asarray(F_new, np.float32))
        if F_new.shape[1] != ent.prior.num_features:
            raise ValueError(
                f"F_new has {F_new.shape[1]} features; entity "
                f"{ent.name!r} was trained with "
                f"{ent.prior.num_features}")
        rows = []
        cache = self.warm_cache()
        if cache is not None:
            for s in range(cache.n_samples):
                rows.append(ent.prior.predict_factor(
                    cache.hyper_at(ue, s), F_new))
        else:
            for st in self.samples():
                rows.append(ent.prior.predict_factor(st.hypers[ue],
                                                     F_new))
        return jnp.swapaxes(jnp.stack(rows), 0, 1)   # (M, S, K)

    def _exclude_mask(self, exclude, B: int, n_items: int):
        """Per-query excluded item ids -> (B, n_items) f32 mask."""
        if exclude is None:
            return None
        mask = np.zeros((B, n_items), np.float32)
        if len(exclude) != B:
            raise ValueError(
                f"exclude has {len(exclude)} entries for {B} queries;"
                " pass one id-sequence (possibly empty) per query")
        for b, ids in enumerate(exclude):
            ids = np.asarray(ids, np.int64)
            if ids.size:
                if ids.min() < 0 or ids.max() >= n_items:
                    raise ValueError(
                        f"exclude ids for query {b} outside "
                        f"[0, {n_items})")
                mask[b, ids] = 1.0
        return mask

    def recommend_rows(self, rows: jnp.ndarray, k: int = 10,
                       block: Union[int, Tuple[str, str]] = 0,
                       exclude=None) -> RecResult:
        """Top-K items for pre-resolved query rows (B, S, K).

        The batched serving primitive: scores every query against the
        item factor stack across all retained samples through the
        fused ``kernels.topk_score`` (posterior mean ranking, std
        reported per score), honoring ``model.use_pallas``.  Queries
        are scored with one identical float program each regardless of
        batch size, so a batched call is BITWISE equal to one call per
        query — the contract that lets ``RecommendServer`` batch
        concurrent requests (asserted in tests/test_serving.py).

        ``exclude``: one sequence of item ids per query (e.g. the
        user's already-observed items) left out of the ranking.
        """
        rows = jnp.asarray(rows)
        if rows.ndim != 3:
            raise ValueError(
                f"rows must be (B, S, K), got {rows.shape}; build "
                "them with user_rows()/cold_rows()")
        _, ie = self._block_entities(block)
        n_items = self.model.entities[ie].n_rows
        with self.obs.span("predict/mask", cat="predict"):
            mask = self._exclude_mask(exclude, rows.shape[0], n_items)
            if mask is not None:
                mask = jnp.asarray(mask)
        cache = self.warm_cache()
        if cache is not None:
            with self.obs.span("predict/score", cat="predict"):
                ids, mean, std = ops.topk_score(
                    rows, cache.factors[ie], k, exclude=mask,
                    use_pallas=self.model.use_pallas)
            with self.obs.span("predict/readback", cat="predict"):
                return RecResult(np.asarray(ids), np.asarray(mean),
                                 np.asarray(std))
        return self._recommend_rows_lazy(rows, k, ie, mask)

    def _recommend_rows_lazy(self, rows, k, item_entity, mask
                             ) -> RecResult:
        """Over-budget fallback: stream the store once, accumulating
        per-item score moments, then select like the reference.
        Statistically identical to the cached path; summation order
        differs, so near-ties MAY rank differently (documented —
        serving at scale wants the cache)."""
        B, S, _ = rows.shape
        mean_sum = None
        ex2_sum = None
        for s, v in enumerate(self._factor_iter(item_entity)):
            p = jnp.einsum("bk,nk->bn", rows[:, s, :], v)
            mean_sum = p if mean_sum is None else mean_sum + p
            p2 = p * p
            ex2_sum = p2 if ex2_sum is None else ex2_sum + p2
        inv_s = jnp.float32(1.0) / jnp.float32(S)
        mean = mean_sum * inv_s
        ex2 = ex2_sum * inv_s
        std = jnp.sqrt(jnp.maximum(ex2 - mean * mean, 0.0))
        excl = (jnp.zeros_like(mean) if mask is None
                else jnp.asarray(mask))
        rank = jnp.where(excl > 0, -jnp.inf, mean)
        k_eff = min(int(k), rank.shape[1])
        order = jnp.argsort(-rank, axis=1)[:, :k_eff]    # stable
        sel_mean = jnp.take_along_axis(mean, order, axis=1)
        sel_std = jnp.take_along_axis(std, order, axis=1)
        n_valid = jnp.sum(excl <= 0, axis=1).astype(jnp.int32)
        bad = jnp.arange(k_eff, dtype=jnp.int32)[None, :] \
            >= n_valid[:, None]
        return RecResult(
            np.asarray(jnp.where(bad, -1, order.astype(jnp.int32))),
            np.asarray(jnp.where(bad, jnp.nan, sel_mean)),
            np.asarray(jnp.where(bad, jnp.nan, sel_std)))

    def recommend(self, user: Optional[Union[int, Sequence[int]]]
                  = None, *, features=None, k: int = 10,
                  block: Union[int, Tuple[str, str]] = 0,
                  exclude=None) -> RecResult:
        """Top-K recommendation for warm and/or cold users.

        ``user``: row id(s) seen in training; ``features``: (M, D)
        side-information rows for UNSEEN users, mapped through the
        sampled Macau link (cold start).  Warm queries come first in
        the result when both are given.  ``exclude`` follows
        ``recommend_rows`` (for a single query, a flat id list is
        accepted).
        """
        parts = []
        n_q = 0
        if user is not None:
            users = np.atleast_1d(np.asarray(user, np.int32))
            parts.append(self.user_rows(users, block))
            n_q += users.shape[0]
        if features is not None:
            cold = self.cold_rows(features, block)
            parts.append(cold)
            n_q += cold.shape[0]
        if not parts:
            raise ValueError(
                "pass user= (warm row ids) and/or features= "
                "(cold-start side info)")
        if exclude is not None and n_q == 1:
            # single-query convenience: accept a flat id list — and an
            # EMPTY one ("nothing to exclude"), which must normalize to
            # one empty per-query sequence, not zero sequences
            ex = list(exclude)
            if not ex or np.ndim(ex[0]) == 0:
                exclude = [ex]
        rows = parts[0] if len(parts) == 1 else \
            jnp.concatenate(parts, axis=0)
        return self.recommend_rows(rows, k, block, exclude)
