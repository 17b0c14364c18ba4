"""High-level session API: compose any multi-relation model, run it.

The paper's claim is a *framework*: priors x noise x matrix types x
side information compose freely (Table 1).  The engine underneath
(``ModelDef``/``BlockDef``/``EntityDef`` + ``gibbs_step`` + the
shard_map sweep in ``distributed.py``) always handled arbitrary
entity/block graphs; this module exposes that through a declarative
builder instead of hardcoded session shapes:

    import repro.core as smurff

    b = smurff.ModelBuilder(num_latent=16)
    b.add_entity("compound", 5000, side_info=ecfp)      # -> Macau
    b.add_entity("target", 600)
    b.add_entity("cellline", 60)
    b.add_block("compound", "target", ic50, test=(i, j, v),
                noise=smurff.AdaptiveGaussian())
    b.add_block("compound", "cellline", viability)      # shares entity
    session = b.session(burnin=200, nsamples=400, seed=0,
                        save_freq=10, save_dir="run0",
                        mesh=mesh, pipeline="ring")
    result = session.run()
    result.rmse_test, result.blocks[1].rmse_train_trace

    p = smurff.PredictSession("run0")                    # from disk
    p.predict(i_new, j_new)                              # in-matrix
    p.predict_new("compound", ecfp_new)                  # out-of-matrix

Validation is eager: unknown entity names, duplicate blocks, and
shape mismatches raise ValueErrors naming the valid choices at
``add_*`` time, not as shape errors deep inside jit.

``TrainSession`` (one R matrix, two entities) and ``GFASession``
(star of dense views) remain as thin wrappers over the builder — they
compose the same ``ModelDef`` graphs they always did, so their sampled
chains are unchanged (pinned by tests/test_golden_chain.py's wrapper
replay).  ``save_freq`` streams posterior samples through
``checkpoint.CheckpointManager``; ``PredictSession`` (core/predict.py)
reloads them for averaged prediction and ``Session.run(resume=True)``
continues an interrupted chain from the last complete sample.
"""
from __future__ import annotations

import dataclasses
import os
from typing import (Any, Callable, Dict, List, NamedTuple, Optional,
                    Sequence, Tuple, Union)

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import clock, resolve_recorder
from .blocks import (BlockDef, DenseBlock, EntityDef, ModelDef,
                     dense_block)
from .diagnostics import (Diagnostics, compute_diagnostics,
                          save_diagnostics)
from .gibbs import (MFData, MFState, gibbs_step, init_chain_states,
                    init_state, multi_chain_step_jit, stack_states,
                    unstack_state)
from .noise import AdaptiveGaussian, FixedGaussian, ProbitNoise
from .predict import PredictAccumulator, TestSet, make_test_set
from .priors import (FixedNormalPrior, MacauPrior, NormalPrior,
                     SpikeAndSlabPrior)
from .sparse import SparseMatrix


# ---------------------------------------------------------------------------
# results
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class BlockResult:
    """Per-block view of a run: traces + posterior-mean test metrics."""

    block: int
    entities: Tuple[str, str]
    rmse_train_trace: List[float]
    rmse_test_trace: List[float]
    rmse_test: Optional[float]
    auc_test: Optional[float]
    predictions: Optional[np.ndarray]
    pred_var: Optional[np.ndarray]


@dataclasses.dataclass
class SessionResult:
    """Result of one run (one chain, or ``chains=C`` stacked chains).

    The scalar fields mirror the first block carrying a test set
    (block 0's train trace for back-compat); ``blocks`` holds every
    block's traces and metrics for multi-relation models.  With
    ``chains=C > 1``:

    * test metrics / ``predictions`` pool the posterior draws of ALL
      chains (step-major, chain-minor summation order — the same order
      ``PredictSession`` replays from a multi-chain store);
    * ``blocks``' train traces follow chain 0; ``chain_blocks[c]``
      carries every chain's per-block traces;
    * ``state`` and ``factor_means`` entries gain a leading ``(C,)``
      chain axis;
    * ``diagnostics`` holds split-R-hat / bulk-ESS per monitored
      quantity (``core.diagnostics``), also written to
      ``save_dir/diagnostics.json`` when streaming samples;
    * ``resumed_from`` records the completed-sweep count a
      ``run(resume=True)`` continued from (``None`` for a fresh run) —
      traces and accumulators cover only post-resume sweeps.
    """

    rmse_test: Optional[float]
    auc_test: Optional[float]
    predictions: Optional[np.ndarray]
    pred_var: Optional[np.ndarray]
    rmse_train_trace: List[float]
    rmse_test_trace: List[float]
    nsamples: int
    runtime_s: float
    state: MFState
    samples: Optional[List[Tuple[np.ndarray, ...]]] = None
    blocks: List[BlockResult] = dataclasses.field(default_factory=list)
    factor_means: Optional[List[np.ndarray]] = None
    save_dir: Optional[str] = None
    n_chains: int = 1
    chain_blocks: Optional[List[List[BlockResult]]] = None
    diagnostics: Optional[Diagnostics] = None
    resumed_from: Optional[int] = None
    # PR 10 split: ``runtime_s`` is sweep wall time ONLY; the one-time
    # jit compilation (plus the discarded warm-up sweep that triggers
    # it) lands here instead of silently inflating the first sweep.
    compile_s: float = 0.0

    def to_dict(self) -> Dict[str, Any]:
        """JSON-able scalar summary of the run.

        Keeps the ``runtime_s`` key (pre-PR-10 consumers read it; it
        now means sweep time only) alongside the ``compile_s`` split;
        ``total_s`` is their sum — what the old ``runtime_s`` used to
        (approximately) report.
        """
        return {
            "rmse_test": self.rmse_test,
            "auc_test": self.auc_test,
            "nsamples": self.nsamples,
            "n_chains": self.n_chains,
            "runtime_s": self.runtime_s,
            "compile_s": self.compile_s,
            "total_s": self.compile_s + self.runtime_s,
            "rmse_train_trace": [float(v) for v in
                                 self.rmse_train_trace],
            "rmse_test_trace": [float(v) for v in self.rmse_test_trace],
            "save_dir": self.save_dir,
            "resumed_from": self.resumed_from,
            "diagnostics": (self.diagnostics.to_dict()
                            if self.diagnostics is not None else None),
        }

    def mean_from_samples(self, test: TestSet, row_entity: int = 0,
                          col_entity: int = 1) -> np.ndarray:
        """Posterior-mean predictions recomputed from kept samples.

        Replays the in-session accumulator over ``samples`` (requires
        ``run(keep_samples=True)``) — same ``predict_one`` kernel, same
        summation order — so for the same test set this reproduces
        ``predictions`` EXACTLY, not just statistically (asserted in
        tests/test_predict_session.py).
        """
        if self.samples is None:
            raise ValueError("no samples kept; run(keep_samples=True)")
        if not isinstance(test, TestSet):
            test = make_test_set(*test)
        acc = PredictAccumulator(test)
        for fs in self.samples:
            acc.update(jnp.asarray(fs[row_entity]),
                       jnp.asarray(fs[col_entity]))
        return np.asarray(acc.mean)


class SweepInfo(NamedTuple):
    """What a per-sweep callback sees (after the sweep completed).

    ``metrics`` are always chain-0 SCALARS (existing single-chain
    callbacks keep working under ``chains=C``); a multi-chain run
    additionally exposes the full stacked ``(C,)`` metrics as
    ``chain_metrics`` (``None`` when ``chains == 1``).  ``state`` is
    the full post-sweep state — chain-stacked for a multi-chain run.
    """

    sweep: int          # 0-based global sweep index
    phase: str          # "burnin" | "sample"
    state: MFState      # post-sweep sampler state (device arrays)
    metrics: Dict[str, jnp.ndarray]   # rmse_train_<b> / alpha_<b>
    chain_metrics: Optional[Dict[str, jnp.ndarray]] = None


_PRIORS = {"normal": NormalPrior, "spikeandslab": SpikeAndSlabPrior,
           "fixednormal": FixedNormalPrior}


def resolve_chains(chains: Optional[int] = None) -> int:
    """Validate the chain-count knob, defaulting from the
    ``REPRO_CHAINS`` environment variable (CI runs a chains=4 smoke
    leg that way), else 1."""
    if chains is None:
        chains = int(os.environ.get("REPRO_CHAINS", "1"))
    chains = int(chains)
    if chains < 1:
        raise ValueError(f"chains must be >= 1, got {chains}")
    return chains


def _prior_by_name(name: str, num_latent: int):
    if name not in _PRIORS:
        raise ValueError(
            f"unknown prior {name!r}; valid priors: "
            f"{', '.join(sorted(_PRIORS))} (side information selects "
            "the macau prior automatically)")
    return _PRIORS[name](num_latent)


def _place_step(model: ModelDef, data: MFData, state: MFState,
                mesh: Any, pipeline: Optional[str]):
    """(data, state, step) — distributed through ``mesh`` when given.

    Shared by every session flavor: builds the explicit shard_map
    sweep with the requested exchange ``pipeline``
    ("eager"/"ring"/None-for-REPRO_PIPELINE) and places data/state on
    the mesh; without a mesh the single-device ``gibbs_step`` runs.
    Warns — naming the offending model piece — when the model falls
    outside the sharded subset: the pjit fallback still samples the
    same chain, just with partitioner-placed collectives.  The
    ``pipeline`` knob is validated even without a mesh (a typo must
    raise, not silently run the single-device sweep), and asking for a
    pipeline WITH no mesh to run it on warns — there is no exchange to
    pipeline.
    """
    from .distributed import (distributed_unsupported_reason,
                              make_distributed_step, resolve_pipeline)
    resolve_pipeline(pipeline)
    if mesh is None:
        if pipeline is not None:
            import warnings
            warnings.warn(
                f"pipeline={pipeline!r} has no effect without mesh=: "
                "the session runs the single-device sweep",
                stacklevel=3)
        return data, state, (lambda d, s: gibbs_step(model, d, s))
    reason = distributed_unsupported_reason(model, mesh, data)
    if reason is not None:
        import warnings
        warnings.warn(
            f"model is outside the sharded subset on this mesh "
            f"({reason}); falling back to auto-partitioned pjit",
            stacklevel=3)
    step, ds, ss = make_distributed_step(model, mesh, data, state,
                                         pipeline=pipeline)
    return jax.device_put(data, ds), jax.device_put(state, ss), step


def _place_multi_step(model: ModelDef, data: MFData, stacked: MFState,
                      mesh: Any, pipeline: Optional[str],
                      chains: int, chain_axis: Optional[str]):
    """``_place_step`` for a chain-stacked state (``chains > 1``).

    Single-device: ``lax.map`` of ``gibbs_step`` over the chain axis
    (bitwise-identical per-chain subgraphs — see
    ``gibbs.multi_chain_step``).  With a mesh: the chain-stacked
    shard_map sweep (``distributed.make_multi_chain_step``), sharding
    chains over ``chain_axis`` when given.
    """
    from .distributed import (distributed_unsupported_reason,
                              make_multi_chain_step, resolve_pipeline)
    resolve_pipeline(pipeline)
    if mesh is None:
        if pipeline is not None:
            import warnings
            warnings.warn(
                f"pipeline={pipeline!r} has no effect without mesh=: "
                "the session runs the single-device sweep",
                stacklevel=3)
        return data, stacked, (
            lambda d, s: multi_chain_step_jit(model, d, s))
    reason = distributed_unsupported_reason(model, mesh, data)
    if reason is not None:
        import warnings
        warnings.warn(
            f"model is outside the sharded subset on this mesh "
            f"({reason}); falling back to auto-partitioned pjit",
            stacklevel=3)
    step, ds, ss = make_multi_chain_step(model, mesh, data, stacked,
                                         pipeline=pipeline,
                                         chains=chains,
                                         chain_axis=chain_axis)
    return jax.device_put(data, ds), jax.device_put(stacked, ss), step


# ---------------------------------------------------------------------------
# the declarative builder
# ---------------------------------------------------------------------------

class ModelBuilder:
    """Compose an arbitrary entity/block graph, validated eagerly.

    * ``add_entity(name, n, prior=..., side_info=...)`` declares a
      latent-factor entity.  ``prior`` is a registry name ("normal",
      "spikeandslab", "fixednormal") or a prior instance; passing
      ``side_info`` (an (n, D) feature matrix) selects the Macau
      prior with a sampled link matrix instead.
    * ``add_block(ent_a, ent_b, data, noise=..., test=...)`` relates
      two entities through an observed matrix — a ``SparseMatrix``,
      a dense ndarray (optionally with ``mask=``), or a prebuilt
      ``DenseBlock``.  ``test=(i, j, v)`` attaches per-block test
      triplets evaluated by posterior-mean prediction.

    Entities may be shared by any number of blocks (the two-relation
    compound x target / compound x cell-line layout, GFA's view star,
    tensor-style chains ...).  Every mistake — unknown or duplicate
    names, shape mismatches, self-blocks — raises a ValueError naming
    the valid choices at ``add_*`` time.

    ``build()`` returns the engine triple; ``session(...)`` wraps it
    in a runnable :class:`Session` carrying the ``mesh=``/``pipeline=``
    distribution knobs, ``save_freq``/``save_dir`` posterior-sample
    streaming, and per-sweep ``callbacks``.
    """

    def __init__(self, num_latent: int = 16, use_pallas: bool = False,
                 bf16_gather: bool = False):
        self.num_latent = num_latent
        self.use_pallas = use_pallas
        self.bf16_gather = bf16_gather
        self._entities: List[Tuple[str, int, Any,
                                   Optional[np.ndarray]]] = []
        self._blocks: List[Tuple[str, str, Any, Any,
                                 Optional[TestSet]]] = []

    # -- entities ----------------------------------------------------------

    def _names(self) -> List[str]:
        return [name for name, *_ in self._entities]

    def add_entity(self, name: str, n: int,
                   prior: Union[str, Any] = "normal",
                   side_info: Optional[np.ndarray] = None,
                   beta_precision: float = 5.0,
                   sample_beta_precision: bool = True) -> "ModelBuilder":
        if name in self._names():
            raise ValueError(
                f"duplicate entity {name!r}; entities already added: "
                f"{', '.join(self._names())}")
        n = int(n)
        if n <= 0:
            raise ValueError(f"entity {name!r} needs n > 0, got {n}")
        side = None
        if side_info is not None:
            if not isinstance(prior, str) or prior != "normal":
                raise ValueError(
                    f"entity {name!r}: pass either prior= or "
                    "side_info=, not both — side information selects "
                    "the macau prior automatically")
            side = np.asarray(side_info, np.float32)
            if side.ndim != 2 or side.shape[0] != n:
                raise ValueError(
                    f"entity {name!r} side_info must be ({n}, D), got "
                    f"{side.shape}")
            p = MacauPrior(self.num_latent, side.shape[1],
                           beta_precision=beta_precision,
                           sample_beta_precision=sample_beta_precision)
        elif isinstance(prior, str):
            p = _prior_by_name(
                prior.replace("-", "").replace("_", "").lower(),
                self.num_latent)
        else:
            p = prior
            pk = getattr(p, "num_latent", None)
            if pk is not None and pk != self.num_latent:
                raise ValueError(
                    f"entity {name!r} prior {type(p).__name__} has "
                    f"num_latent={pk}, but the builder composes a "
                    f"num_latent={self.num_latent} model")
        self._entities.append((name, n, p, side))
        return self

    # -- blocks ------------------------------------------------------------

    def _entity_index(self, name: str) -> int:
        names = self._names()
        if name not in names:
            known = ", ".join(names) if names else "(none yet)"
            raise ValueError(
                f"unknown entity {name!r}; entities added so far: "
                f"{known} — add_entity first")
        return names.index(name)

    def add_block(self, row_entity: str, col_entity: str, data,
                  noise: Any = None, test=None,
                  mask: Optional[np.ndarray] = None) -> "ModelBuilder":
        ri = self._entity_index(row_entity)
        ci = self._entity_index(col_entity)
        if ri == ci:
            raise ValueError(
                f"block {row_entity!r} x {col_entity!r} relates an "
                "entity to itself; blocks must relate two distinct "
                "entities")
        for r2, c2, *_ in self._blocks:
            if {r2, c2} == {row_entity, col_entity}:
                raise ValueError(
                    f"duplicate block {row_entity!r} x {col_entity!r}: "
                    f"the pair already carries the {r2!r} x {c2!r} "
                    "block (one observed matrix per entity pair)")
        if isinstance(data, (SparseMatrix, DenseBlock)):
            if mask is not None:
                raise ValueError("mask= only applies to raw dense "
                                 "ndarray data")
            payload = data
        else:
            payload = dense_block(np.asarray(data, np.float32), mask)
        want = (self._entities[ri][1], self._entities[ci][1])
        got = tuple(payload.shape)
        if got != want:
            raise ValueError(
                f"block {row_entity!r} x {col_entity!r} data has shape "
                f"{got}, expected {want} "
                f"({row_entity}={want[0]} rows x {col_entity}={want[1]}"
                " cols)")
        ts = None
        if test is not None:
            ts = test if isinstance(test, TestSet) else make_test_set(*test)
        self._blocks.append((row_entity, col_entity, payload,
                             noise if noise is not None
                             else FixedGaussian(5.0), ts))
        return self

    # -- build -------------------------------------------------------------

    def build(self) -> Tuple[ModelDef, MFData, Dict[int, TestSet]]:
        """(ModelDef, MFData, {block_index: TestSet}) for the engine."""
        if not self._entities:
            raise ValueError("empty model: add_entity at least two "
                             "entities and add_block a matrix")
        if not self._blocks:
            raise ValueError(
                "model has no blocks: add_block at least one observed "
                f"matrix between entities {', '.join(self._names())}")
        ents = tuple(EntityDef(name, n, prior)
                     for name, n, prior, _ in self._entities)
        blocks = tuple(
            BlockDef(self._entity_index(r), self._entity_index(c),
                     noise, isinstance(payload, SparseMatrix))
            for r, c, payload, noise, _ in self._blocks)
        model = ModelDef(ents, blocks, self.num_latent, self.use_pallas,
                         self.bf16_gather)
        sides = tuple(None if s is None else jnp.asarray(s)
                      for *_, s in self._entities)
        data = MFData(tuple(p for _, _, p, _, _ in self._blocks), sides)
        tests = {bi: ts for bi, (*_, ts) in enumerate(self._blocks)
                 if ts is not None}
        return model, data, tests

    def session(self, **kwargs) -> "Session":
        model, data, tests = self.build()
        return Session(model, data, tests=tests, **kwargs)


# ---------------------------------------------------------------------------
# the generic run loop
# ---------------------------------------------------------------------------

class Session:
    """Run a Gibbs chain over any built model graph.

    * ``mesh=`` routes through the explicit distributed sweep
      (``make_distributed_step``); ``pipeline`` selects the
      fixed-factor exchange — ``"eager"`` (one all-gather per
      half-sweep) or ``"ring"`` (``n_shards - 1`` double-buffered
      ppermute hops).  ``None`` defers to ``REPRO_PIPELINE``; either
      way the sampled chain matches the single-device one at
      reduction-order tolerance (counter-based per-row RNG — see
      ``core/distributed.py``).
    * ``save_freq=k`` streams every k-th post-burnin sample (the full
      ``MFState``) to ``save_dir`` through
      ``checkpoint.CheckpointManager`` plus a ``model.json`` spec —
      the on-disk layout :class:`~repro.core.predict.PredictSession`
      reloads; ``run(resume=True)`` continues an interrupted chain
      from the last complete sample on disk.
    * ``chains=C`` runs C independent Gibbs chains in ONE compiled
      program (``lax.map`` over a leading chain axis — bitwise equal
      to C separate runs keyed ``gibbs.chain_keys(seed, C)``; chain 0
      IS the single-chain run for the same seed).  ``None`` defers to
      the ``REPRO_CHAINS`` environment variable.  Test metrics pool
      the chains' posterior draws; split-R-hat / bulk-ESS over the
      per-chain traces land in ``SessionResult.diagnostics`` and — when
      streaming — in ``save_dir/diagnostics.json``, which
      ``PredictSession(require_converged=True)`` gates on.  Samples
      stream per chain under ``save_dir/chain_<c>/`` (each a valid
      single-chain store).  ``chain_axis=`` names a mesh axis to shard
      the chains over, so chains x row-shards fills a pod
      (``Mesh(devices.reshape(C, -1), ("chain", "data"))``).
    * ``callbacks`` are called after every sweep with a
      :class:`SweepInfo` (trace collection, convergence monitors,
      extra checkpointing ...).
    """

    def __init__(self, model: ModelDef, data: MFData, *,
                 tests: Optional[Dict[int, TestSet]] = None,
                 burnin: int = 100, nsamples: int = 100, seed: int = 0,
                 mesh: Any = None, pipeline: Optional[str] = None,
                 chains: Optional[int] = None,
                 chain_axis: Optional[str] = None,
                 save_freq: int = 0, save_dir: Optional[str] = None,
                 verbose: int = 0,
                 callbacks: Sequence[Callable[[SweepInfo], None]] = (),
                 init_transform: Optional[Callable[[MFState],
                                                   MFState]] = None,
                 accumulate_factor_means: bool = False,
                 recorder: Any = None):
        self.model = model
        self.data = data
        self.tests = dict(tests or {})
        for bi in self.tests:
            if not 0 <= bi < len(model.blocks):
                raise ValueError(
                    f"test set attached to block {bi}, but the model "
                    f"has blocks 0..{len(model.blocks) - 1}")
        self.burnin = burnin
        self.nsamples = nsamples
        self.seed = seed
        self.mesh = mesh
        self.pipeline = pipeline
        self.chains = resolve_chains(chains)
        self.chain_axis = chain_axis
        if chain_axis is not None and mesh is None:
            raise ValueError(
                f"chain_axis={chain_axis!r} shards chains over a mesh "
                "axis; pass mesh= too")
        self.save_freq = save_freq
        self.save_dir = save_dir
        self.verbose = verbose
        self.callbacks = tuple(callbacks)
        self.init_transform = init_transform
        self.accumulate_factor_means = accumulate_factor_means
        # None -> fresh per-run Recorder at run() time, enabled iff
        # REPRO_OBS=1; an explicit Recorder is shared with the
        # checkpoint savers and exported by the caller
        self.recorder = recorder
        if save_freq and not save_dir:
            raise ValueError(
                "save_freq > 0 streams posterior samples to disk; "
                "pass save_dir= too")

    # -- persistence -------------------------------------------------------

    def _run_spec(self, chain: Optional[int] = None) -> dict:
        run = {"burnin": self.burnin, "nsamples": self.nsamples,
               "save_freq": self.save_freq, "seed": self.seed,
               "chains": self.chains}
        if chain is not None:
            run["chain"] = chain
        return run

    def _spec_at(self, directory: str, chain: Optional[int] = None):
        from .modelspec import (MODEL_SPEC_FILE, model_to_spec,
                                save_model_spec)
        os.makedirs(directory, exist_ok=True)
        spec = model_to_spec(self.model)
        spec["run"] = self._run_spec(chain)
        save_model_spec(os.path.join(directory, MODEL_SPEC_FILE), spec)

    def _make_savers(self, recorder=None):
        """One CheckpointManager per chain.

        ``chains == 1`` keeps the PR 5 layout exactly
        (``save_dir/model.json`` + ``save_dir/samples/step_<s>/``).
        ``chains = C > 1`` nests a full single-chain store per chain —
        ``save_dir/chain_<c>/{model.json, samples/}`` — under a shared
        top-level ``model.json`` whose ``run.chains`` announces the
        layout to ``PredictSession``.
        """
        from ..checkpoint import CheckpointManager
        from .modelspec import SAMPLES_SUBDIR, chain_subdir
        self._spec_at(self.save_dir)
        if self.chains == 1:
            # keep=None: a posterior-sample store retains EVERY step
            return [CheckpointManager(
                os.path.join(self.save_dir, SAMPLES_SUBDIR), keep=None,
                recorder=recorder)]
        savers = []
        for c in range(self.chains):
            cdir = os.path.join(self.save_dir, chain_subdir(c))
            self._spec_at(cdir, chain=c)
            savers.append(CheckpointManager(
                os.path.join(cdir, SAMPLES_SUBDIR), keep=None,
                recorder=recorder))
        return savers

    def _restore(self, savers, state: MFState):
        """(start, state) from the newest checkpoint every chain has.

        Single chain: the latest complete step.  Multi-chain: the
        HIGHEST COMMON step across chains (an interrupted run can leave
        chains one save apart; ``keep=None`` retains every earlier
        step, so the common step always exists on disk).  Returns None
        when any chain store is empty.
        """
        if self.chains == 1:
            return savers[0].restore_latest(state)
        common = None
        for sv in savers:
            steps = set(sv.all_steps())
            common = steps if common is None else (common & steps)
        if not common:
            return None
        step = max(common)
        chains = [sv.restore_step(unstack_state(state, c), step)
                  for c, sv in enumerate(savers)]
        return step, stack_states(chains)

    # -- run ---------------------------------------------------------------

    def _wire_bytes(self) -> int:
        """Contract-derived bytes-on-wire per device per sweep — the
        ``args.bytes_on_wire`` annotation on every sweep span.  Pure
        arithmetic over the ModelDef (``analysis.contract``); 0
        without a mesh."""
        # analysis imports the model zoo; keep it out of core's import
        # graph until observability actually asks for it
        from ..analysis.contract import contract_for, contract_wire_bytes
        if self.mesh is None:
            mesh_shape: Tuple[int, ...] = (1,)
            chain_axis_size = None
        else:
            mesh_shape = tuple(int(s)
                               for s in np.asarray(self.mesh.devices).shape)
            chain_axis_size = (int(self.mesh.shape[self.chain_axis])
                               if self.chain_axis is not None else None)
        c = contract_for(self.model, mesh_shape, self.pipeline,
                         chains=self.chains,
                         chain_axis_size=chain_axis_size)
        return contract_wire_bytes(self.model, c)

    def _export_obs(self, rec) -> None:
        """Write the run's trace + metrics snapshots when enabled.

        Destination: ``REPRO_OBS_DIR`` if set, else ``save_dir/obs``
        when the session streams samples; with neither there is
        nowhere sensible to write and the caller owns the export
        (``rec.write_trace(...)``)."""
        if not rec.enabled:
            return
        dest = os.environ.get("REPRO_OBS_DIR")
        if dest is None and self.save_dir:
            dest = os.path.join(self.save_dir, "obs")
        if dest is None:
            return
        rec.write_trace(os.path.join(dest, "train_trace.json"))
        rec.write_metrics(os.path.join(dest, "train_metrics.json"))

    def run(self, keep_samples: bool = False,
            resume: bool = False) -> SessionResult:
        rec = resolve_recorder(self.recorder)
        # every garbage collection of the run is a ``gc`` span
        with rec.gc_spans():
            return self._run(rec, keep_samples, resume)

    def _run(self, rec, keep_samples: bool,
             resume: bool) -> SessionResult:
        model, data = self.model, self.data
        rec.set_kind("session")
        C = self.chains
        if C == 1:
            state = init_state(model, data, self.seed)
            if self.init_transform is not None:
                state = self.init_transform(state)
        else:
            chain_states = init_chain_states(model, data, self.seed, C)
            if self.init_transform is not None:
                chain_states = [self.init_transform(s)
                                for s in chain_states]
            state = stack_states(chain_states)

        savers = []
        start = 0
        resumed_from: Optional[int] = None
        if self.save_freq:
            savers = self._make_savers(recorder=rec)
            if resume:
                restored = self._restore(savers, state)
                if restored is not None:
                    start, state = restored
                    resumed_from = start
        elif resume:
            raise ValueError(
                "resume=True needs save_freq > 0 and a save_dir "
                "holding the interrupted chain's samples")

        if C == 1:
            data, state, step = _place_step(model, data, state,
                                            self.mesh, self.pipeline)
        else:
            data, state, step = _place_multi_step(
                model, data, state, self.mesh, self.pipeline, C,
                self.chain_axis)
        accs = {bi: PredictAccumulator(ts)
                for bi, ts in self.tests.items()}
        total = self.burnin + self.nsamples
        # Compile split: trigger jit compilation with a DISCARDED
        # warm-up sweep before the timed loop, so compile_s and
        # runtime_s separate (the old single perf_counter pair charged
        # compilation to sweep time).  ``step`` is pure (no donated
        # buffers anywhere in gibbs/distributed), so running it once
        # and dropping the result cannot perturb the chain — the
        # recorded sweeps below start from the same (data, state).
        compile_s = 0.0
        if start < total:
            t_c = clock.perf_counter()
            warm = step(data, state)
            jax.block_until_ready(warm)
            del warm
            compile_s = clock.perf_counter() - t_c
            rec.complete("session/compile", t_c, cat="session",
                         phase="compile")
        bytes_on_wire = self._wire_bytes() if rec.enabled else 0
        t0 = clock.perf_counter()
        n_blocks = len(model.blocks)
        train_traces: List[List[float]] = [[] for _ in range(n_blocks)]
        chain_train_traces: List[List[List[float]]] = [
            [[] for _ in range(n_blocks)] for _ in range(C)]
        test_traces: Dict[int, List[float]] = {bi: []
                                               for bi in self.tests}
        samples: List[Tuple[np.ndarray, ...]] = []
        sums = None
        if self.accumulate_factor_means:
            lead = () if C == 1 else (C,)
            sums = [jnp.zeros(lead + (e.n_rows, model.num_latent))
                    for e in model.entities]
        n_acc = 0
        # post-burnin traces of the monitored scalars, (C,) per sweep,
        # feeding split-R-hat / bulk-ESS at the end of the run
        diag_traces: Dict[str, List[np.ndarray]] = {}

        # one span per stage of the loop: the sweep (dispatch through
        # the metrics readback, which waits for it), then the host's
        # work on its result
        for sweep in range(start, total):
            in_sampling = sweep >= self.burnin
            phase = "sample" if in_sampling else "burnin"
            t_sweep = rec.now()
            with rec.span("sweep", cat="session", sweep=sweep, phase=phase,
                          stage="first" if sweep == start else "steady",
                          bytes_on_wire=bytes_on_wire):
                state, metrics = step(data, state)
                with rec.span("session/readback", cat="session"):
                    for bi in range(n_blocks):
                        arr = np.atleast_1d(
                            np.asarray(metrics[f"rmse_train_{bi}"]))
                        train_traces[bi].append(float(arr[0]))
                        for c in range(C):
                            chain_train_traces[c][bi].append(float(arr[c]))
            rec.observe("session.sweep_s", rec.now() - t_sweep)
            rec.add("session.sweeps")
            if in_sampling:
                with rec.span("session/accumulate", cat="session"):
                    # pool posterior draws across chains: step-major,
                    # chain-minor — the summation order PredictSession
                    # replays from a multi-chain store
                    for bi, acc in accs.items():
                        blk = model.blocks[bi]
                        if C == 1:
                            acc.update(state.factors[blk.row_entity],
                                       state.factors[blk.col_entity])
                        else:
                            for c in range(C):
                                acc.update(
                                    state.factors[blk.row_entity][c],
                                    state.factors[blk.col_entity][c])
                        test_traces[bi].append(
                            float(jnp.sqrt(jnp.mean(
                                (acc.mean - acc.test.v) ** 2))))
                    if keep_samples:
                        if C == 1:
                            samples.append(tuple(np.asarray(f)
                                                 for f in state.factors))
                        else:
                            for c in range(C):
                                samples.append(tuple(
                                    np.asarray(f[c])
                                    for f in state.factors))
                    if sums is not None:
                        sums = [s + f for s, f in zip(sums, state.factors)]
                        n_acc += 1
                    for nm, v in metrics.items():
                        diag_traces.setdefault(nm, []).append(
                            np.atleast_1d(np.asarray(v, np.float64)))
                    for e, ent in enumerate(model.entities):
                        f = state.factors[e]
                        rms = jnp.sqrt(jnp.mean(
                            f * f, axis=None if C == 1 else (1, 2)))
                        diag_traces.setdefault(
                            f"factor_rms_{ent.name}", []).append(
                            np.atleast_1d(np.asarray(rms, np.float64)))
                if savers and \
                        (sweep - self.burnin + 1) % self.save_freq == 0:
                    with rec.span("session/save", cat="session"):
                        if C == 1:
                            savers[0].save(sweep + 1, state)
                        else:
                            for c, sv in enumerate(savers):
                                sv.save(sweep + 1, unstack_state(state, c))
            if self.verbose and (sweep % max(1, total // 20) == 0):
                print(f"[{phase} {sweep:4d}] rmse_train="
                      f"{train_traces[0][-1]:.4f}")
            if self.callbacks:
                with rec.span("session/callbacks", cat="session"):
                    if C == 1:
                        info = SweepInfo(sweep, phase, state, metrics)
                    else:
                        m0 = {k: v[0] for k, v in metrics.items()}
                        info = SweepInfo(sweep, phase, state, m0, metrics)
                    for cb in self.callbacks:
                        cb(info)
        for sv in savers:
            sv.wait()

        diag = None
        if diag_traces:
            diag = compute_diagnostics(
                {k: np.stack(v, axis=1) for k, v in diag_traces.items()})
            if savers:
                save_diagnostics(self.save_dir, diag)

        runtime = clock.perf_counter() - t0
        names = model.entity_names
        block_results: List[BlockResult] = []
        head: Optional[BlockResult] = None
        for bi, blk in enumerate(model.blocks):
            acc = accs.get(bi)
            if acc is not None and acc.n == 0:
                acc = None   # resumed past the end: nothing accumulated
            is_probit = isinstance(blk.noise, ProbitNoise)
            br = BlockResult(
                block=bi,
                entities=(names[blk.row_entity], names[blk.col_entity]),
                rmse_train_trace=train_traces[bi],
                rmse_test_trace=test_traces.get(bi, []),
                rmse_test=(acc.rmse() if acc else None),
                auc_test=(acc.auc() if (acc and is_probit) else None),
                predictions=(np.asarray(acc.mean) if acc else None),
                pred_var=(np.asarray(acc.var) if acc else None))
            block_results.append(br)
            if head is None and acc is not None:
                head = br
        if head is None:
            head = block_results[0]
        chain_blocks = None
        if C > 1:
            chain_blocks = [
                [BlockResult(
                    block=bi,
                    entities=(names[blk.row_entity],
                              names[blk.col_entity]),
                    rmse_train_trace=chain_train_traces[c][bi],
                    rmse_test_trace=[], rmse_test=None, auc_test=None,
                    predictions=None, pred_var=None)
                 for bi, blk in enumerate(model.blocks)]
                for c in range(C)]
        means = None
        if sums is not None:
            if n_acc == 0 and self.nsamples > 0:
                raise ValueError(
                    f"run(resume=True) restored the chain at {start} "
                    "completed sweeps — at or past the end of the "
                    f"burnin={self.burnin} + nsamples={self.nsamples} "
                    f"= {total} schedule — so ZERO posterior draws "
                    "were accumulated and factor_means would be "
                    "silently all-zero. The schedule counts TOTAL "
                    "sweeps, not additional ones: raise nsamples to "
                    "extend the chain, or rerun without resume=True.")
            means = [np.asarray(s / max(n_acc, 1)) for s in sums]
        rec.gauge("session.chains", C)
        self._export_obs(rec)
        return SessionResult(
            rmse_test=head.rmse_test,
            auc_test=head.auc_test,
            predictions=head.predictions,
            pred_var=head.pred_var,
            rmse_train_trace=train_traces[0],
            rmse_test_trace=head.rmse_test_trace,
            nsamples=self.nsamples,
            runtime_s=runtime,
            compile_s=compile_s,
            state=state,
            samples=samples if keep_samples else None,
            blocks=block_results,
            factor_means=means,
            save_dir=self.save_dir,
            n_chains=C,
            chain_blocks=chain_blocks,
            diagnostics=diag,
            resumed_from=resumed_from,
        )


# ---------------------------------------------------------------------------
# the classic shapes, as thin wrappers over the builder
# ---------------------------------------------------------------------------

class TrainSession:
    """Single-R-matrix session (BMF / Macau / probit variants).

    A thin wrapper over :class:`ModelBuilder`: two entities ("rows",
    "cols"), one block — it composes the identical ``ModelDef`` graph
    the pre-builder session did, so the sampled chain is unchanged
    (tests/test_golden_chain.py replays it against the engine chain
    bitwise).  Pass ``mesh`` to run the chain through the explicit
    distributed sweep and ``pipeline`` to select the fixed-factor
    exchange ("eager" all-gather vs "ring" ppermute hops; None defers
    to ``REPRO_PIPELINE``).  ``save_freq``/``save_dir`` stream
    posterior samples for :class:`~repro.core.predict.PredictSession`.
    """

    def __init__(self, num_latent: int = 16, burnin: int = 100,
                 nsamples: int = 100, seed: int = 0,
                 priors: Sequence[str] = ("normal", "normal"),
                 use_pallas: bool = False, verbose: int = 0,
                 save_freq: int = 0, save_dir: Optional[str] = None,
                 mesh: Any = None, pipeline: Optional[str] = None,
                 chains: Optional[int] = None,
                 chain_axis: Optional[str] = None,
                 callbacks: Sequence[Callable[[SweepInfo], None]] = (),
                 recorder: Any = None):
        self.num_latent = num_latent
        self.burnin = burnin
        self.nsamples = nsamples
        self.seed = seed
        self.recorder = recorder
        self.prior_names = tuple(p.replace("-", "").replace("_", "")
                                 for p in priors)
        self.use_pallas = use_pallas
        self.verbose = verbose
        self.save_freq = save_freq
        self.save_dir = save_dir
        self.mesh = mesh
        self.pipeline = pipeline
        self.chains = chains
        self.chain_axis = chain_axis
        self.callbacks = callbacks
        self._train: Optional[Any] = None
        self._test: Optional[TestSet] = None
        self._noise: Any = FixedGaussian(5.0)
        self._sides: List[Optional[np.ndarray]] = [None, None]
        # per axis — a second add_side_info call must not clobber the
        # first axis's precision knobs
        self._beta_precisions: List[float] = [5.0, 5.0]
        self._sample_beta_precisions: List[bool] = [True, True]

    # -- construction ------------------------------------------------------

    def add_train_and_test(self, train, test=None, noise=None):
        """train: SparseMatrix | dense np.ndarray; test: (i, j, v)."""
        if isinstance(train, np.ndarray):
            train = dense_block(train)
        self._train = train
        if test is not None:
            self._test = make_test_set(*test)
        if noise is not None:
            self._noise = noise
        return self

    def add_side_info(self, axis: int, F: np.ndarray,
                      beta_precision: float = 5.0,
                      sample_beta_precision: bool = True):
        """Attach side information to rows (axis=0) or cols (axis=1).

        ``beta_precision`` / ``sample_beta_precision`` are stored PER
        AXIS — side info on both axes keeps each axis's own knobs.
        """
        if axis not in (0, 1):
            raise ValueError(
                f"unknown axis {axis!r}; valid axes: (0, 1) — 0 rows, "
                "1 cols")
        self._sides[axis] = np.asarray(F, np.float32)
        self._beta_precisions[axis] = beta_precision
        self._sample_beta_precisions[axis] = sample_beta_precision
        return self

    # -- model assembly ----------------------------------------------------

    def _builder(self) -> ModelBuilder:
        assert self._train is not None, "call add_train_and_test first"
        n_rows, n_cols = self._train.shape
        b = ModelBuilder(self.num_latent, self.use_pallas)
        for axis, (name, n) in enumerate((("rows", n_rows),
                                          ("cols", n_cols))):
            side = self._sides[axis]
            if side is not None:
                b.add_entity(
                    name, n, side_info=side,
                    beta_precision=self._beta_precisions[axis],
                    sample_beta_precision=self._sample_beta_precisions[
                        axis])
            else:
                b.add_entity(name, n, prior=self.prior_names[axis])
        b.add_block("rows", "cols", self._train, noise=self._noise,
                    test=self._test)
        return b

    def _build(self) -> Tuple[ModelDef, MFData]:
        """(ModelDef, MFData) — the benchmark/driver entry point."""
        model, data, _ = self._builder().build()
        return model, data

    # -- run ---------------------------------------------------------------

    def run(self, keep_samples: bool = False,
            resume: bool = False) -> SessionResult:
        sess = self._builder().session(
            burnin=self.burnin, nsamples=self.nsamples, seed=self.seed,
            mesh=self.mesh, pipeline=self.pipeline,
            chains=self.chains, chain_axis=self.chain_axis,
            save_freq=self.save_freq, save_dir=self.save_dir,
            verbose=self.verbose, callbacks=self.callbacks,
            recorder=self.recorder)
        return sess.run(keep_samples=keep_samples, resume=resume)


class GFASession:
    """Group Factor Analysis: M views sharing a sample entity.

    views: list of (N, D_m) dense arrays.  The shared entity gets a
    fixed-Normal prior; each view's loading matrix gets the
    spike-and-slab prior (paper Table 1, GFA row: "Normal + SnS").
    A thin wrapper over :class:`ModelBuilder` — the view star it
    composes is the identical ``ModelDef`` graph as before the
    builder, so the sampled chain is unchanged.

    Pass ``mesh`` to run the chain through the explicit distributed
    sweep: the spike-and-slab coordinate updates are counter-based per
    global row, so the sharded chain matches this single-device one at
    reduction-order tolerance — GFA is in the sharded subset, not on a
    pjit fallback.  ``pipeline`` selects the fixed-factor exchange
    ("eager" all-gather vs "ring" ppermute hops; None defers to
    ``REPRO_PIPELINE``).
    """

    def __init__(self, views: Sequence[np.ndarray], num_latent: int = 8,
                 burnin: int = 200, nsamples: int = 200, seed: int = 0,
                 noise: Any = None, use_pallas: bool = False,
                 zero_init_loadings: bool = True, mesh: Any = None,
                 pipeline: Optional[str] = None,
                 chains: Optional[int] = None,
                 chain_axis: Optional[str] = None,
                 save_freq: int = 0, save_dir: Optional[str] = None,
                 callbacks: Sequence[Callable[[SweepInfo], None]] = (),
                 recorder: Any = None):
        self.views = [np.asarray(v, np.float32) for v in views]
        self.recorder = recorder
        self.num_latent = num_latent
        self.burnin = burnin
        self.nsamples = nsamples
        self.seed = seed
        self.noise = noise or AdaptiveGaussian()
        self.use_pallas = use_pallas
        # Grow-from-empty: starting the loading matrices at zero lets
        # spike-and-slab components switch on one by one, which finds
        # the sparse mode that a random-init Gibbs chain cannot rotate
        # into (the GFA rotation degeneracy; R's CCAGFA needs an
        # explicit rotation-optimization step for the same reason).
        self.zero_init_loadings = zero_init_loadings
        self.mesh = mesh
        self.pipeline = pipeline
        self.chains = chains
        self.chain_axis = chain_axis
        self.save_freq = save_freq
        self.save_dir = save_dir
        self.callbacks = callbacks

    def _builder(self) -> ModelBuilder:
        N = self.views[0].shape[0]
        b = ModelBuilder(self.num_latent, self.use_pallas)
        # GFA pins Z ~ N(0, I) (fixed); SnS on the loadings does the
        # component selection (see FixedNormalPrior docstring).
        b.add_entity("samples", N, prior=FixedNormalPrior(self.num_latent))
        for m, X in enumerate(self.views):
            b.add_entity(f"view{m}", X.shape[1],
                         prior=SpikeAndSlabPrior(self.num_latent))
            b.add_block("samples", f"view{m}", X, noise=self.noise)
        return b

    def _build(self) -> Tuple[ModelDef, MFData]:
        model, data, _ = self._builder().build()
        return model, data

    def _zero_loadings(self, state: MFState) -> MFState:
        fs = list(state.factors)
        for e in range(1, len(fs)):
            fs[e] = jnp.zeros_like(fs[e])
        return state._replace(factors=tuple(fs))

    def run(self, resume: bool = False) -> Dict[str, Any]:
        sess = self._builder().session(
            burnin=self.burnin, nsamples=self.nsamples, seed=self.seed,
            mesh=self.mesh, pipeline=self.pipeline,
            chains=self.chains, chain_axis=self.chain_axis,
            save_freq=self.save_freq, save_dir=self.save_dir,
            callbacks=self.callbacks, recorder=self.recorder,
            init_transform=(self._zero_loadings
                            if self.zero_init_loadings else None),
            accumulate_factor_means=True)
        r = sess.run(resume=resume)
        # Multi-chain: "Z"/"W" follow CHAIN 0 — GFA's rotation/sign
        # indeterminacy makes pooling raw loadings across chains
        # meaningless (chains converge to differently-rotated modes).
        # The stacked per-chain means stay available as */_chains and
        # r.diagnostics carries the cross-chain R-hat/ESS evidence.
        if r.n_chains > 1:
            out = {
                "Z": r.factor_means[0][0],
                "W": [m[0] for m in r.factor_means[1:]],
                "Z_last": np.asarray(r.state.factors[0][0]),
                "W_last": [np.asarray(f[0])
                           for f in r.state.factors[1:]],
                "Z_chains": r.factor_means[0],
                "W_chains": r.factor_means[1:],
            }
        else:
            out = {
                "Z": r.factor_means[0],
                "W": r.factor_means[1:],
                "Z_last": np.asarray(r.state.factors[0]),
                "W_last": [np.asarray(f) for f in r.state.factors[1:]],
            }
        out.update({
            "rmse_train": [b.rmse_train_trace for b in r.blocks],
            "runtime_s": r.runtime_s,
            "compile_s": r.compile_s,
            "state": r.state,
            "diagnostics": r.diagnostics,
            "result": r,
        })
        return out


def smurff(train, test=None, side_info=(None, None), num_latent=16,
           burnin=100, nsamples=100, noise=None, seed=0,
           use_pallas=False, verbose=0, mesh=None, pipeline=None,
           chains=None, chain_axis=None,
           save_freq=0, save_dir=None) -> SessionResult:
    """One-call convenience API (mirrors ``smurff.smurff(...)``).

    Forwards the full knob set — including ``mesh``/``pipeline``
    (distributed sweep + exchange pipeline), ``chains``/``chain_axis``
    (vectorized multi-chain sampling + convergence diagnostics), and
    ``save_freq``/``save_dir`` (posterior-sample streaming for
    ``PredictSession``).
    """
    sess = TrainSession(num_latent=num_latent, burnin=burnin,
                        nsamples=nsamples, seed=seed,
                        use_pallas=use_pallas, verbose=verbose,
                        mesh=mesh, pipeline=pipeline,
                        chains=chains, chain_axis=chain_axis,
                        save_freq=save_freq, save_dir=save_dir)
    sess.add_train_and_test(train, test=test, noise=noise)
    for axis, F in enumerate(side_info):
        if F is not None:
            sess.add_side_info(axis, F)
    return sess.run()
