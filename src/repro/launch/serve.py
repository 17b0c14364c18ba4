"""Serving: shared slot/queue runtime + the two services built on it.

Two very different workloads share one continuous-batching shape —
requests queue, free slots admit them, one device call advances every
active slot at once:

* :class:`BatchedServer` — LM token decoding over fixed KV-cache
  slots (the transformer substrate path).
* :class:`RecommendServer` — batched posterior top-K recommendation
  over a saved BMF sample store (the arXiv:1904.02514 serving story):
  each service step scores all admitted requests in ONE fused
  ``kernels.topk_score`` call against the resident posterior cache,
  serving warm users, cold-start feature rows (sampled Macau link),
  and per-request item exclusions.  Batching changes no answer —
  batched results are BITWISE equal to sequential
  ``PredictSession.recommend`` calls (tests/test_serving.py).

The slot/queue/request-id mechanics live in :class:`SlotServer` so the
two servers can't drift: ids come from a monotonic counter (the old
``f"r{len(self.queue)}"`` default collided once the queue drained),
and explicit duplicate ids raise, naming the clash.

Checkpoint I/O is banned from request paths by construction: the
store is loaded ONCE at server construction (``warm_cache``), and the
``checkpoint-load-in-serving-request-path`` invariant rule
(``repro.analysis``) rejects any ``load_pytree``/``load_sample``-class
call that creeps into this module outside ``__init__``/``warm*``.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import (forward, init_model, init_serve_cache, serve_step)
from ..models.config import ModelConfig
from ..models.transformer import encode
from ..obs import Recorder, clock, integer_buckets
from . import specs as S


def make_serve_step(cfg: ModelConfig, mesh: Optional[Mesh] = None):
    def step(params, caches, tokens):
        return serve_step(params, cfg, caches, tokens, mesh=mesh)
    return step


def make_sharded_serve_step(cfg: ModelConfig, mesh: Mesh, shape,
                            variant: str = "baseline"):
    step = make_serve_step(cfg, mesh)
    params_abs, _ = S.abstract_train_state(cfg)
    from ..models import sharding as shd
    ps = shd.param_shardings(params_abs, mesh)
    cs = S.serve_cache_shardings(cfg, shape, mesh)
    bs = shd.batch_spec(mesh, shape.global_batch)
    dp = bs[0] if len(bs) else None
    tok_sh = NamedSharding(mesh, P(dp, None))
    # logits (B, 1, V): batch over dp, vocab over the model axis
    v_ok = cfg.vocab_size % mesh.shape[shd.TP] == 0
    lg_sh = NamedSharding(mesh, P(dp, None, shd.TP if v_ok else None))
    return jax.jit(step,
                   in_shardings=(ps, cs, tok_sh),
                   out_shardings=(lg_sh, cs),
                   donate_argnums=(1,)), (ps, cs, tok_sh)


def make_sharded_prefill_step(cfg: ModelConfig, mesh: Mesh, shape,
                              variant: str = "baseline"):
    """Forward-only prefill over the full sequence (inference-prefill).

    Lowers ``forward`` (chunked causal attention, no grads); logits are
    returned sharded (batch x vocab) — a real server would fuse the
    sampling, this is the roofline-relevant compute.
    """

    from ..models import sharding as shd
    variant = S.effective_variant(variant, shape, mesh)

    def step(params, batch):
        with shd.policy(variant):   # perf flags live during tracing
            logits, _ = forward(params, cfg, batch, mesh=mesh,
                                remat=False)
            return logits.astype(jnp.bfloat16)

    params_abs, _ = S.abstract_train_state(cfg)
    with shd.policy(variant):
        ps = shd.param_shardings(params_abs, mesh)
        bsh = S.batch_shardings(cfg, shape, mesh, variant=variant)
        bs = shd.batch_spec(mesh, shape.global_batch)
    dp = bs[0] if len(bs) else None
    v_ok = cfg.vocab_size % mesh.shape[shd.TP] == 0
    lg_sh = NamedSharding(mesh, P(dp, None, shd.TP if v_ok else None))
    return jax.jit(step, in_shardings=(ps, bsh),
                   out_shardings=lg_sh), (ps, bsh)


def generate(cfg: ModelConfig, params, prompts: np.ndarray,
             max_new: int = 32, temperature: float = 0.0,
             seed: int = 0) -> np.ndarray:
    """Greedy/temperature decode for a batch of same-length prompts.

    Prefill runs through ``forward`` (chunked attention); decode uses
    the cache path.  Single-host convenience used by examples/tests.
    """
    B, S0 = prompts.shape
    max_len = S0 + max_new
    enc = None
    batch = {"tokens": jnp.asarray(prompts)}
    logits, _ = forward(params, cfg, batch, remat=False)
    caches = init_serve_cache(params, cfg, B, max_len, enc_out=enc,
                              prefilled=0)
    # replay the prompt through the decode path to fill the cache
    # (simple and correct; a production prefill would batch-write)
    step = jax.jit(lambda p, c, t: serve_step(p, cfg, c, t))
    key = jax.random.PRNGKey(seed)
    out = [prompts]
    tok = None
    for i in range(S0):
        tok = jnp.asarray(prompts[:, i:i + 1])
        lg, caches = step(params, caches, tok)
    for i in range(max_new):
        if temperature > 0:
            key, k2 = jax.random.split(key)
            nxt = jax.random.categorical(
                k2, lg[:, -1].astype(jnp.float32) / temperature,
                axis=-1)[:, None]
        else:
            nxt = jnp.argmax(lg[:, -1], axis=-1)[:, None]
        out.append(np.asarray(nxt, np.int32))
        lg, caches = step(params, caches, nxt.astype(jnp.int32))
    return np.concatenate(out, axis=1)


class SlotServer:
    """Shared slot/queue runtime: admission + request-id management.

    Subclasses implement one service ``step()`` that advances every
    active slot.  Request ids default to a MONOTONIC counter — the
    previous ``f"r{len(self.queue)}"`` default reused ids once the
    queue drained, so two live requests could share one.  Explicit ids
    that clash with a queued or active request raise, naming both.
    Every request carries ``t_submit``/``t_admit``/``t_done``
    monotonic timestamps (benchmarks/serve_latency.py derives its
    p50/p99 from them), and the server's ``obs`` Recorder splits
    request latency into the ``serve.queue_wait_s`` and
    ``serve.execute_s`` histograms plus a per-step
    ``serve.batch_occupancy`` histogram — all exposed through
    :meth:`metrics_snapshot`.  The recorder is enabled by default
    (metrics are the serving product, not a debug artifact); inject a
    disabled one via ``recorder=`` to opt out.
    """

    def __init__(self, slots: int, recorder: Optional[Recorder] = None):
        self.slots = slots
        self.obs = Recorder(enabled=True) if recorder is None else recorder
        self.obs.set_kind("serve")
        self.queue: List[Dict[str, Any]] = []
        self.active: List[Optional[Dict[str, Any]]] = [None] * slots
        self.done: List[Dict[str, Any]] = []
        self._next_id = 0                 # never reused, ever
        self._live_ids: set = set()       # queued + active
        self._steps = 0                   # service steps taken

    def _enqueue(self, req: Dict[str, Any],
                 req_id: Optional[str]) -> str:
        if req_id is None:
            req_id = f"r{self._next_id}"
            self._next_id += 1
        elif req_id in self._live_ids:
            raise ValueError(
                f"request id {req_id!r} clashes with a live "
                "(queued or active) request of the same id; pass a "
                "unique id or omit req_id to get a server-assigned "
                "one")
        req["id"] = req_id
        req["t_submit"] = clock.monotonic()
        self._live_ids.add(req_id)
        self.queue.append(req)
        self.obs.add("serve.submitted")
        return req_id

    def _admit(self):
        for s in range(self.slots):
            if self.active[s] is None and self.queue:
                req = self.queue.pop(0)
                req["t_admit"] = clock.monotonic()
                self.obs.observe("serve.queue_wait_s",
                                 req["t_admit"] - req["t_submit"])
                self.active[s] = req

    def _observe_batch(self, occupancy: int) -> None:
        """Batch-occupancy histogram, one observation per service
        step — integer buckets so every occupancy level 0..slots has
        its own exact count."""
        self.obs.observe("serve.batch_occupancy", occupancy,
                         bounds=integer_buckets(self.slots))

    def _finish(self, slot: int):
        req = self.active[slot]
        req["t_done"] = clock.monotonic()
        self.obs.observe("serve.execute_s",
                         req["t_done"] - req["t_admit"])
        self.obs.add("serve.completed")
        self._live_ids.discard(req["id"])
        self.done.append(req)
        self.active[slot] = None

    def metrics_snapshot(self) -> Dict[str, Any]:
        """JSON metrics snapshot of the server's Recorder: submitted/
        completed counters + queue-wait / execute / batch-occupancy
        histograms (the numbers benchmarks/serve_latency.py reports)."""
        return self.obs.metrics()

    def step(self):                       # pragma: no cover
        raise NotImplementedError

    def run(self, max_steps: int = 10_000) -> List[Dict[str, Any]]:
        """Service steps until all requests finish; returns results.
        Garbage collections inside are ``gc`` spans."""
        with self.obs.gc_spans():
            for _ in range(max_steps):
                with self.obs.span("serve/admit", cat="serve"):
                    self._admit()
                if not any(self.active):
                    break
                self.step()
        return self.done


class BatchedServer(SlotServer):
    """Minimal continuous-batching LM server over fixed decode slots.

    Requests (prompt arrays) queue up; each free slot runs prefill for
    its request via the decode path, then decodes until EOS/max —
    enough to demonstrate the serving runtime around ``serve_step``.
    """

    def __init__(self, cfg: ModelConfig, params, slots: int = 4,
                 max_len: int = 256,
                 recorder: Optional[Recorder] = None):
        super().__init__(slots, recorder=recorder)
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.caches = init_serve_cache(params, cfg, slots, max_len,
                                       prefilled=0)
        self._step = jax.jit(
            lambda p, c, t: serve_step(p, cfg, c, t))

    def submit(self, prompt: np.ndarray, max_new: int = 16,
               req_id: Optional[str] = None) -> str:
        return self._enqueue(
            {"prompt": list(prompt), "remaining": max_new,
             "generated": [], "fed": 0}, req_id)

    def step(self):
        """One decode step advancing every active slot."""
        self._observe_batch(sum(r is not None for r in self.active))
        toks = np.zeros((self.slots, 1), np.int32)
        for s, req in enumerate(self.active):
            if req is None:
                continue
            if req["fed"] < len(req["prompt"]):
                toks[s, 0] = req["prompt"][req["fed"]]
            elif req["generated"]:
                toks[s, 0] = req["generated"][-1]
        lg, self.caches = self._step(self.params, self.caches,
                                     jnp.asarray(toks))
        nxt = np.asarray(jnp.argmax(lg[:, -1], axis=-1))
        for s, req in enumerate(self.active):
            if req is None:
                continue
            req["fed"] += 1
            if req["fed"] >= len(req["prompt"]):
                req["generated"].append(int(nxt[s]))
                req["remaining"] -= 1
                if req["remaining"] <= 0:
                    self._finish(s)


class RecommendServer(SlotServer):
    """Batched posterior top-K recommendation over a saved store.

    The online face of ``PredictSession``: requests (a warm user row
    id OR a cold-start feature vector, plus optional per-request item
    exclusions) queue up, and each service step scores ALL admitted
    requests in one fused ``kernels.topk_score`` call against the
    resident posterior cache — top-K item ids with posterior mean and
    std per score.  Each query runs one identical float program
    regardless of batch size, so batching changes no answer: results
    are bitwise equal to sequential ``PredictSession.recommend`` calls
    (asserted in tests/test_serving.py).

    The sample store is loaded exactly once, at construction
    (``warm_cache``); request paths never touch the checkpoint loader
    (enforced by the ``checkpoint-load-in-serving-request-path``
    invariant rule).  Stores above the session's ``cache_bytes``
    budget are refused here — streaming per request is the reload bug
    this server exists to fix, so it is not silently reintroduced.
    """

    def __init__(self, session, slots: int = 8, k: int = 10,
                 block=0, recorder: Optional[Recorder] = None):
        super().__init__(slots, recorder=recorder)
        self.session = session
        self.k = int(k)
        self.block = block
        if session.warm_cache() is None:
            raise ValueError(
                f"store needs {session.store_nbytes()} bytes resident "
                f"but the session budget is {session.cache_bytes}; "
                "RecommendServer requires the resident cache (raise "
                "cache_bytes / REPRO_PREDICT_CACHE_BYTES, or serve "
                "offline via PredictSession.recommend)")

    def submit(self, user: Optional[int] = None, *,
               features: Optional[np.ndarray] = None,
               k: Optional[int] = None,
               exclude: Optional[Sequence[int]] = None,
               req_id: Optional[str] = None) -> str:
        """Queue one recommendation request; returns its id.

        ``user``: a row id seen in training; ``features``: a (D,)
        side-information vector for an UNSEEN user (cold start) —
        exactly one of the two.  ``exclude``: item ids to leave out of
        this request's ranking (e.g. the user's observed items).
        """
        if (user is None) == (features is None):
            raise ValueError(
                "pass exactly one of user= (warm row id) or "
                "features= (cold-start side info)")
        if features is not None:
            features = np.asarray(features, np.float32)
            if features.ndim != 1:
                raise ValueError(
                    f"features must be one (D,) row, got shape "
                    f"{features.shape}; submit one request per user")
        return self._enqueue(
            {"user": None if user is None else int(user),
             "features": features,
             "k": self.k if k is None else int(k),
             "exclude": None if exclude is None else
             list(map(int, exclude))}, req_id)

    def step(self):
        """Score every active request in one batched kernel call.

        One ``serve/step`` span (args: ``batch``, the server's ``step``
        index and the request ``ids``) holds the session's
        ``predict/*`` spans and ``serve/finish``."""
        live = [(s, r) for s, r in enumerate(self.active)
                if r is not None]
        self._observe_batch(len(live))
        step, self._steps = self._steps, self._steps + 1
        pobs = self.session.obs
        with self.obs.span("serve/step", cat="serve", batch=len(live),
                           step=step, ids=[r["id"] for _, r in live]):
            with pobs.span("predict/rows", cat="predict"):
                rows = []
                for _, req in live:
                    if req["user"] is not None:
                        rows.append(self.session.user_rows(
                            [req["user"]], self.block))
                    else:
                        rows.append(self.session.cold_rows(
                            req["features"], self.block))
                batch = jnp.concatenate(rows, axis=0)    # (B, S, K)
            k_max = max(req["k"] for _, req in live)
            excl = [req["exclude"] or [] for _, req in live]
            res = self.session.recommend_rows(batch, k_max, self.block,
                                              exclude=excl)
            # trim each slot to ITS k: the selection loop picks the same
            # first k entries whatever the total K, so a larger shared
            # batch never changes a request's answer
            with self.obs.span("serve/finish", cat="serve"):
                for b, (s, req) in enumerate(live):
                    kk = min(req["k"], res.ids.shape[1])
                    req["ids"] = res.ids[b, :kk].copy()
                    req["mean"] = res.mean[b, :kk].copy()
                    req["std"] = res.std[b, :kk].copy()
                    self._finish(s)
