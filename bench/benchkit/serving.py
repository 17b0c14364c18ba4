"""What the serving traffic kinds share: the posterior store, the
server, its warm-up, and the comparison of its answers with the
reference.

The store holds ``samples`` posterior draws of both factors, made on
the device from the seed in one jitted call (a planted factor plus a
per-sample spread), written through the program's own writers
(``Session`` for ``model.json`` and the layout,
``checkpoint.CheckpointManager`` for the samples) and served by
``PredictSession`` + ``RecommendServer``.  No sweep runs in set-up.
The reference draws the same samples again from the seed, so it takes
nothing the program made.
"""
from __future__ import annotations

import gc
import os
import shutil
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchkit import counts
from benchkit.data import fixed_degree

SPREAD = 0.3        # per-sample spread around the planted factor


@partial(jax.jit, static_argnums=(1, 2, 3, 4))
def posterior(key, samples, n_rows, n_cols, k):
    """(S, n_rows, K) and (S, n_cols, K) float32 factor draws."""
    k0, k1, k2, k3 = jax.random.split(key, 4)
    scale = k ** -0.25
    u0 = jax.random.normal(k0, (n_rows, k), jnp.float32) * scale
    v0 = jax.random.normal(k1, (n_cols, k), jnp.float32) * scale
    u = u0[None] + SPREAD * scale * jax.random.normal(
        k2, (samples, n_rows, k), jnp.float32)
    v = v0[None] + SPREAD * scale * jax.random.normal(
        k3, (samples, n_cols, k), jnp.float32)
    return u, v


def posterior_key(seed: int):
    return jax.random.fold_in(jax.random.PRNGKey(seed), 1)


class Served:
    """The store, the server over it and the traffic's requests."""

    def __init__(self, run):
        from repro.checkpoint import CheckpointManager
        from repro.core import (AdaptiveGaussian, ModelBuilder,
                                PredictSession, from_coo)
        from repro.core.modelspec import SAMPLES_SUBDIR
        from repro.launch.serve import RecommendServer
        cfg = self.cfg = run.config
        self.run = run
        n_rows, n_cols, k = cfg["n_rows"], cfg["n_cols"], cfg["num_latent"]
        run.phase("imports")
        self.prob = fixed_degree(run.seed, n_rows, n_cols,
                                 cfg["nnz_per_row"], cfg["n_test_per_row"])
        store = os.path.join(run.out, "store")
        shutil.rmtree(store, ignore_errors=True)
        b = ModelBuilder(num_latent=k)
        b.add_entity("compound", n_rows)
        b.add_entity("protein", n_cols)
        b.add_block("compound", "protein",
                    from_coo(self.prob.i, self.prob.j, self.prob.v,
                             self.prob.shape), noise=AdaptiveGaussian())
        run.phase("data")
        template = b.session(burnin=0, nsamples=0, seed=run.seed,
                             save_freq=1, save_dir=store).run().state
        run.phase("model.json")
        u, v = posterior(posterior_key(run.seed), cfg["samples"], n_rows,
                         n_cols, k)
        saver = CheckpointManager(os.path.join(store, SAMPLES_SUBDIR),
                                  keep=None)
        for s in range(cfg["samples"]):
            saver.save(s + 1, template._replace(
                factors=(u[s], v[s]), step=jnp.asarray(s + 1, jnp.int32)))
        saver.wait()
        run.phase("store written")
        del u, v, template
        gc.collect()
        budget = 2 * cfg["samples"] * (n_rows + n_cols) * k * 4
        self.session = PredictSession(store, cache_bytes=budget)
        self.server = RecommendServer(self.session, slots=cfg["slots"],
                                      k=cfg["top_k"])
        self.store = store
        run.phase("store loaded")
        # every batch size the slots can form, once, before the window
        for batch in range(1, cfg["slots"] + 1):
            for u_ in range(batch):
                self.submit(u_)
            self.server.run()
        self.server.done.clear()
        self.server.obs.reset()

    def submit(self, user: int, req_id=None) -> str:
        return self.server.submit(int(user),
                                  exclude=self.prob.cols[int(user)],
                                  req_id=req_id)

    def step(self) -> float:
        """One service step (queued requests admitted to free slots,
        then one batched call); returns the benchmark's clock after
        it."""
        with self.run.span("bench.serve_step"):
            self.server.run(max_steps=1)
        return time.perf_counter()

    # -- after the window ----------------------------------------------

    def readings(self):
        """The batch of each service step, from the server's own
        ``serve/step`` spans, and the useful work of those steps."""
        cfg = self.cfg
        steps = [e for e in self.server.obs.trace()["traceEvents"]
                 if e.get("name") == "serve/step"]
        batches = [int(e["args"]["batch"]) for e in steps]
        args = (cfg["samples"], cfg["n_cols"], cfg["num_latent"])
        self.run.readings.update(
            batches=batches,
            useful_flops=sum(counts.topk_flops(b, *args) for b in batches))

    def close(self):
        del self.server, self.session
        gc.collect()
        shutil.rmtree(self.store, ignore_errors=True)


def compare(run, answers, lower: bool = False) -> dict:
    """The comparison of served answers with the reference.

    ``answers``: (user, ids, mean, std) of a sample of the requests
    answered in the window.  Numbers, worst over the sample:
    ``wrong_ids`` counts answers that name an excluded, repeated or
    missing item; ``rank_gap`` is the widest gap by which a served
    item's reference mean lies below the reference's best at that
    rank; ``mean_gap``/``std_gap`` the widest gap between a served
    mean/std and the reference's for that item.  Gaps are shares of
    the spread of the reference's means over the catalogue (of the
    median std for ``std_gap``).  With ``lower`` the bfloat16
    control's own answers are measured instead of the program's.
    """
    from benchkit.harness import load_module
    ref = load_module(os.path.join(run.bench, "references",
                                   run.config["reference"] + ".py"),
                      "bench_reference_" + run.config["reference"])
    cfg = run.config
    prob = fixed_degree(run.seed, cfg["n_rows"], cfg["n_cols"],
                        cfg["nnz_per_row"], cfg["n_test_per_row"])
    u, v = posterior(posterior_key(run.seed), cfg["samples"],
                     cfg["n_rows"], cfg["n_cols"], cfg["num_latent"])
    worst = {"wrong_ids": 0.0, "rank_gap": 0.0, "mean_gap": 0.0,
             "std_gap": 0.0}
    top = cfg["top_k"]
    for user, ids, mean, std in answers:
        m, s = (np.asarray(x) for x in ref.scores(u[:, user], v))
        excl = np.zeros(m.shape[0], bool)
        excl[prob.cols[user]] = True
        if lower:
            mc, sc = (np.asarray(x) for x in
                      ref.scores(u[:, user], v, lower=True))
            ids = np.argsort(-np.where(excl, -np.inf, mc),
                             kind="stable")[:top]
            mean, std = mc[ids], sc[ids]
        ids = np.asarray(ids)
        ok = (ids >= 0) & (ids < m.shape[0])
        wrong = int(np.sum(~ok)) + len(ids) - len(np.unique(ids))
        wrong += int(np.sum(excl[ids[ok]]))
        best = np.sort(np.where(excl, -np.inf, m))[::-1][:len(ids)]
        spread = float(np.std(m[~excl]))
        got = np.where(ok, m[np.clip(ids, 0, m.shape[0] - 1)], -np.inf)
        worst["wrong_ids"] = max(worst["wrong_ids"], float(wrong))
        worst["rank_gap"] = max(worst["rank_gap"],
                                float(np.max(best - got)) / spread)
        idx = np.clip(ids, 0, m.shape[0] - 1)
        worst["mean_gap"] = max(worst["mean_gap"], float(np.max(
            np.abs(np.asarray(mean) - m[idx]))) / spread)
        worst["std_gap"] = max(worst["std_gap"], float(np.max(
            np.abs(np.asarray(std) - s[idx]))) / float(np.median(s)))
    return worst


def check(run, answers):
    """Compare a seeded sample of the answers; record the readings."""
    rng = np.random.default_rng(run.seed)
    n = min(len(answers), run.mix["compared"])
    pick = [answers[i] for i in sorted(rng.choice(len(answers), n,
                                                  replace=False))]
    worst = compare(run, pick)
    run.readings["compared"] = worst
    for k, v in worst.items():
        run.check(k, v)
    run.controls = {"bf16": lambda: compare(run, pick, lower=True)}
