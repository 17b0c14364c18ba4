"""Posterior serving walkthrough: save_freq -> RecommendServer.

The compound-activity serving story of arXiv:1904.02514 end to end:

1. train a Macau session (compound side information) streaming every
   retained posterior sample to disk (``save_freq=1``);
2. reopen the store with ``PredictSession`` — the first request loads
   it ONCE into the resident posterior cache, after which serving does
   zero checkpoint I/O (watch ``load_count``);
3. stand up a ``RecommendServer`` and submit concurrent requests:
   warm users (excluding their already-observed targets) and a
   COLD-START compound known only by its feature vector, mapped
   through the sampled Macau link;
4. read back top-K targets with posterior mean AND std per score —
   the uncertainty the retained Gibbs samples carry for free.

    PYTHONPATH=src python examples/recommend_topk.py [--quick]
"""
import argparse
import tempfile

import numpy as np

from repro.core import (AdaptiveGaussian, ModelBuilder, PredictSession)
from repro.core.sparse import from_coo
from repro.launch.serve import RecommendServer
from repro.launch.compile_cache import enable_compile_cache


def make_activity_data(rng, n_compounds, n_targets, n_feat=12, rank=4):
    """Planted linear feature->latent activity matrix (ChEMBL-like)."""
    F = rng.normal(size=(n_compounds, n_feat)).astype(np.float32)
    B = (rng.normal(size=(n_feat, rank)) / np.sqrt(n_feat)) \
        .astype(np.float32)
    T = rng.normal(size=(n_targets, rank)).astype(np.float32)
    act = (F @ B @ T.T).astype(np.float32)
    return F, act


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller problem / fewer samples")
    args = ap.parse_args()
    enable_compile_cache()
    n_c, n_t, burnin, nsamples = \
        (60, 24, 8, 8) if args.quick else (300, 64, 40, 40)

    rng = np.random.default_rng(0)
    F, act = make_activity_data(rng, n_c, n_t)
    n_warm = n_c - 2              # hold the last two compounds out
    obs = rng.random((n_warm, n_t)) < 0.4
    i, j = np.nonzero(obs)
    mat = from_coo(i, j, act[i, j], (n_warm, n_t))

    # 1. train, streaming every retained sample to the store
    store = tempfile.mkdtemp(prefix="recommend_topk_")
    b = ModelBuilder(num_latent=8)
    b.add_entity("compound", n_warm, side_info=F[:n_warm])
    b.add_entity("target", n_t)
    b.add_block("compound", "target", mat, noise=AdaptiveGaussian())
    res = b.session(burnin=burnin, nsamples=nsamples, seed=0,
                    save_freq=1, save_dir=store).run()
    print(f"trained: final train rmse={res.rmse_train_trace[-1]:.3f},"
          f" {nsamples} samples -> {store}")

    # 2. reopen; the first request warms the resident cache
    session = PredictSession(store)
    print(f"store: {session.num_samples} samples, "
          f"{session.store_nbytes()} bytes resident")

    # 3. serve concurrent requests through the batching runtime
    server = RecommendServer(session, slots=4, k=5)
    print(f"cache warm: load_count={session.load_count} "
          f"(one per sample, never again)")
    req_user = {}
    for u in (0, 1, 2, 3, 4):
        rid = server.submit(user=u, exclude=np.nonzero(obs[u])[0])
        req_user[rid] = f"compound {u}"
    # cold start: a compound the chain never saw, features only
    rid = server.submit(features=F[n_warm], k=5)
    req_user[rid] = "COLD compound (features only)"
    done = server.run()
    assert session.load_count == session.num_samples  # zero while serving

    # 4. top-K with uncertainty
    for req in done:
        who = req_user[req["id"]]
        top = ", ".join(
            f"t{tid}: {m:+.2f}±{s:.2f}"
            for tid, m, s in zip(req["ids"], req["mean"], req["std"])
            if tid >= 0)
        print(f"  {who:>30}: {top}")

    # the cold-start ranking agrees with out-of-matrix prediction
    dense = session.predict_new("compound", F[n_warm:n_warm + 1])
    cold = [r for r in done if "COLD" in req_user[r["id"]]][0]
    assert cold["ids"][0] == int(np.argmax(dense[0]))
    print(f"cold-start top target == predict_new argmax "
          f"(t{cold['ids'][0]}); served {len(done)} requests with "
          f"{session.load_count} total checkpoint loads")


if __name__ == "__main__":
    main()
