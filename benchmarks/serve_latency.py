"""Serving latency/throughput: RecommendServer under offered load.

The paper's payoff is prediction AT SCALE (arXiv:1904.02514 §1) — the
question for the serving layer is not just per-call cost but how
latency degrades as concurrent load grows.  This benchmark drives
``launch.serve.RecommendServer`` open-loop: requests arrive on a fixed
schedule at each offered QPS level (arrival times are set BEFORE the
run, so a slow server cannot throttle its own offered load), mixing
warm-user and cold-start queries with per-request exclusions, and we
record per-request latency from the SCHEDULED arrival to completion —
queueing delay included, the number a client would see.

Reported per QPS level: p50/p99 latency, achieved throughput, and the
batch occupancy the slot runtime reached.  Results land as JSON under
``results/serving/`` next to the dry-run records::

    PYTHONPATH=src python -m benchmarks.serve_latency [--quick]

Container is CPU-only: absolute latencies are CPU-XLA numbers; the
paper-comparable quantity is the SHAPE of the latency/QPS curve
(flat until the knee, then queueing blow-up) and the batching lift
over slots=1.
"""
from __future__ import annotations

import argparse
import json
import os
import tempfile
import time

import numpy as np

from repro.core import (AdaptiveGaussian, ModelBuilder,
                        PredictSession, from_coo)
from repro.launch.serve import RecommendServer
from repro.obs import (Histogram, clock, latency_buckets,
                       percentile_summary)

from .common import emit
from repro.launch.compile_cache import enable_compile_cache

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..",
                           "results", "serving")


def _build_store(save_dir: str, n_users: int, n_items: int,
                 nsamples: int, seed: int = 0):
    """Train a small Macau session streaming samples to ``save_dir``."""
    rng = np.random.default_rng(seed)
    n_feat, rank = 16, 4
    F = rng.normal(size=(n_users, n_feat)).astype(np.float32)
    B = (rng.normal(size=(n_feat, rank)) / np.sqrt(n_feat)) \
        .astype(np.float32)
    T = rng.normal(size=(n_items, rank)).astype(np.float32)
    act = (F @ B @ T.T).astype(np.float32)
    obs = rng.random((n_users, n_items)) < 0.2
    i, j = np.nonzero(obs)
    mat = from_coo(i, j, act[i, j], (n_users, n_items))
    b = ModelBuilder(num_latent=8)
    b.add_entity("user", n_users, side_info=F)
    b.add_entity("item", n_items)
    b.add_block("user", "item", mat, noise=AdaptiveGaussian())
    b.session(burnin=10, nsamples=nsamples, seed=seed, save_freq=1,
              save_dir=save_dir).run()
    return F, obs


def _drive(session: PredictSession, F: np.ndarray, obs: np.ndarray,
           qps: float, n_requests: int, slots: int, seed: int):
    """One offered-QPS level: open-loop arrivals, full drain.

    Returns (client-latency Histogram, achieved qps, the server's
    ``metrics_snapshot()`` for the timed region).
    """
    rng = np.random.default_rng(seed)
    n_users = F.shape[0]
    arrivals = np.arange(n_requests) / qps    # scheduled offsets (s)
    kinds = rng.random(n_requests)            # 10% cold-start
    users = rng.integers(0, n_users, n_requests)

    srv = RecommendServer(session, slots=slots, k=10)
    # warm the jit caches for EVERY batch size the slot runtime can
    # form (the scorer specializes on B) so no timed request pays
    # compilation
    srv.submit(features=F[0])
    srv.run()
    for b in range(1, slots + 1):
        for u in range(b):
            srv.submit(user=u)
        srv.run()
    srv.done.clear()
    srv.obs.reset()     # drop the warm-up's latency observations too

    submitted = 0
    t0 = clock.monotonic()
    while len(srv.done) < n_requests:
        now = clock.monotonic() - t0
        while submitted < n_requests and arrivals[submitted] <= now:
            u = int(users[submitted])
            if kinds[submitted] < 0.1:
                srv.submit(features=F[u],
                           req_id=f"q{submitted}")
            else:
                srv.submit(user=u, exclude=np.nonzero(obs[u])[0],
                           req_id=f"q{submitted}")
            submitted += 1
        srv._admit()
        if any(r is not None for r in srv.active):
            srv.step()
        elif submitted < n_requests:
            time.sleep(min(1e-3, arrivals[submitted] - now))
    t_end = clock.monotonic()

    # client-perceived latency (scheduled arrival -> completion,
    # queueing included) through the shared obs histogram — the same
    # percentile implementation the server's own snapshot uses
    lat = Histogram(latency_buckets(lo=1e-5))
    for d in srv.done:
        lat.observe(d["t_done"] - (t0 + arrivals[int(d["id"][1:])]))
    achieved = n_requests / (t_end - t0)
    return lat, achieved, srv.metrics_snapshot()


def run(quick: bool = False, out: str | None = None,
        store_dir: str | None = None) -> dict:
    n_users, n_items, nsamples = \
        (200, 128, 8) if quick else (2000, 1024, 32)
    n_requests = 40 if quick else 400
    qps_levels = [25.0, 400.0] if quick else [10.0, 40.0, 160.0, 640.0]
    slots = 8

    tmp = store_dir or tempfile.mkdtemp(prefix="serve_latency_")
    F, obs = _build_store(tmp, n_users, n_items, nsamples)
    session = PredictSession(tmp)
    session.warm_cache()

    levels = []
    for qps in qps_levels:
        lat, achieved, snap = _drive(
            session, F, obs, qps, n_requests, slots, seed=int(qps))
        p50 = lat.percentile(0.50)
        p99 = lat.percentile(0.99)
        occ = Histogram.from_dict(
            snap["histograms"]["serve.batch_occupancy"])
        mean_batch = occ.mean()
        levels.append({
            "offered_qps": qps,
            "achieved_qps": round(achieved, 2),
            "p50_latency_s": round(p50, 5),
            "p99_latency_s": round(p99, 5),
            "mean_batch": round(mean_batch, 2),
            "n_requests": n_requests,
            "server_metrics": {
                name.split(".", 1)[1]: {
                    k: round(v, 5) if isinstance(v, float) else v
                    for k, v in percentile_summary(
                        Histogram.from_dict(
                            snap["histograms"][name])).items()}
                for name in ("serve.queue_wait_s", "serve.execute_s")
            } | {"completed": int(snap["counters"]
                                  .get("serve.completed", 0))},
        })
        emit("serving", f"qps_{qps:g}",
             f"{p50 * 1e3:.2f}/{p99 * 1e3:.2f}", "ms p50/p99",
             f"achieved {achieved:.1f} qps, mean batch "
             f"{mean_batch:.1f}")

    rec = {
        "bench": "serve_latency",
        "store": {"n_users": n_users, "n_items": n_items,
                  "num_samples": nsamples, "num_latent": 8},
        "slots": slots,
        "resident_cache_bytes": session.store_nbytes(),
        "load_count": session.load_count,
        "quick": quick,
        "levels": levels,
    }
    os.makedirs(RESULTS_DIR, exist_ok=True)
    out = out or os.path.join(
        RESULTS_DIR,
        f"serve_latency{'_quick' if quick else ''}.json")
    with open(out, "w") as f:
        json.dump(rec, f, indent=1)
    emit("serving", "results_json", out, "path",
         f"{len(levels)} QPS levels")
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller store / fewer QPS levels")
    ap.add_argument("--out", default=None,
                    help="JSON output path (default results/serving/)")
    args = ap.parse_args()
    enable_compile_cache()
    print("section,name,value,unit,notes", flush=True)
    run(quick=args.quick, out=args.out)


if __name__ == "__main__":
    main()
