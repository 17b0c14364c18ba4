"""Regenerate the committed observability samples in results/obs/.

CI schema-audits every JSON under ``results/obs`` with
``python -m repro.analysis --obs results/obs`` (see
``analysis.obsschema``), so the committed files must stay in lockstep
with what ``repro.obs`` actually exports.  After changing the
recorder's trace/metrics formats, span names, or the serve histogram
set, rerun::

    PYTHONPATH=src python scripts_dev/gen_obs_samples.py

Four samples are written:

- ``train_trace.json``   — Chrome-trace export of an instrumented
  TrainSession run (sweep spans with bytes_on_wire, session/compile,
  the session/*, ckpt/* and gc spans)
- ``train_metrics.json`` — the matching metrics snapshot
- ``serve_trace.json``   — the RecommendServer's own trace after a
  short driven load (serve/* spans, serve/step with step and ids)
- ``serve_metrics.json`` — its ``metrics_snapshot()``
  (queue-wait/execute/occupancy histograms)

Wall-clock values in these files differ per run by design; the audit
only pins structure.
"""
from __future__ import annotations

import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core import (AdaptiveGaussian, ModelBuilder,  # noqa: E402
                        PredictSession, from_coo)
from repro.launch.serve import RecommendServer  # noqa: E402
from repro.obs import Recorder, write_json_atomic  # noqa: E402

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "results", "obs")


def _toy_matrix(rng, n_users=48, n_items=32, rank=3):
    U = rng.normal(size=(n_users, rank)).astype(np.float32)
    V = rng.normal(size=(n_items, rank)).astype(np.float32)
    act = (U @ V.T).astype(np.float32)
    obs = rng.random((n_users, n_items)) < 0.35
    i, j = np.nonzero(obs)
    return from_coo(i, j, act[i, j], (n_users, n_items)), obs


def gen_session(out_dir: str, save_dir: str) -> None:
    rng = np.random.default_rng(0)
    mat, _ = _toy_matrix(rng)
    rec = Recorder(enabled=True)
    b = ModelBuilder(num_latent=4)
    b.add_entity("user", mat.shape[0])
    b.add_entity("item", mat.shape[1])
    b.add_block("user", "item", mat, noise=AdaptiveGaussian())
    b.session(burnin=3, nsamples=4, seed=7, save_freq=2,
              save_dir=save_dir, recorder=rec).run()
    rec.write_trace(os.path.join(out_dir, "train_trace.json"))
    rec.write_metrics(os.path.join(out_dir, "train_metrics.json"))


def gen_serve(out_dir: str, store_dir: str) -> None:
    rng = np.random.default_rng(1)
    n_users, n_items, n_feat, rank = 64, 40, 8, 3
    F = rng.normal(size=(n_users, n_feat)).astype(np.float32)
    B = (rng.normal(size=(n_feat, rank)) / np.sqrt(n_feat)) \
        .astype(np.float32)
    T = rng.normal(size=(n_items, rank)).astype(np.float32)
    act = (F @ B @ T.T).astype(np.float32)
    obs = rng.random((n_users, n_items)) < 0.25
    i, j = np.nonzero(obs)
    mat = from_coo(i, j, act[i, j], (n_users, n_items))
    mb = ModelBuilder(num_latent=4)
    mb.add_entity("user", n_users, side_info=F)
    mb.add_entity("item", n_items)
    mb.add_block("user", "item", mat, noise=AdaptiveGaussian())
    mb.session(burnin=4, nsamples=4, seed=1, save_freq=1,
               save_dir=store_dir).run()

    session = PredictSession(store_dir)
    session.warm_cache()
    srv = RecommendServer(session, slots=4, k=5)
    for r in range(12):
        u = int(rng.integers(0, n_users))
        if r % 6 == 0:
            srv.submit(features=F[u])
        else:
            srv.submit(user=u, exclude=np.nonzero(obs[u])[0])
    srv.run()
    srv.obs.write_trace(os.path.join(out_dir, "serve_trace.json"))
    write_json_atomic(os.path.join(out_dir, "serve_metrics.json"),
                      srv.metrics_snapshot())


def main() -> None:
    os.makedirs(OUT_DIR, exist_ok=True)
    with tempfile.TemporaryDirectory(prefix="gen_obs_") as tmp:
        gen_session(OUT_DIR, os.path.join(tmp, "session"))
        gen_serve(OUT_DIR, os.path.join(tmp, "store"))
    for f in sorted(os.listdir(OUT_DIR)):
        print(os.path.join(OUT_DIR, f))


if __name__ == "__main__":
    main()
