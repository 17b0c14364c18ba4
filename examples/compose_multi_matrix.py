"""Compose a two-relation model sharing an entity + reload its samples.

The paper's framework claim, end to end: one ``ModelBuilder`` graph
relates compounds to BOTH protein targets (sparse IC50 activity, with
ECFP-like compound features through the Macau prior) and cell lines
(dense viability) — the two blocks share the compound latent factor,
so evidence flows between the relations.  ``save_freq`` streams every
posterior sample to disk; ``PredictSession`` then reloads them with no
training data in sight and serves

* in-matrix predictions at the held-out test cells (reproducing the
  in-session RMSE), and
* OUT-of-matrix predictions for compounds never present in training,
  mapped through the sampled link matrices beta_s (cold-start, the
  compound-activity workflow of arXiv:1904.02514).

    PYTHONPATH=src python examples/compose_multi_matrix.py [--quick]
"""
import argparse
import tempfile

import numpy as np

from repro.core import AdaptiveGaussian, ModelBuilder, PredictSession, \
    from_coo
from repro.launch.compile_cache import enable_compile_cache


def make_data(seed, n_compounds, n_targets, n_cells, n_features, rank,
              noise, hold_out):
    rng = np.random.default_rng(seed)
    F = rng.normal(size=(n_compounds, n_features)).astype(np.float32)
    B = (rng.normal(size=(n_features, rank)) / np.sqrt(n_features)) \
        .astype(np.float32)
    U = F @ B                                   # features drive latents
    T = rng.normal(size=(n_targets, rank)).astype(np.float32)
    L = rng.normal(size=(n_cells, rank)).astype(np.float32)
    activity = (U @ T.T + noise * rng.normal(
        size=(n_compounds, n_targets))).astype(np.float32)
    viability = (U @ L.T + noise * rng.normal(
        size=(n_compounds, n_cells))).astype(np.float32)

    n_warm = n_compounds - hold_out             # cold rows held out
    obs = rng.random((n_warm, n_targets)) < 0.3
    i, j = np.nonzero(obs)
    perm = rng.permutation(len(i))
    i, j = i[perm], j[perm]
    v = activity[i, j]
    n_test = len(i) // 5
    train = from_coo(i[n_test:], j[n_test:], v[n_test:],
                     (n_warm, n_targets))
    test = (i[:n_test], j[:n_test], v[:n_test])
    return F, train, test, viability[:n_warm], activity, n_warm


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small sizes / few sweeps (CI smoke)")
    ap.add_argument("--compounds", type=int, default=400)
    ap.add_argument("--targets", type=int, default=64)
    ap.add_argument("--cells", type=int, default=24)
    ap.add_argument("--features", type=int, default=32)
    ap.add_argument("--burnin", type=int, default=60)
    ap.add_argument("--nsamples", type=int, default=60)
    ap.add_argument("--save-dir", default=None,
                    help="posterior-sample store (default: a tempdir)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.quick:
        args.compounds, args.targets, args.cells = 96, 24, 12
        args.features, args.burnin, args.nsamples = 12, 15, 15

    rank, hold_out = 4, 4
    F, train, test, viability, activity, n_warm = make_data(
        0, args.compounds, args.targets, args.cells, args.features,
        rank=rank, noise=0.1, hold_out=hold_out)
    save_dir = args.save_dir or tempfile.mkdtemp(prefix="smurff_run_")

    print(f"two relations sharing {n_warm} compounds "
          f"({args.targets} targets sparse + {args.cells} cell lines "
          f"dense), {hold_out} cold compounds held out")

    b = ModelBuilder(num_latent=rank + 2)
    b.add_entity("compound", n_warm, side_info=F[:n_warm])  # -> Macau
    b.add_entity("target", args.targets)
    b.add_entity("cellline", args.cells)
    b.add_block("compound", "target", train,
                noise=AdaptiveGaussian(), test=test)
    b.add_block("compound", "cellline", viability,
                noise=AdaptiveGaussian())
    session = b.session(burnin=args.burnin, nsamples=args.nsamples,
                        seed=0, save_freq=1, save_dir=save_dir)
    result = session.run()

    print(f"\nin-session  test RMSE : {result.rmse_test:.4f} "
          "(noise floor 0.1)")
    for blk in result.blocks:
        print(f"  {blk.entities[0]:>9s} x {blk.entities[1]:<9s}"
              f" train RMSE {blk.rmse_train_trace[-1]:.4f}")

    # --- reload the posterior from disk: no training data needed -----
    p = PredictSession(save_dir)
    pred = p.predict(test[0], test[1], block=("compound", "target"))
    rmse_disk = float(np.sqrt(np.mean((pred - test[2]) ** 2)))
    print(f"\nPredictSession({p.num_samples} samples from {save_dir})")
    print(f"reloaded    test RMSE : {rmse_disk:.4f}  (same chain)")

    cold = p.predict_new("compound", F[n_warm:],
                         block=("compound", "target"))
    truth = activity[n_warm:]
    rmse_cold = float(np.sqrt(np.mean((cold - truth) ** 2)))
    rmse_zero = float(np.sqrt(np.mean(truth ** 2)))
    print(f"out-of-matrix RMSE    : {rmse_cold:.4f} over {hold_out} "
          f"cold compounds (predict-zero baseline {rmse_zero:.4f})")
    assert abs(rmse_disk - result.rmse_test) < 1e-4, \
        "reload must reproduce the in-session posterior mean"
    assert rmse_cold < rmse_zero, \
        "the sampled Macau link must beat the zero baseline"


if __name__ == "__main__":
    main()
