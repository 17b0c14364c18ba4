"""Quickstart: Bayesian Matrix Factorization on compound-activity data.

Mirrors the SMURFF Jupyter quickstart on the builder API: declare the
entity/block graph with ``ModelBuilder`` (here the simplest one — two
entities, one sparse ChEMBL-like activity matrix), run BMF with Gibbs
sampling, report test RMSE.  The classic ``TrainSession`` remains as a
thin wrapper over the same builder for the single-matrix case; pass
``save_freq=``/``save_dir=`` to either to stream posterior samples for
``PredictSession`` (see examples/compose_multi_matrix.py).

    PYTHONPATH=src python examples/quickstart.py [--num-latent 16]
"""
import argparse

from repro.core import AdaptiveGaussian, ModelBuilder
from repro.data.synthetic import chembl_like
from repro.launch.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--num-latent", type=int, default=8)
    ap.add_argument("--burnin", type=int, default=100)
    ap.add_argument("--nsamples", type=int, default=100)
    ap.add_argument("--compounds", type=int, default=2000)
    ap.add_argument("--proteins", type=int, default=200)
    ap.add_argument("--density", type=float, default=0.05)
    args = ap.parse_args()
    enable_compile_cache()

    print("generating ChEMBL-like activity matrix "
          f"({args.compounds} compounds x {args.proteins} proteins)...")
    R_train, test, _ = chembl_like(0, args.compounds, args.proteins,
                                   density=args.density, rank=8,
                                   noise=0.3)

    builder = ModelBuilder(num_latent=args.num_latent)
    builder.add_entity("compound", args.compounds)
    builder.add_entity("protein", args.proteins)
    builder.add_block("compound", "protein", R_train, test=test,
                      noise=AdaptiveGaussian())
    session = builder.session(burnin=args.burnin,
                              nsamples=args.nsamples, seed=0, verbose=1)
    result = session.run()

    print(f"\ntest RMSE  : {result.rmse_test:.4f}")
    print(f"sweeps     : {args.burnin} burn-in + {args.nsamples} samples")
    print(f"runtime    : {result.runtime_s:.1f}s "
          f"({result.runtime_s / (args.burnin + args.nsamples) * 1e3:.1f}"
          " ms/sweep)")


if __name__ == "__main__":
    main()
