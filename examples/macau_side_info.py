"""Macau: side information lifts cold-start predictions (paper §4).

Attaches ECFP-like compound fingerprints through the Macau link
matrix and compares against plain BMF — overall and on compounds with
very few training observations.

    PYTHONPATH=src python examples/macau_side_info.py
"""
import numpy as np

from repro.core import AdaptiveGaussian, TrainSession
from repro.data.synthetic import chembl_like
from repro.launch.compile_cache import enable_compile_cache


def fit(R, test, F, tag):
    s = TrainSession(num_latent=8, burnin=120, nsamples=120, seed=0)
    s.add_train_and_test(R, test=test, noise=AdaptiveGaussian())
    if F is not None:
        s.add_side_info(axis=0, F=F)     # compounds get fingerprints
    r = s.run()
    print(f"{tag:18s} test RMSE {r.rmse_test:.4f}   "
          f"({r.runtime_s:.1f}s)")
    return r


def main():
    enable_compile_cache()
    R, test, F = chembl_like(3, 1500, 120, density=0.04, rank=8,
                             noise=0.2, n_features=64,
                             feature_noise=0.25)
    ti, tj, tv = test
    counts = np.bincount(np.asarray(R.coo_i), minlength=R.shape[0])
    cold = counts[ti] <= 2
    print(f"{int(cold.sum())} of {len(ti)} test points are cold-start "
          "(compound has <=2 train ratings)\n")

    r_bmf = fit(R, test, None, "BMF (no side)")
    r_macau = fit(R, test, F, "Macau (+ECFP)")

    for name, r in (("BMF", r_bmf), ("Macau", r_macau)):
        err = r.predictions - tv
        print(f"{name:6s} cold-start RMSE: "
              f"{np.sqrt(np.mean(err[cold] ** 2)):.4f}")


if __name__ == "__main__":
    main()
