"""Traffic kind ``sweep_side``: the ``sweep`` kind with side information
on the compounds, so the program samples Macau.

Set-up generates the configuration's data and each compound's binary
fingerprint from the seed (``benchkit.fingerprints``), builds the model
through ``ModelBuilder.add_entity(..., side_info=...)`` and starts
``Session.run``.  The window, its fencing and the transitions compared
are ``sweep.py``'s (loaded from beside this file): the window's first
two transitions, hyper-parameters, link matrix and its precision, both
factors and the noise precision, against ``references/macau.py``.
"""
from __future__ import annotations

import gc
import os
import shutil
import threading

import numpy as np

from benchkit.fingerprints import ecfp_like
from benchkit.harness import load_module

sweep = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "sweep.py"), "bench_traffic_sweep_of_side")


def side_info(run):
    cfg = run.config
    return ecfp_like(run.seed, cfg["n_rows"], cfg["num_features"],
                     cfg["side_bits_per_row"])


def build(run, prob, side):
    """The program's Macau model, through its ``ModelBuilder``."""
    from repro.core import AdaptiveGaussian, ModelBuilder, from_coo
    cfg = run.config
    # one padded width for every seed: one compiled shape, one amount
    # of work (the configuration's ``max_nnz_col``)
    mat = from_coo(prob.i, prob.j, prob.v, prob.shape,
                   max_nnz_col=cfg["max_nnz_col"])
    b = ModelBuilder(num_latent=cfg["num_latent"])
    b.add_entity("compound", prob.shape[0], side_info=side,
                 beta_precision=cfg["beta_precision"],
                 sample_beta_precision=cfg["sample_beta_precision"])
    b.add_entity("protein", prob.shape[1])
    b.add_block("compound", "protein", mat,
                test=(prob.ti, prob.tj, prob.tv), noise=AdaptiveGaussian())
    return b


def _state_dict(state) -> dict:
    out = sweep._state_dict(state)
    h = state.hypers[0]
    out["beta"] = np.asarray(h["beta"])
    out["beta_prec"] = float(np.asarray(h["beta_prec"]))
    return out


def run(run):
    cfg, mix = run.config, run.mix
    run.phase("imports")
    prob = sweep.problem(run)
    side = side_info(run)
    run.phase("data")
    store = os.path.join(run.out, "store")
    shutil.rmtree(store, ignore_errors=True)
    stream = bool(mix["stream_samples"])
    win = sweep._Window(run, first=mix["burnin"] + 1)
    sess = build(run, prob, side).session(
        burnin=mix["burnin"], nsamples=10 ** 9, seed=run.seed,
        save_freq=1 if stream else 0, save_dir=store if stream else None,
        callbacks=[win])
    run.phase("model")
    threads = set(threading.enumerate())
    try:
        sess.run()
        raise RuntimeError("Session.run ended before the window closed")
    except sweep._WindowClosed:
        pass
    for t in set(threading.enumerate()) - threads:
        t.join(timeout=120)
    shutil.rmtree(store, ignore_errors=True)
    run.e2e["sweep_s"] = run.window_s / win.n
    run.attempted = win.n
    run.readings.update(observations=len(prob.v),
                        traced_sweeps=win.n if run.trace else None,
                        sweep_flops_per_chip=_sweep_flops(cfg, prob))
    print(f"sweep: {win.n} sweeps in {run.window_s} s, set-up "
          f"{run.setup_s} s", flush=True)
    run.read_memory()
    if run.trace:
        run.hlo.update(sweep._sweep_hlo(sess, None, None, win.keep[0]))
    kept = {k: _state_dict(v) for k, v in win.keep.items()}
    del win, sess
    gc.collect()
    fac = getattr(run, "factors", True)
    check(run, prob, side, kept, factors=fac)
    run.controls = {
        "bf16": lambda: check(run, prob, side, kept, True, fac),
        "link": lambda: check(run, prob, side, kept, "link", fac)}
    run.faults = lambda: faults(run, prob, side, kept, fac)


def _sweep_flops(cfg, prob):
    """The BMF sweep's useful FLOP and the link's (one chip)."""
    from benchkit.link_counts import link_flops
    return sweep._sweep_flops(cfg, prob) + link_flops(
        cfg["n_rows"], cfg["num_features"], cfg["num_latent"])


def _reference(run):
    return load_module(os.path.join(run.bench, "references",
                                    run.config["reference"] + ".py"),
                       "bench_reference_" + run.config["reference"])


def check(run, prob, side, kept, lower=False, factors=True):
    """Compare the kept transitions with the reference (``lower``: a
    control in the program's place)."""
    ref = _reference(run)
    obs = ref.Observations(prob.i, prob.j, prob.v, prob.shape)
    worst = {}
    for a, b in zip(sweep.COMPARED, sweep.COMPARED[1:]):
        if a not in kept or b not in kept:
            continue
        r = ref.check_transition(kept[a], kept[b], obs, side, lower=lower,
                                 factors=factors)
        print(f"transition {a}->{b}: {r}", flush=True)
        for k, v in r.items():
            worst[k] = max(worst.get(k, 0.0), v)
    if not lower:
        run.readings["compared"] = worst
        for k, v in worst.items():
            run.check(k, v)
    return worst


def faults(run, prob, side, kept, factors=True):
    """Readings of the comparison for faults planted in the last kept
    transition: ``sweep.py``'s (the state unchanged, half of the rows
    left out, one row's draw altered, the hyper-parameters of the sweep
    before kept), and the link's: beta kept from the sweep before
    (``stale_link``), beta's rows permuted, and the compound rows drawn
    from the shared prior mean, without the side information's term."""
    import jax.numpy as jnp
    ref = _reference(run)
    obs = ref.Observations(prob.i, prob.j, prob.v, prob.shape)
    last = max(kept)
    prev, nxt = kept[last - 1], kept[last]
    half = dict(nxt, U=nxt["U"].copy())
    n = half["U"].shape[0]
    half["U"][: n // 2] = prev["U"][: n // 2]
    one = dict(nxt, U=nxt["U"].copy())
    rng = np.random.default_rng(run.seed)
    r = int(rng.integers(0, n))
    one["U"][r] = -one["U"][r]
    stale = dict(nxt, **{k: prev[k] for k in ("mu0", "Lam0", "mu1",
                                                "Lam1")})
    perm = dict(nxt, beta=nxt["beta"][rng.permutation(len(nxt["beta"]))])
    planted = {"unchanged": dict(prev), "half_rows": half, "one_row": one,
               "stale_hyper": stale, "stale_link": dict(nxt,
                                                        beta=prev["beta"]),
               "beta_rows_permuted": perm}
    if factors:
        _, ents, _ = ref.sweep_keys(jnp.asarray(prev["key"]))
        Lam = jnp.asarray(nxt["Lam0"])
        shared = jnp.broadcast_to(Lam @ jnp.asarray(nxt["mu0"]),
                                  nxt["U"].shape)
        planted["shared_mean"] = dict(nxt, U=ref.draw_compounds(
            ents[0][1], prev["V"], obs, jnp.asarray(prev["alpha"]), Lam,
            shared))
    out = {}
    for name, bad in planted.items():
        out[name] = ref.check_transition(prev, bad, obs, side,
                                         factors=factors)
        print(f"fault {name}: {out[name]}", flush=True)
    return out
