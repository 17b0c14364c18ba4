"""Traffic kind ``sweep``: post-burn-in Gibbs sampling through
``ModelBuilder`` -> ``Session.run``.

Set-up generates the configuration's data from the seed, builds the
model through the program's ``ModelBuilder`` and starts ``Session.run``; its
own warm-up sweep, ``burnin`` sweeps and the first two sampling sweeps
are set-up: the first compiles the test accumulation, and on a mesh
the second compiles it again, for the sharding the first update left
its sums in.  The window opens at the end of the second sampling sweep
and closes at the first sweep
boundary after ``run.seconds``: a callback fences each sweep with
``block_until_ready`` and ends the run by raising, so ``Session.run``
stays the loop and its host work (metrics, test accumulation, sample
streaming) is in the window.

``sweep_s`` is the window over the sweeps completed in it.  The
comparison with the reference covers the window's first two
transitions, as the callback sees them (``SweepInfo.state``): hyper-parameters, both
factors and the noise precision.  The session's test accumulation runs
in the window but is not compared: its sums are not part of what a
callback sees.
"""
from __future__ import annotations

import gc
import os
import shutil
import threading
import time

import jax
import numpy as np

from benchkit.data import fixed_degree


# the window's sweeps whose states are compared: the first two
# transitions, at the same place in the chain whatever the sweep's speed
# (the gaps grow as the chain moves from its start)
COMPARED = (0, 1, 2)


class _WindowClosed(Exception):
    pass


class _Window:
    """The per-sweep callback that opens, measures and closes the
    window, keeping the states of the transitions to check."""

    def __init__(self, run, first: int):
        self.run, self.first = run, first
        self.t0 = None
        self.n = 0
        self.keep = {}          # window sweep index -> state
        self.started = False

    def __call__(self, info):
        jax.block_until_ready(info.state)
        if not self.started:
            self.started = True
            self.run.phase("placement, compile and first sweeps")
        if info.sweep < self.first:
            return
        if self.t0 is None:
            self.t0 = self.run.open_window()
        else:
            self.n += 1
        if self.n in COMPARED:
            self.keep[self.n] = info.state
        if self.n and time.perf_counter() - self.t0 >= self.run.seconds:
            self.run.close_window()
            raise _WindowClosed


def _state_dict(state) -> dict:
    """The program's state as plain arrays for the reference."""
    (mu0, lam0), (mu1, lam1) = [(h["mu"], h["Lambda"])
                                for h in state.hypers]
    return {"key": np.asarray(state.key), "U": np.asarray(state.factors[0]),
            "V": np.asarray(state.factors[1]), "mu0": np.asarray(mu0),
            "Lam0": np.asarray(lam0), "mu1": np.asarray(mu1),
            "Lam1": np.asarray(lam1),
            "alpha": float(np.asarray(state.noises[0]["alpha"]))}


def build(run, prob):
    """The program's model, through its ``ModelBuilder``."""
    from repro.core import AdaptiveGaussian, ModelBuilder, from_coo
    cfg = run.config
    mat = from_coo(prob.i, prob.j, prob.v, prob.shape)
    b = ModelBuilder(num_latent=cfg["num_latent"])
    b.add_entity("compound", prob.shape[0])
    b.add_entity("protein", prob.shape[1])
    b.add_block("compound", "protein", mat,
                test=(prob.ti, prob.tj, prob.tv), noise=AdaptiveGaussian())
    return b


def problem(run):
    cfg = run.config
    return fixed_degree(run.seed, cfg["n_rows"], cfg["n_cols"],
                        cfg["nnz_per_row"], cfg["n_test_per_row"])


def run(run):
    cfg, mix = run.config, run.mix
    run.phase("imports")
    prob = problem(run)
    run.phase("data")
    mesh = None
    if run.chips > 1:
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((run.chips,), ("data",))
    store = os.path.join(run.out, "store")
    shutil.rmtree(store, ignore_errors=True)
    stream = bool(mix["stream_samples"])
    win = _Window(run, first=mix["burnin"] + 1)
    sess = build(run, prob).session(
        burnin=mix["burnin"], nsamples=10 ** 9, seed=run.seed, mesh=mesh,
        pipeline=cfg.get("pipeline"), save_freq=1 if stream else 0,
        save_dir=store if stream else None, callbacks=[win])
    run.phase("model")
    threads = set(threading.enumerate())
    try:
        sess.run()
        raise RuntimeError("Session.run ended before the window closed")
    except _WindowClosed:
        pass
    # the session's sample writers still at work when the window closed
    for t in set(threading.enumerate()) - threads:
        t.join(timeout=120)
    shutil.rmtree(store, ignore_errors=True)
    run.e2e["sweep_s"] = run.window_s / win.n
    run.attempted = win.n
    flops = _sweep_flops(cfg, prob)
    run.readings.update(observations=len(prob.v),
                        traced_sweeps=win.n if run.trace else None,
                        sweep_flops_per_chip=flops / run.chips)
    print(f"sweep: {win.n} sweeps in {run.window_s} s, set-up "
          f"{run.setup_s} s", flush=True)
    run.read_memory()
    if run.trace:
        run.hlo.update(_sweep_hlo(sess, mesh, cfg.get("pipeline"),
                                  win.keep[0]))
    kept = {k: _state_dict(v) for k, v in win.keep.items()}
    del win, sess
    gc.collect()
    fac = getattr(run, "factors", True)
    check(run, prob, kept, factors=fac)
    run.controls = {
        "bf16": lambda: check(run, prob, kept, True, fac),
        "bf16_solve": lambda: check(run, prob, kept, "solve", fac)}
    run.faults = lambda: faults(run, prob, kept, fac)


def _sweep_hlo(sess, mesh, pipeline, state) -> dict:
    """The compiled sweep's text, which names each op's program
    function, keyed by its module name: the program built again
    through its public entry points, as ``Session.run`` builds it."""
    from repro.core.distributed import make_distributed_step
    from repro.core.gibbs import gibbs_step
    if mesh is None:
        low = gibbs_step.lower(sess.model, sess.data, state)
    else:
        step, ds, _ = make_distributed_step(
            sess.model, mesh, sess.data, state,
            pipeline=pipeline)
        low = step.lower(jax.device_put(sess.data, ds), state)
    compiled = low.compile()
    mem = compiled.memory_analysis()
    if mem is not None:
        print(f"sweep program: temporaries {mem.temp_size_in_bytes} B, "
              f"arguments {mem.argument_size_in_bytes} B, outputs "
              f"{mem.output_size_in_bytes} B", flush=True)
    text = compiled.as_text()
    return {text.split(",", 1)[0].split()[-1]: text}


def _sweep_flops(cfg, prob):
    import benchkit.counts as counts
    return counts.sweep_flops(cfg["n_rows"], cfg["n_cols"],
                              cfg["num_latent"], len(prob.v), len(prob.ti))


def check(run, prob, kept, lower=False, factors=True):
    """Compare the kept transitions with the reference (``lower``: the
    control in the program's place, see ``check_transition``)."""
    ref = _reference(run)
    obs = ref.Observations(prob.i, prob.j, prob.v, prob.shape)
    worst = {}
    for a, b in zip(COMPARED, COMPARED[1:]):
        if a not in kept or b not in kept:
            continue
        r = ref.check_transition(kept[a], kept[b], obs, lower=lower,
                                 factors=factors)
        print(f"transition {a}->{b}: {r}", flush=True)
        for k, v in r.items():
            worst[k] = max(worst.get(k, 0.0), v)
    if not lower:
        run.readings["compared"] = worst
        for k, v in worst.items():
            run.check(k, v)
    return worst


def _reference(run):
    from benchkit.harness import load_module
    return load_module(os.path.join(run.bench, "references",
                                    run.config["reference"] + ".py"),
                       "bench_reference_" + run.config["reference"])


def faults(run, prob, kept, factors=True):
    """Readings of the comparison for faults planted in the last kept
    transition: the state left unchanged, half of the rows left out of
    the update, one row's draw altered, the hyper-parameters of the
    sweep before kept instead of drawn anew, and on a mesh the exchange
    between chips left out (each shard's rows see only the columns of
    their own shard)."""
    ref = _reference(run)
    obs = ref.Observations(prob.i, prob.j, prob.v, prob.shape)
    last = max(kept)
    prev, nxt = kept[last - 1], kept[last]
    half = dict(nxt, U=nxt["U"].copy())
    n = half["U"].shape[0]
    half["U"][: n // 2] = prev["U"][: n // 2]
    one = dict(nxt, U=nxt["U"].copy())
    r = int(np.random.default_rng(run.seed).integers(0, n))
    one["U"][r] = -one["U"][r]
    stale = dict(nxt, **{k: prev[k] for k in ("mu0", "Lam0", "mu1",
                                                "Lam1")})
    planted = {"unchanged": dict(prev), "half_rows": half, "one_row": one,
               "stale_hyper": stale}
    if run.chips > 1 and factors:
        import jax.numpy as jnp
        chips = run.chips
        rows, cols = prob.shape[0] // chips, prob.shape[1] // chips
        _, ents, _ = ref.sweep_keys(jnp.asarray(prev["key"]))
        local = ref.draw_factor(
            ents[0][1], prev["V"], obs, True, jnp.asarray(prev["alpha"]),
            jnp.asarray(nxt["mu0"]), jnp.asarray(nxt["Lam0"]),
            keep=lambda own, other: own // rows == other // cols)
        planted["no_exchange"] = dict(nxt, U=local)
    out = {}
    for name, bad in planted.items():
        out[name] = ref.check_transition(prev, bad, obs, factors=factors)
        print(f"fault {name}: {out[name]}", flush=True)
    return out
