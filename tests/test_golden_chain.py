"""Golden-chain regression: the sampled chain itself is pinned.

Parity and property tests check *relationships* (sharded == single
device, batched == loop); none of them notices if a refactor changes
the RNG consumption order and silently produces a different — equally
valid-looking — chain, which would invalidate every stored checkpoint
and reproducibility claim.  This locks the 3-sweep RMSE/alpha
trajectories of one Gaussian, one probit, and one GFA (spike-and-slab)
model on a fixed seed into ``results/golden_chains.json``.  The GFA
chain pins the counter-based SnS draw order (``row_bernoulli`` +
per-component-folded ``row_normals``) that the distributed sweep's
shard slices are defined against.

``test_golden_chain_ring_pipeline_no_fork`` additionally replays the
same three models through the RING-pipelined distributed sweep
(``pipeline="ring"``) and asserts the trajectories land on the SAME
fixture — the ring exchange must not fork the golden chains, so the
fixture never needs a ring-mode regeneration.

Tolerance: 1e-3 relative.  XLA reduction-order drift across versions
measures ~1e-6..1e-5 on these trajectories; a changed draw sequence
moves them by ~1e-1.  The fixture records the JAX release whose random
streams drew it: a release that changes a PRNG default (as 0.9 did with
``jax_threefry_partitionable``) is a chain-breaking change.  Regenerate
INTENTIONALLY after an acknowledged chain-breaking change:

    PYTHONPATH=src python tests/test_golden_chain.py --regen
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import (AdaptiveGaussian, BlockDef, EntityDef,
                        FixedNormalPrior, MFData, ModelDef, NormalPrior,
                        ProbitNoise, SpikeAndSlabPrior, dense_block,
                        gibbs_step, init_state)
from repro.core.sparse import random_sparse

FIXTURE = os.path.join(os.path.dirname(__file__), "..", "results",
                       "golden_chains.json")
SWEEPS = 3
SEED = 11


def _chain(name):
    K = 4
    if name == "gfa":
        return _gfa_chain(K)
    n_rows, n_cols = 48, 32
    binary = name == "probit"
    mat, _, _ = random_sparse(SEED, (n_rows, n_cols), 0.3, rank=3,
                              binary=binary)
    noise = ProbitNoise() if binary else AdaptiveGaussian()
    model = ModelDef((EntityDef("r", n_rows, NormalPrior(K)),
                      EntityDef("c", n_cols, NormalPrior(K))),
                     (BlockDef(0, 1, noise, sparse=True),), K, False)
    data = MFData((mat,), (None, None))
    state = init_state(model, data, seed=SEED)
    rmse, alpha = [], []
    for _ in range(SWEEPS):
        state, metrics = gibbs_step(model, data, state)
        rmse.append(float(metrics["rmse_train_0"]))
        alpha.append(float(metrics["alpha_0"]))
    return {"rmse_train": rmse, "alpha": alpha}


def _gfa_chain(K):
    """GFA (FixedNormal Z + SnS loadings, two dense views): pins the
    counter-based spike-and-slab draw order."""
    rng = np.random.default_rng(SEED)
    N, dims = 48, (16, 12)
    Z = rng.normal(size=(N, K)).astype(np.float32)
    ents = [EntityDef("samples", N, FixedNormalPrior(K))]
    blocks, payloads = [], []
    for m, D in enumerate(dims):
        W = rng.normal(size=(D, K)).astype(np.float32)
        X = (Z @ W.T + 0.1 * rng.normal(size=(N, D))).astype(np.float32)
        ents.append(EntityDef(f"view{m}", D, SpikeAndSlabPrior(K)))
        blocks.append(BlockDef(0, m + 1, AdaptiveGaussian(),
                               sparse=False))
        payloads.append(dense_block(X))
    model = ModelDef(tuple(ents), tuple(blocks), K, False)
    data = MFData(tuple(payloads), tuple([None] * len(ents)))
    state = init_state(model, data, seed=SEED)
    rmse, alpha = [], []
    for _ in range(SWEEPS):
        state, metrics = gibbs_step(model, data, state)
        rmse.append(float(metrics["rmse_train_0"]))
        alpha.append(float(metrics["alpha_0"]))
    return {"rmse_train": rmse, "alpha": alpha}


def _run_all():
    return {name: _chain(name) for name in ("gaussian", "probit", "gfa")}


def test_golden_chain_trajectories():
    with open(FIXTURE) as f:
        golden = json.load(f)
    got = _run_all()
    assert set(got) == set(golden["chains"])
    for name, traj in got.items():
        for key in ("rmse_train", "alpha"):
            np.testing.assert_allclose(
                traj[key], golden["chains"][name][key],
                rtol=1e-3, atol=1e-5,
                err_msg=f"{name}.{key} drifted — if the chain change "
                        "is intentional, regen the fixture (see module "
                        "docstring)")


def test_wrappers_replay_golden_chain():
    """The session wrappers (now thin layers over ``ModelBuilder``)
    compose the IDENTICAL model graphs the engine fixtures pin:
    ``TrainSession`` replays the gaussian/probit chains and
    ``GFASession(zero_init_loadings=False)`` the GFA chain —
    BITWISE against the in-process engine chain (same jit program,
    same RNG stream) and at the usual tolerance against the on-disk
    fixture.  The builder redesign provably forks no sampled chain."""
    from repro.core import (AdaptiveGaussian, GFASession, ProbitNoise,
                            TrainSession)
    from repro.core.sparse import random_sparse

    with open(FIXTURE) as f:
        golden = json.load(f)["chains"]
    engine = _run_all()

    def trace_cb(store):
        def cb(info):
            store["rmse_train"].append(
                float(info.metrics["rmse_train_0"]))
            store["alpha"].append(float(info.metrics["alpha_0"]))
        return cb

    got = {}
    for name in ("gaussian", "probit"):
        binary = name == "probit"
        mat, _, _ = random_sparse(SEED, (48, 32), 0.3, rank=3,
                                  binary=binary)
        store = {"rmse_train": [], "alpha": []}
        s = TrainSession(num_latent=4, burnin=SWEEPS, nsamples=0,
                         seed=SEED, callbacks=[trace_cb(store)])
        s.add_train_and_test(
            mat, noise=ProbitNoise() if binary else AdaptiveGaussian())
        s.run()
        got[name] = store

    rng = np.random.default_rng(SEED)
    N, dims, K = 48, (16, 12), 4
    Z = rng.normal(size=(N, K)).astype(np.float32)
    views = []
    for m, D in enumerate(dims):
        W = rng.normal(size=(D, K)).astype(np.float32)
        views.append((Z @ W.T + 0.1 * rng.normal(size=(N, D)))
                     .astype(np.float32))
    store = {"rmse_train": [], "alpha": []}
    GFASession(views, num_latent=K, burnin=SWEEPS, nsamples=0,
               seed=SEED, zero_init_loadings=False,
               callbacks=[trace_cb(store)]).run()
    got["gfa"] = store

    for name, traj in got.items():
        for key in ("rmse_train", "alpha"):
            # bitwise vs the engine chain computed in this process
            np.testing.assert_array_equal(
                traj[key], engine[name][key],
                err_msg=f"wrapper {name}.{key} forked off the engine "
                        "chain — the builder rewrite changed the "
                        "sampled draws")
            # and within reduction-order tolerance of the fixture
            np.testing.assert_allclose(
                traj[key], golden[name][key], rtol=1e-3, atol=1e-5,
                err_msg=f"wrapper {name}.{key} drifted off the golden "
                        "fixture")


_RING_GOLDEN_SCRIPT = r"""
import json, os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax

from repro.core import (AdaptiveGaussian, BlockDef, EntityDef,
                        FixedNormalPrior, MFData, ModelDef, NormalPrior,
                        ProbitNoise, SpikeAndSlabPrior, dense_block,
                        init_state)
from repro.core.distributed import (distributed_supported,
                                    make_distributed_step)
from repro.core.sparse import random_sparse
from repro.launch.mesh import make_mesh

FIXTURE = os.environ["GOLDEN_FIXTURE"]
SWEEPS, SEED, K = 3, 11, 4

with open(FIXTURE) as f:
    golden = json.load(f)["chains"]
assert json.load(open(FIXTURE))["seed"] == SEED


def ring_chain(model, data, n_dev):
    # the GFA golden dims (16, 12) divide 4 shards, not 8 — the mesh
    # is part of the harness, the chain must not depend on it
    mesh = make_mesh((n_dev,), ("data",))
    assert distributed_supported(model, mesh, data)
    state = init_state(model, data, seed=SEED)
    step, ds, ss = make_distributed_step(model, mesh, data, state,
                                         pipeline="ring")
    st = jax.device_put(state, ss)
    pdata = jax.device_put(data, ds)
    rmse, alpha = [], []
    for _ in range(SWEEPS):
        st, metrics = step(pdata, st)
        rmse.append(float(metrics["rmse_train_0"]))
        alpha.append(float(metrics["alpha_0"]))
    return {"rmse_train": rmse, "alpha": alpha}


chains = {}
n_rows, n_cols = 48, 32
for name in ("gaussian", "probit"):
    binary = name == "probit"
    mat, _, _ = random_sparse(SEED, (n_rows, n_cols), 0.3, rank=3,
                              binary=binary)
    noise = ProbitNoise() if binary else AdaptiveGaussian()
    model = ModelDef((EntityDef("r", n_rows, NormalPrior(K)),
                      EntityDef("c", n_cols, NormalPrior(K))),
                     (BlockDef(0, 1, noise, sparse=True),), K, False)
    chains[name] = ring_chain(model, MFData((mat,), (None, None)), 8)

rng = np.random.default_rng(SEED)
N, dims = 48, (16, 12)
Z = rng.normal(size=(N, K)).astype(np.float32)
ents = [EntityDef("samples", N, FixedNormalPrior(K))]
blocks, payloads = [], []
for m, D in enumerate(dims):
    W = rng.normal(size=(D, K)).astype(np.float32)
    X = (Z @ W.T + 0.1 * rng.normal(size=(N, D))).astype(np.float32)
    ents.append(EntityDef(f"view{m}", D, SpikeAndSlabPrior(K)))
    blocks.append(BlockDef(0, m + 1, AdaptiveGaussian(), sparse=False))
    payloads.append(dense_block(X))
gfa_model = ModelDef(tuple(ents), tuple(blocks), K, False)
chains["gfa"] = ring_chain(
    gfa_model, MFData(tuple(payloads), tuple([None] * len(ents))), 4)

for name, traj in chains.items():
    for key in ("rmse_train", "alpha"):
        np.testing.assert_allclose(
            traj[key], golden[name][key], rtol=1e-3, atol=1e-5,
            err_msg=f"ring {name}.{key} forked off the golden chain")
    print(name, "ring == golden", traj["rmse_train"])
print("OK")
"""


@pytest.mark.slow
def test_golden_chain_ring_pipeline_no_fork():
    """The ring-pipelined distributed sweep reproduces the pinned
    golden trajectories — ring mode does NOT fork
    ``results/golden_chains.json``, so the fixture regenerates
    identical whichever pipeline produced the running chain."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")
    env["GOLDEN_FIXTURE"] = os.path.abspath(FIXTURE)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _RING_GOLDEN_SCRIPT],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "OK" in out.stdout


def test_recorder_does_not_fork_golden_chain(tmp_path):
    """Bitwise non-interference (the ``repro.obs`` contract): a
    ``TrainSession`` run with an ENABLED recorder replays the
    recorder-off run EXACTLY — every trace value and every state
    leaf — because timestamps are taken outside jitted code and
    never feed back into sampling.  ``chains=1`` is pinned so the
    CI ``REPRO_CHAINS=4`` leg exercises the same baseline."""
    import jax

    from repro.core import TrainSession
    from repro.obs import Recorder

    mat, _, _ = random_sparse(SEED, (48, 32), 0.3, rank=3)

    def run(recorder, sub):
        s = TrainSession(num_latent=4, burnin=2, nsamples=3,
                         seed=SEED, chains=1, recorder=recorder,
                         save_freq=1, save_dir=str(tmp_path / sub))
        s.add_train_and_test(mat, noise=AdaptiveGaussian())
        return s.run()

    off = run(Recorder(enabled=False), "off")
    rec = Recorder(enabled=True)
    on = run(rec, "on")

    assert on.rmse_train_trace == off.rmse_train_trace
    assert on.rmse_test_trace == off.rmse_test_trace
    assert on.rmse_test == off.rmse_test
    for x, y in zip(jax.tree.leaves(on.state),
                    jax.tree.leaves(off.state)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # the enabled run actually recorded: compile split, the sweep
    # loop's spans and the sample writer's
    events = rec.trace()["traceEvents"]
    names = {e["name"] for e in events}
    assert {"session/compile", "sweep", "session/readback",
            "session/accumulate", "session/save", "ckpt/wait",
            "ckpt/host_copy", "ckpt/save"} <= names
    assert all(set(e["args"]) == {"sweep", "phase", "stage",
                                  "bytes_on_wire"}
               for e in events if e["name"] == "sweep")
    assert rec.counter("session.sweeps") == 5.0
    # and the split is visible in the result
    assert on.compile_s > 0.0
    assert off.compile_s > 0.0


if __name__ == "__main__":
    import sys
    if "--regen" not in sys.argv:
        sys.exit("pass --regen to overwrite the fixture")
    import jax
    # the random streams (e.g. the jax_threefry_partitionable default)
    # belong to the JAX release that drew them
    out = {"seed": SEED, "sweeps": SWEEPS, "jax": jax.__version__,
           "chains": _run_all()}
    with open(FIXTURE, "w") as f:
        json.dump(out, f, indent=1)
    print("wrote", FIXTURE)
