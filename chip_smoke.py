"""SMURFF's main path, once, on a TPU: the quickest proof it still runs.

    python chip_smoke.py              # one chip: train, kernels, serve
    python chip_smoke.py --chips 4    # four chips: the sharded sweep only

One chip runs the ``bmf_chembl`` deployment at a 1/32 row share:
32,768 compounds x 8,192 proteins, K=128, adaptive Gaussian noise,
exactly 64 observations per compound, generated from ``--seed``.

* train: ``ModelBuilder`` -> ``Session.run`` (the XLA sweep), a few
  burn-in sweeps and 16 samples streamed to a store.  Test RMSE must be
  finite and beat predicting the training mean.
* kernels: the three Pallas kernels compiled for the chip at the main
  path's widths, against their ``kernels/ref.py`` references
  (``topk_score`` bitwise), then the same sweeps with
  ``use_pallas=True``, whose RMSE trace must follow the XLA run.
* serve: ``PredictSession`` + ``RecommendServer`` over the store, warm
  users with exclusions, answers equal to sequential ``recommend``.

``--chips 4`` runs only the sharded sweep: ``Session(mesh=...)`` on a
("data", 4) mesh at 131,072 x 8,192 (the same 1/32 share per chip),
eager and ring, then parity at 32,768 rows against the single-device
chain on one of the chips.

Each phase prints one line of findings.  The last line is one JSON
object naming the device.  Any failed check raises, so the exit code
is not 0 and no result line is printed.  Without a TPU nothing runs.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import time
import warnings

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import (AdaptiveGaussian, ModelBuilder,  # noqa: E402
                        PredictSession, Session)
from repro.core.distributed import (data_shardings,  # noqa: E402
                                    distributed_supported,
                                    distributed_unsupported_reason,
                                    make_distributed_step)
from repro.core.gibbs import gibbs_step, init_state  # noqa: E402
from repro.data.synthetic import fixed_degree  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.launch.serve import RecommendServer  # noqa: E402

STORE = os.path.join(ROOT, ".smoke_store")

# the 1/32 row share of bmf_chembl (launch/mf_dryrun.py) per chip
N_ROWS, N_COLS, K, NNZ_PER_ROW = 32_768, 8_192, 128, 64

# Gram and SDDMM kernels against their references: normalized max
# error.  Matmuls of f32 operands may take single-pass bf16 on the MXU
# (8-bit mantissa, relative rounding 2^-9 per operand), so a bf16-level
# bound, not an f32 one.
KERNEL_TOL = 2e-2
# RMSE trace of the use_pallas=True sweeps against the XLA sweeps
TRACE_RTOL = 1e-3
# sharded vs single-device factors (the tests/test_distributed.py bound)
PARITY_TOL = 2e-4


def device_check(n_chips: int) -> dict:
    """The chip this run measures; exits before any work elsewhere."""
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, but JAX's first device is on "
            f"platform {d.platform!r}; nothing was run")
    if len(devs) < n_chips:
        raise SystemExit(f"chip_smoke: --chips {n_chips} needs {n_chips} "
                         f"devices, JAX sees {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs),
            "bytes_limit": d.memory_stats()["bytes_limit"]}


def _build(data, num_latent: int, use_pallas: bool = False):
    mat, test, _ = data
    n_rows, n_cols = mat.shape
    b = ModelBuilder(num_latent=num_latent, use_pallas=use_pallas)
    b.add_entity("compound", n_rows)
    b.add_entity("protein", n_cols)
    b.add_block("compound", "protein", mat, test=test,
                noise=AdaptiveGaussian())
    return b


def _sweep_clock(stamps):
    def cb(info):
        jax.block_until_ready(info.state)
        stamps.append(time.perf_counter())
    return cb


def train(data, *, num_latent: int, burnin: int, nsamples: int,
          seed: int, save_dir: str) -> dict:
    """The XLA sweep through the normal entry point, streaming every
    post-burn-in sample to ``save_dir``."""
    stamps = []
    sess = _build(data, num_latent).session(
        burnin=burnin, nsamples=nsamples, seed=seed, save_freq=1,
        save_dir=save_dir, callbacks=[_sweep_clock(stamps)])
    res = sess.run()
    # the sweep program's own buffers: the allocator's peak counts only
    # arrays that outlive a call
    mem = gibbs_step.lower(sess.model, sess.data, res.state).compile(
    ).memory_analysis()
    mat, (_, _, test_v), _ = data
    mean = float(jnp.sum(mat.coo_v) / mat.nnz)
    rmse_mean = float(np.sqrt(np.mean((test_v - mean) ** 2)))
    if not np.isfinite(res.rmse_test) or res.rmse_test >= rmse_mean:
        raise AssertionError(
            f"test RMSE {res.rmse_test} does not beat the mean "
            f"predictor's {rmse_mean}")
    return {"compile_s": res.compile_s,
            # burn-in sweeps only: no sample saving or accumulation
            "sweep_s": float(np.median(np.diff(stamps[:burnin]))),
            "run_s": res.runtime_s, "sweeps": burnin + nsamples,
            "rmse_test": res.rmse_test, "rmse_mean_predictor": rmse_mean,
            "rmse_train_trace": res.rmse_train_trace,
            "sweep_temp_bytes": mem.temp_size_in_bytes,
            "state": res.state}


def _norm_err(got, want) -> float:
    got, want = np.asarray(got), np.asarray(want)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                   1e-30))


def _same(a, b) -> bool:
    """Bitwise equality with NaN slots matching positionally."""
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and bool(np.array_equal(a, b, equal_nan=True))


def kernels(data, factors, *, rows: int, n_items: int, n_samples: int,
            k: int, batch: int, trace: list, num_latent: int, seed: int,
            interpret: bool) -> dict:
    """Each Pallas kernel against its reference at main-path widths,
    then the same sweeps with ``use_pallas=True``."""
    mat, test, _ = data
    nnz = mat.rows.max_nnz
    kk = jax.random.split(jax.random.PRNGKey(seed), 6)
    vg = jax.random.normal(kk[0], (rows, nnz, num_latent), jnp.float32)
    val = jax.random.normal(kk[1], (rows, nnz), jnp.float32)
    mask = (jax.random.uniform(kk[2], (rows, nnz)) > 0.1
            ).astype(jnp.float32)
    g_k, r_k = ops.gram_and_rhs(vg, val, mask, use_pallas=True,
                                interpret=interpret)
    g_r, r_r = ops.gram_and_rhs(vg, val, mask)
    gram_err = max(_norm_err(g_k, g_r), _norm_err(r_k, r_r))

    U, V = factors
    ti, tj, _ = test
    ug, vg_t = U[jnp.asarray(ti)], V[jnp.asarray(tj)]
    sddmm_err = _norm_err(
        ops.sddmm(ug, vg_t, use_pallas=True, interpret=interpret),
        ops.sddmm(ug, vg_t))

    us = jax.random.normal(kk[3], (batch, n_samples, num_latent))
    v = jax.random.normal(kk[4], (n_samples, n_items, num_latent))
    excl = (jax.random.uniform(kk[5], (batch, n_items)) < 0.05
            ).astype(jnp.float32)
    top_k = ops.topk_score(us, v, k, exclude=excl, use_pallas=True,
                           interpret=interpret)
    top_r = ops.topk_score(us, v, k, exclude=excl)
    topk_bitwise = all(_same(a, b) for a, b in zip(top_k, top_r))

    n = len(trace)
    res = _build(data, num_latent, use_pallas=True).session(
        burnin=n, nsamples=0, seed=seed).run()
    trace_err = float(np.max(np.abs(np.asarray(res.rmse_train_trace)
                                    - np.asarray(trace))
                             / np.asarray(trace)))
    out = {"gram_err": gram_err, "sddmm_err": sddmm_err,
           "topk_bitwise": topk_bitwise, "pallas_sweeps": n,
           "pallas_compile_s": res.compile_s,
           "pallas_trace_rel_err": trace_err}
    if gram_err > KERNEL_TOL or sddmm_err > KERNEL_TOL:
        raise AssertionError(f"kernel off its reference: {out}")
    if not topk_bitwise:
        raise AssertionError(f"topk_score kernel != reference: {out}")
    if trace_err > TRACE_RTOL:
        raise AssertionError(f"use_pallas sweeps left the XLA trace: "
                             f"{out}")
    return out


def serve(store: str, observed, *, n_requests: int, k: int, slots: int,
          seed: int) -> dict:
    """Warm-user requests with exclusions through the slot runtime,
    against sequential ``PredictSession.recommend`` calls."""
    ps = PredictSession(store)
    server = RecommendServer(ps, slots=slots, k=k)   # loads the store
    stats = ps.cache_stats()
    if not stats["resident"] or stats["over_budget"]:
        raise AssertionError(f"posterior cache not resident: {stats}")

    def lazy(*_a, **_kw):
        raise AssertionError("served through _recommend_rows_lazy")
    ps._recommend_rows_lazy = lazy
    loads = ps.load_count

    rng = np.random.default_rng(seed)
    users = rng.choice(len(observed), n_requests + slots, replace=False)
    warm, timed = users[:slots], users[slots:]
    for u in warm:                   # compiles the batched program
        server.submit(int(u), exclude=observed[u])
    server.run()
    server.done.clear()
    rid = {server.submit(int(u), exclude=observed[u]): u for u in timed}
    done = server.run()
    lat = [r["t_done"] - r["t_submit"] for r in done]
    for r in done:
        u = rid[r["id"]]
        seq = ps.recommend(int(u), k=k, exclude=observed[u])
        if not (_same(r["ids"], seq.ids[0]) and _same(r["mean"], seq.mean[0])
                and _same(r["std"], seq.std[0])):
            raise AssertionError(f"served answer for user {u} differs "
                                 "from sequential recommend")
    if ps.load_count != loads:
        raise AssertionError("serving loaded checkpoints")
    return {"requests": len(done), "k": k, "slots": slots,
            "p50_ms": 1e3 * float(np.median(lat)),
            "resident_bytes": stats["resident_bytes"],
            "bitwise_vs_sequential": True}


def _quarters(arrays, devices) -> bool:
    """Every array splits evenly, one shard per device."""
    want = set(devices)
    for x in arrays:
        shards = x.addressable_shards
        if {s.device for s in shards} != want or any(
                s.data.shape[0] * len(want) != x.shape[0]
                for s in shards):
            return False
    return True


def sharded(*, n_rows: int, n_cols: int, num_latent: int,
            nnz_per_row: int, n_chips: int, sweeps: int,
            parity_rows: int, seed: int,
            require_memory_stats: bool) -> dict:
    """The explicit shard_map sweep through ``Session(mesh=...)``,
    eager and ring, then parity against the single-device chain."""
    mesh = make_mesh((n_chips,), ("data",))
    devices = list(mesh.devices.flat)
    data = fixed_degree(seed, n_rows, n_cols, nnz_per_row)
    model, mfdata, tests = _build(data, num_latent).build()
    if not distributed_supported(model, mesh, mfdata):
        raise AssertionError(distributed_unsupported_reason(
            model, mesh, mfdata))
    placed = jax.device_put(mfdata, data_shardings(model, mesh, mfdata))
    # the generator built the data on the first device; once that copy
    # is gone, every device should hold the same share
    del mfdata, data
    gc.collect()
    out = {"rows": n_rows, "chips": n_chips}
    blk = placed.blocks[0]
    out["data_quartered"] = _quarters(
        [blk.rows.idx, blk.rows.val, blk.cols.idx, blk.coo_v], devices)
    if not out["data_quartered"]:
        raise AssertionError("data not split over the mesh")
    stats = [d.memory_stats() for d in devices]
    if require_memory_stats or all(s is not None for s in stats):
        in_use = [s["bytes_in_use"] for s in stats]
        out["bytes_in_use_per_device"] = in_use
        if min(in_use) < 0.5 * max(in_use):
            raise AssertionError(f"uneven device memory: {in_use}")
    for pipeline in ("eager", "ring"):
        with warnings.catch_warnings():
            warnings.filterwarnings("error", message=".*sharded subset")
            res = Session(model, placed, tests=tests, mesh=mesh,
                          pipeline=pipeline, burnin=sweeps, nsamples=2,
                          seed=seed).run()
        out[pipeline] = {"compile_s": res.compile_s,
                         "sweep_s": res.runtime_s / (sweeps + 2),
                         "rmse_test": res.rmse_test}
        if not np.isfinite(res.rmse_test):
            raise AssertionError(f"{pipeline}: test RMSE {res.rmse_test}")
        if not _quarters(res.state.factors, devices):
            raise AssertionError(f"{pipeline}: factors not split "
                                 f"{n_chips} ways")
    del placed, res

    # parity: the same chain on one device and sharded, at full f32
    # matmul precision so that bf16 operand rounding cannot turn
    # reduction-order ULPs into visible differences
    small = fixed_degree(seed, parity_rows, n_cols, nnz_per_row)
    model, mfdata, _ = _build(small, num_latent).build()
    state0 = init_state(model, mfdata, seed)
    chains = {}
    with jax.default_matmul_precision("highest"):
        st = state0
        for _ in range(3):
            st, _ = gibbs_step(model, mfdata, st)
        chains["single"] = st
        for pipeline in ("eager", "ring"):
            step, ds, ss = make_distributed_step(model, mesh, mfdata,
                                                 state0, pipeline=pipeline)
            pdata = jax.device_put(mfdata, ds)
            st = jax.device_put(state0, ss)
            for _ in range(3):
                st, _ = step(pdata, st)
            chains[pipeline] = st
    single = [np.asarray(f) for f in chains["single"].factors]
    eager = [np.asarray(f) for f in chains["eager"].factors]
    ring = [np.asarray(f) for f in chains["ring"].factors]
    out["parity_rows"] = parity_rows
    out["eager_vs_single_max_abs"] = max(
        float(np.max(np.abs(a - b))) for a, b in zip(single, eager))
    out["ring_bitwise_eager"] = all(
        np.array_equal(a, b) for a, b in zip(ring, eager))
    out["ring_vs_eager_max_abs"] = max(
        float(np.max(np.abs(a - b))) for a, b in zip(ring, eager))
    for name, got in (("eager", eager), ("ring", ring)):
        for a, b in zip(single, got):
            np.testing.assert_allclose(
                b, a, rtol=PARITY_TOL, atol=PARITY_TOL,
                err_msg=f"{name} chain left the single-device chain")
    return out


def _report(phase: str, findings: dict) -> None:
    print(f"{phase}: {json.dumps(findings)}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    dev = device_check(args.chips)
    _report("device", dev)
    _report("compile_cache", {"dir": enable_compile_cache()})

    if args.chips == 4:
        _report("sharded", sharded(
            n_rows=4 * N_ROWS, n_cols=N_COLS, num_latent=K,
            nnz_per_row=NNZ_PER_ROW, n_chips=4, sweeps=2,
            parity_rows=N_ROWS, seed=args.seed,
            require_memory_stats=True))
    else:
        shutil.rmtree(STORE, ignore_errors=True)
        data = fixed_degree(args.seed, N_ROWS, N_COLS, NNZ_PER_ROW)
        t = train(data, num_latent=K, burnin=4, nsamples=16,
                  seed=args.seed, save_dir=STORE)
        t["peak_bytes_in_use"] = \
            jax.devices()[0].memory_stats()["peak_bytes_in_use"]
        state = t.pop("state")
        _report("train", t)
        _report("kernels", kernels(
            data, state.factors, rows=4096, n_items=N_COLS, n_samples=16,
            k=100, batch=8, trace=t["rmse_train_trace"][:3],
            num_latent=K, seed=args.seed, interpret=False))
        del state
        _report("serve", serve(STORE, data[2], n_requests=64, k=100,
                               slots=8, seed=args.seed))
        shutil.rmtree(STORE, ignore_errors=True)

    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"]}}), flush=True)


if __name__ == "__main__":
    main()
