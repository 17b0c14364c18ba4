"""``chip_smoke.py`` rehearsed on the CPU at tiny sizes.

The smoke's phases are functions of their sizes, so the path the chip
runs (builder -> session -> store -> kernels -> server, and the sharded
sweep) is exercised here with kernels in interpret mode.  ``main()``
itself always uses the real sizes and refuses to run off a TPU, which
the last test checks.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.data.synthetic import fixed_degree  # noqa: E402


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    store = str(tmp_path_factory.mktemp("store"))
    data = fixed_degree(0, 128, 64, 16, n_test_per_row=4, rank=2,
                        noise=0.1)
    out = chip_smoke.train(data, num_latent=4, burnin=3, nsamples=4,
                           seed=0, save_dir=store)
    return data, store, out


def test_fixed_degree_shapes_and_heldout():
    """Exactly nnz_per_row observations per row, held-out cells never
    observed, column degrees near the mean."""
    mat, (ti, tj, tv), cols = fixed_degree(3, 96, 48, 6,
                                           n_test_per_row=2)
    assert cols.shape == (96, 6)
    assert mat.rows.max_nnz == 8              # 6 rounded up to 8
    np.testing.assert_array_equal(np.asarray(mat.rows.mask).sum(1), 6)
    assert all(len(set(r)) == 6 for r in cols.tolist())
    seen = set(zip(np.repeat(np.arange(96), 6).tolist(),
                   cols.ravel().tolist()))
    assert not seen & set(zip(ti.tolist(), tj.tolist()))
    assert len(tv) == 96 * 2 and np.all(np.isfinite(tv))
    with pytest.raises(ValueError, match="not a multiple"):
        fixed_degree(0, 8, 10, 3)


def test_train_phase_beats_mean_predictor(trained):
    _, _, out = trained
    assert out["rmse_test"] < out["rmse_mean_predictor"]
    assert out["compile_s"] > 0 and out["sweep_s"] > 0
    assert len(out["rmse_train_trace"]) == out["sweeps"] == 7


def test_kernels_phase_interpret(trained):
    data, _, out = trained
    res = chip_smoke.kernels(
        data, out["state"].factors, rows=16, n_items=300, n_samples=4,
        k=7, batch=3, trace=out["rmse_train_trace"][:2], num_latent=4,
        seed=0, interpret=True)
    assert res["topk_bitwise"]
    assert res["gram_err"] < 1e-5 and res["sddmm_err"] < 1e-5
    assert res["pallas_trace_rel_err"] <= chip_smoke.TRACE_RTOL


def test_serve_phase_resident_and_bitwise(trained):
    data, store, _ = trained
    res = chip_smoke.serve(store, data[2], n_requests=6, k=5, slots=4,
                           seed=0)
    assert res["requests"] == 6 and res["bitwise_vs_sequential"]
    assert res["resident_bytes"] > 0


_SHARDED = textwrap.dedent("""
    import json, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, os.environ["SMOKE_ROOT"])
    import chip_smoke
    out = chip_smoke.sharded(n_rows=128, n_cols=64, num_latent=4,
                             nnz_per_row=16, n_chips=4, sweeps=2,
                             parity_rows=64, seed=0,
                             require_memory_stats=False)
    print(json.dumps(out))
""")


def _env():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    return env


@pytest.mark.slow
def test_sharded_phase_on_four_host_devices():
    """The --chips 4 phase on four simulated CPU devices: Session on a
    ("data", 4) mesh for both pipelines, every array split four ways,
    and parity with the single-device chain."""
    env = _env()
    env["SMOKE_ROOT"] = ROOT
    out = subprocess.run([sys.executable, "-c", _SHARDED], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["data_quartered"] and res["ring_bitwise_eager"]
    assert res["eager_vs_single_max_abs"] < chip_smoke.PARITY_TOL


def test_main_refuses_cpu():
    """Off a TPU the script names the platform, prints no result line
    and exits non-zero before any work."""
    out = subprocess.run([sys.executable, os.path.join(ROOT,
                                                       "chip_smoke.py")],
                         env=_env(), capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert "'cpu'" in out.stderr
    assert '"ok"' not in out.stdout
