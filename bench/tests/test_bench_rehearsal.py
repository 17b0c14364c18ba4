"""Every cell's harness path, end to end on the CPU at tiny sizes.

The configurations are cut to a few hundred rows (``rehearse.TINY``);
the four-chip cell runs on four virtual host devices in a child
process.  Each run must print a well-formed result line."""
import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import rehearse  # noqa: E402

E2E = {"bmf_chembl.recommend_batch": "recommend_rps"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return rehearse.tiny_root(str(tmp_path_factory.mktemp("bench")))


def _shape_ok(line, trace):
    assert list(line)[:3] == ["correct", "attempted", "failed"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    dev = line["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    if trace:
        assert dev["busy_s"] > 0 and dev["window_s"] > 0
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}


@pytest.mark.parametrize("cell", sorted(E2E))
def test_cell_prints_a_result_line(root, cell):
    rc, line, _ = rehearse.run_cell(root, cell, seconds=0.5)
    assert rc == 0
    _shape_ok(line, trace=False)
    assert set(line["metrics"]) == {"setup_s", E2E[cell]}
    assert all(m["value"] > 0 for m in line["metrics"].values())


def test_traced_sweep_reports_its_layers(root):
    rc, line, out = rehearse.run_cell(root, "bmf_chembl.sweep",
                                      seconds=0.5, trace=1)
    assert rc == 0
    _shape_ok(line, trace=True)
    assert {"device_idle.sweep", "solve_ms", "sweep_mfu",
            "gram_roofline"} <= set(line["metrics"])
    assert "exchange_exposed_ms" not in line["metrics"]
    assert "0 programs compiled or loaded inside the window" in out


def test_four_chip_cell_on_four_host_devices(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = ("import json, sys; sys.path.insert(0, %r); import rehearse; "
            "rc, line, out = rehearse.run_cell(%r, 'bmf_chembl_x4.sweep', "
            "seconds=0.3); print('RESULT', json.dumps(line))"
            % (os.path.dirname(os.path.abspath(__file__)), root))
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.split("RESULT ", 1)[1])
    _shape_ok(line, trace=False)
    assert line["device"]["count"] == 4
    assert set(line["metrics"]) == {"setup_s", "sweep_s"}


def test_no_chip_no_result(root, capsys):
    sys.path.insert(0, os.path.join(root, "bench"))
    try:
        from benchkit import harness
        rc = harness.main(["--workload", "bmf_chembl.sweep", "--seed", "1",
                           "--seconds", "1"], root=root)
    finally:
        sys.path.remove(os.path.join(root, "bench"))
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_a_directory_of_the_benchmark_alone_runs_nothing(tmp_path):
    """Without the program beside it the command fails, printing no
    result."""
    import shutil
    shutil.copytree(rehearse.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    shutil.copy(os.path.join(rehearse.REPO, "BENCHMARK.json"), tmp_path)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "bmf_chembl.sweep",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
