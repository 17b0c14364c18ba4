"""The cell ``macau_chembl.sweep`` rehearsed tiny on the CPU
(``rehearse.TINY``, 32 side features, 6 bits a compound).

* The program's transitions pass ``references/macau.py``'s readings
  under the cell's limits (``bench/limits/macau_chembl.sweep.json``).
* The ``link`` control (the link's side products, Cholesky factor and
  solves in bfloat16), the ``bf16`` control and each planted fault
  read above at least one of the limits; the ``bf16`` control rounds
  lambda_beta's draw too.
* A traced run reports the link stage's metrics.
* The stage table ``link`` claims only Macau's functions: in a compiled
  BMF sweep no op changes stage for it, and in a Macau sweep the
  Normal-Wishart draw stays in ``hyper``.
"""
import json
import os
import subprocess
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import rehearse  # noqa: E402

CELL = "macau_chembl.sweep"
FAULTS = ("unchanged", "half_rows", "one_row", "stale_hyper", "stale_link",
          "beta_rows_permuted", "shared_mean")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return rehearse.tiny_root(str(tmp_path_factory.mktemp("bench")),
                              num_features=32, side_bits_per_row=6)


@pytest.fixture(scope="module")
def readings(root):
    """One tiny run of the cell: (correct, limits, controls' readings,
    faults' readings)."""
    sys.path.insert(0, os.path.join(root, "bench"))
    try:
        from benchkit import harness
        import jax
        bm = json.load(open(os.path.join(root, "BENCHMARK.json")))
        spec = next(c for c in bm["workloads"] if c["name"] == CELL)
        run = harness.Run(root, bm, spec, 2**31 + 11, 0.3, False,
                          time.perf_counter())
        run.devices = jax.devices()[:1]
        kind = harness.load_module(
            os.path.join(root, "bench", "traffic", run.mix["kind"] + ".py"),
            "t_" + run.mix["kind"])
        kind.run(run)
        controls = {k: f() for k, f in run.controls.items()}
        return run.correct, run.limits, controls, run.faults()
    finally:
        sys.path.remove(os.path.join(root, "bench"))


def test_program_passes_the_reference(readings):
    correct, limits, _, _ = readings
    assert correct
    assert {"beta_white", "beta_white_rms", "beta_prec_rel"} <= set(limits)


@pytest.mark.parametrize("control", ["link", "bf16"])
def test_control_reads_above_a_limit(readings, control):
    _, limits, controls, _ = readings
    r = controls[control]
    assert [k for k in limits if r[k] > limits[k]], (r, limits)


def test_bf16_control_draws_its_own_link_precision(readings):
    """The ``bf16`` control computes lambda_beta in bfloat16, so
    ``beta_prec_rel`` reads its rounding; the ``link`` control leaves
    lambda_beta to the reference."""
    _, _, controls, _ = readings
    assert controls["bf16"]["beta_prec_rel"] > 0
    assert controls["link"]["beta_prec_rel"] == 0


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_fault_reads_above_a_limit(readings, fault):
    _, limits, _, faults = readings
    r = faults[fault]
    assert [k for k in limits if r[k] > limits[k]], (r, limits)


def test_traced_run_reports_the_link(root):
    rc, line, out = rehearse.run_cell(root, CELL, seconds=0.5, trace=1)
    assert rc == 0 and line["correct"] is True
    m = line["metrics"]
    assert m["link_ms"]["value"] > 0 and m["link_ms"]["unit"] == "ms"
    assert m["link_roofline"]["value"] > 0
    assert {"solve_ms", "sweep_mfu", "gram_roofline", "device_idle.sweep",
            "host_loop_ms.sweep", "save_stall_ms"} <= set(m)
    assert "link_roofline: least" in out
    assert "0 programs compiled or loaded inside the window" in out


_STAGES = """
import json, os, sys
import numpy as np
sys.path.insert(0, os.path.join(%(root)r, "bench"))
from benchkit import trace
from repro.core import AdaptiveGaussian, ModelBuilder, from_coo
from repro.core.gibbs import gibbs_step, init_state

d = os.path.join(%(root)r, "bench", "stages")
tables = {f[:-5]: json.load(open(os.path.join(d, f)))
          for f in sorted(os.listdir(d)) if f.endswith(".json")}
rng = np.random.default_rng(0)
i = np.repeat(np.arange(64), 4)
j = rng.integers(0, 32, i.size)
b = ModelBuilder(num_latent=4)
side = (rng.random((64, 16)) < 0.3).astype(np.float32) if %(macau)r else None
b.add_entity("compound", 64, side_info=side)
b.add_entity("protein", 32)
b.add_block("compound", "protein",
            from_coo(i, j, rng.normal(size=i.size), (64, 32)),
            noise=AdaptiveGaussian())
model, data, _ = b.build()
text = gibbs_step.lower(model, data, init_state(model, data)).compile(
    ).as_text()
frames = trace.hlo_frames(text)
out = []
for t in (tables, {k: v for k, v in tables.items() if k != "link"}):
    st = trace.Stages(t)
    st.frames = {"m": frames}
    out.append({n: st.of(trace.Op(0, 0, n, "m")) for n in frames})
print("RESULT", json.dumps(out + [frames]))
"""


def _stages(root, macau):
    """Each op's stage in a compiled tiny sweep by the benchmark's own
    stage tables, with and without ``link.json``, and each op's frames.
    Compiled in a process of its own: there a program's ops carry only
    its own functions' names (a process that compiled a Macau sweep
    before may label a later program's ops with Macau's)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(rehearse.REPO, "src"))
    p = subprocess.run([sys.executable, "-c",
                        _STAGES % {"root": root, "macau": macau}],
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.split("RESULT ", 1)[1])


def test_link_stage_claims_only_macau_ops(root):
    with_link, without, _ = _stages(root, macau=False)
    assert with_link == without
    assert "link" not in with_link.values()
    stages, _, frames = _stages(root, macau=True)
    assert "link" in stages.values()
    nw = [n for n, f in frames.items()
          if f and f[0] == "NormalPrior.sample_hyper_moments"]
    assert nw and all(stages[n] == "hyper" for n in nw)
